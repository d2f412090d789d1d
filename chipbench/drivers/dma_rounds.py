"""Closed-loop rounds of descriptor chains through ``DMARuntime``.

A round submits one chain to every layer pool of the cache, drains the
runtime until idle, waits for the destination pools on the device, and
polls the completions: ``submit`` -> ``drain_until_idle`` ->
``block_until_ready`` -> ``poll``, the loop a paged cache manager runs for
copy-on-write, compaction or the write of a decode step's cache rows. The
next round starts when the last one has been polled.

Traffic parameters (``chipbench/traffic/<mix>.json``):

* ``pattern: "page_runs"``: each round copies whole pages of
  ``requests_per_round`` requests inside every pool. A request's source
  pages lie in runs of ``run_pages`` pages separated by gaps of
  ``gap_pages`` (the allocator's sequential preference with
  fragmentation); its destination is one free run. Context lengths are the
  ``(j + 0.5) / R`` quantiles of a lognormal (``context_median``,
  ``context_sigma``, clipped to ``context_clip``), so every round and every
  seed moves the same number of pages; the seed draws where they lie.
  Source and destination zones are disjoint within a round.
* ``pattern: "row_scatter"``: each step writes one cache row for each of
  ``batch`` sequences into every pool, from a per-layer staging pool of
  ``staging_steps`` step buffers. Each sequence's rows live on pages
  scattered over the pool; its position advances one row per step and
  wraps within ``headroom_tokens``.

Pools are made on the device from the seed in one jitted call. Every page
(or row) a chain moves is recorded in an origin map; after the window the
reference rebuilds each pool from the seed, gathers it through the map
with plain ``jnp`` indexing, and counts the rows whose bits differ.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import Check, percentile
from chipbench.work import copy_needed_bytes, copy_payload_bytes

pc = time.perf_counter


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (beyond 32 bits too)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _random_rows(key, shape):
    """Finite bf16 values from random bits (the top exponent bit is
    cleared, so no value is inf or NaN); bit-reproducible from the key."""
    bits = jax.random.bits(key, shape, jnp.uint16) & jnp.uint16(0xBFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("n", "shape"))
def _make_pools(key, *, n: int, shape):
    keys = jax.random.split(key, n)
    return [_random_rows(keys[i], shape) for i in range(n)]


@functools.partial(jax.jit, static_argnames=("shape", "n"))
def _make_pool(key, i, *, shape, n: int):
    return _random_rows(jax.random.split(key, n)[i], shape)


@functools.partial(jax.jit, static_argnames=("unit",))
def _rows_wrong(init, staging, origin, got, *, unit: int):
    """Reference: rows of ``got`` whose bits differ from the initial pool
    (and staging rows) gathered through ``origin``."""
    table = init.reshape(-1, unit)
    if staging is not None:
        table = jnp.concatenate([table, staging.reshape(-1, unit)])
    want = jax.lax.bitcast_convert_type(table[origin], jnp.uint16)
    have = jax.lax.bitcast_convert_type(got.reshape(-1, unit), jnp.uint16)
    return jnp.sum(jnp.any(want != have, axis=1))


def lognormal_quantiles(n: int, median: float, sigma: float, clip):
    """The (j + 0.5) / n quantiles of a lognormal, clipped."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((j + 0.5) / n) for j in range(n)])
    return np.clip(median * np.exp(sigma * z), clip[0], clip[1])


def _control_copy(pool, src_rows, dst_rows, unit, staging=None):
    """The control's copy: the reference's row moves, computed in float8."""
    table = pool.reshape(-1, unit)
    source = table if staging is None else staging.reshape(-1, unit)
    moved = source[src_rows].astype(jnp.float8_e4m3fn).astype(pool.dtype)
    return table.at[dst_rows].set(moved).reshape(pool.shape)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cache = ctx.cell.config["cache"]
        self.traffic = ctx.cell.traffic
        self.pattern = self.traffic["pattern"]
        if self.pattern not in ("page_runs", "row_scatter"):
            raise ValueError(f"unknown pattern {self.pattern!r}")
        self.seed = ctx.seed
        self.spans: Dict[str, List[float]] = {
            "submit": [], "drain": [], "block": [], "poll": []}
        self.counts: Dict[str, float] = {}
        self.latencies: List[float] = []
        self.attempted = 0
        self.failed = 0
        self.setup_s = 0.0
        self._started: Dict[int, float] = {}
        self._done: Dict[int, float] = {}
        self._chains = 0
        self._round = 0

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        from repro.runtime import ChannelConfig, DMARuntime

        c, t = self.cache, self.traffic
        self.dtype = jnp.dtype(c["dtype"])
        self.n_pools = int(c["pools"])
        if self.pattern == "page_runs":
            self.unit = int(c["page_tokens"]) * int(c["row_elems"])
            self.rows = int(c["pool_pages"])
            self.pool_shape = (self.rows * self.unit,)
            self.staging_shape = None
            ch = ChannelConfig("kv", tier="serial", max_len=self.unit,
                               ring_capacity=int(t["ring_capacity"]))
            self.lengths = lognormal_quantiles(
                int(t["requests_per_round"]), t["context_median"],
                t["context_sigma"], t["context_clip"])
            self.req_pages = np.ceil(
                self.lengths / int(c["page_tokens"])).astype(np.int64)
            zone = self.rows // (2 * len(self.req_pages))
            span = int(self.req_pages.max()) * (
                1 + t["gap_pages"][1] / t["run_pages"][0])
            if span > zone:
                raise ValueError(f"a request spans {span} pages, zones "
                                 f"hold {zone}")
        else:
            self.unit = int(c["row_elems"])
            self.rows = int(c["pool_rows"])
            self.pool_shape = (self.rows, self.unit)
            b, steps = int(t["batch"]), int(t["staging_steps"])
            self.staging_shape = (steps * b, self.unit)
            ch = ChannelConfig("rows", tier="blocked_2d", use_kernel=True,
                               ring_capacity=int(t["ring_capacity"]))
            self._place_sequences()
        self.channel = ch.name
        self.elem_bytes = self.dtype.itemsize

        self.key = seed_key(self.seed)
        self.pool_key = jax.random.fold_in(self.key, 1)
        self.staging_key = jax.random.fold_in(self.key, 2)
        self.rt = DMARuntime([ch])
        pools = _make_pools(self.pool_key, n=self.n_pools,
                            shape=self.pool_shape)
        self.names = [f"L{i}" for i in range(self.n_pools)]
        for name, arr in zip(self.names, pools):
            self.rt.register_pool(name, arr)
        del pools
        if self.staging_shape is not None:
            staging = _make_pools(self.staging_key, n=self.n_pools,
                                  shape=self.staging_shape)
            self.src_names = [f"S{i}" for i in range(self.n_pools)]
            for name, arr in zip(self.src_names, staging):
                self.rt.register_pool(name, arr)
            del staging
        else:
            self.src_names = self.names
        self.origin = np.arange(self.rows, dtype=np.int64)
        jax.block_until_ready(list(self.rt.pools.values()))
        # Warm-up: the window's own round, twice (the second must find
        # every program compiled).
        for _ in range(int(self.traffic.get("warmup_rounds", 2))):
            self._one_round(record=False)

    def _place_sequences(self) -> None:
        c, t = self.cache, self.traffic
        page = int(c["page_tokens"])
        b = int(t["batch"])
        rng = np.random.default_rng([self.seed, 0])
        lengths = lognormal_quantiles(b, t["context_median"],
                                      t["context_sigma"], t["context_clip"])
        self.seq_len = rng.permutation(lengths.astype(np.int64))
        self.headroom = int(t["headroom_tokens"])
        need = -(-(self.seq_len + self.headroom) // page)
        total = int(self.rows // page)
        if need.sum() > total:
            raise ValueError(f"{b} sequences need {need.sum()} pages; the "
                             f"pool holds {total}")
        order = rng.permutation(total)
        cuts = np.concatenate([[0], np.cumsum(need)])
        self.page_table = [order[cuts[i]:cuts[i + 1]] for i in range(b)]
        self.page_tokens = page

    # -- traffic --------------------------------------------------------
    def _round_moves(self, r: int):
        """(src rows, dst rows) of round ``r``, in units of ``self.unit``."""
        t = self.traffic
        rng = np.random.default_rng([self.seed, 1, r])
        if self.pattern == "page_runs":
            n_req = len(self.req_pages)
            zone = self.rows // (2 * n_req)
            zones = rng.permutation(2 * n_req)
            src, dst = [], []
            for j, n in enumerate(rng.permutation(self.req_pages)):
                pages, pos = [], 0
                while len(pages) < n:
                    run = int(rng.integers(t["run_pages"][0],
                                           t["run_pages"][1] + 1))
                    pages.extend(range(pos, pos + run))
                    pos += run + int(rng.integers(t["gap_pages"][0],
                                                  t["gap_pages"][1] + 1))
                pages = np.asarray(pages[:n], np.int64)
                z_src, z_dst = zones[2 * j], zones[2 * j + 1]
                off = int(rng.integers(0, zone - pages[-1]))
                src.append(z_src * zone + off + pages)
                d0 = int(rng.integers(0, zone - n + 1))
                dst.append(z_dst * zone + d0 + np.arange(n))
            return np.concatenate(src), np.concatenate(dst)
        b = len(self.seq_len)
        pos = self.seq_len + (r % self.headroom)
        pages = np.array([self.page_table[i][p // self.page_tokens]
                          for i, p in enumerate(pos)], np.int64)
        dst = pages * self.page_tokens + pos % self.page_tokens
        src = (r % int(t["staging_steps"])) * b + np.arange(b)
        return src.astype(np.int64), dst

    def _chain(self, src, dst):
        from repro.core.chain import from_segments
        if self.pattern == "page_runs":
            u = self.unit
            return from_segments(src * u, dst * u,
                                 np.full(len(src), u, np.int64))
        return from_segments(src, dst, np.ones(len(src), np.int64))

    # -- one round ------------------------------------------------------
    def _one_round(self, *, record: bool) -> None:
        from repro.runtime import SubmitRequest

        src, dst = self._round_moves(self._round)
        self._round += 1
        staged = self.pattern == "row_scatter"
        # Origin map: the reference's account of where each row came from.
        new = self.origin.copy()
        new[dst] = (self.rows + src) if staged else self.origin[src]
        self.origin = new
        keys = []
        if self.ctx.control:
            self._control_round(src, dst, keys)
        else:
            chain = self._chain(src, dst)
            for i in range(self.n_pools):
                k = self._chains
                self._chains += 1
                keys.append(k)
                t0 = pc()
                with jax.profiler.TraceAnnotation("submit"):
                    self.rt.submit(SubmitRequest(
                        chain=chain, src_pool=self.src_names[i],
                        dst_pool=self.names[i], channel=self.channel,
                        on_complete=functools.partial(self._complete, k)))
                t1 = pc()
                self._started[k] = t0
                if record:
                    self.spans["submit"].append(t1 - t0)
            t0 = pc()
            with jax.profiler.TraceAnnotation("drain"):
                self.rt.drain_until_idle()
            t1 = pc()
            with jax.profiler.TraceAnnotation("block"):
                jax.block_until_ready([self.rt.pool(n) for n in self.names])
            t2 = pc()
            with jax.profiler.TraceAnnotation("poll"):
                self.rt.poll()
            t3 = pc()
            if record:
                self.spans["drain"].append(t1 - t0)
                self.spans["block"].append(t2 - t1)
                self.spans["poll"].append(t3 - t2)
        if record:
            self.attempted += len(keys)
            self._window_keys.extend(keys)
            self._payload += len(keys) * copy_payload_bytes(
                np.full(len(src), self.unit), self.elem_bytes)
            self._descriptors += len(keys) * len(src)

    def _complete(self, key, _record) -> None:
        self._done[key] = pc()

    def _control_round(self, src, dst, keys) -> None:
        staged = self.pattern == "row_scatter"
        s_rows, d_rows = jnp.asarray(src), jnp.asarray(dst)
        for i in range(self.n_pools):
            k = self._chains
            self._chains += 1
            keys.append(k)
            self._started[k] = pc()
            pool = self.rt.pools[self.names[i]]
            stg = self.rt.pools[self.src_names[i]] if staged else None
            self.rt.pools[self.names[i]] = _control_copy(
                pool, s_rows, d_rows, self.unit, stg)
        jax.block_until_ready([self.rt.pool(n) for n in self.names])
        now = pc()
        for k in keys:
            self._done[k] = now

    # -- window ---------------------------------------------------------
    def window(self, seconds: float, *, start_clock: float) -> None:
        self._window_keys: List[int] = []
        self._payload = 0
        self._descriptors = 0
        t0 = pc()
        self.setup_s = t0 - start_clock
        while pc() - t0 < seconds:
            self._one_round(record=True)
        self.window_s = pc() - t0

    def finish(self) -> None:
        done = [k for k in self._window_keys if k in self._done]
        self.failed = len(self._window_keys) - len(done)
        self.latencies = [self._done[k] - self._started[k] for k in done]
        self.counts = {
            "chains": len(self._window_keys),
            "rounds": len(self.spans["drain"]),
            "descriptors": self._descriptors,
            "payload_bytes": self._payload,
            "needed_bytes": copy_needed_bytes(self._payload),
            "window_s": self.window_s,
        }

    def release(self) -> None:
        """Nothing to free before the reference: it reads the final pools."""

    # -- results --------------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        return {
            "copy_GBps": self._payload / self.window_s / 1e9,
            "chain_p95_ms": percentile(self.latencies, 95) * 1e3,
        }

    def report_lines(self) -> List[str]:
        lat = np.asarray(self.latencies) * 1e3
        rounds = max(len(self.spans["drain"]), 1)
        st = self.rt.stats()
        return [
            f"cell: {self.ctx.cell.name} pattern {self.pattern}, "
            f"{self.n_pools} pools of {self.rows} x {self.unit} "
            f"{self.dtype.name} ({self.rows * self.unit * self.elem_bytes / 2**20:.1f} MiB each)",
            f"window: {self.window_s:.6f} s, {self.counts['rounds']} rounds, "
            f"{self.counts['chains']} chains, {self._descriptors} "
            f"descriptors, {self._payload} payload bytes",
            f"chain latency ms: median {np.median(lat):.4f}, p95 "
            f"{np.percentile(lat, 95):.4f}, max {lat.max():.4f} "
            f"(n={lat.size})" if lat.size else "chain latency: no samples",
            "host per round ms: submit "
            f"{sum(self.spans['submit']) / rounds * 1e3:.4f}, drain "
            f"{np.mean(self.spans['drain']) * 1e3:.4f}, block "
            f"{np.mean(self.spans['block']) * 1e3:.4f}, poll "
            f"{np.mean(self.spans['poll']) * 1e3:.4f}"
            if self.spans["drain"] else "host per round: no rounds",
            f"runtime: coalesce merge ratio "
            f"{st['coalesce_merge_ratio']:.4f}, translation "
            f"{st['translation_cache']}",
        ]

    def checks(self) -> List[Check]:
        missing = self._chains - len(self._done)
        wrong = 0
        origin = jnp.asarray(self.origin.astype(np.int32))
        for i, name in enumerate(self.names):
            init = _make_pool(self.pool_key, i, shape=self.pool_shape,
                              n=self.n_pools)
            stg = None
            if self.staging_shape is not None:
                stg = _make_pool(self.staging_key, i,
                                 shape=self.staging_shape, n=self.n_pools)
            got = self.rt.pools.pop(name)
            wrong += int(_rows_wrong(init, stg, origin, got, unit=self.unit))
            del got, init, stg
        return [Check("missing_completions", missing, 0),
                Check("rows_wrong", wrong, 0)]
