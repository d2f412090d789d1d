#!/usr/bin/env python3
"""Bring-up smoke test: the DMA runtime and a qwen2.5-3b serve on a TPU.

    python3 chip_smoke.py              # one chip: DMA runtime + serve phases
    python3 chip_smoke.py --chips 4    # four chips: sharded runtime only

Every phase runs in this one process (a TPU belongs to one process at a
time) through the entry points a user calls, checks what comes out
against a plain reference, and raises on any mismatch. The script exits
non-zero, and prints no result, unless JAX's first device is a TPU. Its
last stdout line is one JSON object naming the device. Times printed here
are smoke timings of a single run, not benchmarks.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# qwen2.5-3b KV layout (configs/qwen2_5_3b.py): one token's K (or V) row is
# kv_heads x head_dim bf16 elements; a page holds PAGE tokens.
KV_HEADS, HEAD_DIM, PAGE = 2, 128, 16
ROW = KV_HEADS * HEAD_DIM
PAGE_ELEMS = PAGE * ROW
SMALL_UNIT = 16            # fp32 elements: a 64-byte transfer unit


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def bits(x) -> np.ndarray:
    """Host copy of an array as unsigned ints of its width (bit compare)."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


class Monitor:
    """Counts what JAX and the kernels report through ``jax.monitoring``:
    backend-compile seconds (a persistent-cache read counts at its read
    time), persistent-cache hits, and Pallas kernel launches by kernel."""

    def __init__(self):
        from jax import monitoring

        from repro.kernels.ops import LAUNCH_EVENT
        self._launch_event = LAUNCH_EVENT
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.launches = collections.Counter()
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == self._launch_event:
            self.launches[kw["kernel"]] += 1


# ---------------------------------------------------------------------------
# DMA runtime phase (one chip)
# ---------------------------------------------------------------------------

def dma_phase(monitor: Monitor, *, rows: int, kv8_pages: int,
              small_units: int, fused_rows: int, rounds: int,
              seed: int) -> dict:
    """Seeded irregular chains through submit -> drain_all -> poll.

    Pools: qwen2.5-3b KV token rows in bf16, flat (``kv.*``, moved a page
    at a time on a serial channel) and as (rows, 256) row pools
    (``tok.*``, moved a row at a time on two blocked_2d channels: the
    Pallas-kernel channel and the fused multi-channel drain); fp32 pages
    moved through the in-flight kv_int8 transform (``kv8.*``); and fp32
    64-byte units (``small.*``). Every destination pool is checked
    against a numpy oracle of the same moves.
    """
    from repro.core.chain import from_segments
    from repro.core.transform import TransformSpec, kv8_roundtrip_np
    from repro.runtime import ChannelConfig, DMARuntime, SubmitRequest

    pages = rows // PAGE
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    ring = 256
    rt = DMARuntime([
        ChannelConfig("kv", tier="serial", ring_capacity=ring,
                      max_len=PAGE_ELEMS),
        ChannelConfig("kv8", tier="serial", ring_capacity=ring,
                      max_len=PAGE_ELEMS),
        ChannelConfig("small", tier="serial", ring_capacity=ring,
                      max_len=SMALL_UNIT),
        ChannelConfig("rows", tier="blocked_2d", ring_capacity=ring,
                      use_kernel=True),
        ChannelConfig("fused", tier="blocked_2d", ring_capacity=ring),
    ])
    shapes = {
        "kv.src": ((rows * ROW,), jnp.bfloat16),
        "kv.dst": ((rows * ROW,), jnp.bfloat16),
        "tok.src": ((rows, ROW), jnp.bfloat16),
        "tok.dst": ((rows, ROW), jnp.bfloat16),
        "fused.dst": ((fused_rows, ROW), jnp.bfloat16),
        "kv8.src": ((kv8_pages * PAGE_ELEMS,), jnp.float32),
        "kv8.dst": ((kv8_pages * PAGE_ELEMS,), jnp.float32),
        "small.src": ((small_units * SMALL_UNIT,), jnp.float32),
        "small.dst": ((small_units * SMALL_UNIT,), jnp.float32),
    }
    host = {}
    t0 = time.perf_counter()
    for i, (name, (shape, dtype)) in enumerate(shapes.items()):
        arr = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        rt.register_pool(name, arr)
        host[name] = np.asarray(arr)
    pool_gib = {n: host[n].nbytes / 2**30 for n in host}
    print(f"dma: pools made in {time.perf_counter() - t0:.3f} s: "
          + ", ".join(f"{n} {g:.3f} GiB" for n, g in pool_gib.items()))

    # Each channel's moves: (src units, dst units) per round; destinations
    # are drawn without replacement over the whole run, so no chain has
    # overlapping writes and the oracle is order-free.
    space = {"kv": (pages, pages), "kv8": (kv8_pages, kv8_pages),
             "small": (small_units, small_units), "rows": (rows, rows),
             "fused": (rows, fused_rows)}
    plan = {}
    for ch, n in {"kv": 64, "kv8": 64, "small": 256, "rows": 256,
                  "fused": 256}.items():
        n_src, n_dst = space[ch]
        n = min(n, n_dst // rounds)
        dst = rng.choice(n_dst, size=n * rounds, replace=False)
        src = rng.integers(0, n_src, size=n * rounds)
        plan[ch] = [(src[r * n:(r + 1) * n], dst[r * n:(r + 1) * n])
                    for r in range(rounds)]
    pools = {"kv": ("kv.src", "kv.dst", PAGE_ELEMS),
             "kv8": ("kv8.src", "kv8.dst", PAGE_ELEMS),
             "small": ("small.src", "small.dst", SMALL_UNIT),
             "rows": ("tok.src", "tok.dst", 1),
             "fused": ("tok.src", "fused.dst", 1)}

    launches0 = dict(monitor.launches)
    completions = []
    tickets = 0
    times = []
    for r in range(rounds):
        t0 = time.perf_counter()
        for ch, (src_name, dst_name, unit) in pools.items():
            s, d = plan[ch][r]
            if unit == 1:      # row pools: one descriptor per row
                chain = from_segments(s, d, np.ones(len(s), np.int64))
            else:
                chain = from_segments(s * unit, d * unit,
                                      np.full(len(s), unit, np.int64))
            res = rt.submit(SubmitRequest(
                chain=chain, src_pool=src_name, dst_pool=dst_name,
                channel=ch,
                transform=TransformSpec.kv_int8() if ch == "kv8" else None,
                on_complete=completions.append))
            tickets += len(res.tickets)
        rt.drain_until_idle()
        jax.block_until_ready([rt.pool(v[1]) for v in pools.values()])
        times.append(time.perf_counter() - t0)
        polled = rt.poll()
        check(len(polled) == len(pools),
              f"round {r}: polled {len(polled)} completions, "
              f"expected {len(pools)}")
    check(len(completions) == rounds * len(pools),
          "a submission's completion callback did not fire on poll")
    stats = rt.stats()
    retired = sum(c["retired"] for c in stats["channels"].values())
    check(retired == tickets,
          f"{retired} ring entries retired of {tickets} tickets")
    print(f"dma: {rounds * len(pools)} chains, {tickets} tickets, "
          f"all retired and polled")

    served = {k: monitor.launches[k] - launches0.get(k, 0) for k in
              ("descriptor_copy", "descriptor_copy_bucketed",
               "quantize_copy_bucketed")}
    want = {"descriptor_copy": rounds,                  # rows channel
            "descriptor_copy_bucketed": 3 * rounds,     # kv, small, fused
            "quantize_copy_bucketed": rounds}           # kv8 channel
    for k, v in served.items():
        print(f"dma: kernel {k} served {v} drains (expected {want[k]})")
    check(served == want,
          f"drains fell back from the Pallas kernels: {served}")

    # numpy oracle of every move
    for ch, (src_name, dst_name, unit) in pools.items():
        exp = host[dst_name].copy()
        src = host[src_name]
        ev = exp.reshape(-1, unit) if unit > 1 else exp
        sv = src.reshape(-1, unit) if unit > 1 else src
        for s, d in plan[ch]:
            ev[d] = kv8_roundtrip_np(sv[s]) if ch == "kv8" else sv[s]
        got = np.asarray(rt.pool(dst_name))
        if ch == "kv8":
            # The quantize kernel's contract (tests/test_transform.py):
            # within one quantization step of the oracle, per 256-block,
            # plus float32 rounding of scale x (up to 127 quanta).
            moved = np.concatenate([d for _, d in plan[ch]])
            srcs = np.concatenate([s for s, _ in plan[ch]])
            blk = sv[srcs].reshape(len(srcs), -1, 256)
            step = np.abs(blk).max(axis=2, keepdims=True) / np.float32(127)
            err = np.abs(got.reshape(-1, unit)[moved].reshape(blk.shape)
                         - ev[moved].reshape(blk.shape))
            ratio = err / step
            keep = np.ones(len(ev), bool)
            keep[moved] = False
            n_diff = int(np.count_nonzero(bits(got) != bits(exp)))
            print(f"dma: {dst_name}: worst error {float(ratio.max()):.6f} "
                  "quantization steps; "
                  f"{int(np.count_nonzero(ratio > 0.5))} of {ratio.size} "
                  "moved elements on a neighbouring step; "
                  f"{n_diff} of {exp.size} elements differ in bits from "
                  "the numpy oracle")
            check(float(ratio.max()) <= 1 + 128 * np.finfo(np.float32).eps,
                  f"{dst_name}: kv_int8 moves off the oracle by more than "
                  "one quantization step")
            check(np.array_equal(bits(got.reshape(-1, unit)[keep]),
                                 bits(ev[keep])),
                  f"{dst_name}: rows no chain wrote were changed")
        else:
            check(np.array_equal(bits(got), bits(exp)),
                  f"{dst_name}: differs from the numpy oracle")
            print(f"dma: {dst_name} ({pool_gib[dst_name]:.3f} GiB) matches "
                  "the numpy oracle bit for bit")
    print("dma: smoke timing (not a benchmark), seconds per round of "
          f"{len(pools)} drains after block_until_ready: "
          + ", ".join(f"{t:.4f}" for t in times)
          + " (round 0 includes compilation)")
    rt.pools.clear()
    return {"served": served, "round_seconds": times,
            "pool_gib": pool_gib["kv.dst"]}


# ---------------------------------------------------------------------------
# Serve phase (one chip)
# ---------------------------------------------------------------------------

def serve_phase(cfg, *, requests: int, capacity: int, max_len: int,
                new_tokens: int, seed: int) -> dict:
    """Continuous batching through ServeEngine submit/run/poll_completed,
    checked against the model's full forward pass."""
    from repro.models import forward, init_params
    from repro.runtime import SubmitRequest
    from repro.serve import Request, ServeEngine

    t0 = time.perf_counter()
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"serve: {cfg.name} {cfg.num_layers}L d{cfg.d_model} "
          f"H{cfg.num_heads} KV{cfg.num_kv_heads} vocab {cfg.vocab_size}, "
          f"{n_params / 1e9:.3f} B params in {cfg.param_dtype}, made in "
          f"{time.perf_counter() - t0:.3f} s")

    engine = ServeEngine(params, cfg, capacity=capacity, max_len=max_len)
    rng = np.random.default_rng(seed)
    prompts = {uid: [int(t) for t in rng.integers(
        1, cfg.vocab_size, int(rng.integers(4, 17)))]
        for uid in range(requests)}
    t0 = time.perf_counter()
    for uid, prompt in prompts.items():
        engine.submit(SubmitRequest(request=Request(
            uid=uid, prompt=prompt, max_new_tokens=new_tokens)))
    engine.run(max_steps=10_000)
    delivered = {r.uid: r for r in engine.poll_completed()}
    dt = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in delivered.values())
    print(f"serve: {len(delivered)}/{requests} requests completed and "
          f"polled, {tokens} tokens, {engine.steps} steps on "
          f"{jax.devices()[0].device_kind}; smoke timing (not a benchmark) "
          f"{dt:.3f} s including compilation")
    check(sorted(delivered) == sorted(prompts),
          "not every request was completed and polled")
    for r in delivered.values():
        check(len(r.output) == new_tokens
              and all(0 <= t < cfg.padded_vocab for t in r.output),
              f"request {r.uid}: bad output {r.output}")

    # Reference: the full forward pass over prompt + generated tokens. The
    # engine's greedy pick at each step must be the reference argmax, up to
    # bf16 rounding between the two paths.
    seqs = [prompts[u] + delivered[u].output[:-1] for u in sorted(prompts)]
    width = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    logits = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg)[0])(
        params, jnp.asarray(toks))
    logits = np.asarray(logits, np.float32)
    check(bool(np.isfinite(logits).all()), "reference logits not finite")
    gaps, same = [], 0
    for i, u in enumerate(sorted(prompts)):
        first = len(prompts[u]) - 1
        for j, tok in enumerate(delivered[u].output):
            row = logits[i, first + j]
            gaps.append(float((row.max() - row[tok])
                              / max(float(row.max() - row.mean()), 1e-6)))
            same += int(tok == int(row.argmax()))
    worst = max(gaps)
    print(f"serve: {same}/{len(gaps)} tokens equal the reference argmax; "
          f"worst margin below the reference max {worst:.4f} "
          "(fraction of max - mean)")
    check(worst <= 0.05,
          "engine tokens disagree with the reference forward pass")
    return {"completed": len(delivered), "tokens": tokens,
            "steps": engine.steps}


# ---------------------------------------------------------------------------
# Sharded phase (four chips)
# ---------------------------------------------------------------------------

def _migration_plans(rng, num_pages: int, shards: int, per_pair: int):
    """Seeded cross-shard migration, then a compaction of scattered pages
    onto the lowest-numbered pages."""
    pps = num_pages // shards
    used = set()

    def draw(shard, n):
        out = []
        while len(out) < n:
            p = int(shard * pps + rng.integers(0, pps))
            if p not in used:
                used.add(p)
                out.append(p)
        return out

    src, dst = [], []
    for ss in range(shards):
        for ds in range(shards):
            src += draw(ss, per_pair)
            dst += draw(ds, per_pair)
    live = sorted(int(p) for p in rng.choice(
        np.arange(num_pages // 2, num_pages), size=per_pair * shards,
        replace=False))
    live_set = set(live)
    free = [p for p in range(num_pages)
            if p not in live_set][:len(live)]
    return [(src, dst), (live, free)]


def _sharded_run(mesh, num_shards, kv_k, kv_v, plans, ring):
    from repro.distributed.sharded_runtime import (
        ShardedDMARuntime,
        ShardedKVPool,
    )
    srt = ShardedDMARuntime(num_shards=num_shards, mesh=mesh,
                            ring_capacity=ring, max_len=PAGE_ELEMS)
    num_pages = kv_k.shape[0] // PAGE_ELEMS
    kv = ShardedKVPool(srt, num_pages=num_pages, page=PAGE,
                       kv_heads=KV_HEADS, head_dim=HEAD_DIM,
                       dtype=kv_k.dtype)
    srt.register_sharded_pool(kv.POOL_K, kv_k, kv.owner, PAGE_ELEMS)
    srt.register_sharded_pool(kv.POOL_V, kv_v, kv.owner, PAGE_ELEMS)
    times = []
    for src, dst in plans:
        t0 = time.perf_counter()
        kv.move_pages(kv.refs(src), kv.refs(dst))
        jax.block_until_ready([srt.shards[s].pool(kv.POOL_K)
                               for s in range(num_shards)])
        times.append(time.perf_counter() - t0)
    return srt, kv, times


def sharded_phase(monitor: Monitor, devices, *, num_pages: int,
                  per_pair: int, seed: int) -> dict:
    """ShardedDMARuntime over a 4-device mesh, one shard per device,
    compared bit for bit with a 1-shard run of the same page moves and
    with a numpy oracle."""
    from jax.sharding import Mesh

    shards = len(devices)
    rng = np.random.default_rng(seed)
    plans = _migration_plans(rng, num_pages, shards, per_pair)
    ring = max(len(s) for s, _ in plans)
    key = jax.random.PRNGKey(seed)
    kv_k = jax.random.normal(jax.random.fold_in(key, 0),
                             (num_pages * PAGE_ELEMS,), jnp.bfloat16)
    kv_v = jax.random.normal(jax.random.fold_in(key, 1),
                             (num_pages * PAGE_ELEMS,), jnp.bfloat16)
    host_k, host_v = np.asarray(kv_k), np.asarray(kv_v)
    print(f"sharded: {num_pages} pages of {PAGE} qwen2.5-3b tokens "
          f"({host_k.nbytes / 2**30:.3f} GiB per K/V pool), "
          f"{len(plans[0][0])} migrated pages, "
          f"{len(plans[1][0])} compacted pages")

    mesh = Mesh(np.asarray(devices), ("dma",))
    launches0 = monitor.launches["descriptor_copy_bucketed"]
    srt, kv, times = _sharded_run(mesh, shards, kv_k, kv_v, plans, ring)
    launched = monitor.launches["descriptor_copy_bucketed"] - launches0
    placed = [next(iter(srt.shards[s].pool(kv.POOL_K).devices()))
              for s in range(shards)]
    print("sharded: shard pools on " + ", ".join(
        f"shard{s}->{d}" for s, d in enumerate(placed)))
    check(placed == list(devices), "a shard pool is not on its own device")
    stats = srt.migration
    print(f"sharded: {stats.pages} pages moved, {stats.cross_pages} across "
          f"shards in {stats.hops} hops, {stats.hop_completions} hop "
          f"completions polled, {launched} descriptor_copy_bucketed drains; "
          "smoke timing (not a benchmark) per plan: "
          + ", ".join(f"{t:.4f} s" for t in times)
          + " (includes compilation)")
    check(stats.hop_completions == stats.hops,
          "a hop's completion writeback was not observed")
    check(launched > 0, "no migration drain ran through the Pallas kernel")
    got = [srt.gather_pool(kv.POOL_K), srt.gather_pool(kv.POOL_V)]
    del srt, kv

    one, kv1, _ = _sharded_run(None, 1, kv_k, kv_v, plans, ring)
    ref = [one.gather_pool(kv1.POOL_K), one.gather_pool(kv1.POOL_V)]
    del one, kv1
    for name, g, r, h in zip("KV", got, ref, (host_k, host_v)):
        exp = h.copy().reshape(-1, PAGE_ELEMS)
        for src, dst in plans:
            exp[np.asarray(dst, np.int64)] = exp[np.asarray(src, np.int64)]
        check(np.array_equal(bits(g), bits(r)),
              f"{name}: 4-shard pools differ from the 1-shard run")
        check(np.array_equal(bits(g), bits(exp.reshape(-1))),
              f"{name}: pools differ from the numpy oracle")
    print("sharded: 4-shard K and V pools equal the 1-shard run and the "
          "numpy oracle bit for bit")
    return {"pages": stats.pages, "cross_pages": stats.cross_pages,
            "hops": stats.hops}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded runtime across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; {len(devices)} visible", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    monitor = Monitor()
    d0 = devices[0]
    print(f"device: {d0.platform} {d0.device_kind}, {len(devices)} visible; "
          f"compile cache {cache_dir}")

    if args.chips == 4:
        sharded_phase(monitor, devices[:4], num_pages=1 << 17, per_pair=256,
                      seed=args.seed)
    else:
        from repro.configs import get_config
        dma_phase(monitor, rows=1 << 21, kv8_pages=1 << 15, small_units=1 << 20,
                  fused_rows=1 << 19, rounds=4, seed=args.seed)
        # fp32 weights (12.3 GB) plus their bf16 casts do not fit the
        # chip's 16 GB; the serve phase holds bf16 weights.
        cfg = dataclasses.replace(get_config("qwen2.5-3b"),
                                  param_dtype="bfloat16")
        serve_phase(cfg, requests=4, capacity=4, max_len=128, new_tokens=8,
                    seed=args.seed)
    print(f"compile: {monitor.compile_seconds:.3f} s in backend compiles, "
          f"{monitor.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
