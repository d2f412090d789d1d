"""Runtime-layer benchmarks: launch latency, per-channel utilization,
coalescer effectiveness. Emits the machine-readable trajectory consumed by
``benchmarks/run.py`` (BENCH_runtime.json) so future PRs have a baseline.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core.chain import from_segments
from repro.core.simulator import simulate_multichannel
from repro.runtime import SubmitRequest, coalesce, default_runtime


def _bench_launch(n_desc: int = 256, repeats: int = 5, seed: int = 0) -> dict:
    """Wall-clock submit cost per descriptor (the paper's launch latency).

    The workload is seeded, the reported microseconds are wall-clock — the
    descriptor/channel counters regenerate bit-for-bit, the timings do not
    (they live under the ``wall_clock`` key for that reason).
    """
    rt = default_runtime(4, tier="serial", ring_capacity=n_desc + 1,
                         max_len=64)
    pool = 1 << 16
    rng = np.random.default_rng(seed)
    rt.register_pool("src", jnp.zeros(pool, jnp.float32))
    rt.register_pool("dst", jnp.zeros(pool, jnp.float32))
    per_desc_us = []
    for _ in range(repeats):
        lens = rng.integers(1, 64, n_desc)
        srcs = rng.integers(0, pool - 64, n_desc)
        dsts = rng.integers(0, pool - 64, n_desc)
        d = from_segments(srcs, dsts, lens)
        t0 = time.perf_counter()
        rt.submit(SubmitRequest(chain=d, src_pool="src", dst_pool="dst"))
        per_desc_us.append((time.perf_counter() - t0) / n_desc * 1e6)
        rt.drain_until_idle()
    stats = rt.stats()
    # Every wall-clock value moves under wall_clock: runtime_stats must
    # regenerate bit-for-bit from the seed (same strip as the perf sweep's
    # _deterministic_counters).
    wall_us = stats.pop("launch_us_per_descriptor")
    drain_s = {name: ch.pop("drain_seconds")
               for name, ch in stats["channels"].items()}
    return {
        "descriptors_per_submit": n_desc,
        "runtime_stats": stats,
        "wall_clock": {
            "launch_us_per_descriptor_best": float(min(per_desc_us)),
            "launch_us_per_descriptor_mean": float(np.mean(per_desc_us)),
            "launch_us_per_descriptor": wall_us,
            "drain_seconds": drain_s,
        },
    }


def _bench_translation(n_desc: int = 256, warm_rounds: int = 5,
                       seed: int = 0, translation: bool = True) -> dict:
    """Cold-vs-warm dispatch through the chain-lowering JIT (DESIGN.md §7).

    One chain is dispatched cold (canonicalize + plan + lower + XLA
    compile all on the path) and then replayed ``warm_rounds`` times, the
    serve-shaped pattern the translation cache exists for. Timings are
    wall-clock and live under ``wall_clock``; the cache counters are
    deterministic event counts and stored alongside.
    """
    rt = default_runtime(1, tier="serial", ring_capacity=n_desc + 1,
                         max_len=64, translation=translation)
    pool = 1 << 16
    rng = np.random.default_rng(seed + 2)
    rt.register_pool("src", jnp.zeros(pool, jnp.float32))
    rt.register_pool("dst", jnp.zeros(pool, jnp.float32))
    lens = rng.integers(1, 64, n_desc)
    srcs = rng.integers(0, pool - 64, n_desc)
    dsts = rng.integers(0, pool - 64, n_desc)
    d = from_segments(srcs, dsts, lens)

    def dispatch_us() -> float:
        t0 = time.perf_counter()
        rt.submit(SubmitRequest(chain=d, src_pool="src", dst_pool="dst"))
        rt.drain_until_idle()
        return (time.perf_counter() - t0) / n_desc * 1e6

    cold = dispatch_us()
    warm = [dispatch_us() for _ in range(warm_rounds)]
    return {
        "descriptors_per_submit": n_desc,
        "warm_rounds": warm_rounds,
        "translation_enabled": translation,
        "counters": dict(rt.translation_stats()),
        "wall_clock": {
            "cold_dispatch_us_per_descriptor": float(cold),
            "warm_dispatch_us_mean": float(np.mean(warm)),
            "warm_dispatch_us_best": float(np.min(warm)),
            "cold_over_warm_best": float(cold / max(min(warm), 1e-9)),
        },
    }


def _bench_tracing(n_desc: int = 256, rounds: int = 5, seed: int = 0) -> dict:
    """Dispatch cost with the tracer detached / attached-but-sampled-out /
    fully recording (DESIGN.md §8).

    The observability contract is off-by-default-cheap: every hook site is
    one attribute test when no tracer is attached, and one sampling hash
    when one is attached at rate 0. ``tracing_off_overhead_ratio`` is the
    metric the overhead guard test bounds (<= 2%); rounds interleave the
    three variants so machine noise hits them equally.
    """
    from repro.obs.trace import Tracer

    pool = 1 << 16
    rng = np.random.default_rng(seed + 3)
    lens = rng.integers(1, 64, n_desc)
    srcs = rng.integers(0, pool - 64, n_desc)
    dsts = rng.integers(0, pool - 64, n_desc)
    d = from_segments(srcs, dsts, lens)

    def make_rt(tracer):
        rt = default_runtime(2, tier="serial", ring_capacity=n_desc + 1,
                             max_len=64)
        rt.register_pool("src", jnp.zeros(pool, jnp.float32))
        rt.register_pool("dst", jnp.zeros(pool, jnp.float32))
        if tracer is not None:
            rt.attach_tracer(tracer)
        return rt

    def dispatch_us(rt) -> float:
        t0 = time.perf_counter()
        rt.submit(SubmitRequest(chain=d, src_pool="src", dst_pool="dst"))
        rt.drain_until_idle()
        return (time.perf_counter() - t0) / n_desc * 1e6

    variants = {
        "none": make_rt(None),
        "off": make_rt(Tracer(sample_rate=0.0, seed=seed)),
        "on": make_rt(Tracer(sample_rate=1.0, seed=seed)),
    }
    for rt in variants.values():      # warm the translation caches
        dispatch_us(rt)
    us = {k: [] for k in variants}
    for _ in range(rounds):
        for k, rt in variants.items():
            us[k].append(dispatch_us(rt))
    best = {k: float(np.min(v)) for k, v in us.items()}
    return {
        "descriptors_per_submit": n_desc,
        "rounds": rounds,
        "wall_clock": {
            "dispatch_us_tracing_none_best": best["none"],
            "dispatch_us_tracing_off_best": best["off"],
            "dispatch_us_tracing_on_best": best["on"],
            "tracing_off_overhead_ratio":
                best["off"] / max(best["none"], 1e-9),
            "tracing_on_overhead_ratio":
                best["on"] / max(best["none"], 1e-9),
        },
    }


def _bench_channels(mem_latency: int = 13, transfer_bytes: int = 64) -> dict:
    out = {}
    for n in (1, 2, 4, 8):
        r = simulate_multichannel(n, mem_latency, transfer_bytes,
                                  num_transfers=300)
        out[f"{n}ch"] = {
            "aggregate_utilization": r.aggregate_utilization,
            "ideal": r.ideal,
            "per_channel": {c.channel: c.utilization for c in r.channels},
        }
    return out


def _bench_coalescer(pages: int = 256, page_elems: int = 16,
                     seed: int = 0) -> dict:
    """Contiguous-page workload: the planner should fuse page runs."""
    # A block table whose pages mostly landed sequentially (the allocator's
    # sequential preference), with a few fragmentation breaks.
    rng = np.random.default_rng(seed + 1)
    page_ids = []
    next_id = 0
    while len(page_ids) < pages:
        run = int(rng.integers(4, 32))
        page_ids.extend(range(next_id, next_id + run))
        next_id += run + int(rng.integers(1, 4))   # fragmentation gap
    page_ids = page_ids[:pages]
    src = np.asarray(page_ids, np.int64) * page_elems
    dst = np.arange(pages, dtype=np.int64) * page_elems
    d = from_segments(src, dst, np.full(pages, page_elems, np.int64))
    _, stats = coalesce(d, max_len=1 << 20)
    return {
        "n_in": stats.n_in,
        "n_out": stats.n_out,
        "merge_ratio": stats.merge_ratio,
        "input_hit_rate": stats.input_hit_rate,
        "output_hit_rate": stats.output_hit_rate,
    }


def run(csv_rows: list, seed: int = 0, translation: bool = True) -> dict:
    launch = _bench_launch(seed=seed)
    chans = _bench_channels()
    coal = _bench_coalescer(seed=seed)
    trans = _bench_translation(seed=seed, translation=translation)
    tracing = _bench_tracing(seed=seed)
    wall = launch["wall_clock"]
    csv_rows.append(("runtime_launch_per_desc",
                     wall["launch_us_per_descriptor_best"],
                     f"mean={wall['launch_us_per_descriptor_mean']:.2f}us"))
    for key, c in chans.items():
        csv_rows.append((f"runtime_bus_util_{key}",
                         0.0,
                         f"agg={c['aggregate_utilization']:.3f}/"
                         f"ideal={c['ideal']:.3f}"))
    csv_rows.append(("runtime_coalesce", 0.0,
                     f"merge_ratio={coal['merge_ratio']:.2f}"))
    twall = trans["wall_clock"]
    csv_rows.append(("runtime_translation_dispatch",
                     twall["warm_dispatch_us_best"],
                     f"cold={twall['cold_dispatch_us_per_descriptor']:.2f}us/"
                     f"warm={twall['warm_dispatch_us_mean']:.2f}us"))
    trwall = tracing["wall_clock"]
    csv_rows.append(("runtime_tracing_dispatch",
                     trwall["dispatch_us_tracing_off_best"],
                     f"off/none={trwall['tracing_off_overhead_ratio']:.3f}/"
                     f"on/none={trwall['tracing_on_overhead_ratio']:.3f}"))
    return {
        "launch": launch,
        "channels": chans,
        "coalescer": coal,
        "translation": trans,
        "tracing": tracing,
    }
