"""Sharded-runtime benchmarks: per-mesh migration cells for
BENCH_runtime.json (DESIGN.md §6, §10, §11).

One entry per mesh size in {1, 2, 4, 8} — the same cell spec and seeds
the perf sweep gates in BENCH_perf.json, but a *single* repeat, so any
metric downstream of the repeat median can differ from the gated
document (including the cycle model, whose cross_fraction input is that
median). The gated copies live in BENCH_perf.json; here they are
*reported*, with the wall-clock migration drain time isolated under
``wall_clock``, which never enters the deterministic section.

The ``wall_clock`` section also reports ``resize_mesh4_seconds`` and
``migration_overlap_ratio_mesh4`` (the async fabric), plus two
virtual-addressing figures — ``tlb_hit_rate_L13``, the DDR3 MMU cell's
IOTLB hit rate under chain-lookahead prefetch, and
``first_touch_latency_rounds_mesh4``, the fabric rounds from touching an
ownership-flipped page to residency.

The defrag A/B times remap-based compaction (a page-table update)
against the legacy copy leg through the DMA runtime on the *same*
fragmented layout — the pool hands out :class:`PageRef` handles and this
bench holds them end to end; the gated cycle-model copies live in the
``mmu/*`` cells of BENCH_perf.json.

``fabric="sync"`` is the escape hatch (``benchmarks/run.py
--sync-fabric``): every cell re-runs through the synchronous blocking
hop path, bit-identical to the pre-fabric migration planner.
"""
from __future__ import annotations

import dataclasses
import time

from repro.perf.mmu_cell import run_mmu_cell
from repro.perf.sharded_cell import (
    DEFAULT_SHARDED_SPEC,
    MESH_SIZES,
    _make_runtime,
    _resize_retention,
    run_sharded_cell,
)

#: Defrag A/B shape: allocate a run, free every other page, compact the
#: stride-2 survivors. Small enough for the copy leg to stay fast.
_DEFRAG_ALLOC = 48


def _defrag_ab(spec) -> dict:
    """Remap-vs-copy compaction of the same fragmented PageRef set."""
    out = {}
    for mode in ("remap", "copy"):
        _, kv, _ = _make_runtime(2, spec)
        pages = kv.alloc_on(0, _DEFRAG_ALLOC)
        live = pages[_DEFRAG_ALLOC // 2:]   # survivors sit past the hole
        kv.release(pages[:_DEFRAG_ALLOC // 2])
        t0 = time.perf_counter()
        new_refs, _, rate = kv.defragment(live, mode=mode)
        out[f"defrag_{mode}_seconds"] = time.perf_counter() - t0
        out[f"defrag_{mode}_rate"] = float(rate)
        out[f"defrag_{mode}_pages"] = len(new_refs)
    return out


def run(csv_rows: list, seed: int = 0, fabric: str = "async") -> dict:
    spec = (DEFAULT_SHARDED_SPEC if fabric == "async"
            else dataclasses.replace(DEFAULT_SHARDED_SPEC, fabric="sync"))
    cells = {}
    wall = {}
    for mesh in MESH_SIZES:
        t0 = time.perf_counter()
        metrics, counters = run_sharded_cell(seed, mesh, spec, repeats=1)
        wall[f"mesh{mesh}_seconds"] = time.perf_counter() - t0
        cells[f"mesh{mesh}"] = {"metrics": metrics, "counters": counters}
        csv_rows.append((
            f"sharded_migration_mesh{mesh}", 0.0,
            f"cycles={metrics['cross_shard_migration_cycles']:.1f}/"
            f"merge={metrics['migration_chain_merge_ratio']:.2f}/"
            f"overlap={metrics['migration_overlap_ratio']:.2f}"))

    defrag = _defrag_ab(spec)
    wall.update({k: v for k, v in defrag.items() if k.endswith("_seconds")})
    csv_rows.append((
        "sharded_defrag_remap", defrag["defrag_remap_seconds"] * 1e6,
        f"rate={defrag['defrag_remap_rate']:.2f}/"
        f"pages={defrag['defrag_remap_pages']}"))
    csv_rows.append((
        "sharded_defrag_copy", defrag["defrag_copy_seconds"] * 1e6,
        f"rate={defrag['defrag_copy_rate']:.2f}/"
        f"pages={defrag['defrag_copy_pages']}"))

    # Trend series (async only; the sync escape hatch has no fabric to
    # overlap, no paced handoff to time, and no lazy pull to measure).
    if fabric == "async":
        t0 = time.perf_counter()
        resize = _resize_retention(seed, 4, spec)
        wall["resize_mesh4_seconds"] = time.perf_counter() - t0
        wall["migration_overlap_ratio_mesh4"] = \
            cells["mesh4"]["metrics"]["migration_overlap_ratio"]
        wall["first_touch_latency_rounds_mesh4"] = \
            cells["mesh4"]["metrics"]["first_touch_latency_rounds"]
        mmu_metrics, _ = run_mmu_cell(seed, 13)
        wall["tlb_hit_rate_L13"] = mmu_metrics["tlb_hit_rate"]
        csv_rows.append((
            "sharded_resize_mesh4", wall["resize_mesh4_seconds"] * 1e6,
            f"retained={resize['retained']:.2f}/"
            f"handoff={resize['handoff_pages']}"))
        csv_rows.append((
            "mmu_iotlb_L13", 0.0,
            f"tlb_hit={mmu_metrics['tlb_hit_rate']:.3f}/"
            f"walk_stall={mmu_metrics['walk_stall_cycles']:.0f}"))
    return {"fabric": fabric, "cells": cells, "defrag": defrag,
            "wall_clock": wall}
