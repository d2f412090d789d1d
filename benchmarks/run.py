"""Benchmark orchestrator. One module per paper table/figure; prints
``name,us_per_call,derived`` CSV (deliverable d) and regenerates BOTH
baseline artifacts from one entrypoint:

* ``BENCH_runtime.json`` — the runtime perf trajectory (launch latency,
  per-channel utilization, coalescer effectiveness);
* ``BENCH_perf.json``    — the gated scenario-sweep contract consumed by
  ``python -m repro.perf.gate`` (DESIGN.md §4).

``--seed`` threads one seed through every seeded generator, so the
deterministic sections of both documents regenerate bit-for-bit:
``python benchmarks/run.py --seed 0`` twice yields byte-identical
BENCH_perf.json (wall-clock fields in BENCH_runtime.json are excluded
from that claim and marked as such in the document).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

# Runnable as `python benchmarks/run.py` from anywhere: the script's
# parent (the repo root) must be importable for the benchmarks package.
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def _apply_mesh_flag() -> None:
    """Honor ``--mesh N`` before anything imports jax.

    ``--xla_force_host_platform_device_count`` only takes effect if set
    before the XLA backend initializes, so the flag is peeked off argv at
    module import time (argparse validates it again later). The sharded
    cells regenerate bit-for-bit with or without real devices — the flag
    only controls whether shards get placed on a real CPU mesh, matching
    what CI's sharded lane exercises.
    """
    argv = sys.argv[1:]
    n = None
    for i, tok in enumerate(argv):
        try:
            if tok == "--mesh" and i + 1 < len(argv):
                n = int(argv[i + 1])
            elif tok.startswith("--mesh="):
                n = int(tok.split("=", 1)[1])
        except ValueError:
            return   # argparse will produce the real error message
    if n is None:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={n}".strip()


_apply_mesh_flag()

from benchmarks import (  # noqa: E402
    bench_engine,
    bench_runtime,
    bench_sharded,
    bench_transforms,
    fig4_utilization,
    fig5_hitrate,
    roofline,
    table2_area,
    table4_latency,
)

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Regenerate every benchmark table/figure and both "
                    "BENCH_*.json baselines.")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for every deterministic generator "
                         "(baselines regenerate bit-for-bit)")
    ap.add_argument("--perf-mode", choices=("quick", "full", "skip"),
                    default="quick",
                    help="scenario-sweep size for BENCH_perf.json; "
                         "'skip' leaves the committed baseline untouched")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="emulate N host CPU devices "
                         "(--xla_force_host_platform_device_count) so the "
                         "sharded cells place shards on a real mesh, as "
                         "CI's sharded lane does; cells regenerate "
                         "bit-for-bit with or without it")
    ap.add_argument("--trace", metavar="OUT.trace.json",
                    help="also record a seeded serve+simulator lifecycle "
                         "trace (Perfetto/chrome://tracing JSON, DESIGN.md "
                         "§8); includes sharded migration-hop flow arrows "
                         "when --mesh >= 2")
    ap.add_argument("--transforms", action="store_true",
                    help="run only the in-flight transform A/B "
                         "(int8-quantized vs fp32 datapath, real both "
                         "legs) and exit nonzero unless int8 beats fp32 "
                         "on effective bandwidth at equal fidelity "
                         "tolerance with every transform plan fused; "
                         "this is the CI perf-gate job's transform lane")
    ap.add_argument("--sync-fabric", action="store_true",
                    help="escape hatch: run the sharded migration benches "
                         "through the synchronous blocking hop path "
                         "(fabric='sync', bit-identical to the pre-fabric "
                         "planner) instead of the async fabric "
                         "(DESIGN.md §10)")
    ap.add_argument("--no-translation-cache", action="store_true",
                    help="escape hatch: run the legacy uncached dispatch "
                         "path everywhere (runtime benches and the perf "
                         "sweep); the resulting BENCH_perf.json records "
                         "translation_cache_enabled=false")
    ap.add_argument("--no-iotlb", action="store_true",
                    help="escape hatch: drop the MMU/IOTLB cells from the "
                         "perf sweep (physical addressing only, as before "
                         "schema v8); the resulting BENCH_perf.json "
                         "records iotlb_enabled=false")
    ap.add_argument("--out-dir", type=pathlib.Path, default=REPO_ROOT,
                    help="where to write BENCH_*.json")
    args = ap.parse_args(argv)
    translation = not args.no_translation_cache
    enable_compile_cache()

    if args.transforms:
        csv_rows: list = []
        metrics = bench_transforms.run(csv_rows, seed=args.seed)
        print("name,us_per_call,derived")
        for name, us, derived in csv_rows:
            print(f"{name},{us:.2f},{derived}")
        print(json.dumps(metrics, indent=2, sort_keys=True))
        failures = bench_transforms.check(metrics)
        for msg in failures:
            print(f"TRANSFORM A/B FAIL: {msg}", file=sys.stderr)
        if not failures:
            print("transform A/B: int8 beats fp32 at equal fidelity "
                  "tolerance; all transform plans fused")
        return 1 if failures else 0

    if args.mesh:
        import jax
        if len(jax.devices()) < args.mesh:
            # The pre-import peek reads sys.argv; a programmatic
            # main(argv=...) call (or an already-initialized backend)
            # cannot grow the device count retroactively.
            print(f"error: --mesh {args.mesh} requested but only "
                  f"{len(jax.devices())} devices are visible",
                  file=sys.stderr)
            return 2

    csv_rows: list = []
    fig4_utilization.run(csv_rows)
    fig5_hitrate.run(csv_rows)
    table2_area.run(csv_rows)
    table4_latency.run(csv_rows)
    bench_engine.run(csv_rows)
    runtime_metrics = bench_runtime.run(csv_rows, seed=args.seed,
                                        translation=translation)
    runtime_metrics["sharded"] = bench_sharded.run(
        csv_rows, seed=args.seed,
        fabric="sync" if args.sync_fabric else "async")
    roofline.run(csv_rows)
    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.2f},{derived}")

    out = args.out_dir / "BENCH_runtime.json"
    runtime_metrics["seed"] = args.seed
    out.write_text(json.dumps(runtime_metrics, indent=2, sort_keys=True)
                   + "\n")
    print(f"wrote {out}")

    if args.perf_mode != "skip":
        from repro.perf.sweep import default_spec, run_sweep, write_doc
        perf_out = args.out_dir / "BENCH_perf.json"
        doc = run_sweep(default_spec(args.perf_mode, args.seed,
                                     translation=translation,
                                     iotlb=not args.no_iotlb))
        write_doc(doc, str(perf_out))
        print(f"wrote {perf_out}: {len(doc['cells'])} cells "
              f"(mode={args.perf_mode}, seed={args.seed})")

    if args.trace:
        from repro.obs.record import main as record_trace
        rc = record_trace(["--out", args.trace, "--seed", str(args.seed),
                           "--mesh", str(args.mesh or 1)])
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
