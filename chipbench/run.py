#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, its traffic
mix and its per-layer metrics are found by name from ``BENCHMARK.json``
(see ``chipbench/harness.py``). The run builds its inputs from ``--seed``,
warms up every shape the window uses (set-up), measures for ``--seconds``,
then checks what the timed path produced against a plain reference. With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the per-layer metrics. The last stdout line is one JSON object; the last
stderr lines are the numbers compared, each beside its limit.

Exits non-zero, printing no result, unless JAX finds a TPU with at least
the chips the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: Where a traced run writes its profile (a fixed path in the checkout).
TRACE_SUBDIR = pathlib.Path("chipbench") / ".runs" / "trace"
#: Host spans the idle gaps of a trace are attributed to.
HOST_SPANS = ("submit", "drain", "block", "poll", "admit", "step",
              "generate", "window")


@dataclasses.dataclass
class Context:
    """What a driver is given: the cell's files, the seed and the devices."""

    cell: object
    seed: int
    devices: list
    control: bool = False    # the reference in the program's place


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reads."""

    cell: str
    spans: Dict[str, List[float]]
    counts: Dict[str, float]
    trace: Optional[object]
    peaks: dict
    chips: int


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_jax() -> None:
    """Compile cache in the checkout (or where JAX_COMPILATION_CACHE_DIR
    says), kept for every program however fast it compiles."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Backend compiles seen through ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


def execute(args, *, root: pathlib.Path = ROOT, require_tpu: bool = True,
            control: bool = False, out=None, err=None) -> int:
    """One run of one cell of the checkout at ``root``; returns the exit
    code. Tests pass ``require_tpu=False`` to drive a run on the CPU at a
    tiny size."""
    out = out or sys.stdout
    err = err or sys.stderr
    from chipbench import harness, peaks as peak_table

    bench_dir = root / "chipbench"
    trace_dir = root / TRACE_SUBDIR
    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, args.workload, bench_dir)
    chips = int(cell.entry["chips"])

    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"run.py: needs a TPU; JAX's first device is "
              f"{devices[0].platform}", file=err)
        return 2
    if require_tpu and len(devices) < chips:
        print(f"run.py: {cell.name} needs {chips} chips; "
              f"{len(devices)} visible", file=err)
        return 2
    devices = devices[:chips]
    pk = peak_table.peaks(devices[0].device_kind) if require_tpu else {}

    compiles = CompileCounter()
    driver_mod = harness.load_driver(cell)
    ctx = Context(cell=cell, seed=args.seed, devices=devices,
                  control=control)
    drv = driver_mod.Driver(ctx)
    drv.setup()

    trace = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles_before = compiles.count
    with jax.profiler.TraceAnnotation("window"):
        drv.window(args.seconds, start_clock=T_START)
    window_compiles = compiles.count - compiles_before
    # Answers due in the window finish before the profiler stops, so a
    # traced run serves them as an untraced one does.
    drv.finish()
    if args.trace:
        jax.profiler.stop_trace()
    mem_peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                   for d in devices) if require_tpu else 0
    drv.release()
    checks = drv.checks()

    for line in drv.report_lines():
        print(line, file=err)
    print(f"setup: {drv.setup_s:.6f} s; compiles {compiles.count} "
          f"({compiles.seconds:.3f} s), {window_compiles} inside the window",
          file=err)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem_peak}
    metrics, breakdown = {}, None
    if args.trace:
        from chipbench.trace import find_xplane, reduce_trace
        trace = reduce_trace(find_xplane(trace_dir), window_span="window",
                             host_spans=HOST_SPANS)
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        breakdown = {"device_ops": [[k, v] for k, v in trace.top_ops(10)],
                     "idle_gaps": [[k, v] for k, v in trace.idle_gaps]}
        view = RunView(cell=cell.name, spans=drv.spans, counts=drv.counts,
                       trace=trace, peaks=pk, chips=chips)
        for m in cell.per_layer:
            value = harness.load_reader(m["name"], bench_dir)(view)
            if value is not None:
                metrics[m["name"]] = (value, m["unit"])
    else:
        e2e = drv.end_to_end()
        e2e["setup_s"] = drv.setup_s
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = (e2e[m["name"]], m["unit"])
    for line in harness.check_lines(checks):
        print(line, file=err)
    line = harness.result_line(checks=checks, attempted=drv.attempted,
                               failed=drv.failed, metrics=metrics,
                               device=device, breakdown=breakdown)
    print(json.dumps(line), file=out)
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    prepare_jax()
    return execute(args)


if __name__ == "__main__":
    sys.exit(main())
