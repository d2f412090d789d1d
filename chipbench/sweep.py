#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 3 4 5 6

Runs on a TPU, in one process: one set-up, then one window of ``--seconds``
at each rate of the cell's traffic, each followed by the run-out of the
requests it admitted. For each rate it prints the requests due and
finished, the 90th percentile of time to first token, the 95th of the
token gap, and the median queue wait of the first and the last third of the
window's requests: a backlog that grows all through the window (the last
third waiting far longer than the first) marks a rate above the knee. The
cell's rate is then set, in its traffic file, at about 0.8 of the knee.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as runner


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    runner.prepare_jax()
    import jax
    from chipbench import harness

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    cell = harness.find_cell(harness.load_benchmark(runner.ROOT),
                             args.workload)
    drv = harness.load_driver(cell).Driver(runner.Context(
        cell=cell, seed=args.seed, devices=jax.devices()[:1]))
    drv.setup()
    for rate in args.rates:
        drv.t = dict(drv.t, rate_per_s=rate)
        drv.window(args.seconds, start_clock=runner.T_START)
        drv.finish()
        waits = np.asarray([drv.admitted.get(drv._base + i, np.nan)
                            - drv.t0 - drv.arrivals[i]
                            for i in drv.due]) * 1e3
        third = max(len(waits) // 3, 1)
        e2e = drv.end_to_end()
        print(json.dumps({
            "rate_per_s": rate, "due": len(drv.due),
            "missing": len(drv.missing),
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "itl_p95_ms": e2e["itl_p95_ms"],
            "step_ms_median": float(np.median(drv.spans["step"]) * 1e3),
            "mean_batch": drv.window_tokens / max(len(drv.spans["step"]), 1),
            "queue_wait_ms_first_third": float(np.nanmedian(waits[:third])),
            "queue_wait_ms_last_third": float(np.nanmedian(waits[-third:])),
        }), flush=True)
        drv.spans = {"step": [], "poll": [], "queue_wait": []}
    return 0


if __name__ == "__main__":
    sys.exit(main())
