#!/usr/bin/env python3
"""Read the control of a cell on the chip, at the cell's own size.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

The control is the plain reference put in the program's place and computed
in the precision below the configuration's (float8 for bf16): for the copy
cells the window's moves are made by ``jnp`` indexing through float8, for
the serve cell the float8 forward ranks the tokens at the served positions.
Each seed is one full run with the window at ``--seconds``; the numbers
compared, each beside its limit, come out as a run's do. The limits of
``correct`` sit between the program's readings and these.
"""
from __future__ import annotations

import argparse
import sys

import run as runner


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    runner.prepare_jax()
    rc = 0
    for seed in args.seeds:
        run_args = runner.parse(["--workload", args.workload, "--seed",
                                 str(seed), "--seconds", str(args.seconds)])
        print(f"control seed {seed}:", flush=True)
        rc |= runner.execute(run_args, control=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
