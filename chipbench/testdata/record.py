"""Record the small device trace that tests/benchmark/test_trace.py reads.

    python3 chipbench/testdata/record.py [OUT_DIR]

Runs on a TPU: three drains of the runtime's ``descriptor_copy`` kernel over
a small bf16 row pool, each followed by an XLA add, with host sleeps between
them so the trace holds idle gaps of known length. The trace is written
under OUT_DIR (default ``chipbench/.runs/testdata``); the ``.xplane.pb`` file
is then copied to ``chipbench/testdata/small.xplane.pb`` by hand.
"""
from __future__ import annotations

import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(argv) -> int:
    out = pathlib.Path(argv[1]) if len(argv) > 1 else \
        ROOT / "chipbench" / ".runs" / "testdata"
    if jax.devices()[0].platform != "tpu":
        print("record.py needs a TPU", file=sys.stderr)
        return 2
    from repro.kernels import descriptor_copy_op

    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, (4096, 256), jnp.bfloat16)
    sidx = jnp.asarray(np.arange(0, 64, dtype=np.int32))
    didx = jnp.asarray(np.arange(1000, 1064, dtype=np.int32))
    add = jax.jit(lambda x: x + 1)
    jax.block_until_ready(add(descriptor_copy_op(sidx, didx, pool, pool)))
    with jax.profiler.trace(str(out)):
        for _ in range(3):
            pool = descriptor_copy_op(sidx, didx, pool, pool)
            pool = add(pool)
            jax.block_until_ready(pool)
            time.sleep(0.02)
    for f in sorted(out.rglob("*.xplane.pb")):
        print(f, f.stat().st_size)
        data = jax.profiler.ProfileData.from_file(str(f))
        for plane in data.planes:
            print("PLANE", plane.name)
            for line in plane.lines:
                evs = list(line.events)
                names = {}
                for e in evs:
                    n, d = names.get(e.name, (0, 0.0))
                    names[e.name] = (n + 1, d + e.duration_ns)
                top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
                print("  LINE", repr(line.name), len(evs), top)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
