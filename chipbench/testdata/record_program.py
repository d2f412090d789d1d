"""Record the small trace with the runtime's own spans that
tests/benchmark/test_trace_program.py reads.

    python3 chipbench/testdata/record_program.py [OUT_DIR]

Runs on a TPU: one round of the DMA runtime with a ``Tracer`` attached,
after one untraced round that compiles its programs. A round submits one chain of 24 one-page descriptors (8 KiB bf16 pages on a
serial channel, as in ``kv_page_runs``) into one pool, drains, waits for
the pool and polls, inside the benchmark's own host annotations
(``submit``, ``drain``, ``block``, ``poll``) and the profiler options of a
``--trace 1`` run. The runtime's spans (``translate.plan``, ``ring.pack``,
``drain.pull``, ``drain.enqueue``, ...) nest inside them on the host plane.
The trace is written under OUT_DIR (default
``chipbench/.runs/testdata_program``); the ``.xplane.pb`` file is then
copied to ``chipbench/testdata/program.xplane.pb`` by hand.
"""
from __future__ import annotations

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PAGE = 16 * 256          # 16 tokens x 2 KV heads x 128, bf16: 8 KiB
PAGES = 512
POOLS = 1


def one_round(rt, chain) -> None:
    from repro.runtime import SubmitRequest

    for i in range(POOLS):
        with jax.profiler.TraceAnnotation("submit"):
            rt.submit(SubmitRequest(chain=chain, src_pool=f"L{i}",
                                    dst_pool=f"L{i}", channel="kv",
                                    on_complete=lambda r: None))
    with jax.profiler.TraceAnnotation("drain"):
        rt.drain_until_idle()
    with jax.profiler.TraceAnnotation("block"):
        jax.block_until_ready([rt.pool(f"L{i}") for i in range(POOLS)])
    with jax.profiler.TraceAnnotation("poll"):
        rt.poll()


def main(argv) -> int:
    out = pathlib.Path(argv[1]) if len(argv) > 1 else \
        ROOT / "chipbench" / ".runs" / "testdata_program"
    if jax.devices()[0].platform != "tpu":
        print("record_program.py needs a TPU", file=sys.stderr)
        return 2
    from chipbench.run import HOST_SPANS
    from chipbench.trace import find_xplane, reduce_trace
    from repro.core.chain import from_segments
    from repro.obs.trace import Tracer
    from repro.runtime import ChannelConfig, DMARuntime

    rt = DMARuntime([ChannelConfig("kv", tier="serial", max_len=PAGE,
                                   ring_capacity=256)])
    key = jax.random.PRNGKey(0)
    for i in range(POOLS):
        rt.register_pool(f"L{i}", jax.random.normal(
            jax.random.fold_in(key, i), (PAGES * PAGE,), jnp.bfloat16))
    src = np.concatenate([np.arange(8, 16), np.arange(40, 56)])
    dst = np.arange(300, 300 + src.size)
    chain = from_segments(src * PAGE, dst * PAGE,
                          np.full(src.size, PAGE, np.int64))
    one_round(rt, chain)                  # compile every program first
    tracer = Tracer(sample_rate=0.0)
    rt.attach_tracer(tracer)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    one_round(rt, chain)
    jax.profiler.stop_trace()
    path = find_xplane(out)
    print(path, path.stat().st_size)
    print("totals", tracer.totals())
    program = tuple(tracer.totals()["spans"])
    for names in (HOST_SPANS, tuple(HOST_SPANS) + program):
        t = reduce_trace(path, host_spans=names, n_gaps=12)
        print("window", t.window_s, "busy", t.busy_s, "modules", t.module_s)
        print("gaps", t.idle_gaps)
    data = jax.profiler.ProfileData.from_file(str(path))
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
