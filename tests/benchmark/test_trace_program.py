"""Idle gaps put down to the runtime's own spans, on a trace recorded on a
TPU v5e with a ``Tracer`` attached (``chipbench/testdata/record_program.py``):
one serial chain of 24 one-page descriptors submitted, drained, waited for
and polled inside the benchmark's own ``submit``, ``drain``, ``block`` and
``poll`` annotations."""
from __future__ import annotations

import collections

import pytest

from chipbench import trace
from chipbench.run import HOST_SPANS
from conftest import ROOT

PROGRAM = ROOT / "chipbench" / "testdata" / "program.xplane.pb"
RUNTIME_SPANS = ("submit", "coalesce", "translate.plan", "ring.push",
                 "ring.pack", "drain", "drain.pull", "drain.enqueue",
                 "completion.poll")


def _data():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(PROGRAM))


def test_runtime_spans_nest_inside_the_benchmark_spans():
    data = _data()
    spans = collections.defaultdict(list)
    for s, e, name in trace._host_spans(data, RUNTIME_SPANS):
        spans[name].append((s, e))
    # The benchmark's submit and drain, each holding the runtime's own.
    assert len(spans["submit"]) == len(spans["drain"]) == 2
    for child, parent in [("coalesce", "submit"),
                          ("translate.plan", "coalesce"),
                          ("ring.push", "submit"), ("ring.pack", "ring.push"),
                          ("drain.pull", "drain"), ("drain.enqueue", "drain")]:
        (cs, ce), = spans[child]
        assert any(ps <= cs and ce <= pe for ps, pe in spans[parent])
    # JAX marks each read of a device array back to the host; one serial
    # chain asks for the 16 that the runtime's d2h_reads counts.
    reads = sum(e.name == "np.asarray(jax.Array)" for p in data.planes
                for line in p.lines for e in line.events)
    assert reads == 16


def test_idle_gaps_take_the_innermost_runtime_span():
    # Worked from the recorded events: 15 gaps between device programs;
    # the longest, 4961243 ns, lies in ring.pack inside the submit.
    outer = trace.reduce_trace(PROGRAM, host_spans=HOST_SPANS, n_gaps=20)
    inner = trace.reduce_trace(PROGRAM,
                               host_spans=tuple(HOST_SPANS) + RUNTIME_SPANS,
                               n_gaps=20)
    assert [d for _, d in inner.idle_gaps] == [d for _, d in outer.idle_gaps]
    assert len(inner.idle_gaps) == 15
    longest = pytest.approx(4961243e-9, abs=2e-9)
    assert outer.idle_gaps[0] == ("submit", longest)
    assert inner.idle_gaps[0] == ("ring.pack", longest)
    assert collections.Counter(n for n, _ in outer.idle_gaps) == {
        "submit": 13, "drain": 2}
    assert collections.Counter(n for n, _ in inner.idle_gaps) == {
        "translate.plan": 11, "ring.pack": 1, "ring.push": 1,
        "drain.enqueue": 2}
