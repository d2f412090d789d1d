"""Reduce a JAX profiler trace (``.xplane.pb``) to device-time metrics.

The TPU planes (``/device:TPU:<n>``) hold two lines that matter here:

* ``XLA Modules``: one event per executed program (``jit_<name>(<hash>)``);
  programs on one device do not overlap, and their union is the time in
  which the device ran anything.
* ``XLA Ops``: the HLO operations inside those programs. Operations nest
  (a ``conditional`` contains the operations of its branch), so an
  operation's own time is its duration less that of the operations inside
  it. A Pallas kernel is a ``custom-call`` whose target is
  ``tpu_custom_call``; its name is the name of the program that holds it.

The host plane (``/host:CPU``) holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans on the thread that ran them. Host and
device events share one timeline (nanoseconds from the start of the
profile); their clocks agree to about a millisecond.

Everything here is plain arithmetic over the events, so that every PR
computes the same numbers in the same way; ``tests/benchmark/test_trace.py``
checks it on a trace recorded on a TPU v5e.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_OP = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.clone)? = ")


def module_name(event_name: str) -> str:
    """``jit_descriptor_copy(7182...)`` -> ``descriptor_copy``."""
    return _MODULE.match(event_name).group(1)


def op_kind(event_name: str) -> str:
    """``%copy.13 = bf16[...] copy(...)`` -> ``copy``; a Pallas kernel
    -> ``tpu_custom_call``."""
    if KERNEL_TARGET in event_name:
        return "tpu_custom_call"
    m = _OP.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def union_length(intervals: Sequence[Interval]) -> float:
    """Total length covered by possibly overlapping [start, end) intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi) that no interval covers, in time order."""
    out, cur = [], lo
    for s, e in sorted(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_times(ops: Sequence[Tuple[float, float, str]]
               ) -> List[Tuple[float, str]]:
    """Own time of each (start, end, name) operation: its duration less the
    operations nested directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [ops[i][1] - ops[i][0] for i in range(len(ops))]
    stack: List[int] = []
    for i in order:
        s, e, _ = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(max(own[i], 0.0), ops[i][2]) for i in range(len(ops))]


@dataclasses.dataclass
class DeviceTrace:
    """What one traced window reduces to. Times are in seconds."""

    window_s: float
    devices: int
    busy_s: float                      # union of programs, mean over devices
    op_s: Dict[str, float]             # own time by op kind, summed
    kernel_s: Dict[str, float]         # Pallas kernel time by program name
    module_s: Dict[str, float]         # program time by program name
    idle_gaps: List[Tuple[str, float]]  # longest gaps, by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def nonkernel_s(self) -> float:
        return sum(v for k, v in self.op_s.items() if k != "tpu_custom_call")

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        rows = [(f"kernel:{k}", v) for k, v in self.kernel_s.items()]
        rows += [(k, v) for k, v in self.op_s.items()
                 if k != "tpu_custom_call"]
        return sorted(rows, key=lambda kv: -kv[1])[:n]


def _host_spans(data, names: Optional[Sequence[str]]
                ) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if names is None or e.name in names:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    return spans


def find_xplane(log_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def reduce_trace(path, *, window_span: Optional[str] = None,
                 host_spans: Optional[Sequence[str]] = None,
                 n_gaps: int = 10) -> DeviceTrace:
    """Reduce the trace at ``path`` (a ``.xplane.pb`` file).

    ``window_span`` names the host annotation that brackets the measured
    window; without it the window runs from the first to the last device
    program. ``host_spans`` names the annotations that idle gaps are
    attributed to: a gap takes the name of the innermost such span that
    covers its middle, or ``"none"``.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    modules: Dict[str, List[Tuple[float, float, str]]] = {}
    ops: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
            elif line.name == "XLA Ops":
                ops[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
    if not modules:
        raise RuntimeError(f"{path}: no TPU program ran in the trace")

    if window_span is not None:
        marks = [(s, e) for s, e, _ in _host_spans(data, [window_span])]
        if len(marks) != 1:
            raise RuntimeError(f"{path}: {len(marks)} '{window_span}' spans")
        lo, hi = marks[0]
    else:
        every = [iv for evs in modules.values() for iv in evs]
        lo, hi = min(s for s, _, _ in every), max(e for _, e, _ in every)

    spans = _host_spans(data, host_spans) if host_spans else []
    busy, op_s, kernel_s, module_s, all_gaps = [], {}, {}, {}, []
    for dev, mods in modules.items():
        ivs = clip([(s, e) for s, e, _ in mods], lo, hi)
        busy.append(union_length(ivs))
        for s, e, name in mods:
            c = clip([(s, e)], lo, hi)
            if c:
                k = module_name(name)
                module_s[k] = module_s.get(k, 0.0) + (c[0][1] - c[0][0])
        dev_ops = [o for o in ops.get(dev, []) if lo <= o[0] < hi]
        for own, name in self_times(dev_ops):
            kind = op_kind(name)
            op_s[kind] = op_s.get(kind, 0.0) + own
        mod_sorted = sorted(mods)
        starts = [m[0] for m in mod_sorted]
        for s, e, name in dev_ops:
            if op_kind(name) != "tpu_custom_call":
                continue
            j = bisect.bisect_right(starts, s) - 1
            holder = mod_sorted[j] if j >= 0 and s < mod_sorted[j][1] \
                else None
            k = module_name(holder[2]) if holder else "unknown"
            kernel_s[k] = kernel_s.get(k, 0.0) + (e - s)
        for gs, ge in gaps(ivs, lo, hi):
            mid = (gs + ge) / 2
            cover = [sp for sp in spans if sp[0] <= mid < sp[1]]
            label = min(cover, key=lambda sp: sp[1] - sp[0])[2] \
                if cover else "none"
            all_gaps.append((label, ge - gs))
    n_dev = len(modules)
    ns = 1e-9
    all_gaps.sort(key=lambda g: -g[1])
    return DeviceTrace(
        window_s=(hi - lo) * ns,
        devices=n_dev,
        busy_s=sum(busy) / n_dev * ns,
        op_s={k: v * ns for k, v in op_s.items()},
        kernel_s={k: v * ns for k, v in kernel_s.items()},
        module_s={k: v * ns for k, v in module_s.items()},
        idle_gaps=[(n, d * ns) for n, d in all_gaps[:n_gaps]],
    )
