"""Low-overhead span/event recorder for descriptor-lifecycle tracing.

Design constraints (DESIGN.md §8):

* **off-by-default-cheap** — the runtime stores ``tracer = None`` and every
  hook site is a single attribute test; no object is built, no clock read,
  when tracing is off (a hook site enters the shared :data:`NO_SPAN`).  The
  overhead guard test keeps this honest.
* **on the profiler's clock** — :meth:`Tracer.span` enters a
  ``jax.profiler.TraceAnnotation`` under the span's bare name, so the span
  lands on the host plane of any active profile beside the device planes,
  and adds to exact per-name totals (count, total and self seconds) that
  :meth:`Tracer.totals` returns; :meth:`Tracer.count` keeps counters.
* **bounded** — events land in a ``deque(maxlen=capacity)`` ring; the
  ``emitted`` counter keeps counting so ``dropped`` is exact.  Totals live
  outside the ring and stay exact whatever it drops.
* **sampled deterministically** — ``sampled(key)`` hashes ``seed:key`` with
  crc32 against ``sample_rate * 2**32``.  The same (seed, key) samples the
  same way on every shard and every run, so cross-shard traces of one
  request either all record or all skip.  Sampling decides what enters the
  ring, never the totals.
* **dual clocks** — wall events timestamp with ``time.monotonic()``
  microseconds; simulator events pass explicit cycle timestamps with
  ``clock="cycle"`` and are rendered on separate tracks (1 cycle == 1 µs
  in the exported timeline).
"""
from __future__ import annotations

import threading
import time
import zlib
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

monotonic = time.monotonic
"""The one clock used for every wall-time measurement in the runtime.

``time.time()`` is subject to NTP steps and DST jumps; ``perf_counter``
is per-process.  ``monotonic`` is steady and comparable across the whole
process, which is all the probe and tracer need.
"""


def monotonic_us() -> float:
    return monotonic() * 1e6


#: What a hook site enters when no tracer is attached: one shared no-op
#: context, so the detached path builds nothing per call.
NO_SPAN = nullcontext()


@dataclass
class TraceEvent:
    """One trace_event-shaped record (pre-export, track not yet a pid)."""

    name: str
    ph: str                       # X, i, b, e, s, t, f, C
    ts: float                     # µs (wall) or cycles (clock="cycle")
    track: str                    # exported as one Perfetto process/track
    dur: Optional[float] = None   # X only
    id: Optional[int] = None      # async + flow events
    clock: str = "wall"           # "wall" | "cycle"
    args: Dict[str, object] = field(default_factory=dict)


class _Span:
    """One open :meth:`Tracer.span`: a profiler annotation plus totals.

    ``args`` may be extended, and ``ring`` (whether the closed span enters
    the ring) decided, inside the ``with`` block.
    """

    __slots__ = ("_tr", "name", "track", "args", "ring", "_ann", "_t0",
                 "_child")

    def __init__(self, tr: "Tracer", name: str, track: str, ring: bool,
                 args: Dict[str, object]) -> None:
        self._tr = tr
        self.name = name
        self.track = track
        self.args = args
        self.ring = ring

    def __enter__(self) -> "_Span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._tr._open().append(self)
        self._child = 0.0
        self._t0 = monotonic()
        return self

    def __exit__(self, *exc) -> None:
        dt = monotonic() - self._t0
        stack = self._tr._open()
        stack.pop()
        if stack:
            stack[-1]._child += dt
        tot = self._tr._spans.get(self.name)
        if tot is None:
            tot = self._tr._spans[self.name] = [0, 0.0, 0.0]
        tot[0] += 1
        tot[1] += dt
        tot[2] += dt - self._child
        self._ann.__exit__(*exc)
        if self.ring:
            self._tr.complete(self.name, self.track, self._t0 * 1e6,
                              dt * 1e6, **self.args)


class Tracer:
    """Ring-buffered event recorder with seeded sampling.

    All emit helpers are unconditional — *callers* gate on
    ``tracer is not None and tracer.sampled(key)`` so the disabled path
    stays one attribute load.
    """

    def __init__(self, capacity: int = 65536, sample_rate: float = 1.0,
                 seed: int = 0) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.sample_rate = float(sample_rate)
        self.seed = seed
        self.emitted = 0
        self._buf: deque = deque(maxlen=capacity)
        self._next_flow = 1
        self._threshold = int(min(max(self.sample_rate, 0.0), 1.0) * 2**32)
        self._spans: Dict[str, list] = {}      # name -> [count, total, self]
        self._counts: Dict[str, int] = {}
        self._local = threading.local()        # per-thread open-span stack

    # -- sampling ----------------------------------------------------------

    def sampled(self, key: object) -> bool:
        """Deterministic hash-based sampling decision for ``key``.

        Keys are stable identities (first ticket of a submission, request
        uid, translation-lookup ordinal) so the decision is reproducible
        and shard-independent.
        """
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        return zlib.crc32(f"{self.seed}:{key}".encode()) < self._threshold

    # -- clock -------------------------------------------------------------

    def now_us(self) -> float:
        return monotonic() * 1e6

    # -- emission ----------------------------------------------------------

    def emit(self, event: TraceEvent) -> None:
        self.emitted += 1
        self._buf.append(event)

    def complete(self, name: str, track: str, t0_us: float, dur_us: float,
                 *, clock: str = "wall", **args) -> None:
        """A closed span ("X"): began at ``t0_us``, lasted ``dur_us``."""
        self.emit(TraceEvent(name=name, ph="X", ts=t0_us, track=track,
                             dur=max(dur_us, 0.0), clock=clock, args=args))

    def instant(self, name: str, track: str, ts: Optional[float] = None,
                *, clock: str = "wall", **args) -> None:
        if ts is None:
            ts = self.now_us()
        self.emit(TraceEvent(name=name, ph="i", ts=ts, track=track,
                             clock=clock, args=args))

    def counter(self, name: str, track: str, ts: Optional[float] = None,
                *, clock: str = "wall", **values) -> None:
        """A counter sample ("C"): Perfetto renders each numeric value in
        ``values`` as a series on the named counter track (per-link
        fabric occupancy uses one counter per directed link)."""
        if ts is None:
            ts = self.now_us()
        self.emit(TraceEvent(name=name, ph="C", ts=ts, track=track,
                             clock=clock, args=values))

    def async_begin(self, name: str, track: str, id: int,
                    ts: Optional[float] = None, **args) -> None:
        if ts is None:
            ts = self.now_us()
        self.emit(TraceEvent(name=name, ph="b", ts=ts, track=track, id=id,
                             args=args))

    def async_end(self, name: str, track: str, id: int,
                  ts: Optional[float] = None, **args) -> None:
        if ts is None:
            ts = self.now_us()
        self.emit(TraceEvent(name=name, ph="e", ts=ts, track=track, id=id,
                             args=args))

    def flow_start(self, name: str, track: str, id: int,
                   ts: Optional[float] = None, **args) -> None:
        if ts is None:
            ts = self.now_us()
        self.emit(TraceEvent(name=name, ph="s", ts=ts, track=track, id=id,
                             args=args))

    def flow_step(self, name: str, track: str, id: int,
                  ts: Optional[float] = None, **args) -> None:
        if ts is None:
            ts = self.now_us()
        self.emit(TraceEvent(name=name, ph="t", ts=ts, track=track, id=id,
                             args=args))

    def flow_end(self, name: str, track: str, id: int,
                 ts: Optional[float] = None, **args) -> None:
        if ts is None:
            ts = self.now_us()
        self.emit(TraceEvent(name=name, ph="f", ts=ts, track=track, id=id,
                             args=args))

    def span(self, name: str, track: str, *, ring: bool = True,
             **args) -> _Span:
        """``with tracer.span("drain", "dma0", n=8): ...``.

        Enters ``TraceAnnotation(name)`` and adds to ``name``'s totals;
        the time of spans opened inside it (on the same thread) is taken
        off its self time.  ``ring`` is the hook site's sampling decision:
        when true, the closed span also enters the ring as an "X" event.
        """
        return _Span(self, name, track, ring, args)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the exact counter ``name``."""
        self._counts[name] = self._counts.get(name, 0) + n

    def _open(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def next_flow_id(self) -> int:
        """Fresh process-unique id for one flow arrow (s -> t -> f)."""
        fid = self._next_flow
        self._next_flow += 1
        return fid

    # -- reading -----------------------------------------------------------

    @property
    def dropped(self) -> int:
        return self.emitted - len(self._buf)

    def events(self) -> List[TraceEvent]:
        return list(self._buf)

    def totals(self) -> Dict[str, Dict[str, dict]]:
        """Exact totals since construction or :meth:`clear`:
        ``{"spans": {name: {"count", "total_s", "self_s"}},
        "counters": {name: n}}``."""
        return {
            "spans": {k: {"count": c, "total_s": t, "self_s": own}
                      for k, (c, t, own) in self._spans.items()},
            "counters": dict(self._counts),
        }

    def clear(self) -> None:
        """Empty the ring and reset the totals and counters."""
        self._buf.clear()
        self.emitted = 0
        self._spans.clear()
        self._counts.clear()
