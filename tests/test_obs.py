"""Observability subsystem: tracer, histograms, Perfetto export, overhead.

No hypothesis dependency — this module must collect on minimal installs.
The merge-algebra property suite lives in test_obs_properties.py (slow).
"""
import json
import time

import numpy as np
import pytest

from repro.obs import (
    Histogram,
    MetricsRegistry,
    Tracer,
    chrome_trace,
    write_chrome_trace,
    write_metrics_jsonl,
)
from repro.obs.trace import TraceEvent
from repro.runtime.instrumentation import PerfProbe


# ---------------------------------------------------------------------------
# Histogram bucket layout (DESIGN.md §8)
# ---------------------------------------------------------------------------

def test_bucket_layout_linear_below_max_exact_log2_above():
    h = Histogram(max_exact=64, log2_buckets=8)
    # width-1 linear region: bucket i holds exactly integer i
    assert h.bucket_index(0) == 0
    assert h.bucket_index(63) == 63
    assert h.bucket_index(63.9) == 63
    assert h.bucket_lo(17) == 17.0
    # log2 region: [64,128) -> 64, [128,256) -> 65, ...
    assert h.bucket_index(64) == 64
    assert h.bucket_index(127.9) == 64
    assert h.bucket_index(128) == 65
    assert h.bucket_index(255) == 65
    assert h.bucket_index(256) == 66
    assert h.bucket_lo(64) == 64.0
    assert h.bucket_lo(65) == 128.0
    # overflow clamps into the last bucket; negatives clamp to bucket 0
    assert h.bucket_index(1e30) == 64 + 8 - 1
    assert h.bucket_index(-5) == 0
    # every boundary is self-consistent: lo(idx(lo(i))) == lo(i)
    for i in range(len(h.counts)):
        lo = h.bucket_lo(i)
        assert h.bucket_index(lo) == i


def test_small_integer_percentiles_match_numpy_inverted_cdf():
    """Below max_exact the buckets are width-1, so nearest-rank percentiles
    are *exact* — bit-equal to numpy's inverted_cdf method."""
    rng = np.random.default_rng(7)
    samples = rng.integers(0, 64, 500)
    h = Histogram()
    for v in samples:
        h.record(int(v))
    for q in (1, 25, 50, 90, 95, 99, 100):
        assert h.percentile(q) == float(
            np.percentile(samples, q, method="inverted_cdf")), q
    assert h.mean == pytest.approx(float(np.mean(samples)))
    assert h.min == float(samples.min()) and h.max == float(samples.max())


def test_log2_percentile_is_lower_bucket_bound():
    h = Histogram(max_exact=64)
    for v in (100, 100, 100, 100):      # all land in [64, 128)
        h.record(v)
    assert h.percentile(50) == 64.0     # floor estimate, <=2x wide


def test_empty_histogram_reads_zero():
    h = Histogram()
    assert h.percentile(50) == 0.0 and h.percentile(99) == 0.0
    assert h.mean == 0.0
    snap = h.snapshot()
    assert snap["n"] == 0 and snap["min"] == 0.0 and snap["max"] == 0.0


def test_merge_is_order_free_and_layout_checked():
    a, b = Histogram(), Histogram()
    for v in (1, 2, 3, 100):
        a.record(v)
    for v in (3, 5, 2000):
        b.record(v)
    ab = Histogram.from_snapshot(a.snapshot())
    ab.merge(b)
    ba = Histogram.from_snapshot(b.snapshot())
    ba.merge(a)
    assert ab.counts == ba.counts
    assert (ab.n, ab.min, ab.max) == (ba.n, ba.min, ba.max)
    assert ab.total == pytest.approx(ba.total)
    for q in (50, 95, 99):
        assert ab.percentile(q) == ba.percentile(q)
    with pytest.raises(ValueError, match="bucket layouts"):
        a.merge(Histogram(max_exact=32))


def test_snapshot_roundtrip_is_json_safe_and_lossless():
    h = Histogram()
    for v in (4, 9, 9, 77, 3000):
        h.record(v)
    snap = json.loads(json.dumps(h.snapshot()))
    back = Histogram.from_snapshot(snap)
    assert back.counts == h.counts
    assert back.snapshot() == h.snapshot()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_kind_conflicts():
    r = MetricsRegistry()
    r.counter("events").inc(3)
    assert r.counter("events").value == 3          # same instrument back
    r.gauge("depth").set(2)
    r.gauge("depth").set(5)
    assert r.gauge("depth").peak == 5.0
    r.histogram("lat").record(7)
    with pytest.raises(TypeError, match="events"):
        r.gauge("events")
    assert sorted(r.names()) == ["depth", "events", "lat"]


def test_registry_merge_folds_disjoint_and_overlapping_shards():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("reqs").inc(2)
    a.histogram("lat").record(3)
    b.counter("reqs").inc(5)
    b.counter("only_b").inc(1)
    b.histogram("lat").record(9)
    b.gauge("occ").set(4)
    a.merge(b)
    assert a.counter("reqs").value == 7
    assert a.counter("only_b").value == 1
    assert a.histogram("lat").n == 2
    assert a.gauge("occ").peak == 4.0


def test_metrics_jsonl_dump_is_sorted_valid_json(tmp_path):
    r = MetricsRegistry()
    r.counter("z").inc()
    r.histogram("a").record(2)
    p = tmp_path / "m.jsonl"
    n = write_metrics_jsonl(str(p), r,
                            extra={"mid": {"type": "counter", "value": 9}})
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert n == len(lines) == 3
    assert [ln["name"] for ln in lines] == ["a", "mid", "z"]
    assert lines[0]["type"] == "histogram" and lines[0]["n"] == 1


# ---------------------------------------------------------------------------
# Tracer: ring bound, deterministic sampling, span helpers
# ---------------------------------------------------------------------------

def test_ring_is_bounded_and_dropped_is_exact():
    tr = Tracer(capacity=4)
    for k in range(10):
        tr.instant("e", "t", ts=float(k))
    assert len(tr.events()) == 4
    assert tr.emitted == 10 and tr.dropped == 6
    assert [e.ts for e in tr.events()] == [6.0, 7.0, 8.0, 9.0]
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_sampling_is_deterministic_seeded_and_rate_shaped():
    a = Tracer(sample_rate=0.25, seed=3)
    b = Tracer(sample_rate=0.25, seed=3)
    c = Tracer(sample_rate=0.25, seed=4)
    keys = [("req", i) for i in range(2000)]
    da = [a.sampled(k) for k in keys]
    assert da == [b.sampled(k) for k in keys]       # same seed, same decisions
    assert da != [c.sampled(k) for k in keys]       # seed actually matters
    frac = sum(da) / len(da)
    assert 0.18 < frac < 0.32
    assert all(Tracer(sample_rate=1.0).sampled(k) for k in keys)
    assert not any(Tracer(sample_rate=0.0).sampled(k) for k in keys)


def test_span_contextmanager_and_flow_ids():
    tr = Tracer()
    with tr.span("work", "ch0", n=3):
        pass
    (ev,) = tr.events()
    assert ev.ph == "X" and ev.name == "work" and ev.track == "ch0"
    assert ev.dur >= 0.0 and ev.args == {"n": 3}
    assert tr.next_flow_id() == 1 and tr.next_flow_id() == 2


# ---------------------------------------------------------------------------
# Chrome/Perfetto export
# ---------------------------------------------------------------------------

def _mixed_events():
    return [
        TraceEvent(name="launch", ph="X", ts=1000.0, track="ch0", dur=5.0),
        TraceEvent(name="launch", ph="X", ts=1010.0, track="ch1", dur=2.0),
        TraceEvent(name="done", ph="i", ts=1012.0, track="ch0"),
        TraceEvent(name="hop", ph="s", ts=1003.0, track="ch0", id=7),
        TraceEvent(name="hop", ph="f", ts=1011.0, track="ch1", id=7),
        TraceEvent(name="payload", ph="X", ts=500.0, track="sim/ch0",
                   dur=8.0, clock="cycle", args={"transfer": 0}),
    ]


def test_chrome_trace_tracks_pids_and_per_clock_normalization(tmp_path):
    doc = write_chrome_trace(str(tmp_path / "t.json"), _mixed_events())
    # the written file is valid JSON and identical to the returned doc
    assert json.loads((tmp_path / "t.json").read_text()) == doc
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"ch0", "ch1", "sim/ch0"}
    assert len({m["pid"] for m in meta}) == 3       # one pid per track
    # wall events normalize to the earliest wall ts; cycle events to the
    # earliest cycle ts — independent domains
    wall = [e for e in evs if e["ph"] != "M" and e.get("cat") != "flow"
            and e["cat"] == "wall"]
    assert min(e["ts"] for e in wall) == 0.0
    cyc = [e for e in evs if e.get("cat") == "cycle" and e["ph"] != "M"]
    assert min(e["ts"] for e in cyc) == 0.0
    # X spans carry dur; flows carry id + slice binding
    assert all("dur" in e for e in evs if e["ph"] == "X")
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert flows and all(e["bp"] == "e" and e["id"] == 7
                         and e["cat"] == "flow" for e in flows)


def test_chrome_trace_instants_are_thread_scoped():
    doc = chrome_trace(_mixed_events())
    inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert inst and all(e["s"] == "t" for e in inst)


# ---------------------------------------------------------------------------
# PerfProbe: metrics registry rides the same hooks; reset clears everything
# ---------------------------------------------------------------------------

def test_probe_metrics_ride_hooks_and_stay_out_of_gated_snapshot():
    p = PerfProbe()
    p.on_submit("dma0", n_in=4, n_out=2, launch_seconds=1e-4, hit_rate=0.9)
    p.on_drain("dma0", n_descriptors=2, seconds=2e-4)
    p.on_occupancy("dma0", 3)
    p.on_serve_step(2, 1e-3)
    p.on_serve_completion(latency_steps=4)
    p.on_request_latency(11)
    m = p.metrics_snapshot()
    assert m["launch_us"]["n"] == 1
    assert m["drain_us"]["n"] == 1
    assert m["serve_step_us"]["n"] == 1
    assert m["poll_latency_steps"]["p50"] == 4.0
    assert m["request_latency_steps"]["p50"] == 11.0
    assert m["ring_occupancy.dma0"]["peak"] == 3.0
    # the gated snapshot keeps its deterministic schema: no histograms
    snap = p.snapshot()
    assert set(snap) == {"channels", "serve", "translation"}
    assert not any(isinstance(v, dict) and v.get("type") == "histogram"
                   for v in snap["channels"]["dma0"].values())


def test_probe_reset_clears_channels_serve_translation_and_metrics():
    p = PerfProbe()
    p.on_submit("dma0", n_in=1, n_out=1, launch_seconds=1e-5)
    p.on_translation("hit")
    p.on_serve_step(1, 1e-4)
    p.on_request_latency(3)
    p.reset()
    assert p.channels == {}
    assert p.serve.steps == 0 and p.serve.step_seconds == 0.0
    assert p.translation.hits == 0
    assert p.metrics_snapshot() == {}
    # the same object keeps counting after reset (fresh window)
    p.on_submit("dma0", n_in=1, n_out=1, launch_seconds=1e-5)
    assert p.channels["dma0"].submits == 1


# ---------------------------------------------------------------------------
# End-to-end: the seeded recorder produces full lifecycle traces
# ---------------------------------------------------------------------------

def test_recorded_serve_trace_covers_every_lifecycle_phase(tmp_path):
    from repro.obs.record import record_serve_trace
    tracer, probe, pc = record_serve_trace(0, mesh=1)
    evs = tracer.events()
    names = {e.name for e in evs}
    assert {"request", "request.submit", "serve.step", "writeback",
            "delivered", "payload"} <= names
    # every request's async begin has a matching end, correlated by uid
    begins = {e.id for e in evs if e.ph == "b" and e.name == "request"}
    ends = {e.id for e in evs if e.ph == "e" and e.name == "request"}
    assert begins == ends and len(begins) == 6
    # cycle-clock events live on their own tracks, wall events on theirs
    assert {e.track for e in evs if e.clock == "cycle"} == \
        {"sim/ch0", "sim/ch1"}
    assert all(e.clock == "wall" for e in evs
               if not e.track.startswith("sim/"))
    # the whole thing exports as loadable JSON
    doc = write_chrome_trace(str(tmp_path / "serve.trace.json"), evs)
    assert json.loads((tmp_path / "serve.trace.json").read_text()) == doc
    # histograms rode along on the probe
    assert probe.metrics_snapshot()["request_latency_steps"]["n"] == 6
    assert pc["serve.request_latency_steps_p50"] > 0


def test_recorded_trace_is_deterministic_in_seed():
    from repro.obs.record import record_serve_trace

    def shape(seed):
        tr, _, _ = record_serve_trace(seed, mesh=1, simulate=False)
        return [(e.name, e.ph, e.track, e.id) for e in tr.events()]

    assert shape(0) == shape(0)


def test_mesh2_trace_links_migration_hops_with_flow_arrows(tmp_path):
    from repro.obs.record import record_serve_trace
    tracer, _, pc = record_serve_trace(0, mesh=2)
    evs = tracer.events()
    names = {e.name for e in evs}
    assert {"migrate.egress", "migrate.fabric", "migrate.ingress",
            "submit", "drain", "request", "writeback"} <= names
    # hop spans land on per-shard migrate tracks plus the shared fabric
    mig_tracks = {e.track for e in evs if e.name.startswith("migrate.")}
    assert "fabric" in mig_tracks
    assert any(t.startswith("shard") and t.endswith("/migrate")
               for t in mig_tracks)
    # each flow id forms a complete s -> t -> f chain
    chains = {}
    for e in evs:
        if e.ph in ("s", "t", "f"):
            chains.setdefault(e.id, set()).add(e.ph)
    assert chains and all(phs == {"s", "t", "f"}
                          for phs in chains.values())
    # hop spans carry the originating request uid via trace_context
    egress = [e for e in evs if e.name == "migrate.egress"]
    assert egress and all("uid" in e.args and "src_shard" in e.args
                          and "dst_shard" in e.args for e in egress)
    # per-shard serve tracks exist and the mesh-wide latency gated metrics
    # agree with the merged histogram snapshot
    assert {"shard0/serve", "shard1/serve"} <= {e.track for e in evs}
    assert pc["sharded.request_latency_steps"]["n"] == 6
    write_chrome_trace(str(tmp_path / "mesh2.trace.json"), evs)


# ---------------------------------------------------------------------------
# The off-path overhead guard (DESIGN.md §8: off-by-default-cheap)
# ---------------------------------------------------------------------------

def test_disabled_tracer_dispatch_overhead_within_two_percent():
    """An attached-but-sampled-out tracer must cost <= 2% over no tracer
    at all on the warm dispatch path. Min-of-interleaved-rounds with
    retries keeps the bound meaningful on noisy CI machines."""
    import jax.numpy as jnp

    from repro.core.chain import from_segments
    from repro.runtime import SubmitRequest, default_runtime

    pool, n_desc = 1 << 14, 128
    rng = np.random.default_rng(0)
    d = from_segments(rng.integers(0, pool - 64, n_desc),
                      rng.integers(0, pool - 64, n_desc),
                      rng.integers(1, 64, n_desc))

    def make(tracer):
        rt = default_runtime(2, tier="serial", ring_capacity=n_desc + 1,
                             max_len=64)
        rt.register_pool("src", jnp.zeros(pool, jnp.float32))
        rt.register_pool("dst", jnp.zeros(pool, jnp.float32))
        if tracer is not None:
            rt.attach_tracer(tracer)
        return rt

    def dispatch(rt):
        t0 = time.perf_counter()
        rt.submit(SubmitRequest(chain=d, src_pool="src", dst_pool="dst"))
        rt.drain_until_idle()
        return time.perf_counter() - t0

    rt_none = make(None)
    rt_off = make(Tracer(sample_rate=0.0, seed=0))
    dispatch(rt_none), dispatch(rt_off)      # warm translation caches
    ratios = []
    for _ in range(4):                       # retries absorb machine noise
        none = [dispatch(rt_none) for _ in range(7)]
        off = [dispatch(rt_off) for _ in range(7)]
        ratios.append(min(off) / min(none))
        if ratios[-1] <= 1.02:
            return
    pytest.fail(f"disabled-tracer dispatch overhead exceeded 2% in every "
                f"attempt: ratios={[f'{r:.4f}' for r in ratios]}")


# ---------------------------------------------------------------------------
# Profiler-clock spans: exact totals, counters, the runtime's span tree
# ---------------------------------------------------------------------------

class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span
    entered with its parent (the innermost span open at the time)."""

    def __init__(self):
        self.entered = []          # (name, parent or None)
        self.kwargs = []
        self._open = []

    def __call__(self, name, **kwargs):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.entered.append((name, rec._open[-1] if rec._open
                                    else None))
                rec.kwargs.append(kwargs)
                rec._open.append(name)

            def __exit__(self, *exc):
                assert rec._open.pop() == name

        return _Ann()


@pytest.fixture
def annotations(monkeypatch):
    import repro.obs.trace as trace_mod
    rec = _Annotations()
    monkeypatch.setattr(trace_mod, "TraceAnnotation", rec)
    return rec


def test_span_totals_count_total_and_self_time(annotations, monkeypatch):
    import repro.obs.trace as trace_mod
    clock = iter([0.0, 1.0, 3.0, 3.5, 4.0, 6.0])
    monkeypatch.setattr(trace_mod, "monotonic", lambda: next(clock))
    tr = Tracer()
    with tr.span("outer", "t"):           # 0.0 .. 6.0
        with tr.span("inner", "t"):       # 1.0 .. 3.0
            pass
        with tr.span("inner", "t"):       # 3.5 .. 4.0
            pass
    spans = tr.totals()["spans"]
    assert spans["outer"] == {"count": 1, "total_s": 6.0, "self_s": 3.5}
    assert spans["inner"] == {"count": 2, "total_s": 2.5, "self_s": 2.5}
    # the profiler sees the bare names, nested, with no metadata
    assert annotations.entered == [("outer", None), ("inner", "outer"),
                                   ("inner", "outer")]
    assert annotations.kwargs == [{}, {}, {}]


def test_counters_and_clear_reset_ring_and_totals(annotations):
    tr = Tracer()
    tr.count("d2h_reads", 3)
    tr.count("d2h_reads")
    tr.count("admissions")
    with tr.span("a", "t", n=1):
        pass
    assert tr.totals()["counters"] == {"d2h_reads": 4, "admissions": 1}
    assert tr.totals()["spans"]["a"]["count"] == 1 and len(tr.events()) == 1
    tr.clear()
    assert tr.totals() == {"spans": {}, "counters": {}}
    assert tr.events() == [] and tr.emitted == 0


def test_totals_stay_exact_when_the_ring_drops(annotations):
    tr = Tracer(capacity=2)
    for _ in range(10):
        with tr.span("s", "t"):
            pass
    with tr.span("unsampled", "t", ring=False):
        pass
    assert len(tr.events()) == 2 and tr.dropped == 8
    spans = tr.totals()["spans"]
    assert spans["s"]["count"] == 10 and spans["unsampled"]["count"] == 1
    assert [e.name for e in tr.events()] == ["s", "s"]


def _serial_runtime():
    import jax.numpy as jnp

    from repro.runtime import ChannelConfig, DMARuntime
    rt = DMARuntime([ChannelConfig("kv", tier="serial", max_len=8,
                                   ring_capacity=64)])
    rt.register_pool("src", jnp.arange(256, dtype=jnp.float32))
    rt.register_pool("dst", jnp.zeros(256, jnp.float32))
    return rt


def _serial_chain(make=None):
    from repro.core.descriptor import DescriptorArray
    make = make or DescriptorArray.create
    return make(np.array([0, 8, 32]), np.array([64, 72, 80]),
                np.array([8, 8, 8]))


def _round(rt, chain, src="src", dst="dst", channel=None):
    from repro.runtime import SubmitRequest
    rt.submit(SubmitRequest(chain=chain, src_pool=src, dst_pool=dst,
                            channel=channel, on_complete=lambda r: None))
    rt.drain_until_idle()
    return rt.poll()


def test_no_annotation_without_a_tracer(annotations):
    rt = _serial_runtime()
    assert _round(rt, _serial_chain())
    assert annotations.entered == []


def _rows_runtime(use_kernel):
    import jax.numpy as jnp

    from repro.runtime import ChannelConfig, DMARuntime
    rt = DMARuntime([ChannelConfig("rows", tier="blocked_2d",
                                   use_kernel=use_kernel, ring_capacity=64)])
    rt.register_pool("src", jnp.arange(32 * 128, dtype=jnp.float32)
                     .reshape(32, 128))
    rt.register_pool("dst", jnp.zeros((32, 128), jnp.float32))
    return rt


def _rows_chain(make=None):
    from repro.core.descriptor import DescriptorArray
    make = make or DescriptorArray.create
    return make([3, 7, 1, 20], [0, 5, 9, 30], [1, 1, 1, 1])


# Each case: the runtime, its chain, and the spans entered with their
# parents. A device-built chain crosses to the host once, as submit takes
# it (d2h_reads 1); a host-form chain never does (0).
_CASES = {
    "serial": (_serial_runtime, _serial_chain, {
        ("submit", None), ("coalesce", "submit"),
        ("translate.plan", "coalesce"),
        ("translate.compile", "translate.plan"),
        ("ring.push", "submit"), ("ring.pack", "ring.push"),
        ("drain", None), ("drain.pull", "drain"),
        ("drain.enqueue", "drain"), ("completion.poll", None)}),
    "blocked_2d_kernel": (lambda: _rows_runtime(True), _rows_chain, {
        ("submit", None), ("ring.push", "submit"),
        ("ring.pack", "ring.push"), ("drain", None),
        ("drain.pull", "drain"), ("drain.enqueue", "drain"),
        ("completion.poll", None)}),
    "blocked_2d_fused": (lambda: _rows_runtime(False), _rows_chain, {
        ("submit", None), ("ring.push", "submit"),
        ("ring.pack", "ring.push"), ("drain", None),
        ("drain.pull", "drain"), ("drain.enqueue", "drain"),
        ("completion.poll", None)}),
}


@pytest.mark.parametrize("form,reads", [("device", 1), ("host", 0)])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_runtime_span_tree_and_d2h_reads(annotations, case, form, reads):
    from repro.core.descriptor import DescriptorArray
    make_rt, make_chain, tree = _CASES[case]
    build = (DescriptorArray.create if form == "device"
             else DescriptorArray.from_host)
    rt = make_rt()
    want = rt.pools["dst"]
    tr = Tracer()
    rt.attach_tracer(tr)
    assert _round(rt, make_chain(build))
    assert set(annotations.entered) == tree
    tot = tr.totals()
    assert tot["counters"] == ({"d2h_reads": reads} if reads else {})
    assert tot["spans"]["submit"]["count"] == 1
    assert tot["spans"]["drain"]["count"] == 1
    assert all(v["self_s"] <= v["total_s"] for v in tot["spans"].values())
    # the traced drain moved the same data as an untraced one
    ref = make_rt()
    _round(ref, make_chain())
    np.testing.assert_array_equal(np.asarray(rt.pools["dst"]),
                                  np.asarray(ref.pools["dst"]))
    assert not np.array_equal(np.asarray(rt.pools["dst"]), np.asarray(want))


def test_serve_step_spans_and_admissions(annotations):
    import jax

    from repro.configs.registry import get_config
    from repro.models import init_params
    from repro.runtime import SubmitRequest
    from repro.serve import Request, ServeEngine

    cfg = get_config("qwen2.5-3b", reduced=True)
    eng = ServeEngine(init_params(jax.random.PRNGKey(0), cfg), cfg,
                      capacity=2, max_len=16)
    tr = Tracer()
    eng.attach_tracer(tr)
    for uid in range(3):
        eng.submit(SubmitRequest(request=Request(
            uid=uid, prompt=[1 + uid, 2], max_new_tokens=2)))
    eng.run(max_steps=20)
    assert len(eng.completed) == 3
    assert {("serve.admit", "serve.step"), ("serve.dispatch", "serve.step"),
            ("serve.sync", "serve.step")} <= set(annotations.entered)
    tot = tr.totals()
    assert tot["counters"]["admissions"] == 3
    for name in ("serve.step", "serve.admit", "serve.dispatch",
                 "serve.sync"):
        assert tot["spans"][name]["count"] == eng.steps
    step = tot["spans"]["serve.step"]
    parts = sum(tot["spans"][n]["total_s"]
                for n in ("serve.admit", "serve.dispatch", "serve.sync"))
    assert step["self_s"] == pytest.approx(step["total_s"] - parts)
    assert [e.name for e in tr.events() if e.name == "serve.step"] \
        == ["serve.step"] * eng.steps
