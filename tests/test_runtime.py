"""Runtime subsystem: rings, channels, coalescer, completions, scheduler.

No hypothesis dependency — this module must collect on minimal installs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import descriptor as D
from repro.core.chain import from_segments
from repro.core.engine import (
    completion_events,
    execute_blocked_2d,
    execute_chain_host,
    execute_serial,
)
from repro.core.simulator import simulate, simulate_multichannel, SimConfig
from repro.runtime import (
    ChannelConfig,
    CompletionQueue,
    CompletionRecord,
    DMARuntime,
    RingFull,
    RoundRobinArbiter,
    SubmissionRing,
    SubmitRequest,
    WeightedArbiter,
    coalesce,
    default_runtime,
)


# ---------------------------------------------------------------------------
# Completion semantics (§II-D)
# ---------------------------------------------------------------------------

def test_completion_events_irq_masking():
    before = jnp.asarray([0, 0, 1, 0])
    after = jnp.asarray([1, 1, 1, 0])
    irq = jnp.asarray([1, 0, 1, 1])
    ev = np.asarray(completion_events(before, after, irq))
    # Only newly-done AND irq-enabled descriptors raise events: index 0.
    # Index 1 completed without IRQ; 2 was already done; 3 didn't complete.
    np.testing.assert_array_equal(ev, [True, False, False, False])


def test_mark_done_roundtrip_through_packed_forms():
    d = D.DescriptorArray.create([0, 8, 16], [32, 40, 48], [8, 8, 8])
    d = d.mark_done(1)
    tab = D.to_packed(d, elem_bytes=4, src_base=0x100, dst_base=0x200,
                      table_base=0x1000)
    # The done entry carries the all-ones writeback in its first 8 bytes.
    np.testing.assert_array_equal(D.is_done_packed(tab),
                                  [False, True, False])
    back = D.from_packed(tab, elem_bytes=4, src_base=0x100, dst_base=0x200,
                         table_base=0x1000)
    np.testing.assert_array_equal(np.asarray(back.done), np.asarray(d.done))
    keep = np.asarray(d.done) == 0
    for f in ("src", "dst", "length", "nxt"):
        np.testing.assert_array_equal(
            np.asarray(getattr(back, f))[keep],
            np.asarray(getattr(d, f))[keep], err_msg=f)
    # And marking the packed form is observable without any side state.
    D.mark_done_packed(tab, 2)
    np.testing.assert_array_equal(D.is_done_packed(tab),
                                  [False, True, True])


# ---------------------------------------------------------------------------
# Submission ring
# ---------------------------------------------------------------------------

def _one_packed(uid):
    return D.pack([8], [0], [D.END_OF_CHAIN], [uid], [0])[0]


def test_ring_wraparound_preserves_fifo_tickets():
    ring = SubmissionRing(4)
    retired = []
    ticket = 0
    for _ in range(5):   # 10 entries through a 4-slot ring
        for _ in range(2):
            ring.push(_one_packed(ticket), ticket)
            ticket += 1
        for slot in list(ring.live_slots()):
            ring.mark_done(int(slot))
        retired.extend(e.ticket for e in ring.retire())
    assert retired == list(range(10))
    assert ring.empty and ring.head == ring.tail == 10


def test_ring_full_backpressure_and_inorder_retirement():
    ring = SubmissionRing(2)
    ring.push(_one_packed(0), 0)
    ring.push(_one_packed(1), 1)
    with pytest.raises(RingFull):
        ring.push(_one_packed(2), 2)
    # Completing the *younger* entry does not retire it past the older one.
    ring.mark_done_ticket(1)
    assert ring.retire() == []
    ring.mark_done_ticket(0)
    assert [e.ticket for e in ring.retire()] == [0, 1]
    ring.push(_one_packed(2), 2)   # slot freed


# ---------------------------------------------------------------------------
# Arbitration
# ---------------------------------------------------------------------------

def test_round_robin_fairness():
    arb = RoundRobinArbiter(["a", "b", "c"])
    picks = [arb.pick(["a", "b", "c"]) for _ in range(9)]
    assert picks == ["a", "b", "c"] * 3
    # Ineligible channels are skipped without losing rotation fairness.
    picks = [arb.pick(["b", "c"]) for _ in range(4)]
    assert picks == ["b", "c", "b", "c"]


def test_weighted_arbiter_proportional_and_smooth():
    weights = {"a": 3, "b": 2, "c": 1}
    arb = WeightedArbiter(weights)
    picks = [arb.pick(list(weights)) for _ in range(600)]
    counts = {k: picks.count(k) for k in weights}
    assert counts == {"a": 300, "b": 200, "c": 100}
    # Smoothness: no 3-burst of the heavy channel inside one 6-pick cycle.
    assert "".join(p for p in picks[:6]).count("aa") <= 1


# ---------------------------------------------------------------------------
# Coalescer
# ---------------------------------------------------------------------------

def test_coalescer_merges_contiguous_and_matches_oracle():
    # 12 page-sized segments forming 3 contiguous runs.
    unit = 8
    runs = [(0, 4), (64, 5), (200, 3)]
    srcs, dsts, cursor = [], [], 0
    for base, n in runs:
        for k in range(n):
            srcs.append(base + k * unit)
            dsts.append(cursor)
            cursor += unit
    d = from_segments(srcs, dsts, [unit] * len(srcs))
    planned, stats = coalesce(d, max_len=1 << 16)
    assert stats.n_in == 12 and stats.n_out == 3
    assert stats.merge_ratio == pytest.approx(4.0)
    assert stats.output_hit_rate == 1.0

    rng = np.random.default_rng(0)
    src = rng.standard_normal(512).astype(np.float32)
    dst = np.zeros(256, np.float32)
    want, _ = execute_chain_host(d, src, dst)
    got, _ = execute_chain_host(planned, src, dst)
    np.testing.assert_array_equal(got, want)


def test_coalescer_splits_over_max_len_and_matches_oracle():
    d = from_segments([0], [0], [70])
    planned, stats = coalesce(d, max_len=32)
    assert stats.n_out == 3
    assert np.asarray(planned.length).max() <= 32
    assert int(np.asarray(planned.length).sum()) == 70
    src = np.arange(70, dtype=np.float32)
    want, _ = execute_chain_host(d, src, np.zeros(70, np.float32))
    got, _ = execute_chain_host(planned, src, np.zeros(70, np.float32))
    np.testing.assert_array_equal(got, want)


def test_coalescer_respects_irq_barrier_and_nonsequential_chains():
    # Array order [B, C, D, A]; chain order A -> B -> C -> D covers
    # [0..8) [8..16) [16..24) [24..32): all four abut, but A raises an
    # IRQ, so A|B stays split while B+C+D fuse.
    d = D.DescriptorArray.create(
        [8, 16, 24, 0], [8, 16, 24, 0], [8, 8, 8, 8], nxt=[1, 2, -1, 0],
        config=[0, 0, 0, int(D.CONFIG_IRQ_ENABLE)])
    planned, stats = coalesce(d, max_len=64, head=3)
    assert stats.n_out == 2
    assert stats.merged == 2
    src = np.arange(64, dtype=np.float32)
    want, _ = execute_chain_host(d, src, np.zeros(64, np.float32), head=3)
    got, _ = execute_chain_host(planned, src, np.zeros(64, np.float32))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Scheduler: multi-channel drain vs oracle (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # >=4-channel drain/sim: CI slow job
def test_four_channels_drain_irregular_transfers_bit_identical():
    rt = default_runtime(4, tier="serial", max_len=16, ring_capacity=32)
    rng = np.random.default_rng(7)
    pool = 2048
    src = rng.standard_normal(pool).astype(np.float32)
    dst = rng.standard_normal(pool).astype(np.float32)
    rt.register_pool("src", jnp.asarray(src))
    rt.register_pool("dst", jnp.asarray(dst))

    oracle = dst.copy()
    chans = set()
    for k in range(16):   # 16 interleaved submissions over 4 channels
        n = int(rng.integers(1, 7))
        lens = rng.integers(1, 13, n)
        s = rng.integers(0, pool - 16, n)
        # Disjoint destination windows per submission: result is
        # order-independent across channels (within-chain order still
        # exercised by overlapping in-chain writes below).
        t = k * 120 + np.concatenate([[0], np.cumsum(lens[:-1])])
        d = from_segments(s, t, lens)
        res = rt.submit(SubmitRequest(chain=d, src_pool="src",
                                      dst_pool="dst"))
        chans.add(res.channel)
        oracle, _ = execute_chain_host(d, src, oracle)

    assert len(chans) == 4          # all four channels carried work
    rt.drain_until_idle()
    np.testing.assert_array_equal(np.asarray(rt.pool("dst")), oracle)
    st = rt.stats()
    assert st["submitted_descriptors"] > 0
    assert all(c["retired"] == c["submitted"]
               for c in st["channels"].values())


def test_scheduler_coalesces_contiguous_page_workload():
    rt = default_runtime(1, tier="serial", max_len=2048)
    rt.register_pool("src", jnp.arange(4096, dtype=jnp.float32))
    rt.register_pool("dst", jnp.zeros(4096, jnp.float32))
    unit = 32
    d = from_segments(np.arange(64) * unit, np.arange(64) * unit,
                      [unit] * 64)   # fully contiguous page run
    res = rt.submit(SubmitRequest(chain=d, src_pool="src", dst_pool="dst"))
    assert res.coalesce is not None
    assert res.coalesce.n_out < res.coalesce.n_in  # coalescer shrank it
    assert res.coalesce.n_out == 1
    rt.drain_until_idle()
    np.testing.assert_array_equal(np.asarray(rt.pool("dst"))[:64 * unit],
                                  np.arange(64 * unit, dtype=np.float32))
    assert rt.stats()["coalesce_merge_ratio"] == pytest.approx(64.0)


def test_backpressure_block_drains_ring():
    rt = DMARuntime([ChannelConfig(name="c0", tier="serial",
                                   ring_capacity=4, max_len=8)],
                    backpressure="block")
    rt.register_pool("src", jnp.arange(64, dtype=jnp.float32))
    rt.register_pool("dst", jnp.zeros(64, jnp.float32))
    for k in range(6):   # 6 single-descriptor chains through a 4-slot ring
        rt.submit(SubmitRequest(chain=from_segments([k * 8], [k * 8], [8]),
                                src_pool="src", dst_pool="dst",
                                run_coalescer=False))
    rt.drain_until_idle()
    np.testing.assert_array_equal(np.asarray(rt.pool("dst"))[:48],
                                  np.arange(48, dtype=np.float32))


def test_backpressure_spill_replays_on_drain():
    rt = DMARuntime([ChannelConfig(name="c0", tier="serial",
                                   ring_capacity=2, max_len=8)],
                    backpressure="spill")
    rt.register_pool("src", jnp.arange(64, dtype=jnp.float32))
    rt.register_pool("dst", jnp.zeros(64, jnp.float32))
    spilled = 0
    for k in range(6):
        res = rt.submit(
            SubmitRequest(chain=from_segments([k * 8], [k * 8], [8]),
                          src_pool="src", dst_pool="dst",
                          run_coalescer=False))
        spilled += res.spilled
    assert spilled > 0
    rt.drain_until_idle()
    assert rt.stats()["spilled"] == 0
    np.testing.assert_array_equal(np.asarray(rt.pool("dst"))[:48],
                                  np.arange(48, dtype=np.float32))


def test_control_channel_out_of_band_completion_and_callbacks():
    rt = DMARuntime([ChannelConfig(name="done", tier="control",
                                   ring_capacity=8)])
    seen = []
    r0 = rt.submit_control(payload=11, channel="done",
                           on_complete=lambda rec: seen.append(rec.ticket))
    r1 = rt.submit_control(payload=22, channel="done")
    rt.drain_all()
    assert rt.poll() == []           # nothing written back yet
    rt.complete(r0.tickets[-1])
    rt.complete(r1.tickets[-1])
    rt.drain_all()
    recs = rt.poll()
    assert [r.ticket for r in recs] == [r0.tickets[-1], r1.tickets[-1]]
    assert seen == [r0.tickets[-1]]  # callback fired exactly once


def test_completion_queue_only_events_irq_or_callbacked():
    q = CompletionQueue()
    ring = SubmissionRing(4)
    ring.push(_one_packed(0), 0, irq=True)
    ring.push(_one_packed(1), 1, irq=False)
    for s in ring.live_slots():
        ring.mark_done(int(s))
    q.post_retired("ch", ring.retire())
    assert [r.ticket for r in q.poll()] == [0]
    assert q.dropped_irqless == 1


# ---------------------------------------------------------------------------
# Pallas-kernel-driven drain and fused 2d drain
# ---------------------------------------------------------------------------

def _row_move_fixture(rng, rows=16, unit=8):
    src = rng.standard_normal((rows, unit)).astype(np.float32)
    dst = np.zeros((rows, unit), np.float32)
    perm = rng.permutation(rows)
    d = D.DescriptorArray.create(perm, np.arange(rows), np.ones(rows))
    return src, dst, perm, d


def test_channel_drain_via_pallas_kernel_matches_blocked_2d():
    rng = np.random.default_rng(3)
    src, dst, perm, d = _row_move_fixture(rng)
    outs = {}
    for use_kernel in (False, True):
        rt = DMARuntime([ChannelConfig(name="c0", tier="blocked_2d",
                                       use_kernel=use_kernel)])
        rt.register_pool("src", jnp.asarray(src))
        rt.register_pool("dst", jnp.asarray(dst))
        rt.submit(SubmitRequest(chain=d, src_pool="src", dst_pool="dst"))
        rt.drain_until_idle()
        outs[use_kernel] = np.asarray(rt.pool("dst"))
    np.testing.assert_array_equal(outs[False], src[perm])
    np.testing.assert_array_equal(outs[True], outs[False])


@pytest.mark.slow  # >=4-channel drain/sim: CI slow job
def test_fused_2d_drain_across_channels():
    rng = np.random.default_rng(4)
    rows, unit = 32, 4
    src = rng.standard_normal((rows, unit)).astype(np.float32)
    rt = default_runtime(4, tier="blocked_2d")
    rt.register_pool("src", jnp.asarray(src))
    rt.register_pool("dst", jnp.zeros((rows, unit), jnp.float32))
    perm = rng.permutation(rows)
    for part in np.array_split(np.arange(rows), 4):  # 4 chains, 4 channels
        d = D.DescriptorArray.create(perm[part], part, np.ones(len(part)))
        rt.submit(SubmitRequest(chain=d, src_pool="src", dst_pool="dst"))
    rt.drain_all()   # single fused jitted call covers all four channels
    np.testing.assert_array_equal(np.asarray(rt.pool("dst")), src[perm])
    st = rt.stats()["channels"]
    assert sum(c["drained"] for c in st.values()) == rows


def test_chain_longer_than_ring_chunks_instead_of_hanging():
    rt = DMARuntime([ChannelConfig(name="c0", tier="serial",
                                   ring_capacity=4, max_len=8)],
                    backpressure="block")
    rt.register_pool("src", jnp.arange(128, dtype=jnp.float32))
    rt.register_pool("dst", jnp.zeros(128, jnp.float32))
    # 12 descriptors through a 4-slot ring in one submit call.
    d = from_segments(np.arange(12) * 8, np.arange(12) * 8, [8] * 12)
    res = rt.submit(SubmitRequest(chain=d, src_pool="src", dst_pool="dst",
                                  run_coalescer=False))
    assert len(res.tickets) == 12
    rt.drain_until_idle()
    np.testing.assert_array_equal(np.asarray(rt.pool("dst"))[:96],
                                  np.arange(96, dtype=np.float32))
    # A non-sequential serial chain cannot be cut: loud error, no hang.
    bad = D.DescriptorArray.create(np.arange(6) * 8, np.arange(6) * 8,
                                   [8] * 6, nxt=[5, 0, 1, 2, 3, -1])
    with pytest.raises(ValueError, match="not sequentially linked"):
        rt.submit(SubmitRequest(chain=bad, src_pool="src", dst_pool="dst",
                                run_coalescer=False))


def test_fused_2d_drain_respects_cross_batch_dependencies():
    src = np.arange(12, dtype=np.float32).reshape(4, 3)
    rt = DMARuntime([ChannelConfig(name="c0", tier="blocked_2d")])
    rt.register_pool("p", jnp.asarray(src))
    # Dependent moves on one channel: row0 -> row1, then row1 -> row2.
    # Sequential semantics: row2 ends up with the ORIGINAL row0.
    rt.submit(SubmitRequest(chain=D.DescriptorArray.create([0], [1], [1]),
                            src_pool="p", dst_pool="p"))
    rt.submit(SubmitRequest(chain=D.DescriptorArray.create([1], [2], [1]),
                            src_pool="p", dst_pool="p"))
    rt.drain_all()
    got = np.asarray(rt.pool("p"))
    np.testing.assert_array_equal(got[1], src[0])
    np.testing.assert_array_equal(got[2], src[0])   # not the stale row1


def test_ring_live_done_tickets_sees_out_of_order_writeback():
    # A long-running head entry must not hide younger completions from
    # the §II-D table scan (serve poll_completed relies on this).
    ring = SubmissionRing(8)
    ring.push(_one_packed(0), 0)   # old, still running
    ring.push(_one_packed(1), 1)
    ring.mark_done_ticket(1)
    assert ring.retire() == []                 # head-of-line blocked
    assert ring.live_done_tickets() == [1]     # ...but poll sees it


def test_serve_engine_rejects_runtime_without_completion_channel():
    from repro.serve.engine import ServeEngine
    # Validation fires before any model state is built, so params/cfg can
    # be inert placeholders.
    with pytest.raises(ValueError, match="control-tier channel"):
        ServeEngine(params=None, cfg=None,
                    runtime=default_runtime(2, tier="serial", max_len=8))


# ---------------------------------------------------------------------------
# Host-form chains: submit plans, packs and drains without reading back
# ---------------------------------------------------------------------------

_IRQ = int(D.CONFIG_IRQ_ENABLE)


@pytest.fixture
def d2h(monkeypatch):
    """Counts conversions of device arrays to host memory: the ``_value``
    property (``__array__``, ``int``, ``tolist``) and the buffer protocol
    (``np.asarray`` on the CPU backend)."""
    arr_t = type(jnp.zeros(1))
    value, buffer = arr_t._value, arr_t.__buffer__
    n = [0]

    def counted_value(self):
        n[0] += 1
        return value.fget(self)

    def counted_buffer(self, flags):
        n[0] += 1
        return buffer(self, flags)

    monkeypatch.setattr(arr_t, "_value", property(counted_value))
    monkeypatch.setattr(arr_t, "__buffer__", counted_buffer)
    return n


@pytest.mark.parametrize("fields", [
    dict(src=[0, 8, 16], dst=[32, 40, 48], length=[8, 8, 8]),
    dict(src=np.arange(4, dtype=np.int64) * 8, dst=[1, 2, 3, 4],
         length=[8] * 4, nxt=[3, 0, 1, -1], config=[0, 0, _IRQ, 1]),
    dict(src=np.zeros(0, np.int64), dst=np.zeros(0, np.int64),
         length=np.zeros(0, np.int64)),
], ids=["defaults", "linked", "empty"])
def test_host_form_matches_create_and_round_trips(fields):
    dev = D.DescriptorArray.create(**fields)
    host = D.DescriptorArray.from_host(**fields)
    assert host.on_host and not dev.on_host
    assert host.to_host() is host
    for got in (host, dev.to_host()):
        for f in ("src", "dst", "length", "nxt", "config", "done"):
            want = np.asarray(getattr(dev, f))
            assert isinstance(getattr(got, f), np.ndarray)
            assert getattr(got, f).dtype == want.dtype, f
            np.testing.assert_array_equal(getattr(got, f), want, err_msg=f)
    if host.num_descriptors:
        # The writeback keeps each chain in its own form, with equal fields.
        done_h, done_d = host.mark_done(0), dev.mark_done(0)
        assert done_h.on_host and not host.done.any()
        np.testing.assert_array_equal(D.to_packed(done_h),
                                      D.to_packed(done_d))


def _parent_serial(d, pools):
    # The parent runtime: coalesce, the planned chain rebuilt on the device,
    # the serial engine over it.
    planned, _ = coalesce(d, max_len=8)
    planned = D.DescriptorArray.create(planned.src, planned.dst,
                                       planned.length, config=planned.config)
    out, _ = execute_serial(planned, jnp.asarray(pools["src"]),
                            jnp.asarray(pools["dst"]), max_len=8)
    return D.to_packed(planned), out


def _parent_rows(d, pools):
    out, _ = execute_blocked_2d(d, jnp.asarray(pools["src"]),
                                jnp.asarray(pools["dst"]))
    return D.to_packed(d), out


# Chain fields, pools and the parent's result: a serial chain whose first
# two descriptors merge and whose merged run splits at max_len 8; row moves
# with an IRQ on the last; one control descriptor.
_SERIAL = (dict(src=[0, 8, 16, 40], dst=[64, 72, 80, 120], length=[8] * 3
                + [5], config=[0, 0, _IRQ, 0]),
           dict(src=np.arange(256, dtype=np.float32),
                dst=np.zeros(256, np.float32)), _parent_serial)
_ROWS = (dict(src=[3, 7, 1, 12], dst=[0, 5, 9, 14], length=[1] * 4,
              config=[0, 0, 0, _IRQ]),
         dict(src=np.arange(128, dtype=np.float32).reshape(16, 8),
              dst=np.zeros((16, 8), np.float32)), _parent_rows)
_CONTROL = (dict(src=[11], dst=[0], length=[0], nxt=[-1], config=[_IRQ]),
            {}, lambda d, pools: (D.to_packed(d), None))
_ROUTES = {
    "serial_lowered": (dict(tier="serial", max_len=8), True, _SERIAL),
    "serial_legacy": (dict(tier="serial", max_len=8), False, _SERIAL),
    "blocked_2d_kernel": (dict(tier="blocked_2d", use_kernel=True), True,
                          _ROWS),
    "blocked_2d_fused": (dict(tier="blocked_2d"), True, _ROWS),
    "control": (dict(tier="control"), True, _CONTROL),
}


@pytest.mark.parametrize("form", ["device", "host"])
@pytest.mark.parametrize("route", sorted(_ROUTES))
def test_submit_keeps_chains_on_the_host_and_matches_the_parent(d2h, route,
                                                                form):
    kw, translation, (fields, pools, parent) = _ROUTES[route]
    build = (D.DescriptorArray.create if form == "device"
             else D.DescriptorArray.from_host)
    rt = DMARuntime([ChannelConfig("c", ring_capacity=16, **kw)],
                    translation=translation)
    for name, arr in pools.items():
        rt.register_pool(name, jnp.asarray(arr))
    chain = build(**fields)
    want_table, want_dst = parent(D.DescriptorArray.create(**fields), pools)

    before = d2h[0]
    res = rt.submit(SubmitRequest(
        chain=chain, src_pool="src" if pools else None,
        dst_pool="dst" if pools else None, channel="c",
        on_complete=lambda rec: None, run_coalescer=None if pools else False))
    ring = rt.channels["c"].ring
    table = ring.table[ring.live_slots()].copy()
    if not pools:
        rt.complete(res.tickets[-1])
    rt.drain_until_idle()
    rt.drain_all()
    records = rt.poll()
    # One device_get of the six fields for a device-built chain, else none.
    assert d2h[0] - before == (6 if form == "device" else 0)

    np.testing.assert_array_equal(table, want_table)
    irq = (table["config"] & _IRQ) != 0
    last = len(table) - 1
    assert records == [CompletionRecord(t, "c", t, bool(irq[t]))
                       for t in range(len(table)) if irq[t] or t == last]
    if want_dst is not None:
        np.testing.assert_array_equal(np.asarray(rt.pool("dst")),
                                      np.asarray(want_dst))


# ---------------------------------------------------------------------------
# KV-cache page moves through the runtime
# ---------------------------------------------------------------------------

def test_kv_defragment_through_runtime_preserves_contents():
    from repro.serve import PagedKVCache
    kv = PagedKVCache(page=4, num_pages=32, max_seqs=2, max_pages_per_seq=8,
                      kv_heads=2, head_dim=4)
    rng = np.random.default_rng(0)
    kv.admit(0)
    kv.admit(1)
    for i in range(24):   # interleaved appends fragment both slots
        kv.append(i % 2, rng.standard_normal((2, 4)),
                  rng.standard_normal((2, 4)))
    assert kv.alloc.speculation_hit_rate(0) < 1.0
    before = kv.dense_view(0)
    other = kv.dense_view(1)

    rt = default_runtime(4, tier="blocked_2d")
    rate = kv.defragment(0, rt)
    assert rate == 1.0
    after = kv.dense_view(0)
    np.testing.assert_array_equal(after[0], before[0])
    np.testing.assert_array_equal(after[1], before[1])
    # The other sequence is untouched by slot 0's defragmentation.
    np.testing.assert_array_equal(kv.dense_view(1)[0], other[0])


# ---------------------------------------------------------------------------
# Multi-channel cycle model
# ---------------------------------------------------------------------------

def test_multichannel_sim_one_channel_matches_base_config():
    one = simulate_multichannel(1, 13, 64, num_transfers=300)
    base = simulate(SimConfig.base(), 13, 64)
    assert one.aggregate_utilization == pytest.approx(base.utilization,
                                                      rel=0.05)


@pytest.mark.slow  # >=4-channel drain/sim: CI slow job
def test_multichannel_sim_scales_to_bus_saturation():
    two = simulate_multichannel(2, 13, 64, num_transfers=300)
    four = simulate_multichannel(4, 13, 64, num_transfers=300)
    assert two.aggregate_utilization > \
        1.8 * simulate_multichannel(1, 13, 64).aggregate_utilization
    assert four.aggregate_utilization == pytest.approx(four.ideal, rel=0.02)
    utils = [c.utilization for c in four.channels]
    assert max(utils) - min(utils) < 0.02   # fair arbiter: equal shares


@pytest.mark.slow  # >=4-channel drain/sim: CI slow job
def test_multichannel_sim_weighted_shares():
    r = simulate_multichannel(4, 13, 64, num_transfers=300,
                              weights=[4, 2, 1, 1])
    u = [c.utilization for c in r.channels]
    assert u[0] > u[1] > u[2]
    assert u[1] == pytest.approx(2 * u[2], rel=0.25)
    assert u[2] == pytest.approx(u[3], rel=0.1)
