"""Chain-lowering JIT (DESIGN.md §7): signatures, plan memo, artifact LRU,
and cached-vs-uncached drain bit-identity against the host walker oracle.

The fast split has no hypothesis dependency; the property suite at the
bottom guards its import and is marked slow (CI's slow job).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, list_archs
from repro.core.chain import from_segments, walk_chain_host
from repro.core.descriptor import CONFIG_IRQ_ENABLE, DescriptorArray
from repro.core.signature import (
    canonicalize,
    pow2_bucket,
    signature_of,
    walk_order,
)
from repro.core.simulator import SimConfig, simulate
from repro.perf.workloads import Scale, generate
from repro.runtime import (
    ChannelConfig,
    DMARuntime,
    PerfProbe,
    SubmitRequest,
    coalesce,
)
from repro.runtime.lowering import (
    TranslationCache,
    aggregate_stats,
    disabled_stats,
)
from repro.runtime.scheduler import _is_sequential_chain

TINY = Scale("tiny", n_bursts=1, burst_len=24, pool_elems=1 << 12,
             max_len=128, ring_capacity=64, sim_transfers=60)


def _shift(d: DescriptorArray, src_by: int, dst_by: int) -> DescriptorArray:
    return DescriptorArray.create(
        np.asarray(d.src, np.int64) + src_by,
        np.asarray(d.dst, np.int64) + dst_by,
        np.asarray(d.length, np.int64),
        nxt=np.asarray(d.nxt, np.int64),
        config=np.asarray(d.config, np.int64))


def _chains_equal(a: DescriptorArray, b: DescriptorArray) -> None:
    for f in ("src", "dst", "length", "nxt", "config", "done"):
        fa, fb = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        np.testing.assert_array_equal(fa, fb, err_msg=f)
        assert fa.dtype == fb.dtype, f


# ---------------------------------------------------------------------------
# Canonicalization: walk order, base invariance, layout keys
# ---------------------------------------------------------------------------

def test_walk_order_matches_host_walk_on_permuted_storage():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 33):
        perm = rng.permutation(n)
        nxt = np.full(n, -1, np.int64)
        nxt[perm[:-1]] = perm[1:]
        d = DescriptorArray.create(np.arange(n), np.arange(n), np.ones(n),
                                   nxt=nxt)
        order = walk_order(np.asarray(d.nxt, np.int64), int(perm[0]))
        assert order is not None
        np.testing.assert_array_equal(
            order, walk_chain_host(d, int(perm[0])))


def test_walk_order_sequential_fast_path():
    nxt = np.array([1, 2, 3, -1], np.int64)
    np.testing.assert_array_equal(walk_order(nxt, 0), [0, 1, 2, 3])


def test_walk_order_declines_on_malformed_chains():
    # Cycle: the legacy walker raises on these, so the lowering layer must
    # decline and leave the error to the canonical path.
    assert walk_order(np.array([1, 0], np.int64), 0) is None
    # Link past the table.
    assert walk_order(np.array([5, -1], np.int64), 0) is None
    d = DescriptorArray.create([0, 1], [0, 1], [1, 1], nxt=[1, 0])
    assert canonicalize(d, 0) is None


def test_digest_and_signature_invariant_under_base_shift():
    d = from_segments([0, 8, 100], [0, 8, 300], [8, 8, 16])
    s = _shift(d, 512, 1024)
    ca, cb = canonicalize(d, 0), canonicalize(s, 0)
    assert ca.digest == cb.digest
    assert signature_of(ca, tier="serial") == signature_of(cb, tier="serial")
    # ...but the bases themselves are preserved for rematerialization.
    assert cb.src_base - ca.src_base == 512
    assert cb.dst_base - ca.dst_base == 1024


def test_distinct_layouts_get_distinct_signatures_and_digests():
    seq = from_segments([0, 8, 16], [0, 8, 16], [8, 8, 8])
    strided = from_segments([0, 32, 64], [0, 8, 16], [8, 8, 8])
    gather = from_segments([96, 0, 48], [0, 8, 16], [8, 8, 8])
    sigs = {signature_of(canonicalize(d, 0), tier="serial").layout
            for d in (seq, strided, gather)}
    assert sigs == {"sequential", "strided", "gather"}
    digests = {canonicalize(d, 0).digest for d in (seq, strided, gather)}
    assert len(digests) == 3


def test_walk_order_is_part_of_the_digest():
    # Same relative segments, different storage order: the §II-C input hit
    # rate is computed over storage-order fetch addresses, so these chains
    # must NOT share a plan.
    a = from_segments([0, 8], [0, 8], [8, 8])
    b = DescriptorArray.create([8, 0], [8, 0], [8, 8], nxt=[-1, 0])
    assert canonicalize(a, 0).digest != canonicalize(b, 1).digest


def test_signature_buckets_are_pow2():
    assert [pow2_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    d = from_segments(np.arange(5) * 8, np.arange(5) * 8, np.full(5, 8))
    sig = signature_of(canonicalize(d, 0), tier="serial")
    assert sig.n_class == 8 and sig.unit == 8


# ---------------------------------------------------------------------------
# Plan memo: bit-identical to the legacy coalescer
# ---------------------------------------------------------------------------

def _assert_plan_matches_coalesce(cache, d, max_len, spec_depth=0):
    res = cache.plan(d, max_len=max_len, spec_depth=spec_depth)
    assert res is not None
    want_d, want_stats = coalesce(d, max_len=max_len, spec_depth=spec_depth)
    _chains_equal(res.planned, want_d)
    assert res.stats == want_stats
    # The runtime's own chains stay on the host whatever form came in.
    assert res.planned.on_host and want_d.on_host


@pytest.mark.parametrize("form", ["device", "host"])
def test_plan_is_bit_identical_to_coalesce_on_handcrafted_chains(form):
    cache = TranslationCache()
    cases = [
        from_segments([0, 8, 16], [0, 8, 16], [8, 8, 8]),     # merges to 1
        from_segments([0], [0], [500]),                        # splits
        from_segments([0, 8, 100], [0, 8, 300], [8, 8, 16]),  # merge + tail
        from_segments([5, 90, 40], [7, 300, 200], [3, 11, 60]),
        # IRQ barrier mid-run: must not merge across it.
        DescriptorArray.create([0, 8, 16], [0, 8, 16], [8, 8, 8],
                               config=[0, CONFIG_IRQ_ENABLE, 0]),
    ]
    if form == "host":
        cases = [d.to_host() for d in cases]
    for d in cases:
        for max_len in (64, 128):
            _assert_plan_matches_coalesce(cache, d, max_len)
    _assert_plan_matches_coalesce(cache, cases[0], 64, spec_depth=4)


@pytest.mark.parametrize("build", [DescriptorArray.create,
                                   DescriptorArray.from_host],
                         ids=["device", "host"])
def test_plan_matches_coalesce_on_permuted_storage_chain(build):
    cache = TranslationCache()
    perm = np.random.default_rng(7).permutation(12)
    nxt = np.full(12, -1, np.int64)
    nxt[perm[:-1]] = perm[1:]
    src = np.arange(12, dtype=np.int64) * 8
    d = build(src, src + 512, np.full(12, 8), nxt=nxt)
    res = cache.plan(d, max_len=64, head=int(perm[0]))
    want_d, want_stats = coalesce(d, max_len=64, head=int(perm[0]))
    _chains_equal(res.planned, want_d)
    assert res.stats == want_stats


def test_plan_matches_coalesce_across_workloads():
    cache = TranslationCache()
    for arch in list_archs()[:3]:
        cfg = get_config(arch)
        for name in ("paged_kv", "moe_dispatch", "chain_mix",
                     "defrag_churn"):
            for d in generate(name, cfg, TINY, seed=1).chains:
                _assert_plan_matches_coalesce(cache, d, TINY.max_len)


def test_plan_memo_hit_on_base_shift_rematerializes_new_bases():
    cache = TranslationCache()
    d = from_segments([0, 8, 100], [0, 8, 300], [8, 8, 16])
    cache.plan(d, max_len=64)
    assert (cache.plan_misses, cache.plan_hits) == (1, 0)
    s = _shift(d, 256, 512)
    res = cache.plan(s, max_len=64)
    assert (cache.plan_misses, cache.plan_hits) == (1, 1)
    want_d, want_stats = coalesce(s, max_len=64)
    _chains_equal(res.planned, want_d)
    assert res.stats == want_stats


def test_plan_memo_respects_max_len_in_the_key():
    cache = TranslationCache()
    d = from_segments([0], [0], [500])
    a = cache.plan(d, max_len=128)
    b = cache.plan(d, max_len=64)
    assert a.planned.num_descriptors != b.planned.num_descriptors
    assert cache.plan_misses == 2


def test_plan_declines_degenerate_inputs():
    cache = TranslationCache()
    d = from_segments([0], [0], [8])
    assert cache.plan(d, max_len=0) is None
    assert cache.plan(d, max_len=8, spec_depth=-1) is None


# ---------------------------------------------------------------------------
# Artifact LRU
# ---------------------------------------------------------------------------

def _sig_of(n):
    d = from_segments(np.arange(n) * 8, np.arange(n) * 8 + 512,
                      np.full(n, 8))
    return signature_of(canonicalize(d, 0), tier="serial")


def test_artifact_identity_one_compile_many_dispatches():
    cache = TranslationCache()
    sig = _sig_of(4)
    assert cache.lower(sig) is cache.lower(sig)
    assert (cache.misses, cache.hits) == (1, 1)


def test_lru_eviction_counts_and_evicts_oldest():
    cache = TranslationCache(max_entries=2)
    s1, s2, s3 = _sig_of(1), _sig_of(2), _sig_of(4)
    a1 = cache.lower(s1)
    cache.lower(s2)
    cache.lower(s3)                       # evicts s1 (oldest)
    st = cache.stats()
    assert (st["misses"], st["evictions"], st["size"]) == (3, 1, 2)
    assert cache.lower(s3) is not None and cache.hits == 1
    assert cache.lower(s1) is not a1      # recompiled after eviction
    assert cache.misses == 4


def test_probe_receives_translation_events():
    probe = PerfProbe()
    cache = TranslationCache(max_entries=1)
    cache.attach_probe(probe)
    cache.lower(_sig_of(1))
    cache.lower(_sig_of(2))               # miss + evict
    cache.lower(_sig_of(2))               # hit
    t = probe.translation
    assert (t.hits, t.misses, t.evictions) == (1, 2, 1)
    d = from_segments([0, 8], [16, 24], [8, 8])
    cache.plan(d, max_len=64)
    cache.plan(d, max_len=64)
    assert (probe.translation.plan_misses, probe.translation.plan_hits) \
        == (1, 1)


def test_stats_block_shape_and_aggregation():
    cache = TranslationCache()
    cache.lower(_sig_of(2))
    a = cache.stats()
    assert a["enabled"] and a["lookups"] == 1 and a["hit_rate"] == 0.0
    cache.lower(_sig_of(2))
    a = cache.stats()
    assert a["hit_rate"] == 0.5
    merged = aggregate_stats([a, a, disabled_stats()])
    assert merged["enabled"] is True
    assert merged["lookups"] == 4 and merged["hits"] == 2
    assert merged["hit_rate"] == 0.5
    empty = aggregate_stats([disabled_stats()])
    assert empty["enabled"] is False and empty["hit_rate"] == 0.0


# ---------------------------------------------------------------------------
# Lowered execution: identity with the oracle, decline guards
# ---------------------------------------------------------------------------

def _pools(rng, n=TINY.pool_elems):
    src = jnp.asarray(rng.standard_normal(n), jnp.float32)
    dst = jnp.zeros(n, jnp.float32)
    return src, dst


def test_lowered_vector_chain_matches_oracle():
    rng = np.random.default_rng(2)
    src, dst = _pools(rng, 1 << 10)
    d = from_segments([5, 90, 400], [7, 300, 200], [3, 11, 60])
    cache = TranslationCache()
    res = cache.plan(d, max_len=64)
    out = res.lowered(res.planned, src, dst, max_len=64)
    assert out is not None
    want, _ = execute_chain_host_np(res.planned, src, dst)
    np.testing.assert_array_equal(np.asarray(out), want)


def execute_chain_host_np(d, src, dst):
    from repro.core.engine import execute_chain_host
    return execute_chain_host(d, np.asarray(src), np.asarray(dst))


def test_lowered_overlap_chain_preserves_chain_order():
    rng = np.random.default_rng(3)
    src, dst = _pools(rng, 256)
    # dst windows overlap: descriptor 2's writes must land over 1's.
    d = from_segments([0, 64, 128], [10, 14, 18], [8, 8, 8])
    cache = TranslationCache()
    res = cache.plan(d, max_len=64)
    assert res.signature.overlap
    out = res.lowered(res.planned, src, dst, max_len=64)
    assert out is not None
    want, _ = execute_chain_host_np(res.planned, src, dst)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_lowered_declines_near_pool_tail_clamp_hazard():
    # execute_serial's fixed max_len window clamps near the pool tail; the
    # artifact must decline there so the legacy path keeps its semantics.
    rng = np.random.default_rng(4)
    src, dst = _pools(rng, 128)
    d = from_segments([120], [0], [4])     # 120 + max_len(64) > 128
    cache = TranslationCache()
    res = cache.plan(d, max_len=64)
    assert res.lowered(res.planned, src, dst, max_len=64) is None


def test_lowered_declines_on_dtype_mismatch_and_oversize():
    rng = np.random.default_rng(5)
    src, dst = _pools(rng, 256)
    cache = TranslationCache()
    d = from_segments([0, 16], [32, 64], [8, 8])
    res = cache.plan(d, max_len=16)
    assert res.lowered(res.planned, src.astype(jnp.bfloat16), dst,
                       max_len=16) is None
    big = from_segments(np.arange(8) * 16, np.arange(8) * 16 + 1024,
                        np.full(8, 8))
    bigger, _ = coalesce(big, max_len=16)
    assert res.lowered(bigger, src, dst, max_len=16) is None  # n > bucket


def test_bucketed_pallas_kernel_matches_plain_row_copy():
    from repro.kernels.descriptor_copy import (
        descriptor_copy,
        descriptor_copy_bucketed,
    )
    rng = np.random.default_rng(6)
    src = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    dst = jnp.zeros((16, 8), jnp.float32)
    sidx = jnp.asarray([3, 1, -1], jnp.int32)
    didx = jnp.asarray([0, 5, -1], jnp.int32)
    plain = descriptor_copy(sidx, didx, src, dst, interpret=True)
    bucketed = descriptor_copy_bucketed(sidx, didx, src, dst, n_bucket=8,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(bucketed))
    with pytest.raises(ValueError, match="bucket"):
        descriptor_copy_bucketed(sidx, didx, src, dst, n_bucket=2,
                                 interpret=True)


# ---------------------------------------------------------------------------
# Runtime integration: cached == uncached == oracle, across the registry
# ---------------------------------------------------------------------------

def _drain_workload(arch, workload, *, translation, rounds=2, seed=0):
    # Pools carry a max_len tail pad (as the sharded runtime's pools do):
    # without it the legacy serial engine's fixed-window dynamic_slice
    # clamps near the pool tail and the raw-chain oracle comparison would
    # test the clamp artifact, not the drain.
    cfg = get_config(arch)
    wl = generate(workload, cfg, TINY, seed=seed)
    n_padded = wl.pool_elems + TINY.max_len
    rng = np.random.default_rng([seed, 99])
    src0 = rng.standard_normal(n_padded).astype(np.float32)
    rt = DMARuntime(
        [ChannelConfig(name="ch0", tier="serial",
                       ring_capacity=TINY.ring_capacity,
                       max_len=TINY.max_len)],
        translation=translation)
    rt.register_pool("src", jnp.asarray(src0))
    rt.register_pool("dst", jnp.zeros(n_padded, jnp.float32))
    for _ in range(rounds):
        for d in wl.chains:
            rt.submit(SubmitRequest(chain=d, src_pool="src",
                                    dst_pool="dst", channel="ch0"))
        rt.drain_until_idle()
    return np.asarray(rt.pools["dst"]), rt, wl, src0


@pytest.mark.parametrize("arch", list_archs())
def test_cached_drains_bit_identical_across_registry(arch):
    cached, rt, wl, src0 = _drain_workload(arch, "paged_kv",
                                           translation=True)
    uncached, _, _, _ = _drain_workload(arch, "paged_kv", translation=False)
    np.testing.assert_array_equal(cached, uncached)
    # ...and both equal the host walker oracle over the raw chains.
    want = np.zeros_like(src0)
    for d in wl.chains:
        want, _ = execute_chain_host_np(d, src0, want)
    np.testing.assert_array_equal(cached, want)
    st = rt.translation_stats()
    assert st["translation.enabled"] and st["translation.lookups"] > 0


@pytest.mark.parametrize("workload",
                         ["moe_dispatch", "chain_mix", "defrag_churn"])
def test_cached_drains_bit_identical_other_workloads(workload):
    for arch in ("qwen2.5-3b", "dbrx-132b"):
        cached, _, wl, src0 = _drain_workload(arch, workload,
                                              translation=True)
        uncached, _, _, _ = _drain_workload(arch, workload,
                                            translation=False)
        np.testing.assert_array_equal(cached, uncached, err_msg=arch)
        want = np.zeros_like(src0)
        for d in wl.chains:
            want, _ = execute_chain_host_np(d, src0, want)
        np.testing.assert_array_equal(cached, want, err_msg=arch)


def test_steady_state_replays_hit_both_cache_layers():
    _, rt, _, _ = _drain_workload("qwen2.5-3b", "paged_kv",
                                  translation=True, rounds=4)
    st = rt.translation_stats()
    # Rounds 2..4 resubmit identical chains: plan memo and artifact cache
    # both run hot, so hits dominate lookups by at least the replay share.
    assert st["translation.hit_rate"] >= 0.5
    assert st["translation.plan_hits"] >= 3 * st["translation.plan_misses"]


def test_runtime_stats_and_disabled_escape_hatch():
    _, rt, _, _ = _drain_workload("qwen2.5-3b", "paged_kv",
                                  translation=True, rounds=1)
    block = rt.stats()["translation_cache"]
    assert block["translation.enabled"] and block["translation.capacity"] > 0
    _, rt_off, _, _ = _drain_workload("qwen2.5-3b", "paged_kv",
                                      translation=False, rounds=1)
    off = rt_off.stats()["translation_cache"]
    # The public stats block is namespaced (DESIGN.md §9); the raw
    # bare-key block is the canonical disabled sentinel.
    assert off["translation.enabled"] is False
    assert rt_off._translation_stats_raw() == disabled_stats()
    assert rt_off.translation is None


def test_is_sequential_memo_matches_predicate():
    cache = TranslationCache()
    seq = from_segments([0, 8], [0, 8], [8, 8])
    perm = DescriptorArray.create([0, 1], [0, 1], [1, 1], nxt=[-1, 0])
    for d in (seq, perm, seq):            # third call exercises the memo
        assert cache.is_sequential(d) == _is_sequential_chain(d)


# ---------------------------------------------------------------------------
# Cycle model: the launch-speedup claim behind the gated cell
# ---------------------------------------------------------------------------

def test_translated_frontend_speedup_at_64_byte_class():
    # The gated claim: >=1.66x launch speedup vs the §II-A serialized
    # baseline at 64-byte-class units, across the sweep's latencies.
    for tb in (32, 64):
        for lat in (13, 100):
            base = simulate(SimConfig.base(), lat, tb, num_transfers=200)
            tr = simulate(SimConfig.translated_frontend(), lat, tb,
                          num_transfers=200)
            ratio = base.cycles / tr.cycles
            assert ratio >= 1.66, (tb, lat, ratio)


def test_translated_frontend_never_slower_and_saturates_large_units():
    for tb in (64, 256, 1024):
        base = simulate(SimConfig.base(), 13, tb, num_transfers=200)
        tr = simulate(SimConfig.translated_frontend(), 13, tb,
                      num_transfers=200)
        assert tr.cycles <= base.cycles
    # Bus-bound at large units: the frontend is no longer the bottleneck.
    big_b = simulate(SimConfig.base(), 13, 4096, num_transfers=100)
    big_t = simulate(SimConfig.translated_frontend(), 13, 4096,
                     num_transfers=100)
    assert big_b.cycles / big_t.cycles < 1.2


# ---------------------------------------------------------------------------
# Property suite (hypothesis; slow job)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # minimal installs
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _SHARED_CACHE = TranslationCache()

    @pytest.mark.slow
    @settings(max_examples=60, deadline=None)
    @given(n=st.integers(1, 40), unit=st.integers(1, 16),
           gap=st.integers(0, 8),
           src_shift=st.integers(0, 1 << 20),
           dst_shift=st.integers(0, 1 << 20))
    def test_equal_signatures_reuse_one_artifact(n, unit, gap, src_shift,
                                                 dst_shift):
        stride = unit + gap
        src = np.arange(n, dtype=np.int64) * stride
        dst = np.arange(n, dtype=np.int64) * stride + (n * stride)
        ln = np.full(n, unit, np.int64)
        a = from_segments(src, dst, ln)
        b = from_segments(src + src_shift, dst + dst_shift, ln)
        ca, cb = canonicalize(a, 0), canonicalize(b, 0)
        assert ca.digest == cb.digest
        sa = signature_of(ca, tier="serial")
        sb = signature_of(cb, tier="serial")
        assert sa == sb
        # One signature -> one compiled artifact, whatever the bases.
        assert _SHARED_CACHE.lower(sa) is _SHARED_CACHE.lower(sb)

    @pytest.mark.slow
    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 48),
           max_len=st.sampled_from([16, 64, 128]))
    def test_plan_property_bit_identical_to_coalesce(seed, n, max_len):
        rng = np.random.default_rng(seed)
        ln = rng.integers(1, 32, n)
        src = rng.integers(0, 1 << 16, n)
        dst = rng.integers(0, 1 << 16, n)
        cfg = np.where(rng.random(n) < 0.2, CONFIG_IRQ_ENABLE, 0)
        d = DescriptorArray.create(src, dst, ln, config=cfg)
        cache = TranslationCache()
        res = cache.plan(d, max_len=max_len)
        want_d, want_stats = coalesce(d, max_len=max_len)
        _chains_equal(res.planned, want_d)
        assert res.stats == want_stats


# ---------------------------------------------------------------------------
# Kernel entry points: backend choice, launch counts, inactive descriptors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True),
                                               ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    import jax

    from repro.kernels.ops import _interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="neither"):
            _interpret()
    else:
        assert _interpret() is interpret


def test_kernel_entry_points_report_launches():
    from jax import monitoring

    from repro.kernels import ref
    from repro.kernels.ops import LAUNCH_EVENT, descriptor_copy_bucketed_op
    rng = np.random.default_rng(7)
    src = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
    dst = jnp.zeros((16, 128), jnp.float32)
    sidx = jnp.asarray([4, 9, 2], jnp.int32)
    didx = jnp.asarray([0, 7, 15], jnp.int32)
    seen = []

    def listener(event, **kw):
        if event == LAUNCH_EVENT:
            seen.append(kw["kernel"])

    monitoring.register_event_listener(listener)
    try:
        out = descriptor_copy_bucketed_op(sidx, didx, src, dst, n_bucket=4)
    finally:
        monitoring.unregister_event_listener(listener)
    assert seen == ["descriptor_copy_bucketed"]
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.descriptor_copy_ref(sidx, didx,
                                                            src, dst)))


def test_inactive_descriptors_repeat_an_active_move():
    from repro.kernels.descriptor_copy import descriptor_copy, fill_inactive
    s, d, any_active = fill_inactive(jnp.asarray([-1, 2, -1, 0, 3]),
                                     jnp.asarray([5, 1, 3, 4, -1]))
    assert bool(any_active)
    np.testing.assert_array_equal(np.asarray(s), [2, 2, 2, 0, 0])
    np.testing.assert_array_equal(np.asarray(d), [1, 1, 1, 4, 4])
    # No active descriptor at all: the pool comes back unchanged.
    src = jnp.ones((4, 128), jnp.float32)
    dst = jnp.arange(4 * 128, dtype=jnp.float32).reshape(4, 128)
    out = descriptor_copy(jnp.full((3,), -1, jnp.int32),
                          jnp.asarray([0, 1, 2], jnp.int32), src, dst,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dst))
