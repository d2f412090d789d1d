"""Sharded DMA serving layer (DESIGN.md §6): ownership, migration chains,
single-shard pinning, mesh-shape equivalence, shardlib lifecycle.

No hypothesis dependency — this module must collect on minimal installs.
Mesh-placement tests guard on the host device count, so they run for real
in the multi-device CI lane (``--xla_force_host_platform_device_count=8``)
and skip, rather than fake, elsewhere.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.chain import from_segments
from repro.distributed import shardlib
from repro.distributed.sharded_runtime import (
    MigrationStats,
    PageOwnerMap,
    ShardedDMARuntime,
    ShardedKVPool,
    resolve_num_shards,
)
from repro.runtime import ChannelConfig, DMARuntime
from repro.runtime.submit import SubmitRequest


# ---------------------------------------------------------------------------
# shardlib mesh/rules lifecycle (regression: set_mesh(None) left stale rules)
# ---------------------------------------------------------------------------

class _FakeMesh:
    shape = {"data": 2, "model": 2}


def test_set_mesh_none_clears_rules_like_clear_mesh():
    shardlib.set_mesh(_FakeMesh())
    shardlib.set_rules({"batch": "data", "heads": "model"})
    assert shardlib.current_rules()
    shardlib.set_mesh(None)   # must be symmetric with clear_mesh()
    assert shardlib.current_mesh() is None
    assert shardlib.current_rules() == {}

    shardlib.set_mesh(_FakeMesh())
    shardlib.set_rules({"batch": "data"})
    shardlib.clear_mesh()
    assert shardlib.current_mesh() is None
    assert shardlib.current_rules() == {}


class _BigFakeMesh:
    shape = {"data": 4, "model": 2}


def test_use_mesh_restores_state_when_body_resizes_mesh_and_raises():
    # Elastic-resize hazard: the body legitimately swaps in a grown mesh
    # (and new rules), then fails mid-launch. The pre-with pair must come
    # back — not the resized one, and not a half-cleared state.
    shardlib.set_mesh(_FakeMesh())
    shardlib.set_rules({"batch": "data"})
    with pytest.raises(RuntimeError):
        with shardlib.use_mesh(_FakeMesh(), {"batch": "data"}):
            shardlib.set_mesh(_BigFakeMesh())
            shardlib.set_rules({"batch": "data", "heads": "model"})
            raise RuntimeError("resize failed mid-launch")
    assert isinstance(shardlib.current_mesh(), _FakeMesh)
    assert shardlib.current_rules() == {"batch": "data"}
    # A body that tears the mesh down entirely restores the same way.
    with pytest.raises(RuntimeError):
        with shardlib.use_mesh(_BigFakeMesh()):
            shardlib.clear_mesh()
            raise RuntimeError("boom")
    assert isinstance(shardlib.current_mesh(), _FakeMesh)
    assert shardlib.current_rules() == {"batch": "data"}
    shardlib.clear_mesh()


def test_use_mesh_restores_state_when_install_itself_throws():
    # A bad rule table must not leave the new mesh installed with the old
    # rules: the install happens inside the restore scope.
    shardlib.set_mesh(_FakeMesh())
    shardlib.set_rules({"batch": "data"})
    with pytest.raises(TypeError):
        with shardlib.use_mesh(_BigFakeMesh(), rules=42):   # not a mapping
            pragma = None   # pragma: no cover - body never runs
            del pragma
    assert isinstance(shardlib.current_mesh(), _FakeMesh)
    assert shardlib.current_rules() == {"batch": "data"}
    shardlib.clear_mesh()


def test_use_mesh_restores_previous_state_even_on_error():
    shardlib.set_mesh(None)
    with shardlib.use_mesh(_FakeMesh(), {"batch": "data"}):
        assert shardlib.current_rules() == {"batch": "data"}
    assert shardlib.current_mesh() is None
    assert shardlib.current_rules() == {}
    with pytest.raises(RuntimeError):
        with shardlib.use_mesh(_FakeMesh(), {"batch": "data"}):
            raise RuntimeError("boom")
    assert shardlib.current_mesh() is None
    assert shardlib.current_rules() == {}


def test_mesh_state_is_thread_local():
    shardlib.set_mesh(_FakeMesh())
    shardlib.set_rules({"batch": "data"})
    seen = {}

    def worker():
        seen["mesh"] = shardlib.current_mesh()
        seen["rules"] = shardlib.current_rules()
        shardlib.set_mesh(_FakeMesh())
        shardlib.set_rules({"batch": "model"})

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    # The worker saw a pristine thread and its writes never leaked back.
    assert seen == {"mesh": None, "rules": {}}
    assert shardlib.current_rules() == {"batch": "data"}
    shardlib.clear_mesh()


# ---------------------------------------------------------------------------
# Page ownership
# ---------------------------------------------------------------------------

def test_page_owner_map_partition_and_validation():
    m = PageOwnerMap(num_pages=32, num_shards=4)
    assert m.pages_per_shard == 8
    assert [m.owner(p) for p in (0, 7, 8, 31)] == [0, 0, 1, 3]
    assert m.local_row(17) == 1
    assert list(m.shard_pages(2)) == list(range(16, 24))
    with pytest.raises(IndexError):
        m.owner(32)
    with pytest.raises(ValueError, match="partition evenly"):
        PageOwnerMap(num_pages=10, num_shards=4)


def test_resolve_num_shards_is_shape_agnostic():
    class M1:
        shape = {"a": 1, "b": 4}

    class M2:
        shape = {"a": 4, "b": 1}
    assert resolve_num_shards(M1()) == resolve_num_shards(M2()) == 4
    assert resolve_num_shards(None) == 1


# ---------------------------------------------------------------------------
# Single-shard pinning: the sharded drain is bit-identical to the plain
# DMARuntime drain (the PR-2 trick — same chains, same channels, same bytes)
# ---------------------------------------------------------------------------

def test_single_shard_migration_bit_identical_to_unsharded_runtime():
    rng = np.random.default_rng(11)
    num_pages, row_elems = 32, 16
    content = rng.standard_normal(num_pages * row_elems).astype(np.float32)

    srt = ShardedDMARuntime(num_shards=1, data_channels=2, max_len=512)
    kv = ShardedKVPool(srt, num_pages=num_pages, page=row_elems,
                       kv_heads=1, head_dim=1)
    for p in range(num_pages):
        row = content[p * row_elems:(p + 1) * row_elems]
        kv.write_page(p, row, -row)
    src = [3, 4, 5, 9, 20, 21, 22, 23, 7]
    dst = [12, 13, 14, 26, 0, 1, 2, 28, 30]
    kv.move_pages(src, dst)

    # The unsharded reference: identical channel set, identically padded
    # pools, the same two chains through the same coalescer path.
    rt = DMARuntime([
        ChannelConfig(name="dma0", tier="serial", ring_capacity=256,
                      max_len=512),
        ChannelConfig(name="dma1", tier="serial", ring_capacity=256,
                      max_len=512),
        ChannelConfig(name="completion", tier="control"),
    ])
    pad = jnp.zeros(512, jnp.float32)
    rt.register_pool("kv.k", jnp.concatenate([jnp.asarray(content), pad]))
    rt.register_pool("kv.v", jnp.concatenate([jnp.asarray(-content), pad]))
    s = np.asarray(src, np.int64) * row_elems
    t = np.asarray(dst, np.int64) * row_elems
    ln = np.full(len(src), row_elems, np.int64)
    rt.submit(SubmitRequest(chain=from_segments(s, t, ln), src_pool="kv.k",
                            dst_pool="kv.k", tier="serial"))
    rt.submit(SubmitRequest(chain=from_segments(s, t, ln), src_pool="kv.v",
                            dst_pool="kv.v", tier="serial"))
    rt.drain_until_idle()

    logical = num_pages * row_elems
    np.testing.assert_array_equal(
        srt.gather_pool(ShardedKVPool.POOL_K),
        np.asarray(rt.pool("kv.k"))[:logical])
    np.testing.assert_array_equal(
        srt.gather_pool(ShardedKVPool.POOL_V),
        np.asarray(rt.pool("kv.v"))[:logical])


# ---------------------------------------------------------------------------
# Migration chains under defrag churn (contents vs oracle)
# ---------------------------------------------------------------------------

def _filled_pool(num_shards, num_pages, row_elems, seed=0, **kw):
    rng = np.random.default_rng(seed)
    srt = ShardedDMARuntime(num_shards=num_shards, **kw)
    kv = ShardedKVPool(srt, num_pages=num_pages, page=row_elems,
                       kv_heads=1, head_dim=1)
    content = rng.standard_normal((num_pages, row_elems)).astype(np.float32)
    for p in range(num_pages):
        kv.write_page(p, content[p], -content[p])
    return srt, kv, content


def test_migration_chains_correct_under_defrag_churn():
    rng = np.random.default_rng(5)
    srt, kv, content = _filled_pool(4, 64, 8, seed=5)
    # Churn: free ~a third of the pages, compact survivors onto the freed
    # low ids (disjoint src/dst by construction -> a clean numpy oracle).
    freed = rng.random(64) < 0.35
    live = np.flatnonzero(~freed)
    free = np.flatnonzero(freed)
    n = min(24, len(free))
    src, dst = live[-n:].tolist(), free[:n].tolist()
    stats = kv.move_pages(src, dst)

    assert stats.pages == n
    assert stats.cross_pages > 0            # churn crossed shard boundaries
    assert stats.hops > 0
    assert stats.hop_completions == stats.hops   # §II-D per-hop writeback
    assert stats.merge_ratio >= 1.0

    want = content.copy()
    want[dst] = content[src]
    got_k = srt.gather_pool(kv.POOL_K).reshape(64, 8)
    got_v = srt.gather_pool(kv.POOL_V).reshape(64, 8)
    np.testing.assert_array_equal(got_k, want)
    np.testing.assert_array_equal(got_v, -want)


def test_defragment_compacts_to_sequential_layout_and_frees_sources():
    srt, kv, content = _filled_pool(4, 64, 8, seed=7)
    pages = kv.alloc_on(3, 5) + kv.alloc_on(1, 3)
    before_k, _ = kv.page_rows(pages)
    free_before = sum(kv.free_pages_on(s) for s in range(4))
    new, stats, rate = kv.defragment(pages)
    assert new == list(range(len(pages)))   # lowest free run
    assert rate == 1.0                      # §II-C sequential by construction
    after_k, _ = kv.page_rows(new)
    np.testing.assert_array_equal(after_k, before_k)
    # Sources returned to their owners: net free count unchanged.
    assert sum(kv.free_pages_on(s) for s in range(4)) == free_before


def test_migration_stats_merge_and_empty_move():
    srt = ShardedDMARuntime(num_shards=2)
    kv = ShardedKVPool(srt, num_pages=8, page=4, kv_heads=1, head_dim=1)
    assert kv.move_pages([], []) == MigrationStats()
    with pytest.raises(ValueError, match="pair up"):
        kv.move_pages([1], [2, 3])


def test_migration_rejects_overlapping_and_duplicate_destinations():
    srt = ShardedDMARuntime(num_shards=2)
    kv = ShardedKVPool(srt, num_pages=8, page=4, kv_heads=1, head_dim=1)
    # A destination that is also a source is ambiguous once moves are
    # grouped by shard pair (a cross-shard swap would silently corrupt).
    with pytest.raises(ValueError, match="reads and writes"):
        kv.move_pages([0, 5], [5, 0])
    with pytest.raises(ValueError, match="duplicate destination"):
        kv.move_pages([0, 1], [6, 6])


# ---------------------------------------------------------------------------
# Mesh placement: 1xN and Nx1 meshes are the same sharded runtime
# ---------------------------------------------------------------------------

def _mesh(shape, axes):
    devs = np.asarray(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return jax.sharding.Mesh(devs, axes)


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >=4 devices (the sharded CI lane)")
def test_mesh_shape_equivalence_1xN_vs_Nx1():
    outs = {}
    for name, shape in (("1x4", (1, 4)), ("4x1", (4, 1))):
        mesh = _mesh(shape, ("a", "b"))
        srt = ShardedDMARuntime(mesh=mesh)
        assert srt.num_shards == 4
        kv = ShardedKVPool(srt, num_pages=32, page=8, kv_heads=1,
                           head_dim=1)
        rng = np.random.default_rng(3)
        content = rng.standard_normal((32, 8)).astype(np.float32)
        for p in range(32):
            kv.write_page(p, content[p], -content[p])
        stats = kv.move_pages([25, 26, 27, 9, 2], [0, 1, 3, 30, 17])
        outs[name] = (srt.gather_pool(kv.POOL_K),
                      stats.cross_pages, stats.hops, stats.merge_ratio)
    np.testing.assert_array_equal(outs["1x4"][0], outs["4x1"][0])
    assert outs["1x4"][1:] == outs["4x1"][1:]


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 devices (the sharded CI lane)")
def test_meshed_pools_land_on_their_shard_devices():
    mesh = _mesh((2,), ("dma",))
    srt = ShardedDMARuntime(mesh=mesh)
    kv = ShardedKVPool(srt, num_pages=8, page=4, kv_heads=1, head_dim=1)
    devs = [next(iter(srt.shards[s].pool(kv.POOL_K).devices()))
            for s in range(2)]
    assert devs[0] != devs[1]
    # and migration still round-trips across the two devices
    kv.write_page(1, np.ones(4), np.ones(4))
    kv.move_pages([1], [6])
    k, _ = kv.page_rows([6])
    np.testing.assert_array_equal(k[0], np.ones(4))


def test_mesh_shard_count_mismatch_rejected():
    class M:
        shape = {"a": 2}
        devices = np.asarray(jax.devices()[:1])
    with pytest.raises(ValueError, match="mesh has 2"):
        ShardedDMARuntime(num_shards=4, mesh=M())


def test_ambient_mesh_of_wrong_size_does_not_veto_explicit_shard_count():
    # The mesh-1 perf cell must run (unplaced) inside anyone's mesh
    # context: an *ambient* mesh only applies when the sizes agree.
    with shardlib.use_mesh(_FakeMesh()):   # 2x2 = 4 ambient shards
        srt = ShardedDMARuntime(num_shards=1)
        assert srt.num_shards == 1 and srt.mesh is None
        kv = ShardedKVPool(srt, num_pages=8, page=4, kv_heads=1,
                           head_dim=1)
        kv.write_page(0, np.ones(4), np.ones(4))
        kv.move_pages([0], [5])
        np.testing.assert_array_equal(kv.page_rows([5])[0][0], np.ones(4))


# ---------------------------------------------------------------------------
# Sharded cycle model + perf cell
# ---------------------------------------------------------------------------

def test_simulate_sharded_single_shard_has_no_migration_traffic():
    from repro.core.simulator import simulate_sharded
    r = simulate_sharded(1, 2, 13, 64, num_transfers=100,
                         cross_fraction=0.5)
    assert r.sharded.cross_transfers == 0
    assert r.sharded.migration_cycles_mean == 0.0


def test_simulate_sharded_interconnect_contention_grows_with_cross_traffic():
    from repro.core.simulator import simulate_sharded
    lo = simulate_sharded(4, 2, 13, 64, num_transfers=150,
                          cross_fraction=0.05)
    hi = simulate_sharded(4, 2, 13, 64, num_transfers=150,
                          cross_fraction=0.6)
    assert hi.sharded.cross_transfers > lo.sharded.cross_transfers
    assert hi.sharded.migration_cycles_mean > \
        lo.sharded.migration_cycles_mean
    # Shard-local buses are untouched by the fabric: same local shares.
    assert hi.sharded.per_shard_utilization == \
        pytest.approx(lo.sharded.per_shard_utilization)


def test_simulate_multichannel_default_path_unchanged_by_sharding_params():
    from repro.core.simulator import SimConfig, simulate, simulate_multichannel
    one = simulate_multichannel(1, 13, 64, num_transfers=300)
    base = simulate(SimConfig.base(), 13, 64)
    assert one.aggregate_utilization == pytest.approx(base.utilization,
                                                      rel=0.05)
    assert one.sharded is None
    with pytest.raises(ValueError, match="cross_fraction requires"):
        simulate_multichannel(2, 13, 64, cross_fraction=0.5)


@pytest.mark.slow  # full mesh axis incl. 8 shards: CI sharded/slow lane
def test_sharded_cell_deterministic_and_meets_fabric_floors():
    from repro.perf.sharded_cell import (
        MIN_OVERLAP_RATIO,
        MIN_RETAINED_THROUGHPUT,
        SHARDED_GATED_METRICS,
        run_sharded_cell,
    )
    cells = {}
    for mesh in (1, 2, 4, 8):
        m1, c1 = run_sharded_cell(0, mesh, repeats=2)
        m2, c2 = run_sharded_cell(0, mesh, repeats=2)
        assert (m1, c1) == (m2, c2), f"mesh {mesh} not deterministic"
        assert set(m1) == set(SHARDED_GATED_METRICS)
        cells[mesh] = m1
    # Mesh 1 has no fabric: every fabric-dependent metric pins to zero.
    assert cells[1]["cross_shard_migration_cycles"] == 0.0
    assert cells[1]["migration_overlap_ratio"] == 0.0
    assert cells[1]["throughput_retained_during_resize"] == 1.0
    for mesh in (2, 4, 8):
        assert cells[mesh]["cross_shard_migration_cycles"] > 0.0
        assert cells[mesh]["p99_migration_stall_cycles"] > 0.0
        assert cells[mesh]["rebalance_convergence_steps"] > 0
    # The cell enforces these floors itself at mesh >= 4 (RuntimeError);
    # assert them here too so a silently-weakened cell still fails.
    for mesh in (4, 8):
        assert cells[mesh]["migration_overlap_ratio"] >= MIN_OVERLAP_RATIO
        assert cells[mesh]["throughput_retained_during_resize"] >= \
            MIN_RETAINED_THROUGHPUT
    for m in cells.values():
        assert m["migration_chain_merge_ratio"] >= 1.0
        assert 0.0 < m["per_shard_bus_utilization"] <= 1.0


# ---------------------------------------------------------------------------
# Sharded serve path: ownership routing, remote reads become migrations
# ---------------------------------------------------------------------------

def test_sharded_serve_routes_by_ownership_and_migrates_remote_pages():
    from repro.configs.registry import get_config
    from repro.models import init_params
    from repro.serve import Request
    from repro.distributed.sharded_runtime import ShardedServeEngine

    cfg = get_config("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    srt = ShardedDMARuntime(num_shards=2)
    kv = ShardedKVPool(srt, num_pages=32, page=2, kv_heads=2, head_dim=4)
    eng = ShardedServeEngine(params, cfg, runtime=srt, kv_pool=kv,
                             capacity=1, max_len=32)

    # Shard-local requests go to their owner; no migration happens.
    for uid in range(4):
        pages = kv.alloc_on(uid % 2, 2)
        t = eng.submit(SubmitRequest(request=Request(
            uid=uid, prompt=[1, 2, 3], max_new_tokens=2, kv_pages=pages)))
        assert t.shard == uid % 2
    assert eng.remote_page_reads == 0

    # A request whose pages straddle shards routes to the majority owner
    # and pulls the minority pages across as a migration chain.
    p0 = kv.alloc_on(0, 1)
    p1 = kv.alloc_on(1, 2)
    mixed = Request(uid=9, prompt=[4, 5], max_new_tokens=2,
                    kv_pages=p0 + p1)
    shard = eng.submit(SubmitRequest(request=mixed)).shard
    assert shard == 1
    assert eng.remote_page_reads == 1
    assert eng.migration.pages == 1 and eng.migration.hops == 1
    # The request's page list was rewritten to all-local pages.
    assert all(kv.owner.owner(p) == 1 for p in mixed.kv_pages)

    # A duplicated remote page migrates (and frees) exactly once: no
    # double-free into the allocator, no leaked allocation.
    free_before = [kv.free_pages_on(s) for s in range(2)]
    p0b = kv.alloc_on(0, 1)
    dup = Request(uid=10, prompt=[6], max_new_tokens=2,
                  kv_pages=p0b + p0b + kv.alloc_on(1, 3))
    # majority owner wins, 2 vs 3
    assert eng.submit(SubmitRequest(request=dup)).shard == 1
    assert len(set(dup.kv_pages)) == 4      # both remote copies remapped alike
    assert all(kv.owner.owner(p) == 1 for p in dup.kv_pages)
    kv.release(sorted(set(dup.kv_pages)))
    assert [kv.free_pages_on(s) for s in range(2)] == free_before
    assert sorted(set(kv._free[0] + kv._free[1])) == \
        sorted(kv._free[0] + kv._free[1])   # free lists hold no duplicates

    done = eng.run(max_steps=200)
    assert sorted(done) == [0, 1, 2, 3, 9, 10]
    assert len(eng.poll_completed()) == 6
    pc = eng.perf_counters()
    assert pc["sharded.requests_per_shard"] == [2, 4]
    assert pc["sharded.completed"] == 6


def test_shared_page_not_freed_while_another_request_reads_it():
    from repro.configs.registry import get_config
    from repro.models import init_params
    from repro.serve import Request
    from repro.distributed.sharded_runtime import ShardedServeEngine

    cfg = get_config("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    srt = ShardedDMARuntime(num_shards=2)
    kv = ShardedKVPool(srt, num_pages=16, page=2, kv_heads=2, head_dim=4)
    eng = ShardedServeEngine(params, cfg, runtime=srt, kv_pool=kv,
                             capacity=2, max_len=16)

    (p,) = kv.alloc_on(0, 1)
    kv.write_page(p, np.full(kv.row_elems, 7.0), np.full(kv.row_elems, 7.0))
    a = Request(uid=0, prompt=[1], max_new_tokens=1, kv_pages=[p])
    eng.submit(SubmitRequest(request=a))
    # B shares page p but routes to shard 1, migrating p's contents away.
    b = Request(uid=1, prompt=[2], max_new_tokens=1,
                kv_pages=[p] + kv.alloc_on(1, 2))
    eng.submit(SubmitRequest(request=b))
    # p is still read by A: it must NOT be back on the free list...
    assert p not in kv._free[0]
    # ...and its contents survive for A (migration copies, never zeroes).
    np.testing.assert_array_equal(kv.page_rows([p])[0][0],
                                  np.full(kv.row_elems, 7.0))
    eng.run(max_steps=50)
    eng.poll_completed()
    # Last reader delivered -> the shared source page frees exactly once.
    assert kv._free[0].count(p) == 1


def test_migration_hop_does_not_steal_serve_completion_events():
    """A cross-shard hop landing on a shard must not consume that shard's
    pending serve-request completions (shared completion queue)."""
    from repro.configs.registry import get_config
    from repro.models import init_params
    from repro.serve import Request
    from repro.distributed.sharded_runtime import ShardedServeEngine

    cfg = get_config("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    srt = ShardedDMARuntime(num_shards=2)
    kv = ShardedKVPool(srt, num_pages=16, page=2, kv_heads=2, head_dim=4)
    eng = ShardedServeEngine(params, cfg, runtime=srt, kv_pool=kv,
                             capacity=1, max_len=16)
    # Request A completes on shard 1 but is deliberately NOT polled yet.
    a = Request(uid=0, prompt=[1], max_new_tokens=1,
                kv_pages=kv.alloc_on(1, 1))
    eng.submit(SubmitRequest(request=a))
    for _ in range(10):
        eng.step()
        if 0 in eng.engines[1].completed:
            break
    assert 0 in eng.engines[1].completed
    # A remote-page admission now triggers a migration hop INTO shard 1,
    # which drains shard 1's runtime before A's writeback was polled.
    b = Request(uid=1, prompt=[2], max_new_tokens=1,
                kv_pages=kv.alloc_on(0, 1) + kv.alloc_on(1, 2))
    assert eng.submit(SubmitRequest(request=b)).shard == 1
    assert eng.migration.hops == 1
    # A's completion must still be observable through the poll path.
    delivered = {r.uid for r in eng.poll_completed()}
    assert 0 in delivered


def test_sharded_pool_rejects_reserved_staging_name():
    srt = ShardedDMARuntime(num_shards=2)
    with pytest.raises(ValueError, match="reserved"):
        srt.register_sharded_pool(
            ShardedDMARuntime.STAGE_POOL, jnp.zeros(16, jnp.float32),
            PageOwnerMap(4, 2), 2)


def test_sharded_serve_without_kv_pool_routes_round_robin():
    from repro.configs.registry import get_config
    from repro.models import init_params
    from repro.serve import Request
    from repro.distributed.sharded_runtime import ShardedServeEngine

    cfg = get_config("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    srt = ShardedDMARuntime(num_shards=2)
    eng = ShardedServeEngine(params, cfg, runtime=srt, capacity=1,
                             max_len=16)
    # kv_pages without a pool must not crash: ownership is unknowable, so
    # the router falls back to round-robin.
    shards = [eng.submit(SubmitRequest(request=Request(
                  uid=u, prompt=[1], max_new_tokens=1,
                  kv_pages=[3] if u == 1 else None))).shard
              for u in range(4)]
    assert shards == [0, 1, 0, 1]
    assert eng.remote_page_reads == 0


def test_ambient_mesh_of_wrong_size_rejects_multi_shard_count():
    # More than one shard under a mesh of another size would run unplaced
    # (all shards on the default device): refuse instead.
    with shardlib.use_mesh(_FakeMesh()):   # 2x2 = 4 ambient shards
        with pytest.raises(ValueError, match="mesh has 4"):
            ShardedDMARuntime(num_shards=2)


def test_sharded_cell_refuses_to_place_more_shards_than_devices(monkeypatch):
    from repro.perf import sharded_cell
    monkeypatch.setattr(jax, "devices", lambda: [object(), object()])
    with pytest.raises(RuntimeError, match="4 shards on 2 devices"):
        sharded_cell._mesh_for(4)
    # one visible device: shards stay logical, no mesh is asked for
    monkeypatch.setattr(jax, "devices", lambda: [object()])
    assert sharded_cell._mesh_for(4) is None
