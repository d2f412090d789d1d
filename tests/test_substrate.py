"""Substrate tests: optimizer, data pipeline, checkpointing, compression,
paged KV cache, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.data import DataConfig, DataIterator, IteratorState, make_batch
from repro.models import init_params
from repro.runtime import SubmitRequest
from repro.serve import PagedKVCache, Request, ServeEngine


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def test_adamw_matches_reference_math():
    cfg = optim.AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8,
                            weight_decay=0.0, grad_clip=0.0,
                            schedule="constant", warmup_steps=0)
    params = {"w": jnp.array([1.0, 2.0])}
    grads = {"w": jnp.array([0.5, -0.5])}
    state = optim.init(params)
    new_p, state, _ = optim.apply(cfg, params, grads, state)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    mhat, vhat = m / 0.1, v / 0.01
    want = 1.0 - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    assert float(new_p["w"][0]) == pytest.approx(want, rel=1e-5)


def test_grad_clip_limits_update():
    cfg = optim.AdamWConfig(lr=1.0, grad_clip=1e-6, weight_decay=0.0,
                            schedule="constant", warmup_steps=0)
    params = {"w": jnp.ones(4)}
    grads = {"w": jnp.full(4, 1e6)}
    state = optim.init(params)
    _, _, metrics = optim.apply(cfg, params, grads, state)
    assert float(metrics["grad_norm"]) == pytest.approx(2e6, rel=1e-3)


def test_schedule_warmup_and_decay():
    cfg = optim.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                            schedule="cosine", min_lr_ratio=0.1)
    assert float(optim.learning_rate(cfg, jnp.asarray(5))) == pytest.approx(0.5)
    assert float(optim.learning_rate(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(optim.learning_rate(cfg, jnp.asarray(110))) == pytest.approx(0.1)


def test_training_reduces_loss_small_model():
    """End-to-end: a few steps of AdamW reduce loss on a fixed batch."""
    from repro.train import TrainConfig, init_state, train_step
    cfg = get_config("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab_size)}
    tcfg = TrainConfig(optimizer=optim.AdamWConfig(
        lr=1e-3, warmup_steps=0, total_steps=100, schedule="constant",
        weight_decay=0.0))
    state = init_state(params, tcfg)
    step = jax.jit(lambda s, b: train_step(s, b, cfg, tcfg))
    first = None
    for i in range(8):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first


def test_microbatch_grad_accum_matches_full_batch():
    from repro.train import grads_and_metrics
    cfg = get_config("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (4, 16), 0, cfg.vocab_size)}
    g1, _ = jax.jit(lambda p, b: grads_and_metrics(p, b, cfg, 1))(params, batch)
    g2, _ = jax.jit(lambda p, b: grads_and_metrics(p, b, cfg, 2))(params, batch)
    flat1, flat2 = jax.tree.leaves(g1), jax.tree.leaves(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-4)


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_int8_compression_error_feedback_converges():
    """EF property: accumulated quantization error stays bounded and the
    long-run mean of transmitted values matches the true gradient."""
    from repro.optim.compress import _dequantize, _quantize
    rng = np.random.default_rng(0)
    g = rng.standard_normal(512).astype(np.float32)
    residual = np.zeros_like(g)
    sent_sum = np.zeros_like(g)
    for step in range(200):
        x = g + residual
        q, s = _quantize(jnp.asarray(x))
        sent = np.asarray(_dequantize(q, s))
        residual = x - sent
        sent_sum += sent
    np.testing.assert_allclose(sent_sum / 200, g, rtol=0, atol=1e-2)
    assert np.abs(residual).max() < 0.1


def test_compression_ratio_near_4x():
    assert optim.compression_ratio() == pytest.approx(0.26, abs=0.01)


def test_compressed_psum_under_shard_map():
    """Compressed allreduce over a 'pod' axis == mean of shards (approx)."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("pod",))
    g = {"w": jnp.arange(8, dtype=jnp.float32) / 7.0}
    r = optim.init_residuals(g)

    def fn(g, r):
        return optim.compressed_psum_tree(g, r, "pod")

    out, new_r = jax.shard_map(fn, mesh=mesh,
                               in_specs=(P(), P()), out_specs=(P(), P()))(g, r)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=0.02)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def _dcfg(**kw):
    return DataConfig(vocab_size=1000, seq_len=128, global_batch=4, **kw)


def test_data_deterministic_across_restarts():
    cfg = _dcfg()
    a = make_batch(cfg, step=7)
    b = make_batch(cfg, step=7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_data_hosts_disjoint():
    a = make_batch(_dcfg(num_hosts=2, host_id=0), 0)
    b = make_batch(_dcfg(num_hosts=2, host_id=1), 0)
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_iterator_resume_mid_stream():
    cfg = _dcfg()
    it = DataIterator(cfg)
    batches = [next(it) for _ in range(3)]
    state = IteratorState.from_dict(it.state.to_dict())
    it.close()
    it2 = DataIterator(cfg, state)
    b3 = next(it2)
    it2.close()
    want = make_batch(cfg, 3)
    np.testing.assert_array_equal(b3["tokens"], want["tokens"])


def test_packing_descriptors_cover_sequences():
    from repro.data import pack_documents
    cfg = _dcfg()
    rng = np.random.default_rng(0)
    tokens, seg, chain = pack_documents(cfg, rng, batch_rows=2)
    lens = np.asarray(chain.length)
    dsts = np.asarray(chain.dst)
    # Descriptors tile the packed space exactly, without overlap.
    covered = np.zeros(2 * cfg.seq_len, bool)
    for dst, ln in zip(dsts, lens):
        assert not covered[dst:dst + ln].any()
        covered[dst:dst + ln] = True
    assert covered.all()
    assert (seg > 0).all()


# ---------------------------------------------------------------------------
# Checkpointing / fault tolerance
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones(4, jnp.bfloat16)}}
    ck.save(10, tree, blocking=True, extra={"iterator": {"step": 10}})
    got, extra = ck.restore(10, tree)
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))
    assert got["nested"]["b"].dtype == jnp.bfloat16
    assert extra["iterator"]["step"] == 10


def test_checkpoint_ignores_uncommitted(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = {"a": jnp.zeros(2)}
    ck.save(1, tree, blocking=True)
    # Simulate a torn write: step dir without COMMIT.
    os.makedirs(tmp_path / "step_000000002")
    assert ck.latest_step() == 1


def test_checkpoint_gc_keeps_last_k(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.zeros(2)}
    for s in (1, 2, 3, 4):
        ck.save(s, tree, blocking=True)
    assert ck.committed_steps() == [3, 4]


def test_trainer_resumes_after_interrupt(tmp_path):
    """Kill training mid-run; a fresh Trainer resumes from the checkpoint
    with identical data stream position."""
    from repro.train import Trainer, TrainConfig, TrainerConfig
    cfg = get_config("qwen2.5-3b", reduced=True)
    tcfg = TrainConfig(optimizer=optim.AdamWConfig(
        lr=1e-4, warmup_steps=0, schedule="constant"))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=2)
    run = TrainerConfig(total_steps=6, checkpoint_every=3,
                        checkpoint_dir=str(tmp_path), log_every=100)
    t1 = Trainer(cfg, tcfg, run, dcfg)
    r1 = t1.train()
    assert r1["final_step"] == 6
    # Resume: should detect step 6 checkpoint and do nothing more.
    run2 = TrainerConfig(total_steps=8, checkpoint_every=3,
                         checkpoint_dir=str(tmp_path), log_every=100)
    t2 = Trainer(cfg, tcfg, run2, dcfg)
    r2 = t2.train()
    assert r2["final_step"] == 8
    assert len(r2["losses"]) == 2   # only steps 6,7 ran after resume


def test_elastic_restore_to_new_sharding(tmp_path):
    """Restore a checkpoint with explicit (different) shardings — the
    elastic re-mesh path."""
    from jax.sharding import NamedSharding, PartitionSpec as P, Mesh
    ck = Checkpointer(str(tmp_path))
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    ck.save(1, tree, blocking=True)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    got, _ = ck.restore(1, tree, shardings=sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))


def test_straggler_monitor_flags_slow_steps():
    from repro.train import StragglerMonitor
    m = StragglerMonitor(threshold=2.0)
    for s in range(10):
        m.observe(s, 1.0)
    assert m.observe(10, 5.0)
    assert 10 in m.flagged


# ---------------------------------------------------------------------------
# Paged KV cache + serving engine
# ---------------------------------------------------------------------------

def test_page_allocator_and_chains():
    from repro.serve import PageAllocator
    a = PageAllocator(16)
    p0 = a.alloc(0, 3)
    assert len(p0) == 3 and a.free_pages == 13
    # Sequential allocation -> perfect speculation hit rate by construction.
    assert a.speculation_hit_rate(0) == 1.0
    chain = a.chain(0, page_elems=8)
    assert chain.num_descriptors == 3
    a.free(0)
    assert a.free_pages == 16


def test_paged_cache_append_and_dense_view():
    c = PagedKVCache(page=4, num_pages=8, max_seqs=2, max_pages_per_seq=3,
                     kv_heads=2, head_dim=8)
    c.admit(0)
    rows = [np.full((2, 8), i, np.float32) for i in range(6)]
    for r in rows:
        c.append(0, jnp.asarray(r), jnp.asarray(r * 2))
    k, v = c.dense_view(0)
    assert k.shape == (6, 2, 8)
    for i in range(6):
        np.testing.assert_array_equal(k[i], rows[i])
        np.testing.assert_array_equal(v[i], rows[i] * 2)


def test_paged_cache_kernel_consistency():
    """Engine-managed pool + Pallas paged kernel == dense attention."""
    from repro.kernels import paged_attention_op, ref
    c = PagedKVCache(page=8, num_pages=6, max_seqs=2, max_pages_per_seq=3,
                     kv_heads=2, head_dim=128)
    rng = np.random.default_rng(0)
    for slot, ln in [(0, 20), (1, 9)]:
        c.admit(slot)
        for _ in range(ln):
            c.append(slot, jnp.asarray(rng.standard_normal((2, 128)),
                                       jnp.float32),
                     jnp.asarray(rng.standard_normal((2, 128)), jnp.float32))
    q = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.float32)
    out = paged_attention_op(q, *c.kernel_args())
    want = ref.paged_attention_ref(q, *c.kernel_args())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_out_of_pages_raises():
    from repro.serve import OutOfPages
    c = PagedKVCache(page=2, num_pages=1, max_seqs=1, max_pages_per_seq=4,
                     kv_heads=1, head_dim=4)
    c.admit(0)
    for _ in range(2):
        c.append(0, jnp.zeros((1, 4)), jnp.zeros((1, 4)))
    with pytest.raises(OutOfPages):
        c.append(0, jnp.zeros((1, 4)), jnp.zeros((1, 4)))


def test_serve_engine_continuous_batching_matches_reference():
    from repro.models import prefill, decode_step
    cfg = get_config("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = list(rng.integers(1, 500, 5))
    eng = ServeEngine(params, cfg, capacity=3, max_len=64)
    eng.submit(SubmitRequest(request=Request(uid=0, prompt=prompt,
                                             max_new_tokens=4)))
    eng.submit(SubmitRequest(request=Request(
        uid=1, prompt=list(rng.integers(1, 500, 3)), max_new_tokens=4)))
    eng.submit(SubmitRequest(request=Request(
        uid=2, prompt=list(rng.integers(1, 500, 7)), max_new_tokens=4)))
    done = eng.run(max_steps=100)
    assert sorted(done) == [0, 1, 2]
    assert len(eng.poll_completed()) == 3

    logits, state = prefill(params, {"tokens": jnp.asarray([prompt])}, cfg,
                            max_len=64)
    ref_out = []
    tok = jnp.argmax(logits, -1)
    for _ in range(4):
        ref_out.append(int(tok[0]))
        logits, state = decode_step(params, tok, state, cfg)
        tok = jnp.argmax(logits, -1)
    assert done[0].output == ref_out


def test_serve_engine_slot_reuse_is_clean():
    """A request admitted into a previously-used slot must not see stale KV."""
    cfg = get_config("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompt = list(rng.integers(1, 500, 5))
    # Engine A: slot 0 used twice (uid 0 then uid 2).
    eng = ServeEngine(params, cfg, capacity=1, max_len=64)
    eng.submit(SubmitRequest(request=Request(
        uid=0, prompt=list(rng.integers(1, 500, 9)), max_new_tokens=3)))
    eng.submit(SubmitRequest(request=Request(uid=2, prompt=prompt,
                                             max_new_tokens=3)))
    out_reused = eng.run(max_steps=200)[2].output
    # Engine B: fresh engine, same request.
    eng2 = ServeEngine(params, cfg, capacity=1, max_len=64)
    eng2.submit(SubmitRequest(request=Request(uid=2, prompt=prompt,
                                              max_new_tokens=3)))
    out_fresh = eng2.run(max_steps=100)[2].output
    assert out_reused == out_fresh


# ---------------------------------------------------------------------------
# Launch helpers: compile cache placement, mesh axis types
# ---------------------------------------------------------------------------

def test_compile_cache_uses_env_dir_and_sets_nothing(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_launch_meshes_have_auto_axes():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)


def test_chip_entry_points_do_not_import_dryrun():
    # launch/dryrun.py sets XLA_FLAGS for 512 host devices when imported;
    # a process that drives a chip must never load it.
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys, chip_smoke, repro.launch.serve, repro.launch.train,"
            " benchmarks.run; print('repro.launch.dryrun' in sys.modules)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(repo, "src"), repo]))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split()[-1] == "False"
