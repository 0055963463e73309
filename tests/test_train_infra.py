"""Training infra: grad-accum equivalence, compression, checkpoint, data."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointError, CheckpointManager
from repro.data import DataConfig, batch_at_step
from repro.dist.compression import compress_int8, compress_tree, decompress_int8
from repro.models.registry import get_config
from repro.optim import AdamWConfig, schedule
from repro.train.step import init_train_state, make_train_step


def _tiny():
    return get_config("granite_8b").reduced()


def test_loss_decreases():
    cfg = _tiny()
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=60)))
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
    losses = []
    for i in range(40):
        t, l = batch_at_step(dc, i)
        params, opt, m = step(params, opt, {"tokens": t, "labels": l})
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3


def test_grad_accum_equivalent():
    """microbatches=2 must match microbatches=1 on the same global batch."""
    cfg = _tiny()
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
    oc = AdamWConfig(lr=1e-3)
    s1 = jax.jit(make_train_step(cfg, oc, microbatches=1))
    s2 = jax.jit(make_train_step(cfg, oc, microbatches=2))
    p0, o0 = init_train_state(jax.random.PRNGKey(0), cfg)
    t, l = batch_at_step(dc, 0)
    p1, _, m1 = s1(p0, o0, {"tokens": t, "labels": l})
    p0b, o0b = init_train_state(jax.random.PRNGKey(0), cfg)
    p2, _, m2 = s2(p0b, o0b, {"tokens": t, "labels": l})
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
    l1 = jax.tree.leaves(p1)
    l2 = jax.tree.leaves(p2)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-4)


def test_schedule_shape():
    oc = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    assert float(schedule(oc, jnp.int32(0))) == 0.0
    assert abs(float(schedule(oc, jnp.int32(10))) - 1.0) < 0.01
    assert float(schedule(oc, jnp.int32(100))) <= 0.11


def test_compression_roundtrip(key):
    g = jax.random.normal(key, (64,)) * 3.0
    q, s = compress_int8(g)
    deq = decompress_int8(q, s)
    assert float(jnp.max(jnp.abs(deq - g))) <= float(s) / 2 + 1e-6


def test_compression_error_feedback(key):
    g = {"a": jax.random.normal(key, (32,))}
    deq, res = compress_tree(g)
    np.testing.assert_allclose(
        np.asarray(deq["a"] + res["a"]), np.asarray(g["a"]), rtol=1e-6
    )


def test_checkpoint_roundtrip_and_atomicity(key):
    tree = {"w": jax.random.normal(key, (8, 8)), "step": jnp.int32(3)}
    with tempfile.TemporaryDirectory() as td:
        cm = CheckpointManager(td)
        cm.save(10, tree, blocking=True)
        cm.save(20, tree, blocking=True)
        # fake an aborted save: dir without `done`
        os.makedirs(os.path.join(td, "step_000000030"))
        like = {"w": jnp.zeros((8, 8)), "step": jnp.int32(0)}
        restored, step = cm.restore(like)
        assert step == 20
        np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(tree["w"]))
        assert cm.latest_step() == 20


def test_checkpoint_corrupt_load_is_a_clear_error(key):
    """Damaged bytes under a committed ``done`` marker must surface as
    CheckpointError naming the step — not a zipfile/json traceback."""
    tree = {"w": jax.random.normal(key, (8, 8)), "step": jnp.int32(3)}
    like = {"w": jnp.zeros((8, 8)), "step": jnp.int32(0)}
    with tempfile.TemporaryDirectory() as td:
        cm = CheckpointManager(td)
        cm.save(10, tree, blocking=True)
        sdir = os.path.join(td, "step_000000010")
        # truncated array archive
        npz = os.path.join(sdir, "arrays_h0.npz")
        blob = open(npz, "rb").read()
        open(npz, "wb").write(blob[: len(blob) // 2])
        with pytest.raises(CheckpointError, match="step 10.*corrupt"):
            cm.restore(like)
        open(npz, "wb").write(blob)          # heal, then damage the metadata
        open(os.path.join(sdir, "tree.json"), "w").write('{"paths": [')
        with pytest.raises(CheckpointError, match="corrupt or truncated"):
            cm.restore(like)


def test_checkpoint_tree_mismatch_is_a_clear_error(key):
    tree = {"w": jax.random.normal(key, (8, 8))}
    with tempfile.TemporaryDirectory() as td:
        cm = CheckpointManager(td)
        cm.save(5, tree, blocking=True)
        with pytest.raises(CheckpointError, match="missing leaf"):
            cm.restore({"v": jnp.zeros((8, 8))})
        with pytest.raises(CheckpointError, match="shape"):
            cm.restore({"w": jnp.zeros((4, 4))})
        # an honest absence is still FileNotFoundError, not corruption
        with tempfile.TemporaryDirectory() as empty:
            with pytest.raises(FileNotFoundError):
                CheckpointManager(empty).restore(tree)


def test_checkpoint_keeps_n(key):
    tree = {"w": jnp.ones((4,))}
    with tempfile.TemporaryDirectory() as td:
        cm = CheckpointManager(td, keep=2)
        for s in (1, 2, 3, 4):
            cm.save(s, tree, blocking=True)
        assert cm.committed_steps() == [3, 4]


def test_data_determinism_and_host_sharding():
    dc = DataConfig(vocab_size=100, seq_len=16, global_batch=8)
    a1, b1 = batch_at_step(dc, 7)
    a2, b2 = batch_at_step(dc, 7)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    # host sharding partitions the global batch
    h0, _ = batch_at_step(dc, 7, host_index=0, host_count=2)
    h1, _ = batch_at_step(dc, 7, host_index=1, host_count=2)
    assert h0.shape == (4, 16)
    assert not np.array_equal(np.asarray(h0), np.asarray(h1))
    # labels are next-token shifted
    np.testing.assert_array_equal(np.asarray(a1[:, 1:]), np.asarray(b1[:, :-1]))


def test_trainer_resume_exact(tmp_path):
    from repro.train import Trainer
    cfg = _tiny()
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)
    t1 = Trainer(cfg, dc, ckpt_dir=str(tmp_path), ckpt_every=5,
                 opt_cfg=AdamWConfig(lr=1e-3))
    t1.run(10, log_every=100, log_fn=lambda *_: None)
    t2 = Trainer(cfg, dc, ckpt_dir=str(tmp_path), opt_cfg=AdamWConfig(lr=1e-3))
    assert t2.start_step == 10
    w1 = jax.tree.leaves(t1.params)[0]
    w2 = jax.tree.leaves(t2.params)[0]
    np.testing.assert_allclose(np.asarray(w1, np.float32), np.asarray(w2, np.float32))


def test_factored_optimizer_memory_and_convergence():
    """bf16-m + factored-v AdamW: state is smaller and still trains."""
    import jax
    from repro.optim import AdamWConfig, state_structs
    cfg = _tiny()
    oc = AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=60,
                     m_dtype="bfloat16", factored_v=True)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    step = jax.jit(make_train_step(cfg, oc))
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg, oc)
    # factored v of a (d, ff) weight stores d + ff floats, not d*ff
    wi = opt["v"]["blocks"]["layer0"]["mlp"]["wi"]
    assert isinstance(wi, dict) and set(wi) == {"row", "col"}
    losses = []
    for i in range(30):
        t, l = batch_at_step(dc, i)
        params, opt, m = step(params, opt, {"tokens": t, "labels": l})
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    full = state_structs(jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params), AdamWConfig())
    small = state_structs(jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params), oc)
    assert nbytes(small) < 0.7 * nbytes(full)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """The entry points' cache: ``JAX_COMPILATION_CACHE_DIR`` wins and is
    left to JAX; unset, the cache sits at the fixed ``<repo>/.jax_cache``."""
    from repro.launch import compile_cache

    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            want = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".jax_cache")
            assert got == want
            assert jax.config.jax_compilation_cache_dir == want
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
