"""CP-ALS: convergence, sparse path, pSRAM-quantized variant."""
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.cp_als import cp_als, cp_als_psram, reconstruct
from repro.core.mttkrp import dense_to_coo
from repro.data.tensors import lowrank_dense, sparse_coo


def test_exact_lowrank_recovery(key):
    x, _ = lowrank_dense(key, (12, 10, 8), rank=3)
    st = cp_als(x, rank=3, n_iter=200, key=jax.random.PRNGKey(7))
    assert st.fit > 0.995


def test_fit_improves(key):
    x, _ = lowrank_dense(key, (10, 9, 8), rank=4, noise=0.01)
    st5 = cp_als(x, rank=4, n_iter=3, key=jax.random.PRNGKey(3))
    st50 = cp_als(x, rank=4, n_iter=50, key=jax.random.PRNGKey(3))
    assert st50.fit >= st5.fit - 1e-6


def test_reconstruct_matches_model(key):
    x, factors = lowrank_dense(key, (6, 5, 4), rank=2)
    xr = reconstruct(factors)
    assert float(jnp.max(jnp.abs(x - xr))) < 1e-5


def test_sparse_coo_path(key):
    x, _ = lowrank_dense(key, (8, 7, 6), rank=2)
    idx, vals = dense_to_coo(x)
    st = cp_als(None, rank=2, n_iter=40, coo=(idx, vals, x.shape),
                key=jax.random.PRNGKey(5))
    assert st.fit > 0.98


def test_psram_quantized_als_tracks_float(key):
    """The paper's engine (8-bit + ADC) must converge close to float ALS."""
    x, _ = lowrank_dense(key, (10, 8, 6), rank=3)
    idx, vals = dense_to_coo(x)
    st_f = cp_als(None, rank=3, n_iter=30, coo=(idx, vals, x.shape),
                  key=jax.random.PRNGKey(11))
    st_q = cp_als_psram((idx, vals, x.shape), rank=3, n_iter=30,
                        key=jax.random.PRNGKey(11))
    assert st_q.fit > 0.9
    assert st_f.fit - st_q.fit < 0.08  # quantization-limited gap


def test_als_on_sampled_sparse(key):
    """A sampled sparse tensor is not globally low-rank (implicit zeros), so
    assert progress rather than a high absolute fit."""
    idx, vals, shape = sparse_coo(key, (30, 25, 20), nnz=2000, rank=3)
    st2 = cp_als(None, rank=4, n_iter=2, coo=(idx, vals, shape),
                 key=jax.random.PRNGKey(13), tol=0)
    st25 = cp_als(None, rank=4, n_iter=25, coo=(idx, vals, shape),
                  key=jax.random.PRNGKey(13), tol=0)
    assert st25.fit > st2.fit
    assert st25.fit > 0.05


def test_cp_als_spans_prepare_and_fit_read(key):
    """One ``als/prepare`` ends before the first sweep starts, and every
    ``als/fit`` holds the host read of its fit."""
    idx, vals, shape = sparse_coo(key, (12, 10, 8), nnz=300, rank=3)
    obs.get_tracer().clear()
    obs.enable()
    try:
        st = cp_als(None, rank=3, n_iter=4, coo=(idx, vals, shape),
                    key=jax.random.PRNGKey(2), tol=0)
        events = obs.get_tracer().events()
    finally:
        obs.disable()
        obs.get_tracer().clear()

    def named(name):
        return sorted((e for e in events if e["name"] == name),
                      key=lambda e: e["ts"])

    prepare, sweeps = named("als/prepare"), named("als/sweep")
    fits, reads = named("als/fit"), named("als/fit/read")
    assert len(prepare) == 1 and len(sweeps) == st.iters == 4
    assert prepare[0]["ts"] + prepare[0]["dur"] <= sweeps[0]["ts"]
    assert len(fits) == len(reads) == st.iters
    for fit, read in zip(fits, reads):
        assert fit["ts"] <= read["ts"]
        assert read["ts"] + read["dur"] <= fit["ts"] + fit["dur"] + 1e-3
