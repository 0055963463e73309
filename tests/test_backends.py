"""The backend registry's standing correctness contract.

* parity: every registered executable backend runs the SAME dense + sparse
  fixtures and lands on ``"exact"``'s answer — bit-identical for exact
  backends, within the documented ADC quantization envelope
  (``Capabilities.rel_tol``) for lossy ones; the two schedule
  interpretations (``psram-oracle`` / ``psram-scheduled``) are bit-identical
  to each other on matmuls (the PR-2 invariant, now a registry property);
* registry error paths: unknown names, cost-only backends asked to execute,
  executable-only backends asked to price;
* config: validation happens at backend *construction* (satellite: the
  analytical path rejects invalid configs instead of silently pricing
  them), and ``resolve_config`` threads the canonical paper default;
* the acceptance bar: on the §V-A paper config, ``"analytical"``'s cost
  equals ``"psram-scheduled"``'s counted cycles exactly (dense), and
  ``"psram-stream"``'s counted cycles exactly (sparse) — preserving the
  PR 2/3 analytical-vs-measured invariants through the new seam.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, backends
from repro.core.mttkrp import dense_to_coo, mttkrp_dense
from repro.core.perf_model import MTTKRPWorkload, SparseMTTKRPWorkload
from repro.core.psram import PsramConfig
from repro.sparse import csf_for_mode, powerlaw_coo, powerlaw_fiber_lengths

RANK = 5
DENSE_SHAPE = (12, 10, 8)
EXECUTABLE = [n for n in backends.list_backends()
              if backends.get(n).capabilities().executes]
SPARSE_CAPABLE = [n for n in EXECUTABLE
                  if backends.get(n).capabilities().sparse]
MATMUL_CAPABLE = [n for n in EXECUTABLE
                  if backends.get(n).capabilities().matmul]


@pytest.fixture(scope="module")
def dense_fixture():
    x = jax.random.normal(jax.random.PRNGKey(0), DENSE_SHAPE)
    fs = tuple(jax.random.normal(jax.random.PRNGKey(d + 1), (s, RANK))
               for d, s in enumerate(DENSE_SHAPE))
    return x, fs


@pytest.fixture(scope="module")
def sparse_fixture():
    coo = powerlaw_coo(jax.random.PRNGKey(7), (40, 30, 20), nnz=1500,
                       rank=3, alpha=1.1)
    fs = tuple(jax.random.normal(jax.random.PRNGKey(d + 11), (s, RANK))
               for d, s in enumerate(coo.shape))
    return coo, fs


def _tol(name) -> float:
    return backends.get(name).capabilities().rel_tol


def _assert_parity(got, want, name):
    if _tol(name) == 0.0:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < _tol(name), (name, rel)


# ------------------------------------------------------------------- parity

@pytest.mark.parametrize("name", EXECUTABLE)
@pytest.mark.parametrize("mode", [0, 1, 2])
def test_dense_mttkrp_parity(name, mode, dense_fixture):
    x, fs = dense_fixture
    want = mttkrp_dense(x, list(fs), mode)
    got = backends.get(name).mttkrp(x, fs, mode)
    assert got.shape == want.shape
    # the lossy envelope is looser than exact's bit-identity; pallas's fused
    # kernel reassociates, so exact backends get allclose-or-equal per caps
    if name == "exact":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    elif _tol(name) == 0.0:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    else:
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < _tol(name), (name, rel)


@pytest.mark.parametrize("name", SPARSE_CAPABLE)
def test_sparse_mttkrp_parity(name, sparse_fixture):
    coo, fs = sparse_fixture
    csf = csf_for_mode(coo, 0)                # shared sorted fixture
    want = backends.get("exact").mttkrp(csf, fs, 0)
    got = backends.get(name).mttkrp(csf, fs, 0)
    if name == "exact":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    tol = _tol(name) or 1e-4                  # pallas: float reassociation
    assert rel < tol, (name, rel)


@pytest.mark.parametrize("name", SPARSE_CAPABLE)
def test_mttkrp_data_forms_agree(name, sparse_fixture):
    """One workload union: COO triple, container, and CSF must hit the same
    path — identical results on the sorted stream."""
    coo, fs = sparse_fixture
    be = backends.get(name)
    csf = csf_for_mode(coo, 1)
    sorted_coo = csf.to_coo()
    triple = (sorted_coo.indices, sorted_coo.values, tuple(sorted_coo.shape))
    np.testing.assert_array_equal(
        np.asarray(be.mttkrp(csf, fs, 1)),
        np.asarray(be.mttkrp(triple, fs, 1)),
    )


@pytest.mark.parametrize("name", MATMUL_CAPABLE)
def test_matmul_parity(name):
    x = jax.random.normal(jax.random.PRNGKey(2), (7, 33))
    w = jax.random.normal(jax.random.PRNGKey(3), (33, 9))
    got = backends.get(name).matmul(x, w)
    _assert_parity(got, x @ w, name)


def test_oracle_and_scheduled_bit_identical():
    """PR 2's executor invariant, restated as a registry property: the
    vectorized schedule and the per-cycle array physics are the same
    function."""
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 40))
    w = jax.random.normal(jax.random.PRNGKey(5), (40, 17))
    cfg = PsramConfig(rows=16, word_cols=8, wavelengths=4)
    a = backends.get("psram-oracle", cfg).matmul(x, w)
    b = backends.get("psram-scheduled", cfg).matmul(x, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_matches_flat_quantized_on_sorted_stream(sparse_fixture):
    """PR 3's invariant through the registry: the streaming schedule equals
    the flat quantized chain bit-for-bit on the same sorted nonzeros."""
    coo, fs = sparse_fixture
    csf = csf_for_mode(coo, 0)
    a = backends.get("psram-stream").mttkrp(csf, fs, 0)
    b = backends.get("psram-oracle").mttkrp(csf.to_coo(), fs, 0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------- registry plumbing

def test_registry_lists_all_first_class_backends():
    names = backends.list_backends()
    for expected in ("exact", "psram-oracle", "psram-scheduled",
                     "psram-stream", "pallas", "analytical"):
        assert expected in names


def test_unknown_backend_raises():
    with pytest.raises(backends.UnknownBackendError, match="registered:"):
        backends.get("does-not-exist")
    with pytest.raises(backends.UnknownBackendError):
        api.estimate(MTTKRPWorkload(), backend="nope")


def test_cost_only_backend_refuses_to_execute(dense_fixture):
    x, fs = dense_fixture
    be = backends.get("analytical")
    with pytest.raises(backends.CapabilityError):
        be.mttkrp(x, fs, 0)
    with pytest.raises(backends.CapabilityError):
        be.matmul(x[0:2, 0:2], x[0:2, 0:2])
    with pytest.raises(backends.CapabilityError):
        api.execute(api.MTTKRPProblem(x, fs, 0), backend="analytical")


def test_execute_only_backend_refuses_to_price():
    with pytest.raises(backends.CapabilityError):
        backends.get("exact").cost(MTTKRPWorkload())
    with pytest.raises(backends.CapabilityError):
        backends.get("pallas").cost(MTTKRPWorkload())


def test_instance_passthrough_and_config_conflict():
    be = backends.get("exact")
    assert backends.get(be) is be
    with pytest.raises(ValueError):
        backends.get(be, PsramConfig())


def test_scheduled_backend_rejects_sparse(sparse_fixture):
    coo, fs = sparse_fixture
    with pytest.raises(backends.CapabilityError):
        backends.get("psram-scheduled").mttkrp(coo, fs, 0)


# --------------------------------------------- compiled fast mode (PR 5)

@pytest.mark.parametrize("name", ["psram-scheduled", "psram-stream"])
def test_compiled_capability_wiring(name):
    be = backends.get(name, compiled=True)
    caps = be.capabilities()
    assert caps.compiled and not caps.bit_exact
    eager = backends.get(name).capabilities()
    assert not eager.compiled and eager.bit_exact
    assert caps.rel_tol == eager.rel_tol      # same quantization envelope


def test_compiled_stream_parity(sparse_fixture):
    """Compiled stream backend: same ADC envelope vs exact, tight
    reassociation envelope vs its own eager twin, and bit-identical to the
    flat blocked reference with the quantized chain."""
    from repro.core.mttkrp import mttkrp_sparse_blocked

    coo, fs = sparse_fixture
    csf = csf_for_mode(coo, 0)
    fast = backends.get("psram-stream", compiled=True).mttkrp(csf, fs, 0)
    eager = backends.get("psram-stream").mttkrp(csf, fs, 0)
    want = backends.get("exact").mttkrp(csf, fs, 0)
    assert float(jnp.linalg.norm(fast - want) / jnp.linalg.norm(want)) < 0.05
    assert float(jnp.linalg.norm(fast - eager) / jnp.linalg.norm(eager)) < 1e-4
    s = csf.to_coo()
    ref = mttkrp_sparse_blocked(s.indices, s.values, fs, 0, coo.shape[0],
                                psram=True)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(ref))


def test_compiled_scheduled_matmul_envelope():
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 70))
    w = jax.random.normal(jax.random.PRNGKey(1), (70, 30))
    fast = backends.get("psram-scheduled", compiled=True).matmul(x, w)
    eager = backends.get("psram-scheduled").matmul(x, w)
    assert float(jnp.linalg.norm(fast - eager) / jnp.linalg.norm(eager)) < 1e-6


def test_get_rejects_kwargs_on_instances_and_unknown_kwargs():
    be = backends.get("exact")
    with pytest.raises(ValueError):
        backends.get(be, compiled=True)
    with pytest.raises(TypeError):
        backends.get("exact", compiled=True)   # no compiled mode there


def test_cp_als_compiled_backend(sparse_fixture):
    from repro.core.cp_als import cp_als

    coo, _ = sparse_fixture
    a = cp_als(None, rank=3, n_iter=5, sparse=coo, backend="psram-stream",
               key=jax.random.PRNGKey(2))
    b = cp_als(None, rank=3, n_iter=5, sparse=coo, backend="psram-stream",
               compiled=True, key=jax.random.PRNGKey(2))
    assert b.fit == pytest.approx(a.fit, abs=1e-3)
    with pytest.raises(ValueError):
        cp_als(None, rank=3, n_iter=2, sparse=coo, compiled=True)


# ------------------------------------------------- config resolution rules

def test_config_validated_at_construction():
    """Satellite: analytical-only paths reject invalid configs up front
    instead of silently pricing them."""
    bad = PsramConfig(wavelengths=99)
    for name in backends.list_backends():
        with pytest.raises(ValueError):
            backends.get(name, bad)
    with pytest.raises(ValueError):
        api.estimate(MTTKRPWorkload(), backend="analytical", config=bad)


def test_resolve_config_threads_paper_default():
    from repro.configs.psram_mttkrp import CONFIG

    assert backends.resolve_config(None) == CONFIG.array
    custom = PsramConfig(rows=16, word_cols=8, wavelengths=4)
    assert backends.resolve_config(custom) is custom
    assert backends.get("analytical").config == CONFIG.array


# ---------------------------------------- analytical == counted (§V-A bar)

def test_analytical_matches_scheduled_counts_exactly_on_paper_config():
    """Acceptance: for the §V-A paper config the closed-form model and the
    counted-cycle accountant are the same numbers, term by term — exactly."""
    wl = MTTKRPWorkload()  # I=J=K=1e6, R=32 on the 256x32x52@20GHz array
    a = api.estimate(wl, backend="analytical")
    s = api.estimate(wl, backend="psram-scheduled")
    assert a.breakdown == s.breakdown
    assert a.utilization == s.utilization
    assert a.sustained_petaops == s.sustained_petaops
    assert s.counts is not None and s.counts.total_cycles > 0
    assert a.counts is None  # closed form carries no op walk


def test_analytical_matches_stream_counts_exactly_on_paper_config():
    f = powerlaw_fiber_lengths(0, 10**4, 4 * 10**4, alpha=1.1)
    wl = SparseMTTKRPWorkload(fiber_lengths=f, rank=32)
    a = api.estimate(wl, backend="analytical")
    s = api.estimate(wl, backend="psram-stream")
    assert a.breakdown == s.breakdown
    assert a.sustained_petaops == s.sustained_petaops


def test_estimate_from_raw_data_matches_descriptor(sparse_fixture):
    coo, _ = sparse_fixture
    via_data = api.estimate(coo, backend="analytical", rank=RANK, mode=0)
    wl = SparseMTTKRPWorkload(
        fiber_lengths=csf_for_mode(coo, 0).fiber_lengths(), rank=RANK)
    via_desc = api.estimate(wl, backend="analytical")
    assert via_data.breakdown == via_desc.breakdown


# ----------------------------------------------------------- api facade

def test_api_execute_forms(dense_fixture):
    x, fs = dense_fixture
    want = mttkrp_dense(x, list(fs), 0)
    a = api.execute(api.MTTKRPProblem(x, fs, 0), backend="exact")
    b = api.execute(x, backend="exact", factors=fs)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(want))
    with pytest.raises(ValueError):
        api.execute(x, backend="exact")  # factors missing
    with pytest.raises(ValueError):
        api.execute(api.MTTKRPProblem(x, fs, 0), backend="exact", factors=fs)


def test_api_matmul_dispatch():
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 16))
    w = jax.random.normal(jax.random.PRNGKey(9), (16, 8))
    got = api.matmul(x, w, backend="psram-scheduled",
                     config=PsramConfig(rows=16, word_cols=8, wavelengths=4))
    rel = float(jnp.linalg.norm(got - x @ w) / jnp.linalg.norm(x @ w))
    assert 0 < rel < 0.05  # went through the quantized array, not jnp


def test_estimate_requires_rank_for_raw_data(dense_fixture):
    x, _ = dense_fixture
    with pytest.raises(ValueError, match="rank"):
        api.estimate(x, backend="analytical")


# -------------------------------------------------- cp_als backend dispatch

def test_cp_als_backend_names_agree(dense_fixture):
    from repro.core.cp_als import cp_als

    x, _ = dense_fixture
    st_default = cp_als(x, rank=3, n_iter=8, key=jax.random.PRNGKey(1), tol=0)
    st_exact = cp_als(x, rank=3, n_iter=8, key=jax.random.PRNGKey(1), tol=0,
                      backend="exact")
    assert st_exact.fit == pytest.approx(st_default.fit, abs=1e-6)
    st_q = cp_als(x, rank=3, n_iter=8, key=jax.random.PRNGKey(1), tol=0,
                  backend="psram-stream")
    assert st_q.fit == pytest.approx(st_default.fit, abs=0.05)


def test_cp_als_rejects_cost_only_backend(dense_fixture):
    from repro.core.cp_als import cp_als

    x, _ = dense_fixture
    with pytest.raises(backends.CapabilityError):
        cp_als(x, rank=2, n_iter=2, backend="analytical")


def test_cp_als_mttkrp_fn_deprecated(dense_fixture):
    from repro.core.cp_als import cp_als
    from repro.core.mttkrp import mttkrp_dense as md

    x, _ = dense_fixture
    fn = lambda t, fs, m: md(x, list(fs), m)
    with pytest.deprecated_call():
        st = cp_als(x, rank=2, n_iter=3, mttkrp_fn=fn, tol=0)
    assert np.isfinite(st.fit)
    with pytest.raises(ValueError):
        cp_als(x, rank=2, n_iter=2, backend="exact", mttkrp_fn=fn)


# ------------------------------------------------------- kernel lowerings

def test_kernel_lowering_strings_registry_owned():
    from repro.kernels.ops import psram_matmul_op

    assert backends.resolve_lowering("ref") == "ref"
    assert backends.resolve_lowering("auto") in ("pallas", "interpret")
    with pytest.raises(ValueError, match="unknown kernel lowering"):
        backends.resolve_lowering("cuda")
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    with pytest.raises(ValueError):
        psram_matmul_op(x, w, backend="not-a-lowering")


def test_pallas_backend_wraps_kernels():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    from repro.kernels.ops import psram_matmul_op

    got = backends.get("pallas").matmul(x, w)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(psram_matmul_op(x, w)))


def test_exec_lowering_registry_owned():
    """Execution resolves separately from validation: ``"auto"`` must land
    on a *fast* lowering (real pallas on TPU, the fused XLA twin elsewhere),
    never interpret mode; both resolvers reject unknown strings."""
    assert backends.resolve_exec_lowering("auto") in ("pallas", "xla")
    assert backends.resolve_exec_lowering("ref") == "ref"
    for low in backends.RESOLVED_LOWERINGS:
        assert backends.resolve_exec_lowering(low) == low
    with pytest.raises(ValueError, match="unknown kernel lowering"):
        backends.resolve_exec_lowering("cuda")


def test_stream_lowering_on_tpu_is_xla(monkeypatch):
    """On a TPU ``"auto"`` sends the fused stream op to its ``"xla"``
    lowering (the v5e compiler refuses its Pallas kernel) while the other
    ops take real Pallas; an explicit ``"pallas"`` request is passed through
    untouched, so it fails loudly at compile time instead of falling back."""
    from repro.backends import lowering

    monkeypatch.delenv("REPRO_KERNEL_LOWERING", raising=False)
    lowering._env_override.cache_clear()
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    assert backends.resolve_stream_lowering("auto") == "xla"
    assert backends.resolve_stream_lowering("pallas") == "pallas"
    assert backends.resolve_exec_lowering("auto") == "pallas"
    assert backends.resolve_lowering("auto") == "pallas"
    with pytest.raises(ValueError, match="unknown kernel lowering"):
        backends.resolve_stream_lowering("cuda")
    assert backends.get("pallas").lowerings == {
        "psram_matmul": "pallas", "mttkrp_psram": "pallas",
        "stream_mttkrp": "xla"}
    assert backends.get("pallas", lowering="pallas").lowerings[
        "stream_mttkrp"] == "pallas"
    monkeypatch.setattr(lowering, "on_tpu", lambda: False)
    assert backends.get("pallas").lowerings == {
        "psram_matmul": "xla", "mttkrp_psram": "xla", "stream_mttkrp": "xla"}


def test_pallas_capabilities_compiled_autotune_wiring():
    caps = backends.get("pallas").capabilities()
    assert caps.compiled and not caps.autotune      # fused family by default
    legacy = backends.get("pallas", compiled=False).capabilities()
    assert not legacy.compiled
    assert "legacy" in legacy.description
    tuned = backends.get("pallas", autotune=True).capabilities()
    assert tuned.compiled and tuned.autotune


def test_pallas_lowering_resolved_at_construction():
    """The lowering string resolves ONCE, at backend construction — an
    invalid string fails there, not at the first kernel call, and the
    resolved value is a concrete lowering (never "auto")."""
    be = backends.get("pallas")
    assert be.lowering in backends.RESOLVED_LOWERINGS
    with pytest.raises(ValueError, match="unknown kernel lowering"):
        backends.get("pallas", lowering="cuda")
    # the legacy per-op path resolves through the validation contract
    legacy = backends.get("pallas", compiled=False)
    assert legacy.lowering in backends.RESOLVED_LOWERINGS


def test_kernel_op_missing_dispatch_entry_is_clear():
    """An op asked for a resolved lowering it doesn't implement reports
    exactly what exists instead of a bare KeyError (satellite: flash
    attention has no fused-XLA twin — interpret/pallas/ref only)."""
    from repro.kernels.ops import flash_attention_op

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 16))
    with pytest.raises(RuntimeError, match="no dispatch entry.*implemented"):
        flash_attention_op(q, q, q, backend="xla")


def test_pallas_autotuned_sparse_parity(sparse_fixture):
    """autotune=True tunes in-process and stays inside the envelope; the
    winner lands in the autotune cache."""
    from repro.kernels.autotune import cache_stats, clear_autotune_cache

    coo, fs = sparse_fixture
    csf = csf_for_mode(coo, 0)
    want = backends.get("exact").mttkrp(csf, fs, 0)
    clear_autotune_cache()
    try:
        be = backends.get("pallas", autotune=True)
        got = be.mttkrp(csf, fs, 0)
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < _tol("pallas")
        assert cache_stats()[0] == 1
    finally:
        clear_autotune_cache()
