"""CP-ALS (Algorithm 1 of the paper): Canonical Polyadic Decomposition via
alternating least squares, with MTTKRP as the inner kernel.

Each mode update solves  A_n <- MTTKRP_n(X, factors) @ pinv(hadamard of grams)
followed by column normalization; fit is tracked against ||X||. The MTTKRP
engine is pluggable through the unified backend registry
(``repro.backends``): pass ``backend="psram-stream"`` (or any registered
name — ``"exact"``, ``"psram-oracle"``, ``"psram-scheduled"``, ``"pallas"``)
and the factor updates run on that substrate, whatever form the data takes
(dense array, COO triple, or a ``repro.sparse`` container). A bare callable
is still accepted via a deprecation adapter (the pre-registry
``mttkrp_fn=`` contract). Lossy backends get an exact convergence metric
via ``exact_fit`` (the factor updates stay on the engine under test; only
the fit inner product is recomputed exactly).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs

from .mttkrp import khatri_rao, mttkrp_dense, mttkrp_sparse
from .psram import PsramConfig
from .quantization import ADCConfig


@dataclasses.dataclass
class CPState:
    factors: list[jax.Array]     # [(I_n, R)]
    lambdas: jax.Array           # (R,) column norms
    fit: float
    iters: int


def init_factors(key: jax.Array, shape: tuple[int, ...], rank: int) -> list[jax.Array]:
    keys = jax.random.split(key, len(shape))
    return [jax.random.uniform(k, (s, rank)) for k, s in zip(keys, shape)]


def reconstruct(factors: list[jax.Array], lambdas: jax.Array | None = None) -> jax.Array:
    """Full tensor from its CP factors (small tensors only)."""
    rank = factors[0].shape[1]
    lam = jnp.ones((rank,)) if lambdas is None else lambdas
    kr = khatri_rao(factors[1:])                      # (prod I_1.., R)
    mat = (factors[0] * lam) @ kr.T                   # (I_0, prod)
    return mat.reshape([f.shape[0] for f in factors])


def _hadamard_of(grams, skip):
    """Hadamard of precomputed per-factor Grams, skipping ``skip``.

    Mode-ascending product order over ``f.T @ f`` Grams — the ALS loop
    keeps the (R, R) Grams current incrementally (recompute only the mode
    it just updated) instead of re-materializing all N of them N+1 times
    per sweep; the bits are unchanged (same op, same operand, same fold
    order as computing every Gram fresh)."""
    out = None
    for d, g in enumerate(grams):
        if d == skip:
            continue
        out = g if out is None else out * g
    return out


def _resolve_backend(backend, config, compiled=False):
    """Turn ``backend`` (registry name | Backend instance | bare callable)
    into ``(callable_fn, registry_backend)`` — exactly one is non-None.

    The callable form is the deprecation adapter for the pre-registry
    ``mttkrp_fn=`` contract (same signature, ``fn(x_or_none, factors,
    mode)``) — prefer a registered backend name.
    """
    from repro import backends as _backends

    if callable(backend) and not isinstance(backend, (str, _backends.Backend)):
        if config is not None:
            raise ValueError(
                "config= has no effect on a bare-callable backend (the "
                "callable closes over its own engine); pass a registry name "
                "or drop config="
            )
        if compiled:
            raise ValueError(
                "compiled= selects a registry backend's fast mode and has "
                "no effect on a bare callable"
            )
        return backend, None
    if compiled:
        if not isinstance(backend, str):
            raise ValueError(
                "compiled= needs a backend *name* (the instance you passed "
                "was already constructed with its own compiled setting)"
            )
        be = _backends.get(backend, config, compiled=True)
    else:
        be = _backends.get(backend, config)
    caps = be.capabilities()
    if not caps.executes:
        raise _backends.CapabilityError(
            f"backend {be.name!r} is cost-only and cannot drive CP-ALS "
            "factor updates; pick an executable backend "
            f"({', '.join(n for n in _backends.list_backends() if _backends.get(n).capabilities().executes)})"
        )
    return None, be


def _csf_cache(get_triple):
    """Per-mode CSF builder over a lazily-materialized COO triple: the
    host-side sort happens once per mode, not once per ALS sweep."""
    state: dict = {}

    def data_for(m: int):
        from repro.sparse.formats import COO, csf_for_mode

        if "coo" not in state:
            idx, vals, shp = get_triple()
            state["coo"] = COO(indices=idx, values=vals, shape=shp)
        if m not in state:
            state[m] = csf_for_mode(state["coo"], m)
        return state[m]

    return data_for


def cp_als(
    x: jax.Array | None,
    rank: int,
    n_iter: int = 25,
    key: jax.Array | None = None,
    backend=None,
    config: PsramConfig | None = None,
    mttkrp_fn: Callable | None = None,
    coo: tuple[jax.Array, jax.Array, tuple[int, ...]] | None = None,
    sparse=None,
    tol: float = 1e-7,
    exact_fit: bool | None = None,
    csfs: list | None = None,
    compiled: bool = False,
) -> CPState:
    """Run CP-ALS on ``x`` (dense), ``coo=(indices, values, shape)``, or
    ``sparse`` — any ``repro.sparse.formats`` container (COO/SortedCOO/
    BlockedCOO/CSF).

    ``backend`` selects the MTTKRP engine by registry name
    (``repro.backends``): ``"exact"``, ``"psram-oracle"``,
    ``"psram-scheduled"``, ``"psram-stream"``, ``"pallas"`` — or a prebuilt
    :class:`~repro.backends.Backend`; ``config`` is its ``PsramConfig``
    (default: the paper §V-A array). ``None`` keeps the exact default path
    for the given data form (dense einsum / COO segment-sum / streamed CSF).
    A bare callable is still accepted as a deprecation adapter with the
    pre-registry contract ``fn(x_or_none, factors, mode) -> (I_mode, R)``
    — it receives the dense ``x`` (or None for coo/sparse data), exactly as
    ``mttkrp_fn=`` always did (that spelling still works and warns).

    ``compiled=True`` opts the selected registry backend into its compiled
    fast mode (``backends.get(name, config, compiled=True)`` — the
    blocked-fold stream executor / the cached jitted matmul executor);
    factor updates then run the reassociated-fold numerics while the
    convergence metric stays exact (``exact_fit`` defaults on for any
    supplied backend). Only meaningful with a backend *name*.

    ``exact_fit`` controls the convergence metric: the inner-product fit
    trick reuses the backend's last-mode MTTKRP, so a *lossy* backend (the
    pSRAM-quantized engine, a custom callable) biases the reported fit
    — the tracked quantity drifts from ``1 - ||X - X̂||/||X||``. With
    ``exact_fit`` (default: on whenever a backend/callable is supplied),
    the fit inner product is recomputed with the exact sparse/dense path
    each sweep while the factor updates still come from the engine under
    test.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    if mttkrp_fn is not None:
        if backend is not None:
            raise ValueError("pass either backend= or (deprecated) mttkrp_fn=")
        warnings.warn(
            "cp_als(mttkrp_fn=...) is deprecated; pass backend=<registry "
            "name> (or the callable itself via backend=)",
            DeprecationWarning, stacklevel=2,
        )
        backend = mttkrp_fn
    if backend is None and config is not None:
        raise ValueError(
            "config= selects the backend's array config and needs backend=; "
            "the default exact paths don't touch a PsramConfig"
        )
    if compiled and backend is None:
        raise ValueError(
            "compiled= selects a backend's fast mode and needs backend=; "
            "the default exact paths have no compiled variant"
        )
    # from entry to the first sweep: the backend, the dedupe sort and norm,
    # the initial factors and their Grams
    with obs.span("als/prepare", rank=rank):
        callable_fn = be = None
        lossy = None
        if backend is not None:
            callable_fn, be = _resolve_backend(backend, config, compiled)
            lossy = (True if callable_fn is not None
                     else be.capabilities().lossy)
        # a backend that sorts into a mode-rooted CSF per call (psram-stream,
        # pallas sparse) must see prebuilt per-mode CSFs, or every sweep
        # re-sorts the nonzeros — mirror the sparse branch's lazy cache for
        # coo/dense too
        wants_csf = be is not None and be.capabilities().prefers_csf
        exact_last_mode_fn = None
        if sparse is not None:
            if coo is not None or x is not None:
                raise ValueError("pass exactly one of x / coo / sparse")
            from repro.sparse.formats import CSF, SortedCOO, csf_for_mode
            from repro.sparse.stream import stream_mttkrp

            base = sparse.to_coo() if isinstance(sparse, CSF) else sparse
            # duplicate coordinates are legal in the containers but would
            # corrupt ||X|| (norm of values ≠ norm of the collapsed tensor)
            # and with it the fit and the tol stopping rule — merge them up
            # front
            base = SortedCOO.from_coo(base, getattr(base, "mode_order", None),
                                      dedupe=True)
            shape = tuple(base.shape)
            norm_x = jnp.linalg.norm(base.values)
            # per-mode CSFs are the expensive host-side preprocessing:
            # callers that already built them pass csfs= through, and a
            # callable backend only ever needs the last mode (exact_fit), so
            # build lazily on first use and share the cache with the
            # registry backend
            built: dict = {}

            def mode_csf(m):
                if csfs is not None:
                    return csfs[m]
                if m not in built:
                    built[m] = csf_for_mode(base, m)
                return built[m]

            default_fn = lambda _, fs, m: stream_mttkrp(mode_csf(m),
                                                        tuple(fs))
            exact_last_mode_fn = default_fn
            backend_data = mode_csf          # a backend sees the per-mode CSF
        elif coo is not None:
            indices, values, shape = coo
            norm_x = jnp.linalg.norm(values)
            default_fn = lambda _, fs, m: mttkrp_sparse(
                indices, values, tuple(fs), m, shape[m]
            )
            exact_last_mode_fn = default_fn
            if wants_csf:
                backend_data = _csf_cache(
                    lambda: (indices, values, tuple(shape)))
            else:
                backend_data = lambda m: (indices, values, tuple(shape))
        else:
            shape = x.shape
            norm_x = jnp.linalg.norm(x)
            default_fn = lambda t, fs, m: mttkrp_dense(t, fs, m)
            exact_last_mode_fn = default_fn
            if wants_csf:
                from .mttkrp import dense_to_coo

                backend_data = _csf_cache(
                    lambda: (*dense_to_coo(x), tuple(x.shape)))
            else:
                backend_data = lambda m: x
        if callable_fn is not None:
            fn = callable_fn  # legacy contract: fn(x_or_none, factors, mode)
        elif be is not None:
            fn = lambda _, fs, m: be.mttkrp(backend_data(m), tuple(fs), m)
        else:
            fn = default_fn
        if exact_fit is None:
            # a lossy engine biases the inner-product fit; exact engines don't
            exact_fit = bool(lossy)

        factors = init_factors(key, tuple(shape), rank)
        lam = jnp.ones((rank,))
        prev_fit, fit = -1.0, 0.0
        it = 0
        last = len(shape) - 1
        # per-sweep Gram reuse: each (R, R) Gram changes only when its
        # factor does, so keep them current incrementally — N Gram matmuls
        # per sweep instead of N·(N-1) + N (the bits are unchanged: same op,
        # same operand). The Gram itself comes from the backend: local
        # ``f.T @ f`` everywhere except distributed backends ("psram-mesh"),
        # whose override all-reduces per-shard partial Grams — the sweep
        # then executes SPMD end to end.
        gram = be.gram if be is not None else (lambda f: f.T @ f)
        grams = [gram(f) for f in factors]
    backend_name = be.name if be is not None else (
        "callable" if callable_fn is not None else "default")
    for it in range(1, n_iter + 1):
        with obs.span("als/sweep", iteration=it, backend=backend_name,
                      rank=rank):
            for mode in range(len(shape)):
                m = fn(x, factors, mode)                      # MTTKRP
                g = _hadamard_of(grams, mode)                 # (R, R)
                a = m @ jnp.linalg.pinv(g)
                lam = jnp.maximum(jnp.linalg.norm(a, axis=0), 1e-12)
                factors[mode] = a / lam
                grams[mode] = gram(factors[mode])
        with obs.span("als/fit", iteration=it, exact=bool(exact_fit)):
            # fit = 1 - ||X - X_hat|| / ||X||, the standard inner-product trick
            g_all = _hadamard_of(grams, skip=-1) * jnp.outer(lam, lam)
            # <X, X_hat> needs the final-mode MTTKRP against the *current*
            # other factors — m already is that (they don't change after the
            # last update). A lossy backend's m would bias the metric, so
            # recompute it exactly when asked.
            m_fit = exact_last_mode_fn(x, factors, last) if exact_fit else m
            inner = jnp.sum(m_fit * (factors[-1] * lam))
            norm_hat_sq = jnp.sum(g_all)
            resid = jnp.sqrt(
                jnp.maximum(norm_x**2 + norm_hat_sq - 2 * inner, 0.0))
            with obs.span("als/fit/read"):   # the host waits on the device
                fit = float(1.0 - resid / norm_x)
        if abs(fit - prev_fit) < tol:
            break
        prev_fit = fit
    return CPState(factors=factors, lambdas=lam, fit=fit, iters=it)


def cp_als_psram(
    coo,
    rank: int,
    n_iter: int = 25,
    key: jax.Array | None = None,
    adc_bits: int = 16,
) -> CPState:
    """CP-ALS with the MTTKRP kernel running through the pSRAM numerics.

    ``coo`` is either the raw ``(indices, values, shape)`` triple — the flat
    quantized path, i.e. ``backend="psram-oracle"`` — or a ``repro.sparse``
    container (COO/SortedCOO/BlockedCOO/CSF), which runs the *streaming*
    schedule with the quantized chain (``backend="psram-stream"``), the full
    §IV array mapping. Thin convenience wrapper over
    ``cp_als(backend=...)``; either way the reported fit is the exact one
    (``exact_fit``): factor updates see the lossy engine, the convergence
    metric does not.
    """
    from repro.backends import resolve_config

    cfg = dataclasses.replace(
        resolve_config(None), adc=ADCConfig(bits=adc_bits))
    if isinstance(coo, tuple):
        return cp_als(None, rank, n_iter=n_iter, key=key, coo=coo,
                      backend="psram-oracle", config=cfg)
    from repro.sparse.formats import CSF

    base = coo.to_coo() if isinstance(coo, CSF) else coo
    return cp_als(None, rank, n_iter=n_iter, key=key, sparse=base,
                  backend="psram-stream", config=cfg)
