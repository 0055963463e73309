"""Plain reference of a CP-ALS sweep on a sparse tensor, in ``jax.numpy``.

Imports nothing of the program. It gets the tensor the benchmark made
(coordinates and values) and the factors the program fed to each MTTKRP,
and computes what the program should have computed from them:

* ``mttkrp``: exact MTTKRP, float32 products summed per output row;
* ``als_update``: the factor update of that mode, ``M @ pinv(H)`` with
  ``H`` the Hadamard product of the other factors' Grams, columns
  normalized;
* ``fit``: ``1 - ||X - X_hat|| / ||X||`` of a Kruskal tensor.

``mttkrp(..., bits=4)`` and ``mttkrp(..., dtype=bfloat16)`` are the
control: the same arithmetic one precision below what the configuration
states (int4 factor words where the pSRAM array stores int8; bfloat16
where the fit's exact MTTKRP is float32). Matmuls run at ``highest``
precision, elementwise work in float32, and nonzeros in chunks so that
the chip holds it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

CHUNK = 1 << 20


def _chunks(n: int, chunk: int = CHUNK):
    for a in range(0, n, chunk):
        yield a, min(a + chunk, n)


@partial(jax.jit, static_argnames=("mode", "out_rows", "bits", "dtype"))
def _mttkrp_chunk(idx, vals, factors, mode, out_rows, bits, dtype):
    rows = vals[:, None].astype(dtype)
    for d, f in enumerate(factors):
        if d == mode:
            continue
        if bits:
            f = quantize_rows(f, bits)
        rows = rows * f.astype(dtype)[idx[:, d]]
    return jax.ops.segment_sum(rows, idx[:, mode], num_segments=out_rows
                               ).astype(jnp.float32)


def quantize_rows(f, bits: int):
    """Symmetric per-row quantization to ``bits``-bit signed words,
    dequantized: the store-side treatment of the factor rows, at the
    given word width."""
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(f), axis=-1, keepdims=True) / qmax
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(f / scale) * scale


def mttkrp(idx, vals, factors, mode: int, bits: int = 0,
           dtype=jnp.float32):
    """(I_mode, R) MTTKRP of the tensor ``(idx, vals)``; ``bits`` > 0
    quantizes the other factors' rows to that word width first; ``dtype``
    is the type the products are formed and summed in."""
    out_rows = factors[mode].shape[0]
    factors = tuple(jnp.asarray(f, jnp.float32) for f in factors)
    out = None
    for a, b in _chunks(vals.shape[0]):
        part = _mttkrp_chunk(idx[a:b], vals[a:b], factors, mode, out_rows,
                             bits, dtype)
        out = part if out is None else out + part
    return out


def _gram(f):
    f = f.astype(jnp.float32)
    return jnp.matmul(f.T, f, precision=jax.lax.Precision.HIGHEST)


def als_update(m, factors, mode: int):
    """The mode's new factor from its MTTKRP ``m`` and the factors it was
    computed from: ``m @ pinv(hadamard of the other Grams)``, columns
    normalized. Returns ``(factor, column norms)``."""
    h = None
    for d, f in enumerate(factors):
        if d != mode:
            g = _gram(f)
            h = g if h is None else h * g
    a = jnp.matmul(m, jnp.linalg.pinv(h),
                   precision=jax.lax.Precision.HIGHEST)
    lam = jnp.maximum(jnp.linalg.norm(a, axis=0), 1e-12)
    return a / lam, lam


@jax.jit
def _inner_chunk(idx, vals, factors, lam):
    rows = jnp.broadcast_to(lam, (idx.shape[0], lam.shape[0]))
    for d, f in enumerate(factors):
        rows = rows * f[idx[:, d]]
    return jnp.sum(vals * jnp.sum(rows, axis=1)), jnp.sum(jnp.square(vals))


def fit(idx, vals, factors, lam) -> float:
    """``1 - ||X - X_hat|| / ||X||`` of ``X_hat = [[lam; factors]]``."""
    factors = tuple(jnp.asarray(f, jnp.float32) for f in factors)
    lam = jnp.asarray(lam, jnp.float32)
    inner = norm_sq = jnp.zeros((), jnp.float32)
    for a, b in _chunks(vals.shape[0]):
        i, n = _inner_chunk(idx[a:b], vals[a:b], factors, lam)
        inner, norm_sq = inner + i, norm_sq + n
    h = None
    for f in factors:
        g = _gram(f)
        h = g if h is None else h * g
    hat_sq = jnp.sum(h * jnp.outer(lam, lam))
    resid = jnp.sqrt(jnp.maximum(norm_sq + hat_sq - 2 * inner, 0.0))
    return float(1.0 - resid / jnp.sqrt(norm_sq))


def rel(got, want) -> float:
    """||got - want|| / ||want||, in float32."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
