"""The nonzero pattern of a sparse-tensor configuration, and its values.

The pattern is a property of the configuration, as a dataset's is: its
``pattern_seed`` draws the coordinates once, and every run of every seed
decomposes the same tensor through the same compiled programs. The draw
is FROSTT-style (``repro.sparse.synth.powerlaw_coo``'s method, copied
here): mode ``skew_mode`` Zipf with exponent ``alpha`` over a random
permutation of its rows, the other modes uniform, duplicates merged.

The first run in a checkout draws the pattern and keeps it in
``.cache/patterns/`` (git-ignored), named by configuration, pattern seed
and requested nonzeros; later runs load it.

``--seed`` draws only the values (a CP model of rank ``value_rank``, on
the device) and, in the runner, the initial factors.
"""
from __future__ import annotations

import hashlib
import os
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

CACHE = Path(__file__).resolve().parent / ".cache" / "patterns"


def _zipf_rows(rng, n_rows: int, nnz: int, alpha: float) -> np.ndarray:
    weights = np.arange(1, n_rows + 1, dtype=np.float64) ** (-alpha)
    weights /= weights.sum()
    ranks = rng.choice(n_rows, size=nnz, p=weights)
    perm = rng.permutation(n_rows)
    return perm[ranks]


def draw(dims, nnz: int, pattern_seed: int, alpha: float,
         skew_mode: int = 0) -> np.ndarray:
    """Sorted, duplicate-free coordinates ``(nnz', N)`` int32, nnz' <= nnz."""
    rng = np.random.default_rng(pattern_seed)
    lin = np.zeros(nnz, dtype=np.int64)
    for d, s in enumerate(dims):
        col = _zipf_rows(rng, s, nnz, alpha) if d == skew_mode \
            else rng.integers(0, s, size=nnz)
        lin = lin * s + col
    lin = np.unique(lin)
    idx = np.empty((len(lin), len(dims)), dtype=np.int32)
    for d in reversed(range(len(dims))):
        idx[:, d] = lin % dims[d]
        lin //= dims[d]
    return idx


def digest(idx: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(idx).tobytes()).hexdigest()[:16]


def load(cfg: dict) -> tuple[np.ndarray, str]:
    """The configuration's pattern, from the checkout's cache or drawn."""
    name = (f"{cfg['name']}_p{cfg['pattern_seed']}_n{cfg['nnz']}"
            ".npy")
    path = CACHE / name
    if path.exists():
        idx = np.load(path)
    else:
        idx = draw(cfg["dims"], cfg["nnz"], cfg["pattern_seed"],
                   cfg["alpha"], cfg.get("skew_mode", 0))
        CACHE.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp")
        with open(tmp, "wb") as fh:
            np.save(fh, idx)
        os.replace(tmp, path)
    return idx, digest(idx)


VALUE_BLOCK = 1 << 20


@partial(jax.jit, static_argnums=(2, 3))
def values(key, idx, dims, value_rank: int):
    """Nonzero values of a CP model of rank ``value_rank`` on the pattern,
    drawn on the device from ``key``: value = sum_r prod_d U_d[i_d, r].
    Blocks of ``VALUE_BLOCK`` nonzeros at a time, each factor's rows
    gathered transposed (rank by nonzero), so that the chip holds one
    block's rows and not the whole tensor's."""
    keys = jax.random.split(key, len(dims))
    us = [jax.random.normal(k, (s, value_rank), jnp.float32).T
          / np.sqrt(value_rank) for k, s in zip(keys, dims)]
    n = idx.shape[0]
    pad = -n % VALUE_BLOCK
    cols = [jnp.pad(idx[:, d], (0, pad)).reshape(-1, VALUE_BLOCK)
            for d in range(len(dims))]

    def block(cs):
        prod = None
        for u, c in zip(us, cs):
            rows = jnp.take(u, c, axis=1)
            prod = rows if prod is None else prod * rows
        return jnp.sum(prod, axis=0)

    return jax.lax.map(block, cols).reshape(-1)[:n]
