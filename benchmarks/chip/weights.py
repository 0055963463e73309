"""Random weights of a served model, drawn by the benchmark from the seed.

The program's model module gives the parameter tree's shapes
(``jax.eval_shape`` of its ``init``); the values are the benchmark's own,
drawn on the device in one jitted call in the type they are served in, so
the plain reference can take the same weights without taking anything the
program made. Norm gains are ones; every other leaf is normal with standard
deviation ``fan_in ** -0.5``, where ``fan_in`` is a matrix's input width;
the embedding's is ``d_model ** -0.5``, so that an unembedding tied to it
gives logits of unit scale.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def draw(key, shapes):
    """Leaves like ``shapes`` (a tree of ShapeDtypeStructs), from ``key``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, s) in zip(keys, flat):
            name = _path_str(path)
            if "norm" in name:
                out.append(jnp.ones(s.shape, s.dtype))
                continue
            width = s.shape[-1] if name == "embed" else s.shape[-2]
            std = 1.0 / math.sqrt(width)
            out.append((jax.random.normal(k, s.shape, jnp.float32) * std)
                       .astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)
