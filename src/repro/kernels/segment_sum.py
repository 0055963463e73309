"""Pallas TPU kernel: blocked segment-sum — the CSF CP3 stage on the MXU.

TPU adaptation of the streaming sparse schedule (repro.sparse.stream): one
grid step per nonzero block. The block's gather masks — exactly the binary
word-line drives of the pSRAM mapping, one per output-row segment — are
formed *in VMEM* as a (S, bn) one-hot from the block's local segment ids
(2-D broadcasted_iota vs the id row), then a single MXU matmul against the
(bn, R) chain-row tile performs all of the block's segment sums at once.
The global ``(out_rows, nnz)`` scatter matrix the pre-streaming path built
never exists: per block the mask is at most (bn, bn), lives in VMEM, and
dies with the grid step — the same locality the analog array gets from its
per-channel masks.

The host-side wrapper scatters the per-block partials into the output rows
(one add per (block, segment) — O(segments), not O(nnz)). Combining partial
sums reassociates the float adds, so this path is allclose-not-bit-equal to
``jax.ops.segment_sum``; the bit-exact electrical-order path is
``repro.sparse.stream.stream_mttkrp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(data_ref, seg_ref, out_ref, *, n_seg: int):
    seg = seg_ref[0]                             # (1, bn) int32 local ids
    # gather masks: one row per segment, formed in VMEM (2-D iota for TPU)
    sids = jax.lax.broadcasted_iota(jnp.int32, (n_seg, seg.shape[1]), 0)
    mask = (sids == seg).astype(jnp.float32)     # (S, bn) one-hot
    # all of this block's segment sums in one MXU contraction
    acc = jax.lax.dot_general(
        mask, data_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (S, R)
    out_ref[...] = acc[None]


@functools.partial(jax.jit, static_argnames=("n_seg", "interpret"))
def blocked_segment_sum(
    data: jax.Array,      # (B, bn, R) f32 chain-row blocks (zero-padded)
    seg_ids: jax.Array,   # (B, bn) int32 local segment id per row, in [0, S)
    n_seg: int,           # S — max segments per block
    interpret: bool = False,
) -> jax.Array:
    """Per-block partial segment sums: (B, S, R).

    ``seg_ids`` are block-local (0-based within each block, padding rows
    pointing at any in-range id with zero data). The caller owns the
    local→global segment mapping and the cross-block combine.
    """
    b, bn, r = data.shape
    assert seg_ids.shape == (b, bn), (seg_ids.shape, data.shape)
    # a leading unit axis makes the id block's last two dims (1, bn) whole,
    # which the TPU tiling rule accepts for any B (a (1, bn) block of a
    # (B, bn) array is not 8-row aligned)
    seg3 = seg_ids.reshape(b, 1, bn)
    return pl.pallas_call(
        functools.partial(_kernel, n_seg=n_seg),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, bn, r), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_seg, r), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_seg, r), jnp.float32),
        interpret=interpret,
    )(data, seg3)
