"""Pallas TPU kernel: fused dense MTTKRP with Khatri-Rao formed on the fly.

TPU adaptation of §IV: the paper's CP1->CP2->CP3 chain is a scalar/vector
schedule tailored to an analog crossbar. On TPU the same computation is a
matmul against the Khatri-Rao product,

    A = X_(0) @ (B ⊙ C),    (B ⊙ C)[j*K + k, r] = B[j, r] * C[k, r]

but materializing (B ⊙ C) in HBM costs J*K*R bytes — more than the tensor
itself when R > 1. The kernel instead forms each (bk x R) KR tile *in VMEM*
from row j of B (held whole in VMEM) and a (bk, R) tile of C (CP 1, on
the VPU), feeds the
MXU with X tiles (CP 2's scaling is the matmul itself), and accumulates into
the output across the grid (CP 3). HBM traffic: X once + tiny factor reads.

Grid: (I/bi, J, K/bk) — j and k innermost walk the KR rows in row-major
order, matching the mode-0 unfolding layout, so X_(0) is read contiguously.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import adc_transfer


def _kernel(x_ref, b_ref, c_ref, out_ref, acc_ref, *, nj: int, nk: int):
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when((j == 0) & (kk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # CP 1 in VMEM: one row of the VMEM-resident B broadcast against a
    # tile of C
    kr = b_ref[pl.ds(j, 1), :] * c_ref[...]   # (bk, R) on the VPU
    x = x_ref[...]                        # (bi, bk) slice of X_(0) at (i, j*K+k)
    # CP 2 + CP 3 on the MXU: scale-by-tensor-element and accumulate
    acc_ref[...] += jax.lax.dot_general(
        x.astype(jnp.float32), kr.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when((j == nj - 1) & (kk == nk - 1))
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bi", "bk", "interpret"))
def mttkrp_fused(
    x0: jax.Array,   # (I, J*K) mode-0 unfolding, row-major over (j, k)
    b: jax.Array,    # (J, R)
    c: jax.Array,    # (K, R)
    bi: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    i, jk = x0.shape
    j, r = b.shape
    k = c.shape[0]
    assert jk == j * k and c.shape[1] == r
    bi, bk = min(bi, i), min(bk, k)
    assert i % bi == 0 and k % bk == 0
    nj, nk = j, k // bk
    grid = (i // bi, nj, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nj=nj, nk=nk),
        grid=grid,
        in_specs=[
            # X_(0) tile at row-block ii, columns [j*K + kk*bk : ... + bk].
            # Block shape (bi, bk) with index (ii, j*nk + kk) walks row-major.
            pl.BlockSpec((bi, bk), lambda ii, j_, kk: (ii, j_ * nk + kk)),
            # the small (J, R) factor stays whole in VMEM (a (1, R) block is
            # not tile-aligned); the kernel slices row j
            pl.BlockSpec((j, r), lambda ii, j_, kk: (0, 0)),
            pl.BlockSpec((bk, r), lambda ii, j_, kk: (kk, 0)),
        ],
        out_specs=pl.BlockSpec((bi, r), lambda ii, j_, kk: (ii, 0)),
        out_shape=jax.ShapeDtypeStruct((i, r), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, r), jnp.float32)],
        interpret=interpret,
    )(x0, b, c)


# ------------------------------------------------- quantized (pSRAM) variant


def _psram_kernel(qx_ref, sx_ref, qb_ref, sb_ref, qc_ref, sc_ref, out_ref,
                  acc_ref, *, nj: int, nk: int, adc_bits: int):
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when((j == 0) & (kk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # CP 1 in VMEM from the *quantized* factors: the int8xint8 row products
    # are exact in f32 (<= 127^2), the per-row scales fold into one multiply
    qb = qb_ref[pl.ds(j, 1), :]                             # (1, R) f32
    sb = sb_ref[pl.ds(j, 1), :]                             # (1, 1)
    kr = (qb * qc_ref[...].astype(jnp.float32)) * (sb * sc_ref[...])  # (bk, R)
    x = qx_ref[...].astype(jnp.float32) * sx_ref[...]      # (bi, bk)
    acc_ref[...] += jax.lax.dot_general(
        x, kr, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when((j == nj - 1) & (kk == nk - 1))
    def _done():
        # ADC epilogue on the completed output tile, digitized across its
        # observed dynamic range (the ADCConfig contract) — fused, so the
        # analog accumulator never round-trips
        acc = acc_ref[...]
        full_scale = jnp.maximum(jnp.max(jnp.abs(acc)), 1e-30)
        out_ref[...] = adc_transfer(acc, 2 ** adc_bits, full_scale)


@functools.partial(jax.jit, static_argnames=("bi", "bk", "adc_bits",
                                             "interpret"))
def mttkrp_psram_fused(
    qx0: jax.Array,  # (I, J*K) int8 mode-0 unfolding, per-row quantized
    sx: jax.Array,   # (I, 1) f32
    qb: jax.Array,   # (J, R) int8 per-row quantized
    sb: jax.Array,   # (J, 1) f32
    qc: jax.Array,   # (K, R) int8 per-row quantized
    sc: jax.Array,   # (K, 1) f32
    bi: int = 128,
    bk: int = 128,
    adc_bits: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """The dense matricized-KR MTTKRP through the array numerics, fused:
    int8 operands, KR tiles formed in VMEM from quantized factor rows, f32
    accumulation, ADC transfer epilogue per output tile."""
    i, jk = qx0.shape
    j, r = qb.shape
    k = qc.shape[0]
    assert jk == j * k and qc.shape[1] == r
    assert sx.shape == (i, 1) and sb.shape == (j, 1) and sc.shape == (k, 1)
    bi, bk = min(bi, i), min(bk, k)
    assert i % bi == 0 and k % bk == 0
    nj, nk = j, k // bk
    grid = (i // bi, nj, nk)
    # qb rides as f32 (its int8 codes, exactly): Mosaic slices row j of a
    # 32-bit VMEM array at a dynamic sublane offset, not of a packed int8 one
    return pl.pallas_call(
        functools.partial(_psram_kernel, nj=nj, nk=nk, adc_bits=adc_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bk), lambda ii, j_, kk: (ii, j_ * nk + kk)),
            pl.BlockSpec((bi, 1), lambda ii, j_, kk: (ii, 0)),
            # (J, R) and (J, 1) whole in VMEM, row j sliced in the kernel
            pl.BlockSpec((j, r), lambda ii, j_, kk: (0, 0)),
            pl.BlockSpec((j, 1), lambda ii, j_, kk: (0, 0)),
            pl.BlockSpec((bk, r), lambda ii, j_, kk: (kk, 0)),
            pl.BlockSpec((bk, 1), lambda ii, j_, kk: (kk, 0)),
        ],
        out_specs=pl.BlockSpec((bi, r), lambda ii, j_, kk: (ii, 0)),
        out_shape=jax.ShapeDtypeStruct((i, r), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, r), jnp.float32)],
        interpret=interpret,
    )(qx0, sx, qb.astype(jnp.float32), sb, qc, sc)


@functools.partial(jax.jit, static_argnames=("bi", "adc_bits"))
def mttkrp_psram_xla(
    qx0: jax.Array, sx: jax.Array, qb: jax.Array, sb: jax.Array,
    qc: jax.Array, sc: jax.Array, bi: int = 128, adc_bits: int = 16,
) -> jax.Array:
    """The XLA lowering of :func:`mttkrp_psram_fused`: one fused jit of the
    same arithmetic (flat contraction instead of the tile walk — float adds
    reassociate, so the pair is allclose, not bit-equal), with the identical
    per-``bi``-tile observed-range ADC epilogue."""
    i = qx0.shape[0]
    j, r = qb.shape
    k = qc.shape[0]
    kr = (qb.astype(jnp.float32)[:, None, :] * qc.astype(jnp.float32)[None]
          ) * (sb[:, None, :] * sc[None])                  # (J, K, R)
    x = qx0.astype(jnp.float32) * sx
    out = jnp.matmul(x, kr.reshape(j * k, r),
                     preferred_element_type=jnp.float32)
    bi = min(bi, i)
    assert i % bi == 0
    tiles = out.reshape(i // bi, bi, r)
    full_scale = jnp.maximum(
        jnp.max(jnp.abs(tiles), axis=(1, 2), keepdims=True), 1e-30)
    tiles = adc_transfer(tiles, 2 ** adc_bits, full_scale)
    return tiles.reshape(i, r)


def quantize_mttkrp_operands(x0: jax.Array, b: jax.Array, c: jax.Array):
    """Per-row int8 quantization of the unfolding + both factors — the
    operand treatment both lowerings of the psram variant share."""
    from repro.core.quantization import quantize_symmetric

    qx, sx = quantize_symmetric(x0, axis=-1)
    qb, sb = quantize_symmetric(b, axis=-1)
    qc, sc = quantize_symmetric(c, axis=-1)
    return (qx, sx.astype(jnp.float32), qb, sb.astype(jnp.float32),
            qc, sc.astype(jnp.float32))
