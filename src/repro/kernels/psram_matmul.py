"""Pallas TPU kernel: the pSRAM array's quantized matmul (bit-plane int8 MAC).

TPU adaptation of §III: the array's analog bit-plane accumulate is an exact
int8xint8->int32 MAC, which the MXU executes natively; the 52-wavelength WDM
dimension maps to the N-tile (each "wavelength" = an independent output lane
group), and the ADC becomes an output requantization epilogue fused into the
same kernel so the int32 accumulator never round-trips to HBM.

Blocking: grid (M/bm, N/bn, K/bk), K innermost; int32 accumulator lives in a
VMEM scratch tile across the K loop; on the last K step the ADC transfer +
dequant runs and a single f32 tile is written out. Default tiles are
MXU-aligned (128x128) with bk=512 to amortize the epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import QMAX, adc_transfer


def _kernel(qx_ref, qw_ref, sx_ref, sw_ref, out_ref, acc_ref, *, nk: int, adc_bits: int, k_total: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU, int32 accumulation (widening
    # them first is refused by Mosaic: the MXU has no int32 x int32 mode)
    acc_ref[...] += jax.lax.dot_general(
        qx_ref[...], qw_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(kk == nk - 1)
    def _epilogue():
        # ADC transfer curve (mid-rise, saturating) — §III-C; shared with
        # every non-kernel path via core.quantization
        full_scale = float(QMAX) * float(QMAX) * k_total
        analog = adc_transfer(acc_ref[...], 2 ** adc_bits, full_scale)
        out_ref[...] = analog * (sx_ref[...] * sw_ref[...])


@functools.partial(jax.jit, static_argnames=("adc_bits",))
def psram_matmul_xla(
    qx: jax.Array,   # (M, K) int8
    qw: jax.Array,   # (K, N) int8
    sx: jax.Array,   # (M, 1) f32
    sw: jax.Array,   # (1, N) f32
    adc_bits: int = 16,
) -> jax.Array:
    """The XLA lowering of the same kernel: one fused jit, bit-identical.

    The int accumulation is exact whatever the tiling, so the int32
    accumulator equals the Pallas kernel's VMEM scratch bit-for-bit; the
    identical ADC epilogue then lands on identical codes. When the
    worst-case accumulation ``QMAX^2 * K`` fits f32's integer range the
    contraction runs on the f32 BLAS path (every partial sum an exact
    integer — the ``schedule._execute_tiles`` trick), else int32.
    """
    k_total = qx.shape[-1]
    if float(QMAX) * float(QMAX) * k_total < 2.0 ** 24:
        acc = jnp.matmul(qx.astype(jnp.float32), qw.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    else:
        acc = jnp.matmul(qx.astype(jnp.int32), qw.astype(jnp.int32),
                         preferred_element_type=jnp.int32)
    full_scale = float(QMAX) * float(QMAX) * k_total
    analog = adc_transfer(acc, 2 ** adc_bits, full_scale)
    return analog * (sx * sw)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "adc_bits", "interpret")
)
def psram_matmul(
    qx: jax.Array,   # (M, K) int8
    qw: jax.Array,   # (K, N) int8
    sx: jax.Array,   # (M, 1) f32
    sw: jax.Array,   # (1, N) f32
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    adc_bits: int = 16,
    interpret: bool = False,
) -> jax.Array:
    m, k = qx.shape
    k2, n = qw.shape
    assert k == k2 and sx.shape == (m, 1) and sw.shape == (1, n)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, adc_bits=adc_bits, k_total=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(qx, qw, sx, sw)
