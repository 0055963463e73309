"""Fused streaming-MTTKRP kernel family: ONE kernel body, four lowerings.

The PR 5 compiled scan executor (``sparse.stream._stream_exec_compiled``)
drains the sorted nonzero stream block by block, but each scan step still
round-trips between separate stages: the exact f32 CP chain (two full-width
factor gathers per nonzero), the gather-mask segment contraction, and — on
the pSRAM path — a per-product quantize/ADC pass. This module fuses the
whole per-chunk pipeline into one kernel body:

1. **int8 factor-row gathers** (CP 1/2): the non-target factors are
   pre-quantized per row (``quantize_symmetric(f, axis=-1)``) and stored
   as ``(J, R + 4)`` int8 rows, the ``R`` codes followed by the row's f32
   scale bit-cast into four int8 lanes. One row gather per factor per
   nonzero fetches ``R + 4`` bytes, codes and scale together, instead of
   ``R`` f32 values — the gather traffic dominates the stream executor.
2. **exact integer Hadamard chain**: two-factor chains multiply the int8
   gathers in int16 (``|q1*q2| <= 127^2 < 2^15``) and convert once to f32;
   the *combined* scale ``prod_d s_d[idx_d] * value`` is folded into the
   gather mask — ``n_seg`` multiplies per nonzero instead of ``R`` — so the
   contraction's FMA consumes the unrounded scale*row product directly.
3. **gather-mask contraction** per block — the §IV per-channel binary
   word-line drives as one ``(E, S, rows) @ (E, rows, R)`` matmul (the
   mask rows carry the per-nonzero chain scale; diagonal scaling commutes
   into either operand of the contraction).
4. **ADC transfer epilogue** on the per-segment partials: the accumulated
   per-channel photocurrents digitized through ``quantization.adc_transfer``
   across the chunk's observed dynamic range (the ``ADCConfig`` contract),
   *before* they accumulate electrically.
5. **cross-block electrical carry**: the partials scatter into the output
   accumulator, which threads through the chunk loop — the carry ref of the
   Pallas grid, the ``lax.scan`` carry of the XLA lowering.

The four lowerings of this one body (``backends.lowering.EXEC_LOWERINGS``):

* ``"pallas"``    — real ``pallas_call``, grid over chunks, factors resident
  in VMEM, chunk operands double-buffered by the Pallas pipeline (each
  grid step's block specs prefetch the next chunk while the current one
  drains), the output accumulator ref carrying across the grid. The TPU
  v5e compiler refuses it (the in-kernel row gather and scatter), so
  ``"auto"`` never picks it: ``backends.lowering.resolve_stream_lowering``.
* ``"interpret"`` — the same ``pallas_call``, Python-executed. CPU
  validation of the kernel body; far too slow to race.
* ``"xla"``       — the same body as a ``lax.scan`` step over chunks, jitted
  whole: what ``"auto"`` runs on every platform, the TPU included.
* ``"ref"``       — the flat oracle: every chunk at once, one scatter; no
  scan, no carry threading. Parity anchor for the other three.

All lowerings share ``sparse.stream``'s blocking (``stream_layout`` /
``_block_segments``) — one preprocessing, cached on the CSF, whichever
executor drains it. Tile shapes (``exec_blocks``) come from
``kernels.autotune`` when enabled, else its deterministic heuristic.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.core.quantization import adc_transfer, quantize_symmetric


SCALE_LANES = 4        # int8 lanes that hold one f32 row scale


def pack_rows(q, s):
    """``(J, R)`` int8 codes and ``(J, 1)`` f32 scales -> ``(J, R + 4)``
    int8 rows: each row's codes, then its scale's four bytes."""
    sb = jax.lax.bitcast_convert_type(s.astype(jnp.float32), jnp.int8)
    return jnp.concatenate([q, sb.reshape(q.shape[0], SCALE_LANES)], axis=-1)


def unpack_rows(g):
    """Inverse of ``pack_rows`` on gathered rows ``(..., R + 4)``: the
    int8 codes ``(..., R)`` and the f32 scales ``(...)``, bit for bit."""
    return (g[..., :-SCALE_LANES],
            jax.lax.bitcast_convert_type(g[..., -SCALE_LANES:], jnp.float32))


def quantize_stream_factors(factors, mode: int):
    """Per-row int8 quantization of the non-target factors, packed.

    Returns a tuple ordered like ``factors`` of ``(J, R + 4)`` int8 arrays
    (``pack_rows``), with the target mode's slot holding a size-(1,1)
    placeholder (never gathered — the chain skips ``mode``); per-row
    scales keep the quantization envelope identical to
    ``cp_chain_psram``'s factor treatment.
    """
    ps = []
    for d, f in enumerate(factors):
        if d == mode:
            ps.append(jnp.zeros((1, 1), jnp.int8))
        else:
            ps.append(pack_rows(*quantize_symmetric(f, axis=-1)))
    return tuple(ps)


_quantize_stream_factors_jit = jax.jit(
    quantize_stream_factors, static_argnames=("mode",))
_FACTOR_QUANT_CACHE: dict = {}
_FACTOR_QUANT_CACHE_MAX = 32


def stream_factor_quants(factors, mode: int):
    """Store-side quantization cache: the array *stores* the quantized
    factors once (the physical store-then-drive split of §III/§IV), so the
    per-row int8 conversion is keyed on factor identity and paid once per
    factor set, not once per drive. Weakref-guarded against id reuse; an
    ALS sweep that rebuilds a factor naturally misses and re-stores."""
    key = (mode,) + tuple(id(f) for f in factors)
    hit = _FACTOR_QUANT_CACHE.get(key)
    if hit is not None and all(r() is f for r, f in zip(hit[0], factors)):
        return hit[1]
    val = _quantize_stream_factors_jit(tuple(factors), mode)
    if len(_FACTOR_QUANT_CACHE) >= _FACTOR_QUANT_CACHE_MAX:
        _FACTOR_QUANT_CACHE.clear()
    _FACTOR_QUANT_CACHE[key] = (
        tuple(weakref.ref(f) for f in factors), val)
    return val


def _chunk_partials(ip_c, vp_c, lp_c, ps, *, mode, n_seg, adc_bits):
    """The fused body for ONE execution chunk — shared verbatim by every
    lowering (the Pallas kernel calls it on refs' values, the XLA scan on
    its per-step slices, the flat oracle on the full stack).

    ip_c: (E, rows, nmodes) int32 nonzero coordinates
    vp_c: (E, rows) f32 nonzero values (0.0 padding)
    lp_c: (E, rows) int32 block-local segment ids
    ps:   per mode, (J, R + 4) packed int8 factor rows (``pack_rows``)
    Returns (E, n_seg, R) ADC-digitized per-segment partials.
    """
    nmodes = ip_c.shape[-1]
    others = [d for d in range(nmodes) if d != mode]
    # two-factor chains accumulate the Hadamard exactly in int16
    # (|q1*q2| <= 127^2 < 2^15); longer chains stay f32 (exact below 2^24)
    acc_t = jnp.int16 if len(others) <= 2 else jnp.float32
    had = None
    scale = vp_c                                        # (E, rows)
    for d in others:
        # one gather brings the row's codes and its scale: (E, rows, R+4)
        g, s = unpack_rows(ps[d][ip_c[..., d]])
        had = g.astype(acc_t) if had is None else had * g.astype(acc_t)
        scale = scale * s
    had = had.astype(jnp.float32)
    rows = had.shape[-2]
    sids = jax.lax.broadcasted_iota(jnp.int32, (1, n_seg, rows), 1)
    mask = (sids == lp_c[:, None, :]).astype(jnp.float32)
    # fold the per-nonzero chain scale into the mask: n_seg multiplies per
    # nonzero instead of R, and the contraction's FMA then consumes the
    # scale*row product unrounded
    mask = mask * scale[:, None, :]
    parts = jax.lax.dot_general(
        mask, had, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                                   # (E, n_seg, R)
    if adc_bits:
        # §III-C: digitize the accumulated per-channel photocurrents across
        # the chunk's observed dynamic range before electrical accumulation
        full_scale = jnp.maximum(jnp.max(jnp.abs(parts)), 1e-30)
        parts = adc_transfer(parts, 2 ** adc_bits, full_scale)
    return parts


def _rank(ps, mode):
    """R of the packed ``(J, R + 4)`` factor rows."""
    width = next(p.shape[-1] for d, p in enumerate(ps) if d != mode)
    return width - SCALE_LANES


# --------------------------------------------------------------- Pallas


def _stream_kernel(ip_ref, vp_ref, lp_ref, sp_ref, *rest, mode, n_seg,
                   adc_bits, nmodes):
    ps_refs, out_ref = rest[:nmodes], rest[nmodes]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    parts = _chunk_partials(
        ip_ref[0], vp_ref[0], lp_ref[0], tuple(r[...] for r in ps_refs),
        mode=mode, n_seg=n_seg, adc_bits=adc_bits,
    )
    rank = parts.shape[-1]
    out_ref[...] = out_ref[...].at[sp_ref[0]].add(parts.reshape(-1, rank))


@functools.partial(jax.jit, static_argnames=(
    "mode", "n_seg", "adc_bits", "out_rows", "interpret"))
def stream_mttkrp_fused_pallas(ip, vp, lp, sp, ps, mode, n_seg,
                               adc_bits, out_rows, interpret=False):
    """The ``pallas_call`` lowering: grid over chunks, output accumulator
    ref as the electrical cross-block carry, factors VMEM-resident, the
    per-chunk operand blocks prefetched by the grid pipeline."""
    nb, e, rows, nmodes = ip.shape
    rank = _rank(ps, mode)
    in_specs = [
        pl.BlockSpec((1, e, rows, nmodes), lambda i: (i, 0, 0, 0)),
        pl.BlockSpec((1, e, rows), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, e, rows), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, e * n_seg), lambda i: (i, 0)),
    ]
    in_specs += [pl.BlockSpec(p.shape, lambda i: (0, 0)) for p in ps]
    out = pl.pallas_call(
        functools.partial(_stream_kernel, mode=mode, n_seg=n_seg,
                          adc_bits=adc_bits, nmodes=nmodes),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((out_rows + 1, rank), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows + 1, rank), jnp.float32),
        interpret=interpret,
    )(ip, vp, lp, sp, *ps)
    return out[:out_rows]


# ------------------------------------------------------------------ XLA


@functools.lru_cache(maxsize=256)
def fused_stream_executor(mode: int, n_seg: int, adc_bits: int,
                          out_rows: int):
    """The jitted XLA lowering for one static signature: ``fn(ip, vp, lp,
    sp, ps) -> (out_rows, R)``.

    Cached with the PR 5 keying discipline: equal-by-value static keys
    return the *identical* callable (and with it XLA's compilation cache
    entry) — the contract tests/test_autotune.py pins. The body is the same
    ``_chunk_partials`` the Pallas kernel runs; the ``lax.scan`` carry is
    the electrical cross-block carry.
    """

    @jax.jit
    def run(ip, vp, lp, sp, ps):
        rank = _rank(ps, mode)

        def step(out, blk):
            ip_c, vp_c, lp_c, sp_c = blk
            parts = _chunk_partials(
                ip_c, vp_c, lp_c, ps,
                mode=mode, n_seg=n_seg, adc_bits=adc_bits,
            )
            return out.at[sp_c].add(parts.reshape(-1, rank)), None

        out0 = jnp.zeros((out_rows + 1, rank), jnp.float32)
        out, _ = jax.lax.scan(step, out0, (ip, vp, lp, sp))
        return out[:out_rows]

    return run


def stream_mttkrp_fused_xla(ip, vp, lp, sp, ps, mode, n_seg, adc_bits,
                            out_rows):
    return fused_stream_executor(mode, n_seg, adc_bits, out_rows)(
        ip, vp, lp, sp, ps)


# ------------------------------------------------------------------ ref


@functools.partial(jax.jit, static_argnames=(
    "mode", "n_seg", "adc_bits", "out_rows"))
def stream_mttkrp_fused_ref(ip, vp, lp, sp, ps, mode, n_seg, adc_bits,
                            out_rows):
    """Flat oracle: all chunks at once (vmapped body), one scatter. Same
    arithmetic as the scan/grid lowerings with the adds reassociated — the
    parity anchor, not a racer."""
    parts = jax.vmap(
        lambda i_c, v_c, l_c: _chunk_partials(
            i_c, v_c, l_c, ps, mode=mode, n_seg=n_seg,
            adc_bits=adc_bits)
    )(ip, vp, lp)                                       # (nb, E, n_seg, R)
    rank = parts.shape[-1]
    out = jnp.zeros((out_rows + 1, rank), jnp.float32)
    out = out.at[sp.reshape(-1)].add(parts.reshape(-1, rank))
    return out[:out_rows]


# ----------------------------------------------------------- front door


_LOWERING_FNS = {
    "pallas": functools.partial(stream_mttkrp_fused_pallas, interpret=False),
    "interpret": functools.partial(stream_mttkrp_fused_pallas, interpret=True),
    "xla": stream_mttkrp_fused_xla,
    "ref": stream_mttkrp_fused_ref,
}


def fused_stream_mttkrp(csf, factors, config=None, adc_bits: int = 16,
                        lowering: str = "xla",
                        exec_blocks: int | None = None) -> jax.Array:
    """Fused streaming MTTKRP over a mode-rooted CSF: (out_rows, R).

    Reuses ``sparse.stream``'s cached block layout (one blocking shared
    with the scan executors), quantizes the non-target factors per row, and
    drains the stream through the requested lowering of the fused body.
    ``lowering`` must already be resolved (``backends.lowering.
    resolve_stream_lowering``); ``exec_blocks=None`` asks ``kernels.autotune``
    for the cached winner or its deterministic heuristic.
    """
    from repro.backends.base import resolve_config
    from repro.kernels.autotune import stream_params
    from repro.sparse.stream import stream_layout

    try:
        fn = _LOWERING_FNS[lowering]
    except KeyError:
        raise RuntimeError(
            f"no fused-stream dispatch for resolved lowering {lowering!r}; "
            f"known: {', '.join(_LOWERING_FNS)}"
        ) from None
    cfg = resolve_config(config)
    mode = csf.mode_order[0]
    if exec_blocks is None:
        exec_blocks = stream_params(csf, tuple(factors), cfg)["exec_blocks"]
    ip, vp, lp, sp, n_seg = stream_layout(csf, cfg.rows, exec_blocks)
    ps = stream_factor_quants(tuple(factors), mode)
    if obs.enabled():
        obs.counter("stream/row_gathers", csf.nnz * (len(csf.shape) - 1))
    return fn(ip, vp, lp, sp, ps, mode, n_seg, adc_bits, csf.shape[mode])
