"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import quantize_symmetric
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mttkrp import (
    mttkrp_fused,
    mttkrp_psram_fused,
    mttkrp_psram_xla,
    quantize_mttkrp_operands,
)
from repro.kernels.psram_matmul import psram_matmul, psram_matmul_xla


# ---------------- psram_matmul ----------------

@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 256, 128, 128, 128, 128),
    (256, 512, 256, 128, 128, 256),
    (64, 128, 32, 32, 32, 64),     # non-default tiles
    (128, 1024, 128, 128, 128, 512),  # multi-step K accumulation
])
def test_psram_matmul_vs_ref(key, m, k, n, bm, bn, bk):
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    qx, sx = quantize_symmetric(x, axis=-1)
    qw, sw = quantize_symmetric(w, axis=0)
    sx = sx.reshape(m, 1)
    sw = sw.reshape(1, n)
    got = psram_matmul(qx, qw, sx, sw, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.psram_matmul_ref(qx, qw, sx, sw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("adc_bits", [8, 12, 16])
def test_psram_matmul_adc_sweep(key, adc_bits):
    x = jax.random.normal(key, (64, 128))
    w = jax.random.normal(jax.random.PRNGKey(2), (128, 64))
    qx, sx = quantize_symmetric(x, axis=-1)
    qw, sw = quantize_symmetric(w, axis=0)
    got = psram_matmul(qx, qw, sx.reshape(-1, 1), sw.reshape(1, -1),
                       bm=64, bn=64, bk=64, adc_bits=adc_bits, interpret=True)
    want = ref.psram_matmul_ref(qx, qw, sx.reshape(-1, 1), sw.reshape(1, -1),
                                adc_bits=adc_bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("adc_bits", [8, 12, 16])
def test_kernel_epilogue_adc_bit_for_bit(key, adc_bits):
    """The kernel's ADC epilogue and core adc_requantize are ONE curve.

    The Pallas epilogue calls core.quantization.adc_transfer; this pins the
    helper to adc_requantize bit-for-bit on raw int32 accumulations, and the
    full kernel to the oracle (which goes through adc_requantize) exactly —
    a reintroduced inline reimplementation shows up as a 1-ulp drift here.
    """
    from repro.core.quantization import ADCConfig, adc_requantize, adc_transfer
    acc = jax.random.randint(key, (256,), -2_000_000, 2_000_000).astype(jnp.int32)
    full_scale = 127.0 * 127.0 * 128
    got = adc_transfer(acc, 2 ** adc_bits, full_scale)
    want = adc_requantize(acc, ADCConfig(bits=adc_bits), full_scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    x = jax.random.normal(key, (64, 128))
    w = jax.random.normal(jax.random.PRNGKey(7), (128, 64))
    qx, sx = quantize_symmetric(x, axis=-1)
    qw, sw = quantize_symmetric(w, axis=0)
    kern = psram_matmul(qx, qw, sx.reshape(-1, 1), sw.reshape(1, -1),
                        bm=64, bn=64, bk=64, adc_bits=adc_bits, interpret=True)
    oracle = ref.psram_matmul_ref(qx, qw, sx.reshape(-1, 1), sw.reshape(1, -1),
                                  adc_bits=adc_bits)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(oracle))


# ---------------- fused MTTKRP ----------------

@pytest.mark.parametrize("i,j,k,r,bi,bk", [
    (128, 8, 256, 32, 128, 128),
    (64, 4, 64, 16, 32, 32),
    (256, 3, 512, 8, 128, 256),
    (32, 16, 32, 64, 32, 32),
])
def test_mttkrp_fused_vs_ref(key, i, j, k, r, bi, bk):
    x0 = jax.random.normal(key, (i, j * k))
    b = jax.random.normal(jax.random.PRNGKey(1), (j, r))
    c = jax.random.normal(jax.random.PRNGKey(2), (k, r))
    got = mttkrp_fused(x0, b, c, bi=bi, bk=bk, interpret=True)
    want = ref.mttkrp_ref(x0, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_mttkrp_fused_matches_core_dense(key):
    """The Pallas kernel == core.mttkrp.mttkrp_dense on the same tensor."""
    from repro.core.mttkrp import mttkrp_dense
    x = jax.random.normal(key, (64, 4, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    c = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    got = mttkrp_fused(x.reshape(64, -1), b, c, bi=32, bk=32, interpret=True)
    want = mttkrp_dense(x, [jnp.zeros((64, 8)), b, c], 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


# ---------------- psram_matmul: xla lowering bit-identity ----------------

@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8),        # the backend-parity fixture shape
    (64, 128, 32),
    (128, 512, 64),    # multi-step K, still inside the f32-exact bound
    (16, 2048, 8),     # QMAX^2*K > 2^24: the int32 contraction path
])
def test_psram_matmul_xla_bit_identical_to_kernel(key, m, k, n):
    """The XLA lowering == the Pallas kernel, bit for bit.

    int8xint8->int32 accumulation is exact under any tiling, so the
    accumulator matches the kernel's VMEM scratch exactly; the shared ADC
    epilogue then lands on identical codes. This is the contract that lets
    the pallas backend serve ``matmul`` through the fast lowering off-TPU
    while tests pin it against the kernel.
    """
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    qx, sx = quantize_symmetric(x, axis=-1)
    qw, sw = quantize_symmetric(w, axis=0)
    sx, sw = sx.reshape(m, 1), sw.reshape(1, n)
    got = psram_matmul_xla(qx, qw, sx, sw)
    want = psram_matmul(qx, qw, sx, sw, bm=min(128, m), bn=min(128, n),
                        bk=min(512, k), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n", [
    (16, 32, 8),
    (16, 2048, 8),     # int32 contraction regime of the fused drive chain
])
def test_psram_matmul_op_drive_chain_bit_identical(m, k, n):
    """The op-level store-then-drive contract: the one-jit fused ``"xla"``
    drive chain produces bit-identical results to the interpret-mode kernel
    through the same op — both consume the SAME store-quantized weights and
    the same jitted drive quantization, so no eager/jit rounding skew can
    split the lowerings."""
    from repro.kernels.ops import psram_matmul_op

    x = jax.random.normal(jax.random.PRNGKey(5), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(6), (k, n))
    fast = psram_matmul_op(x, w, backend="xla")
    slow = psram_matmul_op(x, w, backend="interpret")
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(slow))


def test_store_quantization_cache_identity_keyed():
    """The stored operand's quantization is cached on array identity with a
    weakref guard: same array object hits, an equal-valued copy misses (new
    store), and results never change either way."""
    from repro.kernels import ops as kops

    x = jax.random.normal(jax.random.PRNGKey(7), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(8), (16, 8))
    first = kops.psram_matmul_op(x, w, backend="xla")
    hit = kops._stored((w,), "matmul_w", kops._store_matmul_weights)
    again = kops._stored((w,), "matmul_w", kops._store_matmul_weights)
    assert all(a is b for a, b in zip(hit, again))   # pure cache hit
    w_copy = jnp.array(w)                            # equal values, new id
    second = kops.psram_matmul_op(x, w_copy, backend="xla")
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))


# ---------------- quantized-KR dense MTTKRP (pSRAM variant) ----------------

@pytest.mark.parametrize("i,j,k,r", [
    (64, 4, 64, 8),
    (128, 8, 128, 16),
    (32, 16, 32, 8),
])
def test_mttkrp_psram_kernel_vs_xla_vs_ref(key, i, j, k, r):
    """The quantized matricized-KR kernel: interpret vs XLA twin vs the
    plain-jnp oracle, all within f32 reassociation of each other."""
    x0 = jax.random.normal(key, (i, j * k))
    b = jax.random.normal(jax.random.PRNGKey(1), (j, r))
    c = jax.random.normal(jax.random.PRNGKey(2), (k, r))
    qx, sx, qb, sb, qc, sc = quantize_mttkrp_operands(x0, b, c)
    bi, bk = min(128, i), min(128, k)
    kern = mttkrp_psram_fused(qx, sx, qb, sb, qc, sc, bi=bi, bk=bk,
                              interpret=True)
    xla = mttkrp_psram_xla(qx, sx, qb, sb, qc, sc, bi=bi)
    oracle = ref.mttkrp_psram_ref(qx, sx, qb, sb, qc, sc, bi=bi)
    # the kernel's tile walk reassociates the f32 accumulation vs the flat
    # contraction; a sum landing on an ADC code boundary may round one code
    # apart — tolerate one 16-bit step of the observed full scale
    step = 2.0 * float(jnp.max(jnp.abs(oracle))) / 2 ** 16
    np.testing.assert_allclose(np.asarray(kern), np.asarray(xla),
                               rtol=2e-4, atol=2 * step)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(oracle),
                               rtol=2e-4, atol=2 * step)


def test_mttkrp_psram_within_quantization_envelope(key):
    """End to end (quantize + kernel + ADC) vs the exact dense MTTKRP:
    inside the documented 8-bit envelope (rel < 0.05)."""
    from repro.core.mttkrp import mttkrp_dense
    i, j, k, r = 64, 16, 32, 8
    x = jax.random.normal(key, (i, j, k))
    b = jax.random.normal(jax.random.PRNGKey(1), (j, r))
    c = jax.random.normal(jax.random.PRNGKey(2), (k, r))
    qx, sx, qb, sb, qc, sc = quantize_mttkrp_operands(x.reshape(i, -1), b, c)
    got = mttkrp_psram_xla(qx, sx, qb, sb, qc, sc, bi=i)
    want = mttkrp_dense(x, [jnp.zeros((i, r)), b, c], 0)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.05


# ------------- kernels with TPU-aligned blocks, at aligned shapes -------------

@pytest.mark.parametrize("i,j,k,r", [(256, 8, 256, 32), (128, 16, 512, 128)])
def test_mttkrp_fused_aligned_vs_ref(key, i, j, k, r):
    """Exact dense kernel with B held whole in VMEM (row j sliced in the
    kernel) at MXU-aligned tiles, against the oracle."""
    x0 = jax.random.normal(key, (i, j * k))
    b = jax.random.normal(jax.random.PRNGKey(1), (j, r))
    c = jax.random.normal(jax.random.PRNGKey(2), (k, r))
    got = mttkrp_fused(x0, b, c, bi=128, bk=128, interpret=True)
    want = ref.mttkrp_ref(x0, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("i,j,k,r", [(256, 8, 256, 32), (128, 16, 512, 128)])
def test_mttkrp_psram_aligned_vs_ref(key, i, j, k, r):
    """Quantized dense kernel with qb/sb held whole in VMEM, at aligned
    tiles, against the oracle: within one ADC code (the tile walk
    reassociates the f32 accumulation)."""
    x0 = jax.random.normal(key, (i, j * k))
    b = jax.random.normal(jax.random.PRNGKey(1), (j, r))
    c = jax.random.normal(jax.random.PRNGKey(2), (k, r))
    ops = quantize_mttkrp_operands(x0, b, c)
    got = mttkrp_psram_fused(*ops, bi=128, bk=128, interpret=True)
    want = ref.mttkrp_psram_ref(*ops, bi=128)
    step = 2.0 * float(jnp.max(jnp.abs(want))) / 2 ** 16
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2 * step)


@pytest.mark.parametrize("b,bn,r,n_seg", [(16, 256, 32, 64), (9, 128, 128, 128)])
def test_blocked_segment_sum_aligned_vs_ref(b, bn, r, n_seg):
    """Blocked segment sum with the (B, 1, bn) id layout, against the
    one-hot oracle."""
    from repro.kernels.segment_sum import blocked_segment_sum

    data = jax.random.normal(jax.random.PRNGKey(4), (b, bn, r))
    seg = jnp.sort(jax.random.randint(jax.random.PRNGKey(5), (b, bn), 0,
                                      n_seg), axis=1).astype(jnp.int32)
    got = blocked_segment_sum(data, seg, n_seg, interpret=True)
    want = ref.blocked_segment_sum_ref(data, seg, n_seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(256, 1024, 256), (128, 4096, 512)])
def test_psram_matmul_int8_operands_aligned_vs_ref(key, m, k, n):
    """int8 operands fed straight to the MXU dot, int32 accumulation: bit
    for bit the oracle's int32 matmul + ADC, at the default 128/128/512
    tiles."""
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    qx, sx = quantize_symmetric(x, axis=-1)
    qw, sw = quantize_symmetric(w, axis=0)
    sx, sw = sx.reshape(m, 1), sw.reshape(1, n)
    got = psram_matmul(qx, qw, sx, sw, interpret=True)
    want = ref.psram_matmul_ref(qx, qw, sx, sw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------- fused streaming MTTKRP ----------------

def _small_stream_case(nnz=800, shape=(30, 24, 18), rank=6):
    from repro.sparse import csf_for_mode, powerlaw_coo
    coo = powerlaw_coo(jax.random.PRNGKey(3), shape, nnz=nnz, rank=4,
                       alpha=1.1)
    csf = csf_for_mode(coo, 0)
    fs = tuple(
        jax.random.normal(jax.random.PRNGKey(d + 1), (s, rank))
        for d, s in enumerate(shape)
    )
    return csf, fs


@pytest.mark.parametrize("adc_bits", [0, 16])
def test_fused_stream_lowerings_agree(adc_bits):
    """One kernel body, three CPU-runnable lowerings: the scan-carried XLA
    twin, the interpreted Pallas kernel, and the flat oracle agree bit for
    bit (same int8 gathers, same f32 chain, same ADC codes, same
    accumulation order per segment)."""
    from repro.kernels.stream_mttkrp import fused_stream_mttkrp
    csf, fs = _small_stream_case()
    got = {
        low: fused_stream_mttkrp(csf, fs, adc_bits=adc_bits, lowering=low)
        for low in ("xla", "interpret", "ref")
    }
    np.testing.assert_array_equal(np.asarray(got["xla"]),
                                  np.asarray(got["interpret"]))
    np.testing.assert_allclose(np.asarray(got["xla"]),
                               np.asarray(got["ref"]), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_fused_stream_within_envelope_every_mode(mode):
    """Fused quantized stream vs the exact COO segment-sum, per mode:
    inside the documented pallas envelope (rel < 0.05)."""
    from repro.core.mttkrp import mttkrp_sparse
    from repro.kernels.stream_mttkrp import fused_stream_mttkrp
    from repro.sparse import csf_for_mode, powerlaw_coo
    shape = (30, 24, 18)
    coo = powerlaw_coo(jax.random.PRNGKey(3), shape, nnz=800, rank=4,
                       alpha=1.1)
    csf = csf_for_mode(coo, mode)
    fs = tuple(
        jax.random.normal(jax.random.PRNGKey(d + 1), (s, 6))
        for d, s in enumerate(shape)
    )
    s = csf.to_coo()
    want = mttkrp_sparse(s.indices, s.values, fs, mode, shape[mode])
    got = fused_stream_mttkrp(csf, fs, lowering="xla")
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.05


def test_fused_stream_exec_blocks_invariant():
    """Different exec-block tilings stay within a few ADC codes of each
    other: the tiling moves chunk boundaries, and the epilogue digitizes
    each chunk over its *observed* dynamic range, so a candidate switch may
    re-round partials — but never beyond code granularity. This is the
    contract that lets the autotuner pick any candidate without moving
    results at the envelope level."""
    from repro.kernels.stream_mttkrp import fused_stream_mttkrp
    csf, fs = _small_stream_case()
    outs = [
        np.asarray(fused_stream_mttkrp(csf, fs, lowering="xla",
                                       exec_blocks=eb))
        for eb in (1, 2, 4)
    ]
    for other in outs[1:]:
        rel = np.linalg.norm(outs[0] - other) / np.linalg.norm(outs[0])
        assert rel < 1e-3


def _two_gather_partials(ip_c, vp_c, lp_c, qs, ss, *, mode, n_seg, adc_bits):
    """The reference formula for the fused chunk body: per factor, one
    gather of the ``(J, R)`` int8 codes and a second of the ``(J, 1)`` f32
    row scales, then the same chain, mask, contraction and ADC epilogue."""
    from repro.core.quantization import adc_transfer
    others = [d for d in range(ip_c.shape[-1]) if d != mode]
    acc_t = jnp.int16 if len(others) <= 2 else jnp.float32
    had, scale = None, vp_c
    for d in others:
        idx = ip_c[..., d]
        g = qs[d][idx]
        had = g.astype(acc_t) if had is None else had * g.astype(acc_t)
        scale = scale * ss[d][idx, 0]
    had = had.astype(jnp.float32)
    sids = jax.lax.broadcasted_iota(jnp.int32, (1, n_seg, had.shape[-2]), 1)
    mask = (sids == lp_c[:, None, :]).astype(jnp.float32) * scale[:, None, :]
    parts = jax.lax.dot_general(mask, had, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
    if adc_bits:
        full_scale = jnp.maximum(jnp.max(jnp.abs(parts)), 1e-30)
        parts = adc_transfer(parts, 2 ** adc_bits, full_scale)
    return parts


@pytest.mark.parametrize("adc_bits", [0, 16])
@pytest.mark.parametrize("shape,mode", [
    ((30, 24, 18), 0),
    ((30, 24, 18), 1),
    ((30, 24, 18), 2),
    ((12, 10, 9, 8), 1),       # three-factor chain: the f32 Hadamard
])
def test_packed_rows_match_two_gather_body(shape, mode, adc_bits):
    """One gather of the packed ``(J, R + 4)`` rows gives, bit for bit, the
    partials of the two-gather body on the same ``quantize_symmetric``
    codes and scales; unpacking the packed factors gives those codes and
    scales back exactly."""
    from repro.core.psram import PsramConfig
    from repro.kernels.stream_mttkrp import (
        _chunk_partials, quantize_stream_factors, unpack_rows)
    from repro.sparse import csf_for_mode, powerlaw_coo
    from repro.sparse.stream import stream_layout
    coo = powerlaw_coo(jax.random.PRNGKey(3), shape, nnz=2000, rank=4,
                       alpha=1.1, mode=mode)
    csf = csf_for_mode(coo, mode)
    fs = tuple(jax.random.normal(jax.random.PRNGKey(d + 1), (s, 6))
               for d, s in enumerate(shape))
    ip, vp, lp, _, n_seg = stream_layout(csf, PsramConfig().rows, 2)
    ps = quantize_stream_factors(fs, mode)
    qs, ss = list(ps), list(ps)
    for d, f in enumerate(fs):
        if d == mode:
            continue
        qs[d], ss[d] = quantize_symmetric(f, axis=-1)
        codes, scales = unpack_rows(ps[d])
        np.testing.assert_array_equal(np.asarray(codes), np.asarray(qs[d]))
        np.testing.assert_array_equal(np.asarray(scales),
                                      np.asarray(ss[d][:, 0]))
    kw = dict(mode=mode, n_seg=n_seg, adc_bits=adc_bits)
    want = jax.jit(jax.vmap(
        lambda i, v, l: _two_gather_partials(i, v, l, qs, ss, **kw)))(ip, vp, lp)
    got = jax.jit(jax.vmap(
        lambda i, v, l: _chunk_partials(i, v, l, ps, **kw)))(ip, vp, lp)
    assert float(jnp.max(jnp.abs(want))) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_stream_counts_row_gathers():
    """With tracing on, one drive records nnz x (N - 1) factor-row gathers
    under ``stream/row_gathers``; with tracing off it records nothing."""
    from repro import obs
    from repro.kernels.stream_mttkrp import fused_stream_mttkrp
    csf, fs = _small_stream_case()
    obs.disable()
    obs.get_tracer().clear()
    try:
        fused_stream_mttkrp(csf, fs, lowering="xla")
        assert "stream/row_gathers" not in obs.get_tracer().counters()
        obs.enable()
        fused_stream_mttkrp(csf, fs, lowering="xla")
        counters = obs.get_tracer().counters()
        assert counters["stream/row_gathers"] == csf.nnz * (len(fs) - 1)
    finally:
        obs.disable()
        obs.get_tracer().clear()


def test_fused_stream_unknown_lowering_raises():
    from repro.kernels.stream_mttkrp import fused_stream_mttkrp
    csf, fs = _small_stream_case(nnz=50)
    with pytest.raises(RuntimeError, match="lowering"):
        fused_stream_mttkrp(csf, fs, lowering="tpu-but-misspelled")


# ---------------- flash attention ----------------

@pytest.mark.parametrize("b,h,hkv,s,d,causal,softcap", [
    (2, 4, 4, 256, 64, True, 0.0),
    (2, 4, 2, 256, 64, True, 0.0),    # GQA
    (1, 8, 1, 128, 32, True, 0.0),    # MQA
    (2, 4, 4, 256, 64, False, 0.0),
    (2, 4, 2, 128, 64, True, 50.0),   # softcap (gemma2-style)
])
def test_flash_vs_ref(key, b, h, hkv, s, d, causal, softcap):
    q = jax.random.normal(key, (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, softcap=softcap,
                          bq=64, bkv=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_bf16(key):
    q = jax.random.normal(key, (1, 2, 128, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 128, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 128, 64), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, bq=64, bkv=64, interpret=True)
    want = ref.attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)


def test_flash_matches_model_chunked_attention(key):
    """Pallas flash == the pure-JAX chunked path used by the dry-run models."""
    from repro.models.config import ArchConfig
    from repro.models.layers import _sdpa_chunked
    cfg = ArchConfig(name="t", attn_chunk=64)
    b, h, s, d = 2, 4, 256, 64
    q = jax.random.normal(key, (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, s, d), jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=64, bkv=64, interpret=True)
    # chunked path takes (B, S, H, D)
    want = _sdpa_chunked(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), cfg, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want.transpose(0, 2, 1, 3)),
                               rtol=2e-3, atol=2e-3)
