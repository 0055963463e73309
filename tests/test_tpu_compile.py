"""Compile the main path for a TPU v5e that is described, not attached.

Each test lowers a kernel (or a jitted step) at real widths for one chip of
a ``v5e:2x2`` topology and asks the TPU compiler for an executable: what it
refuses here would fail on the chip at its first call. Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and it keeps it
until it exits, so a test worker that is not given this file must not touch
it. Where no topology can be described, every test here skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

NELL2_DIMS = (12092, 9184, 28818)
NELL2_NNZ_CUT = 76_879_419 // 8
NELL2_NNZ_QUARTER = 19_196_820   # the nell2.cpals cell's, duplicates merged
RANK = 32
ROWS = 256                       # PsramConfig().rows: nonzeros per block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shape_of(topo):
    chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=chip)

    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_psram_matmul_compiles(shape_of):
    """int8 pSRAM matmul at a granite_8b MLP projection, 256 x 4096 @
    4096 x 14336: int8 operands straight into the MXU."""
    from repro.kernels.psram_matmul import psram_matmul

    m, k, n = 256, 4096, 14336
    c = _compile(psram_matmul, shape_of((m, k), jnp.int8),
                 shape_of((k, n), jnp.int8), shape_of((m, 1), jnp.float32),
                 shape_of((1, n), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_mttkrp_psram_dense_compiles(shape_of):
    """Quantized dense MTTKRP kernel on a 1024 x 256 x 2048 int8 tensor
    (512 MB) at rank 32, both small factors whole in VMEM."""
    from repro.kernels.mttkrp import mttkrp_psram_fused

    i, j, k = 1024, 256, 2048
    c = _compile(mttkrp_psram_fused,
                 shape_of((i, j * k), jnp.int8), shape_of((i, 1), jnp.float32),
                 shape_of((j, RANK), jnp.int8), shape_of((j, 1), jnp.float32),
                 shape_of((k, RANK), jnp.int8), shape_of((k, 1), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_blocked_segment_sum_compiles(shape_of):
    """Blocked segment sum over the 1/8-nell-2 stream: one 256-row block per
    grid step, rank 32, up to 256 segments a block."""
    from repro.kernels.segment_sum import blocked_segment_sum

    b = -(-NELL2_NNZ_CUT // ROWS)
    c = _compile(lambda d, s: blocked_segment_sum(d, s, n_seg=ROWS),
                 shape_of((b, ROWS, RANK), jnp.float32),
                 shape_of((b, ROWS), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def _stream_args(shape_of, mode, nnz=NELL2_NNZ_CUT, n_seg=ROWS):
    """Fused-stream operands for a nell-2 stream of ``nnz`` nonzeros rooted
    at ``mode`` (default 1/8 of nell-2), with the autotuner's default chunk
    of 8192 nonzeros: each non-target factor packed as ``(J, R + 4)`` int8
    rows, codes and scale (``stream_mttkrp.pack_rows``)."""
    from repro.kernels.stream_mttkrp import SCALE_LANES

    e = 8192 // ROWS
    nb = -(-nnz // (e * ROWS))
    ps = tuple(shape_of((1, 1) if d == mode else (n, RANK + SCALE_LANES),
                        jnp.int8)
               for d, n in enumerate(NELL2_DIMS))
    return (shape_of((nb, e, ROWS, 3), jnp.int32),
            shape_of((nb, e, ROWS), jnp.float32),
            shape_of((nb, e, ROWS), jnp.int32),
            shape_of((nb, e * n_seg), jnp.int32), ps)


def test_stream_mttkrp_tpu_lowering_compiles(shape_of, monkeypatch):
    """The lowering ``"auto"`` picks for the fused stream op on a TPU, at
    nell-2's largest mode (28,818 output rows, rank 32)."""
    from repro.backends import lowering
    from repro.kernels.stream_mttkrp import _LOWERING_FNS

    monkeypatch.delenv("REPRO_KERNEL_LOWERING", raising=False)
    lowering._env_override.cache_clear()
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    low = lowering.resolve_stream_lowering("auto")
    mode = 2
    fn = _LOWERING_FNS[low]
    c = _compile(lambda *a: fn(*a, mode, ROWS, 16, NELL2_DIMS[mode]),
                 *_stream_args(shape_of, mode))
    # the (28818, 32) f32 result, padded only to the chip's tiling
    out = c.memory_analysis().output_size_in_bytes
    assert NELL2_DIMS[mode] * RANK * 4 <= out < 1.01 * NELL2_DIMS[mode] * RANK * 4


def test_stream_mttkrp_one_int8_gather_per_factor(shape_of):
    """The ``"xla"`` lowering at the ``nell2.cpals`` cell's shapes (1/4 of
    nell-2, 19,196,820 nonzeros, mode 0, ``n_seg`` 4) gathers each
    non-target factor once per nonzero: exactly N - 1 = 2 gathers in the
    compiled program, each of a packed ``(R + 4)``-byte int8 row (codes
    and scale), and no f32 scale gather beside them."""
    import re

    from repro.kernels.stream_mttkrp import SCALE_LANES, _LOWERING_FNS

    mode, n_seg = 0, 4
    fn = _LOWERING_FNS["xla"]
    c = _compile(lambda *a: fn(*a, mode, n_seg, 16, NELL2_DIMS[mode]),
                 *_stream_args(shape_of, mode, nnz=NELL2_NNZ_QUARTER,
                               n_seg=n_seg))
    gathers = re.findall(r"= (\w+)\[([\d,]*)\]\S* gather\(", c.as_text())
    assert len(gathers) == len(NELL2_DIMS) - 1, gathers
    for dtype, dims in gathers:
        assert dtype == "s8"
        assert int(dims.split(",")[-1]) == RANK + SCALE_LANES


def test_stream_mttkrp_pallas_lowering_refused(shape_of):
    """An explicit ``"pallas"`` stream lowering fails loudly at compile
    time (the refusal quoted in ``lowering.resolve_stream_lowering``), so
    it can never be picked without notice."""
    from repro.kernels.stream_mttkrp import stream_mttkrp_fused_pallas

    mode = 2
    with pytest.raises(Exception, match="block shape|Shape mismatch"):
        _compile(lambda *a: stream_mttkrp_fused_pallas(
            *a, mode=mode, n_seg=ROWS, adc_bits=16,
            out_rows=NELL2_DIMS[mode]), *_stream_args(shape_of, mode))


def test_serve_decode_step_compiles(shape_of):
    """One ServeLoop decode step of granite_8b at its published widths,
    16 layers deep, 8 rows against a 2048-slot view of a 32,768-slot page
    pool, from ``jax.eval_shape`` shapes (no weights are drawn). It must
    leave the 16 GB chip at least 2 GB for the page pool."""
    import dataclasses

    from repro.models.registry import get_config, get_module
    from repro.serve.loop import ServeLoop, ServeLoopConfig

    cfg = dataclasses.replace(get_config("granite_8b"), num_layers=16)
    mod = get_module(cfg)
    params = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: shape_of(a.shape, a.dtype), params)
    loop = ServeLoop(cfg, params, ServeLoopConfig(max_batch=8, num_pages=4))
    n_slots = 2048 * 16 + 1
    slab = jax.tree.map(lambda a: shape_of((n_slots, *a.shape[1:]), a.dtype),
                        loop.slab)
    b, s_v = 8, 2048
    c = loop._decode_fn.lower(
        params, slab, shape_of((b,), jnp.int32), shape_of((b,), jnp.int32),
        shape_of((b, s_v), jnp.int32), shape_of((b,), jnp.int32)).compile()
    mem = c.memory_analysis()
    slab_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(slab))
    assert slab_bytes >= 2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
