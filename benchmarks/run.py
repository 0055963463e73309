"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived,backend`` CSV rows. "derived" carries the
figure-specific number (PetaOps, fit, rel-error...) so each row maps back to
a paper claim; "backend" is the registry name (repro.backends) the row
exercises, so the perf trajectory is attributable per backend. Wall-clock
rows time the *JAX CPU* execution (this container); modeled rows come from
the paper's predictive performance model and the TPU roofline constants.

``--json BENCH_psram.json`` additionally writes the rows as a JSON list of
``{name, us_per_call, derived, backend}`` objects so the perf trajectory
(notably the loop-oracle vs. vectorized-executor speedup) is
machine-trackable across PRs. ``--backend NAME`` (repeatable) scopes the
run to the benches exercising those backends — sweeps can be scoped during
development instead of always running the full matrix.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.cp_als import cp_als, cp_als_psram
from repro.core.mttkrp import dense_to_coo, mttkrp_dense, mttkrp_sparse
from repro.core.perf_model import (
    MTTKRPWorkload,
    mttkrp_energy,
    ops_per_joule,
    peak_petaops,
    sustained_mttkrp,
    sweep_channels,
    sweep_frequency,
    time_to_solution_s,
    tpu_mttkrp_time_s,
    tpu_ops_per_joule,
)
from repro.core.psram import PsramConfig
from repro.data.tensors import lowrank_dense
from repro.kernels.ops import mttkrp_op, psram_matmul_op


def _time(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6  # us


def _time_interleaved(fns, n=5, warmup=1):
    """Per-call medians of several callables timed in alternating rounds.

    Back-to-back ``_time(a); _time(b)`` windows let machine-state drift
    (frequency scaling, cache pressure from a neighbour) bias a/b speedup
    ratios; interleaving a,b,a,b samples both under the same conditions.
    """
    for fn in fns:
        for _ in range(warmup):
            jax.block_until_ready(fn())
    samples = [[] for _ in fns]
    for _ in range(n):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            samples[i].append(time.perf_counter() - t0)
    return tuple(sorted(s)[n // 2] * 1e6 for s in samples)


def _model_time(fn, *args, n=10):
    """Wall time of a pure-python/numpy model evaluation, in us.

    Analytical rows used to report ``us_per_call: 0.0`` — the *model* is
    also code on the hot estimate path (serve sizing sweeps call it per
    request), so the trajectory tracks its cost too (satellite: a cost-path
    regression now moves a number instead of hiding behind a literal 0)."""
    fn(*args)  # warm any lazy imports/caches
    t0 = time.perf_counter()
    for _ in range(n):
        fn(*args)
    return (time.perf_counter() - t0) / n * 1e6


ROWS: list[dict] = []
SELECTED: set | None = None   # None = every registered backend


def selected(*names) -> bool:
    """Is any of these backends in the --backend scope?"""
    return SELECTED is None or bool(SELECTED & set(names))


def row(name, us, derived, backend="analytical", meta=None):
    """Record one row. ``meta`` (optional dict) rides along in the JSON
    only — the serve rows use it to pin the traffic seed and sweep params
    so a regression can be replayed from the row alone."""
    if not selected(backend):
        return
    r = {"name": name, "us_per_call": round(us, 1),
         "derived": str(derived), "backend": backend}
    if meta is not None:
        r["meta"] = meta
    ROWS.append(r)
    print(f"{name},{us:.1f},{derived},{backend}")


# ----------------------------------------------------------------- Fig 5(i)
def bench_fig5_channels():
    """Sustained PetaOps vs wavelength channels @ 20 GHz (paper Fig. 5 i)."""
    channels = [4, 8, 13, 26, 39, 52]
    us = _model_time(lambda: sweep_channels(channels=channels)) / len(channels)
    for ch, pops in sweep_channels(channels=channels):
        row(f"fig5i_channels_{ch}", us, f"{pops:.3f} PetaOps")


# ---------------------------------------------------------------- Fig 5(ii)
def bench_fig5_frequency():
    """Sustained PetaOps vs operating frequency @ 52 channels (Fig. 5 ii)."""
    freqs = (1, 2, 5, 10, 15, 20)
    us = _model_time(lambda: sweep_frequency(freqs=freqs)) / len(freqs)
    for f, pops in sweep_frequency(freqs=freqs):
        row(f"fig5ii_freq_{int(f)}GHz", us, f"{pops:.3f} PetaOps")


# ------------------------------------------------------------- §V headline
def bench_headline():
    """The 17 PetaOps claim + utilization breakdown + TPU comparison."""
    cfg = PsramConfig()
    wl = MTTKRPWorkload()
    sb = sustained_mttkrp(cfg, wl)
    us_model = _model_time(sustained_mttkrp, cfg, wl)
    row("headline_peak", _model_time(peak_petaops, cfg),
        f"{peak_petaops(cfg):.3f} PetaOps (paper: 17)")
    row("headline_sustained", us_model, f"{sb.sustained_petaops:.3f} PetaOps")
    row("headline_utilization", us_model, f"{sb.utilization:.4f}")
    small = MTTKRPWorkload(i=10**4, j=10**4, k=10**4, rank=32)
    row("tts_psram_1e4cube", time_to_solution_s(cfg, small) * 1e6, "pSRAM array")
    row("tts_tpu_v5e_int8", tpu_mttkrp_time_s(small) * 1e6, "1 chip roofline")
    row("speedup_vs_tpu", _model_time(tpu_mttkrp_time_s, small),
        f"{tpu_mttkrp_time_s(small) / time_to_solution_s(cfg, small):.1f}x")


# ------------------------------------------------- MTTKRP kernel wall-clock
def bench_mttkrp_paths():
    """Dense einsum vs sparse COO vs materialized-KR oracle wall time."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 64, 128))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    c = jax.random.normal(jax.random.PRNGKey(2), (128, 32))
    a = jax.random.normal(jax.random.PRNGKey(3), (256, 32))
    fs = [a, b, c]
    flops = 2 * 256 * 64 * 128 * 32 * 2

    if selected("exact"):
        f_dense = jax.jit(lambda t: mttkrp_dense(t, fs, 0))
        us = _time(f_dense, x)
        row("mttkrp_dense_einsum", us, f"{flops/us/1e3:.1f} GFLOP/s cpu",
            "exact")

        idx, vals = dense_to_coo(x)
        f_sparse = jax.jit(lambda i, v: mttkrp_sparse(i, v, tuple(fs), 0, 256))
        us = _time(f_sparse, idx, vals)
        want = f_sparse(idx, vals)
        row("mttkrp_sparse_coo", us, f"{flops/us/1e3:.1f} GFLOP/s cpu",
            "exact")

        # the blocked-segment fold on the same stream: exact arithmetic,
        # per-block gather-mask contractions instead of a per-nonzero
        # scatter (tentpole 3) — the speedup the compiled stream executor
        # inherits
        from repro.sparse import csf_for_mode, stream_mttkrp
        from repro.sparse.formats import COO

        csf = csf_for_mode(COO(indices=idx, values=vals, shape=x.shape), 0)
        f_blocked = lambda: stream_mttkrp(csf, tuple(fs), PsramConfig(),
                                          compiled=True)
        us_b = _time(f_blocked, n=5, warmup=1)
        got = f_blocked()
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        row("mttkrp_sparse_coo_blocked", us_b,
            f"{flops/us_b/1e3:.1f} GFLOP/s cpu rel_vs_segsum={rel:.1e} "
            f"speedup={us/us_b:.1f}x", "exact")

    if selected("pallas"):
        f_kr = jax.jit(lambda t: mttkrp_op(t, b, c, backend="ref"))
        us = _time(f_kr, x)
        row("mttkrp_kr_oracle", us, f"{flops/us/1e3:.1f} GFLOP/s cpu",
            "pallas")

    wl = MTTKRPWorkload(i=256, j=64, k=128, rank=32)
    row("mttkrp_psram_modeled", time_to_solution_s(PsramConfig(), wl) * 1e6,
        "paper engine @ 52ch/20GHz")


# ------------------------------------------------- pSRAM matmul numerics
def bench_psram_matmul():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 512))
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
    f = jax.jit(lambda a, b_: psram_matmul_op(a, b_, backend="ref"))
    us = _time(f, x, w)
    exact = x @ w
    got = f(x, w)
    rel = float(jnp.linalg.norm(got - exact) / jnp.linalg.norm(exact))
    row("psram_matmul_ref", us, f"rel_err={rel:.4f}", "pallas")


# ------------------------------------------- tile-schedule executor (§IV)
def bench_schedule_executor():
    """Vectorized schedule executor vs the per-cycle loop oracle — the PR-2
    refactor's headline speedup, on the 256x512 @ 512x128 reference matmul.
    Both interpret the same tile program and are bit-identical. The compiled
    rows add the PR-5 layer: the program cache (build + validate now O(1)
    on repeats) and the cached jitted executor, timed on the full
    build→validate→execute path a repeated same-shape caller pays."""
    from repro.core.perf_model import measured_utilization
    from repro.core.schedule import (
        build_matmul_program, count_cycles, execute, execute_reference,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 512))
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 128))
    cfg = PsramConfig()
    prog = build_matmul_program(256, 512, 128, cfg)
    us_vec = _time(execute, prog, x, w, n=5, warmup=1) \
        if selected("psram-scheduled") else None
    us_loop = _time(execute_reference, prog, x, w, n=3, warmup=1) \
        if selected("psram-oracle") else None
    if us_vec is not None:
        derived = "vectorized executor"
        if us_loop is not None:
            bit = bool(jnp.all(
                execute(prog, x, w) == execute_reference(prog, x, w)))
            derived = f"bit_identical={bit}"
        row("schedule_exec_vectorized", us_vec, derived, "psram-scheduled")
    if us_loop is not None:
        row("schedule_exec_loop_oracle", us_loop,
            "per-cycle PsramArray interpreter", "psram-oracle")
    if us_vec is not None and us_loop is not None:
        row("schedule_exec_speedup", 0.0, f"{us_loop / us_vec:.1f}x",
            "psram-scheduled")
    if selected("psram-scheduled"):
        counts = count_cycles(prog)
        mu = measured_utilization(prog)
        row("schedule_exec_counted_cycles", 0.0,
            f"{counts.compute_cycles} compute + {counts.write_cycles} write "
            f"util={mu.utilization:.3f}", "psram-scheduled")
        # repeated same-shape calls, full front-door path: program cache
        # (O(1) validate) + eager executor vs + cached jitted executor
        repeat = lambda c: execute(
            build_matmul_program(256, 512, 128, cfg), x, w, compiled=c)
        us_rep = _time(repeat, False, n=5, warmup=1)
        us_cmp = _time(repeat, True, n=5, warmup=1)
        a, b = repeat(True), repeat(False)
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        row("schedule_exec_repeat_cached", us_rep,
            "build+validate+eager on a cache-hot program", "psram-scheduled")
        row("schedule_exec_compiled", us_cmp,
            f"cached jitted executor rel_vs_eager={rel:.1e} "
            f"speedup={us_rep/us_cmp:.1f}x", "psram-scheduled")


# --------------------------------------------------------- CP-ALS end2end
def bench_cp_als():
    key = jax.random.PRNGKey(0)
    x, _ = lowrank_dense(key, (40, 36, 32), rank=4)
    if selected("exact"):
        t0 = time.perf_counter()
        st = cp_als(x, rank=4, n_iter=30, key=jax.random.PRNGKey(5))
        us = (time.perf_counter() - t0) * 1e6
        row("cp_als_float_30it", us, f"fit={st.fit:.4f}", "exact")
    if selected("psram-oracle"):
        idx, vals = dense_to_coo(x)
        t0 = time.perf_counter()
        stq = cp_als_psram((idx, vals, x.shape), rank=4, n_iter=30,
                           key=jax.random.PRNGKey(5))
        us = (time.perf_counter() - t0) * 1e6
        row("cp_als_psram_30it", us, f"fit={stq.fit:.4f} (8-bit+ADC engine)",
            "psram-oracle")


# ---------------------------------------------------- energy (beyond-paper)
def bench_energy():
    """Energy per MTTKRP from the paper's bitcell data (1.04 pJ/bit write,
    16.7 aJ/bit static) — ops/J of the array vs a TPU chip at wall power."""
    cfg = PsramConfig()
    wl = MTTKRPWorkload(i=10**4, j=10**4, k=10**4, rank=32)
    e = mttkrp_energy(cfg, wl)
    row("energy_mttkrp_1e4cube", _model_time(mttkrp_energy, cfg, wl),
        f"{e.total_j:.2f} J (write {e.write_j:.2f}, adc {e.adc_j:.2f})")
    row("energy_array_tops_per_j", _model_time(ops_per_joule, cfg, wl),
        f"{ops_per_joule(cfg, wl)/1e12:.1f} TOps/J")
    row("energy_tpu_tops_per_j", _model_time(tpu_ops_per_joule, wl),
        f"{tpu_ops_per_joule(wl)/1e12:.2f} TOps/J")
    row("energy_advantage", 0.0, f"{ops_per_joule(cfg, wl)/tpu_ops_per_joule(wl):.0f}x")


# ------------------------------------------ sparse MTTKRP density sweep
def bench_sparse_mttkrp(smoke: bool = False):
    """Streamed sparse MTTKRP (repro.sparse) across densities: wall-clock of
    the streaming executor (bit-identical to the COO segment-sum path),
    counted-cycle utilization of its schedule, and agreement with the
    sparse-aware analytical model — the paper's actual workload class."""
    from repro.core.perf_model import (
        SparseMTTKRPWorkload, measured_utilization, sustained_mttkrp,
    )
    from repro.core.schedule import count_cycles
    from repro.sparse import (
        build_stream_program, csf_for_mode, powerlaw_coo, stream_mttkrp,
    )

    from repro.sparse import blocked_fold_reference

    cfg = PsramConfig()
    shape = (400, 300, 200) if smoke else (2000, 1500, 1200)
    size = shape[0] * shape[1] * shape[2]
    densities = (1e-4, 1e-3) if smoke else (1e-5, 1e-4, 1e-3)
    rank = 32
    for dens in densities if selected("psram-stream") else ():
        nnz = max(1000, int(size * dens))
        coo = powerlaw_coo(jax.random.PRNGKey(0), shape, nnz=nnz,
                           rank=8, alpha=1.1)
        csf = csf_for_mode(coo, 0)
        fs = tuple(
            jax.random.normal(jax.random.PRNGKey(d + 1), (s, rank))
            for d, s in enumerate(shape)
        )
        s = csf.to_coo()
        exact = mttkrp_sparse(s.indices, s.values, fs, 0, shape[0])
        prog = build_stream_program(csf.fiber_lengths(), rank, cfg)
        if obs.enabled():
            # cycle-domain view of this exact schedule: per-channel tracks
            # in the trace (--trace), alongside the wall-clock spans
            obs.get_tracer().add_events(obs.program_timeline(
                prog, name=f"stream d{dens:g} nnz{coo.nnz}"))
        counts = count_cycles(prog)
        measured = measured_utilization(prog)
        model = sustained_mttkrp(cfg, SparseMTTKRPWorkload(
            fiber_lengths=csf.fiber_lengths(), rank=rank))
        agree = measured.utilization / max(model.utilization, 1e-30)
        # the hot path: the compiled blocked-fold executor — bit-identical
        # to its flat blocked reference (mttkrp_sparse_blocked), exact
        # arithmetic reassociated vs the per-nonzero segment-sum fold
        fc = lambda: stream_mttkrp(csf, fs, cfg, compiled=True)
        us = _time(fc, n=3, warmup=1)
        got = fc()
        bit = bool(jnp.all(got == blocked_fold_reference(csf, fs, cfg)))
        rel = float(jnp.linalg.norm(got - exact) / jnp.linalg.norm(exact))
        row(f"sparse_mttkrp_d{dens:g}_nnz{coo.nnz}", us,
            f"bit_identical={bit} (vs blocked reference) "
            f"rel_vs_segsum={rel:.1e} cycles={counts.total_cycles} "
            f"util={measured.utilization:.4f} model_agree={agree:.3f}",
            "psram-stream")
        # the eager parity oracle: per-nonzero electrical fold, bit-identical
        # to mttkrp_sparse — the trajectory of the default (oracle) path
        fe = lambda: stream_mttkrp(csf, fs, cfg)
        us_e = _time(fe, n=3, warmup=1)
        bit_e = bool(jnp.all(fe() == exact))
        row(f"sparse_mttkrp_eager_d{dens:g}_nnz{coo.nnz}", us_e,
            f"bit_identical={bit_e} (vs segment-sum) "
            f"compiled_speedup={us_e/us:.1f}x", "psram-stream")
    # modeled §V-A-scale sparse sustained rate from the distribution alone
    from repro.sparse import powerlaw_fiber_lengths
    f = powerlaw_fiber_lengths(0, 10**6 if not smoke else 10**4,
                               4 * 10**6 if not smoke else 4 * 10**4,
                               alpha=1.1)
    wl = SparseMTTKRPWorkload(fiber_lengths=f, rank=32)
    sb = sustained_mttkrp(cfg, wl)
    row("sparse_sustained_powerlaw",
        _model_time(sustained_mttkrp, cfg, wl, n=3),
        f"{sb.sustained_petaops:.4f} PetaOps occ={sb.wavelength_occupancy:.3f}")


# ------------------------------------- fused Pallas kernel family (PR 6)
def bench_pallas_fused(smoke: bool = False):
    """The fused streaming-MTTKRP kernel family vs the PR-5 compiled scan
    executors — the pallas backend's speed-champion claim, measured.

    Sparse: the fused kernel (int8 prequantized gathers + Hadamard chain +
    one-hot segment contraction + ADC epilogue, one jitted scan over exec
    blocks) against ``stream_mttkrp(..., psram=True, compiled=True)`` on
    the same CSF — the like-for-like baseline: both drain the identical
    blocking through the array numerics, but the scan executor re-quantizes
    every gathered chain product per block while the fused kernel stores
    the factors quantized once. The exact-arithmetic scan's time rides
    along in ``derived`` for context (the fused kernel beats even that:
    int8 gathers move a quarter of the bytes). Dense: the one-jit fused
    drive chain against the compiled schedule executor on the reference
    256x512 @ 512x128 matmul. Both rows carry the speedup in ``derived``;
    the acceptance bar is 1.3x.
    """
    if not selected("pallas"):
        return
    from repro import backends
    from repro.kernels.ops import fused_stream_mttkrp_op
    from repro.sparse import csf_for_mode, powerlaw_coo, stream_mttkrp

    cfg = PsramConfig()
    suffix = "_smoke" if smoke else ""
    shape = (400, 300, 200) if smoke else (2000, 1500, 1200)
    size = shape[0] * shape[1] * shape[2]
    rank = 32
    dens = 1e-3
    nnz = max(1000, int(size * dens))
    coo = powerlaw_coo(jax.random.PRNGKey(0), shape, nnz=nnz,
                       rank=8, alpha=1.1)
    csf = csf_for_mode(coo, 0)
    fs = tuple(
        jax.random.normal(jax.random.PRNGKey(d + 1), (s, rank))
        for d, s in enumerate(shape)
    )
    s = csf.to_coo()
    exact = mttkrp_sparse(s.indices, s.values, fs, 0, shape[0])

    # timed back-to-back, not interleaved: the psram scan's per-block
    # requantization churns ~2.5s of (E,rows,R) intermediates per call and
    # would hand every follow-up executor a cold LLC
    f_scan = lambda: stream_mttkrp(csf, fs, cfg, psram=True,
                                   adc_bits=cfg.adc.bits, compiled=True)
    us_scan = _time(f_scan, n=3, warmup=1)
    f_scan_exact = lambda: stream_mttkrp(csf, fs, cfg, compiled=True)
    us_scan_exact = _time(f_scan_exact, n=3, warmup=1)
    f_fused = lambda: fused_stream_mttkrp_op(csf, fs, cfg,
                                             adc_bits=cfg.adc.bits)
    us_fused = _time(f_fused, n=3, warmup=1)
    got = f_fused()
    rel = float(jnp.linalg.norm(got - exact) / jnp.linalg.norm(exact))
    row(f"pallas_fused_stream_d{dens:g}_nnz{coo.nnz}{suffix}", us_fused,
        f"rel_vs_exact={rel:.1e} speedup_vs_scan={us_scan/us_fused:.2f}x "
        f"(psram_scan={us_scan:.0f}us exact_scan={us_scan_exact:.0f}us "
        f"speedup_vs_exact_scan={us_scan_exact/us_fused:.2f}x)", "pallas")
    # tuned variant: the autotuner sweeps exec-block candidates in-process
    # and caches the winner per (shape, nnz-profile, config) key
    f_tuned = lambda: fused_stream_mttkrp_op(csf, fs, cfg,
                                             adc_bits=cfg.adc.bits,
                                             autotune=True)
    f_tuned()  # first call pays the sweep; steady-state is what we time
    us_tuned = _time(f_tuned, n=3, warmup=1)
    from repro.kernels.autotune import cache_stats
    row(f"pallas_fused_stream_tuned_d{dens:g}_nnz{coo.nnz}{suffix}",
        us_tuned,
        f"speedup_vs_scan={us_scan/us_tuned:.2f}x "
        f"speedup_vs_exact_scan={us_scan_exact/us_tuned:.2f}x "
        f"winners={cache_stats()[0]}", "pallas")

    # dense: fused bit-plane matmul (xla lowering) vs compiled scheduled
    # executor on the reference shape
    from repro.core.schedule import build_matmul_program, execute

    m, k, n = (64, 128, 32) if smoke else (256, 512, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    prog = build_matmul_program(m, k, n, cfg)
    f_sched = lambda: execute(prog, x, w, compiled=True)
    f_mm = lambda: psram_matmul_op(x, w, adc_bits=cfg.adc.bits,
                                   backend="xla")
    us_sched, us_mm = _time_interleaved((f_sched, f_mm), n=9, warmup=1)
    exact_mm = x @ w
    got_mm = f_mm()
    rel_mm = float(jnp.linalg.norm(got_mm - exact_mm)
                   / jnp.linalg.norm(exact_mm))
    row(f"pallas_fused_matmul_{m}x{k}x{n}{suffix}", us_mm,
        f"rel_vs_exact={rel_mm:.1e} speedup_vs_scheduled="
        f"{us_sched/us_mm:.2f}x (scheduled={us_sched:.0f}us)", "pallas")

    # dense MTTKRP: the quantized-KR fused kernel (xla lowering) vs the
    # exact einsum — rel documents the 8-bit+ADC envelope on this shape
    from repro.kernels.ops import mttkrp_psram_op

    i, j, kk = (64, 32, 48) if smoke else (256, 64, 128)
    xt = jax.random.normal(jax.random.PRNGKey(0), (i, j, kk))
    b = jax.random.normal(jax.random.PRNGKey(1), (j, rank))
    c = jax.random.normal(jax.random.PRNGKey(2), (kk, rank))
    f_dm = lambda: mttkrp_psram_op(xt, b, c, backend="xla",
                                   adc_bits=cfg.adc.bits)
    us_dm = _time(f_dm, n=5, warmup=1)
    want_dm = mttkrp_dense(xt, [jnp.zeros((i, rank)), b, c], 0)
    rel_dm = float(jnp.linalg.norm(f_dm() - want_dm)
                   / jnp.linalg.norm(want_dm))
    row(f"pallas_fused_mttkrp_dense_{i}x{j}x{kk}{suffix}", us_dm,
        f"rel_vs_exact={rel_dm:.1e}", "pallas")


# ------------------------------------------ backend matrix (registry tour)
def bench_backend_matrix(smoke: bool = False):
    """One MTTKRP across every registered backend via repro.api: wall-clock,
    relative error vs "exact", and the backend's own utilization estimate —
    the machine-readable version of examples/backend_tour.py. Scoped by
    --backend."""
    from repro import api, backends

    shape, rank = ((24, 20, 16) if smoke else (48, 40, 32)), 8
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    fs = tuple(
        jax.random.normal(jax.random.PRNGKey(d + 1), (s, rank))
        for d, s in enumerate(shape)
    )
    want = api.mttkrp(x, fs, 0, backend="exact")
    wl = MTTKRPWorkload(i=shape[0], j=shape[1], k=shape[2], rank=rank)
    suffix = "_smoke" if smoke else ""   # smoke sizes get their own names so
                                         # the CI regression check compares
                                         # like against like
    for name in backends.list_backends():
        if not selected(name):
            continue
        be = backends.get(name)
        caps = be.capabilities()
        if caps.executes:
            n = 1 if name == "psram-oracle" else 3  # the loop oracle is slow
            us = _time(lambda: be.mttkrp(x, fs, 0), n=n, warmup=1)
            got = be.mttkrp(x, fs, 0)
            rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            derived = f"rel_err={rel:.4f} (tol {caps.rel_tol:g})"
        else:
            us = _model_time(lambda: api.estimate(wl, backend=be), n=3)
            derived = "cost-only"
        if caps.cost_model:
            try:
                est = api.estimate(wl, backend=be)
                derived += f" est_util={est.utilization:.4f}"
            except backends.CapabilityError:
                pass  # e.g. psram-stream prices sparse distributions only
        row(f"backend_matrix_{name}{suffix}", us, derived, name)


# --------------------------------------------- multi-array engine scaling
def bench_mesh(smoke: bool = False):
    """Mesh-sharded streaming MTTKRP (repro.sparse.mesh) — the fused stream
    scaled past one pSRAM array.

    Two row families per array count:

    * ``mesh_price_a{A}`` — the modeled mesh bill: per-array makespan from
      the makespan planner + the fabric all-reduce, with the analytical
      closed form asserted equal to the counted schedule (the
      estimate==measured contract at mesh scale).
    * ``mesh_stream_a{A}`` — wall-clock of the sharded executor under
      ``shard_map`` on this host's devices (run CI under
      ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get all
      four points). On a single-core container the extra devices
      timeshare one CPU, so wall-clock does NOT drop with A — the modeled
      makespan in ``derived`` carries the architecture's scaling while
      ``us_per_call`` stays an honest measurement of this box.
    """
    from repro.core.perf_model import MeshSparseMTTKRPWorkload, mesh_sparse_price
    from repro.sparse import (
        csf_for_mode, mesh_counted_price, mesh_stream_mttkrp, powerlaw_coo,
    )

    if not selected("psram-mesh"):
        return
    cfg = PsramConfig()
    shape = (400, 300, 200) if smoke else (2000, 1500, 1200)
    rank = 32
    nnz = max(1000, int(shape[0] * shape[1] * shape[2] * 1e-3))
    coo = powerlaw_coo(jax.random.PRNGKey(0), shape, nnz=nnz, rank=8,
                       alpha=1.1)
    csf = csf_for_mode(coo, 0)
    fs = tuple(jax.random.normal(jax.random.PRNGKey(d + 1), (s, rank))
               for d, s in enumerate(shape))
    fibers = csf.fiber_lengths()
    n_dev = len(jax.devices())
    base_cycles = base_us = None
    for a in (1, 2, 4, 8):
        # the modeled bill — device-count independent, always emitted
        price, _ = mesh_counted_price(fibers, rank, cfg, n_arrays=a)
        ana = mesh_sparse_price(cfg, MeshSparseMTTKRPWorkload(
            fiber_lengths=fibers, rank=rank, n_arrays=a))
        exact = (ana.counts == price.counts
                 and ana.total_cycles == price.total_cycles)
        if base_cycles is None:
            base_cycles = price.total_cycles
        if obs.enabled() and a == 4:
            # one mesh timeline in the trace: per-array shard tracks plus
            # the fabric all-reduce, at the 4-array §V-B operating point
            obs.get_tracer().add_events(obs.mesh_timeline(
                fibers, rank, config=cfg, n_arrays=a))
        row(f"mesh_price_a{a}_nnz{coo.nnz}",
            _model_time(lambda: mesh_counted_price(
                fibers, rank, cfg, n_arrays=a), n=3),
            f"makespan={price.makespan_cycles} reduce={price.reduce_cycles} "
            f"model_time_s={price.duration_s(cfg):.3e} "
            f"model_speedup={base_cycles / price.total_cycles:.2f}x "
            f"analytical_exact={exact}", "psram-mesh")
        # the measured executor — only where the host actually has A devices
        if a > n_dev:
            continue
        fn = lambda: mesh_stream_mttkrp(csf, fs, cfg, n_arrays=a,
                                        lowering="fused")
        us = _time(fn, n=3, warmup=1)
        if base_us is None:
            base_us = us
        # device_get: outputs are committed to their mesh's device set, so
        # a=2 and a=1 results can't meet in one jitted subtract
        ref = jax.device_get(mesh_stream_mttkrp(csf, fs, cfg, n_arrays=1,
                                                lowering="eager"))
        got = jax.device_get(fn())
        rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        row(f"mesh_stream_a{a}_nnz{coo.nnz}", us,
            f"rel_vs_eager={rel:.1e} wall_speedup={base_us / us:.2f}x "
            f"devices={n_dev}", "psram-mesh")


# ------------------------------------------------------- live serving loop
def bench_serve(smoke: bool = False):
    """The live serving loop (repro.serve.loop) under synthetic traffic:
    per-request p50/p99 latency, TTFT, and sustained throughput, with the
    offload scheduler's modeled per-batch makespan recorded next to the
    measured decode-step wall time.

    Rows are tagged ``backend="serve"`` and are **presence-gated, not
    ratio-gated**: `check_regression.py --require-prefixes serve_` fails if
    they disappear, while the ratio gate's --backends list excludes
    ``serve`` because queueing latency is wall-clock noisy. Each row's
    ``meta`` pins the full traffic config (seed included) and the
    arrival-rate sweep, so a regression replays from the row alone."""
    import numpy as np

    from repro.models.registry import get_config, get_module
    from repro.serve import ServeLoop, ServeLoopConfig, TrafficConfig

    if not selected("serve"):
        return
    arch = get_config("granite_8b").reduced()
    params = get_module(arch).init(jax.random.PRNGKey(0), arch)
    lc = ServeLoopConfig(max_batch=4, num_pages=24, page_size=8,
                         speedup=200.0)
    # one loop reused across streams: the KV pool drains to zero between
    # runs (asserted) and reuse keeps the jit caches warm
    loop = ServeLoop(arch, params, lc)
    # compile every prefill pad and decode view bucket up front so the
    # first measured row isn't jit-compile-dominated
    loop.warmup(max_prompt=24, max_decode=12)
    suffix = "_smoke" if smoke else ""
    n_req = 24 if smoke else 120
    rates = (60.0,) if smoke else (40.0, 120.0)
    arrivals = ("poisson",) if smoke else ("poisson", "bursty")
    for arrival in arrivals:
        for rate in rates:
            tc = TrafficConfig(
                n_requests=n_req, seed=0, arrival=arrival, rate_rps=rate,
                prompt_min=2, prompt_max=24, decode_min=2, decode_max=12,
                vocab_size=arch.vocab_size)
            rep = loop.run_sync(tc)
            s = rep.summary()
            assert s["leaked_pages"] == 0, "serve loop leaked KV pages"
            # modeled-vs-measured per batch size (the offload decision trail,
            # aggregated so the row stays readable)
            by_batch: dict[int, dict] = {}
            for o in rep.offload:
                d = by_batch.setdefault(o["batch"], {
                    "batch": o["batch"], "modeled_s": o["modeled_s"],
                    "makespan_cycles": o["makespan_cycles"],
                    "n_arrays": o["n_arrays"], "measured_s": []})
                d["measured_s"].append(o["measured_s"])
            per_batch = [
                {**{k: v for k, v in d.items() if k != "measured_s"},
                 "mean_measured_s": float(np.mean(d["measured_s"])),
                 "steps": len(d["measured_s"])}
                for _, d in sorted(by_batch.items())
            ]
            meta = {
                "traffic": tc.asdict(),
                "arrival_rate_sweep_rps": list(rates),
                "loop": {"max_batch": lc.max_batch,
                         "num_pages": lc.num_pages,
                         "page_size": lc.page_size,
                         "speedup": lc.speedup},
                "per_batch_offload": per_batch,
            }
            row(f"serve_{arrival}_r{int(rate)}{suffix}",
                s["p50_latency_s"] * 1e6,
                f"p99={s['p99_latency_s']*1e3:.1f}ms "
                f"ttft_p50={s['p50_ttft_s']*1e3:.1f}ms "
                f"ttft_p99={s['p99_ttft_s']*1e3:.1f}ms "
                f"tput={s['throughput_rps']:.1f}req/s "
                f"{s['throughput_tok_s']:.0f}tok/s "
                f"completed={s['completed']} preempt={s['preemptions']} "
                f"offload={s['offload_fraction']:.2f} "
                f"step_model={s['mean_modeled_step_s']*1e9:.1f}ns "
                f"step_meas={s['mean_measured_step_s']*1e6:.0f}us",
                "serve", meta=meta)


def bench_faults(smoke: bool = False):
    """Fault tolerance (repro.faults): disabled-hook overhead, ABFT
    detection overhead, recovery latency, and degraded-mode throughput.

    Rows are tagged ``backend="fault"`` and are **presence-gated, not
    ratio-gated** (same contract as ``serve_``): `check_regression.py
    --require-prefixes fault_` fails CI if they disappear, while the ratio
    gate's --backends list excludes ``fault`` because recovery wall time is
    retry-count-shaped, not throughput-shaped.
    """
    import numpy as np

    from repro import faults
    from repro.faults import plan as plan_mod
    from repro.core.schedule import build_matmul_program, count_cycles, execute
    from repro.configs.psram_mttkrp import CONFIG
    from repro.sparse.formats import COO, csf_for_mode
    from repro.sparse.mesh import mesh_stream_mttkrp

    if not selected("fault"):
        return
    suffix = "_smoke" if smoke else ""
    cfg = CONFIG.array
    rng = np.random.default_rng(0)
    m, k, n = (8, 64, 96) if smoke else (16, 256, 256)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    prog = build_matmul_program(m, k, n, cfg)
    clean = np.asarray(execute(prog, x, w))

    # -- fault_overhead: the hooks cost one module-global read when no plan
    # is armed; measure that read against the executor call it guards
    us_exec = _time(execute, prog, x, w)
    reads = 10 ** 6
    t0 = time.perf_counter()
    for _ in range(reads):
        if plan_mod._ACTIVE is not None:
            raise AssertionError
    hook_ns = (time.perf_counter() - t0) / reads * 3 * 1e9  # 3 reads/execute
    a_us, b_us = _time_interleaved(
        [lambda: execute(prog, x, w), lambda: execute(prog, x, w)])
    row(f"fault_overhead{suffix}", us_exec,
        f"hook={hook_ns:.0f}ns/call frac={hook_ns / (us_exec * 1e3):.1e} "
        f"ab_noise={a_us / b_us:.3f}x armed=False", "fault")

    # -- fault_detect: ABFT checksum drive on a clean run (no faults):
    # zero detections, overhead = checksum cycles / program cycles
    us_abft = _time(lambda: faults.abft_matmul(x, w, cfg), n=3, warmup=1)
    y, rep = faults.abft_matmul(x, w, cfg)
    prog_cycles = count_cycles(prog).total_cycles
    row(f"fault_detect{suffix}", us_abft,
        f"detected={len(rep.detected)} checked={rep.checked} "
        f"cycle_overhead={rep.checksum_cycles / prog_cycles:.3f} "
        f"wall_overhead={us_abft / us_exec:.2f}x rel_tol={rep.rel_tol}",
        "fault")

    # -- fault_recover: persistent stuck-MSB faults — detect, retries
    # exhaust, fault-suppressed fallback; corrected output matches clean
    plan = faults.FaultPlan(seed=7, stuck_bits=(faults.StuckBit(rate=5e-3),))

    def recover():
        with faults.inject(plan):
            return faults.abft_matmul(x, w, cfg)

    us_rec = _time(recover, n=3, warmup=1)
    y2, rep2 = recover()
    err = float(np.max(np.abs(np.asarray(y2) - clean))
                / max(np.max(np.abs(clean)), 1e-9))
    row(f"fault_recover{suffix}", us_rec,
        f"detected={len(rep2.detected)} retries={rep2.retries} "
        f"fallbacks={rep2.fallbacks} "
        f"recovery_cycles={rep2.recovery_cycles} "
        f"recovery_s={rep2.recovery_s(cfg):.2e} rel_err={err:.1e}", "fault",
        meta={"seed": plan.seed, "stuck_rate": 5e-3, "shape": [m, k, n]})

    # -- fault_degraded: one of 4 arrays dead mid-MTTKRP — recover the lost
    # fiber ranges on survivors (bit-identical) and re-plan; throughput_frac
    # is the honest capacity hit the serve scheduler consumes
    shape = (64, 48, 40) if smoke else (256, 192, 160)
    nnz = 2000 if smoke else 20000
    idx = np.stack([rng.integers(0, s, nnz) for s in shape], 1)
    coo = COO(indices=jnp.asarray(idx.astype(np.int32)),
              values=jnp.asarray(rng.normal(size=nnz).astype(np.float32)),
              shape=shape)
    factors = tuple(jnp.asarray(rng.normal(size=(s, 32)).astype(np.float32))
                    for s in shape)
    csf = csf_for_mode(coo, 0)
    loss = faults.FaultPlan(seed=0, array_loss=(faults.ArrayLoss(2),))

    def degraded():
        with faults.inject(loss):
            return faults.degraded_mesh_mttkrp(csf, factors, config=cfg,
                                               n_arrays=4)

    us_deg = _time(degraded, n=3, warmup=1)
    yd, drep = degraded()
    ref = np.asarray(mesh_stream_mttkrp(csf, factors, cfg, n_arrays=1))
    bitident = bool((np.asarray(yd) == ref).all())
    row(f"fault_degraded{suffix}", us_deg,
        f"dead={len(drep.dead)}/{drep.n_arrays} "
        f"throughput_frac={drep.throughput_frac:.2f} "
        f"recovered_rows={drep.recovered_rows} "
        f"recovery_cycles={drep.recovery_cycles} bitident={bitident}",
        "fault", meta={"nnz": nnz, "shape": list(shape), "rank": 32})
    assert bitident, "degraded recovery drifted from the survivors-only plan"


def bench_scaling():
    """Beyond-paper: the 'scalable engine' (paper SIII) quantified — arrays
    scale linearly until the engine fabric saturates at the knee."""
    from repro.core.scaling import knee, sweep
    counts = (1, 4, 16, 64, 256)
    us = _model_time(sweep, counts) / len(counts)
    for p in sweep(counts=counts):
        row(f"scaling_{p.arrays}_arrays", us,
            f"{p.delivered_petaops:.1f} PetaOps eff={p.efficiency:.2f}")
    row("scaling_knee_default_fabric", _model_time(knee, n=3),
        f"{knee()} arrays")


def main(argv=None) -> None:
    from repro import backends
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as JSON (e.g. BENCH_psram.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: modeled rows + a reduced sparse sweep, "
                         "skip the slow wall-clock benches")
    ap.add_argument("--backend", action="append", default=None,
                    metavar="NAME", choices=backends.list_backends(),
                    help="scope the run to benches exercising this backend "
                         "(repeatable; default: all registered)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable tracing for the whole run and write a "
                         "Chrome trace_event JSON (open in Perfetto): "
                         "wall-clock spans plus cycle-domain schedule-IR "
                         "and mesh shard timelines")
    args = ap.parse_args(argv)
    enable_compile_cache()
    global SELECTED
    SELECTED = set(args.backend) if args.backend else None
    if args.trace:
        obs.enable()
    print("name,us_per_call,derived,backend")
    bench_fig5_channels()
    bench_fig5_frequency()
    bench_headline()
    if not args.smoke:
        if selected("exact", "pallas", "analytical"):
            bench_mttkrp_paths()
        if selected("pallas"):
            bench_psram_matmul()
        if selected("psram-scheduled", "psram-oracle"):
            bench_schedule_executor()
        if selected("exact", "psram-oracle"):
            bench_cp_als()
    bench_energy()
    if selected("psram-stream", "analytical"):
        bench_sparse_mttkrp(smoke=args.smoke)
    bench_pallas_fused(smoke=args.smoke)
    bench_backend_matrix(smoke=args.smoke)
    bench_mesh(smoke=args.smoke)
    bench_serve(smoke=args.smoke)
    bench_faults(smoke=args.smoke)
    bench_scaling()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(ROWS, f, indent=2)
        print(f"# wrote {len(ROWS)} rows to {args.json}")
    if args.trace:
        n = obs.write_trace(args.trace)
        print(f"# wrote {n} trace events to {args.trace}")


if __name__ == "__main__":
    main()
