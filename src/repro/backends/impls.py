"""First-class backends wrapping every existing execution path.

=================  =========================================================
name               wraps
=================  =========================================================
``exact``          float einsum / COO segment-sum — the parity baseline
``psram-oracle``   per-cycle :class:`PsramArray` physics (matmul) and the
                   flat quantized CP chain (sparse MTTKRP) — slow, faithful
``psram-scheduled``the tile-schedule IR: vectorized executor for matmuls
                   and the §IV dense mapping (matricized MTTKRP as an array
                   matmul); counted-cycle cost model
``psram-stream``   the nonzero-streaming sparse schedule (repro.sparse):
                   quantized chain + gather-mask drains; fiber-distribution
                   cost model
``pallas``         the Pallas TPU kernels (interpret mode on CPU): bit-plane
                   matmul, fused dense MTTKRP, blocked segment-sum stream
``psram-mesh``     many arrays: the streaming schedule SPMD over a 1-D
                   device mesh (repro.sparse.mesh) — planned shards under
                   shard_map, psum as the electrical reduction fabric
``analytical``     the closed-form §V model — cost-only, never executes
=================  =========================================================

Numeric contracts the parity suite (tests/test_backends.py) enforces:
``psram-oracle`` and ``psram-scheduled`` matmuls are *bit-identical* (PR 2);
``psram-stream`` equals ``mttkrp_sparse_psram`` on the sorted stream (PR 3);
every lossy backend lands within its documented ``rel_tol`` of ``exact``;
and ``analytical``'s §V-A dense breakdown equals ``psram-scheduled``'s
counted cycles exactly.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from .base import Backend, Capabilities, CapabilityError, Estimate, register
from .workload import (
    MatmulWorkload,
    NormalizedMTTKRP,
    describe,
    mode_csf,
    normalize_mttkrp_data,
    to_coo_triple,
)


def _program_estimate(name, cfg, program, workload) -> Estimate:
    """Estimate from a schedule (the counted-cycle pricing every scheduled
    backend shares)."""
    from repro.core.perf_model import breakdown_from_counts
    from repro.core.schedule import count_cycles, program_energy

    counts = count_cycles(program)
    return Estimate(
        backend=name,
        config=cfg,
        workload=workload,
        breakdown=breakdown_from_counts(cfg, counts),
        time_s=counts.duration_s(cfg),
        counts=counts,
        energy=program_energy(program),
    )


def _matmul_program(cfg, wl: MatmulWorkload):
    from repro.core.schedule import build_matmul_program

    prog = build_matmul_program(wl.m, wl.k, wl.n, cfg)
    if wl.repeats != 1:
        prog = dataclasses.replace(prog, repeats=wl.repeats)
    return prog


class _SchedulePricing:
    """cost() shared by the two dense schedule backends: the canonical §IV/§V
    programs, counted."""

    def cost(self, workload) -> Estimate:
        from repro.core.perf_model import MTTKRPWorkload
        from repro.core.schedule import build_mttkrp_program

        workload = describe(workload)
        if isinstance(workload, MatmulWorkload):
            return _program_estimate(
                self.name, self.config, _matmul_program(self.config, workload),
                workload)
        if isinstance(workload, MTTKRPWorkload):
            return _program_estimate(
                self.name, self.config,
                build_mttkrp_program(self.config, workload), workload)
        raise CapabilityError(
            f"backend {self.name!r} prices dense schedules; use "
            "'psram-stream' or 'analytical' for sparse workloads"
        )


@register("exact")
class ExactBackend(Backend):
    """Float reference numerics — the baseline every backend is compared to."""

    def capabilities(self) -> Capabilities:
        return Capabilities(
            executes=True, cost_model=False, matmul=True,
            description="exact float einsum / COO segment-sum",
        )

    def matmul(self, x, w):
        return jnp.asarray(x, jnp.float32) @ jnp.asarray(w, jnp.float32)

    def mttkrp(self, data, factors, mode: int):
        from repro.core.mttkrp import mttkrp_dense, mttkrp_sparse

        norm = normalize_mttkrp_data(data)
        if norm.kind == "dense":
            return mttkrp_dense(norm.dense, list(factors), mode)
        idx, vals, shape = to_coo_triple(norm)
        return mttkrp_sparse(idx, vals, tuple(factors), mode, shape[mode])


@register("psram-oracle")
class PsramOracleBackend(Backend):
    """The array physics, op by op: ``execute_reference`` for matmuls, the
    flat quantized CP chain (``mttkrp_sparse_psram``) for MTTKRP — the
    slowest and most transparently faithful substrate."""

    def capabilities(self) -> Capabilities:
        return Capabilities(
            executes=True, cost_model=True, matmul=True, lossy=True,
            rel_tol=0.05, prices=("dense", "matmul"),
            description="per-cycle PsramArray interpreter / quantized chain",
        )

    def matmul(self, x, w):
        from repro.core.schedule import build_matmul_program, execute_reference

        m, k = x.shape
        n = w.shape[1]
        return execute_reference(build_matmul_program(m, k, n, self.config), x, w)

    def mttkrp(self, data, factors, mode: int):
        from repro.core.mttkrp import mttkrp_sparse_psram

        idx, vals, shape = to_coo_triple(normalize_mttkrp_data(data))
        return mttkrp_sparse_psram(
            idx, vals, tuple(factors), mode, shape[mode],
            adc_bits=self.config.adc.bits,
        )

    cost = _SchedulePricing.cost


@register("psram-scheduled")
class PsramScheduledBackend(Backend):
    """The tile-schedule IR's vectorized executor (§IV dense mapping).

    MTTKRP runs as the matricized matmul ``X_(n) @ KhatriRao(others)``
    through the array — weights stationary, inputs WDM-batched — which is
    bit-identical to the per-cycle oracle on the same program (PR 2) and
    lands within the ADC envelope of ``exact``.

    ``compiled=True`` opts into the cached jit-compiled executor
    (``schedule.compiled_matmul_executor``): several times faster on
    repeated same-shape calls, within a ~1e-7 envelope of the eager
    bit-identity oracle (whole-program fusion drifts the dequant chain by
    ~1 ulp — ``bit_exact`` drops accordingly).
    """

    def __init__(self, config=None, compiled: bool = False):
        super().__init__(config)
        self.compiled = bool(compiled)

    def capabilities(self) -> Capabilities:
        return Capabilities(
            executes=True, cost_model=True, matmul=True, sparse=False,
            lossy=True, rel_tol=0.05, prices=("dense", "matmul"),
            bit_exact=not self.compiled, compiled=self.compiled,
            description="vectorized tile-schedule executor (dense mapping)"
                        + (" [compiled]" if self.compiled else ""),
        )

    def matmul(self, x, w):
        from repro.core.schedule import build_matmul_program, execute

        m, k = x.shape
        n = w.shape[1]
        return execute(build_matmul_program(m, k, n, self.config), x, w,
                       compiled=self.compiled)

    def mttkrp(self, data, factors, mode: int):
        from repro.core.mttkrp import khatri_rao, matricize

        norm = normalize_mttkrp_data(data)
        self._require("sparse MTTKRP (use 'psram-stream')",
                      norm.kind == "dense")
        others = [factors[d] for d in range(norm.dense.ndim) if d != mode]
        return self.matmul(matricize(norm.dense, mode), khatri_rao(others))

    cost = _SchedulePricing.cost


@register("psram-stream")
class PsramStreamBackend(Backend):
    """The nonzero-streaming sparse schedule (repro.sparse.stream): blocks
    of quantized CP2 chain rows stored down the word-lines, per-output-row
    gather masks driven per WDM channel, electrical cross-block carry.
    Dense data is accepted by COO-ifying (all entries stream as nonzeros).

    ``compiled=True`` opts into the blocked-segment-fold executor
    (gather-mask contractions, scan carry): ~10x+ faster on paper-scale
    streams, bit-identical to its flat reference
    (``core.mttkrp.mttkrp_sparse_blocked`` with ``psram=True``) but a
    reassociated fold vs. the eager per-nonzero oracle — ``bit_exact``
    drops, the quantization envelope (``rel_tol``) is unchanged."""

    def __init__(self, config=None, compiled: bool = False):
        super().__init__(config)
        self.compiled = bool(compiled)

    def capabilities(self) -> Capabilities:
        return Capabilities(
            executes=True, cost_model=True, matmul=False, lossy=True,
            rel_tol=0.05, prices=("sparse",), prefers_csf=True,
            bit_exact=not self.compiled, compiled=self.compiled,
            description="nonzero-streaming sparse schedule (quantized chain)"
                        + (" [compiled]" if self.compiled else ""),
        )

    def mttkrp(self, data, factors, mode: int):
        from repro.sparse.stream import stream_mttkrp

        csf = mode_csf(normalize_mttkrp_data(data), mode)
        return stream_mttkrp(
            csf, tuple(factors), self.config,
            psram=True, adc_bits=self.config.adc.bits,
            compiled=self.compiled,
        )

    def cost(self, workload) -> Estimate:
        from repro.core.perf_model import SparseMTTKRPWorkload
        from repro.sparse.stream import build_stream_program

        workload = describe(workload)
        if not isinstance(workload, SparseMTTKRPWorkload):
            raise CapabilityError(
                "backend 'psram-stream' prices fiber-length distributions "
                "(SparseMTTKRPWorkload); use 'psram-scheduled' or "
                "'analytical' for dense descriptors"
            )
        prog = build_stream_program(
            workload.fiber_lengths, workload.rank, self.config)
        return _program_estimate(self.name, self.config, prog, workload)


@register("pallas")
class PallasBackend(Backend):
    """The fused Pallas kernel family (one kernel body per op, lowered to
    real Pallas on TPU, a fused XLA twin off-TPU, interpret mode for
    validation): bit-plane pSRAM matmul, quantized matricized-KR dense
    MTTKRP, and the fused streaming sparse MTTKRP (chain + gather-mask
    contraction + ADC epilogue + cross-block carry in one kernel).

    The default ``compiled=True`` runs that family — the speed-champion
    configuration the BENCH trajectory tracks. ``compiled=False`` keeps the
    legacy per-op path (exact-chain blocked segment-sum stream, exact dense
    kernel). ``autotune=True`` lets ``kernels.autotune`` sweep and cache
    chunk/tile shapes per ``(shape, nnz-profile, PsramConfig)``; off, the
    deterministic heuristic is used, so untuned runs never regress.

    Lowering (env/platform probe included) resolves ONCE at construction;
    every call dispatches on the stored resolved strings, one per op
    (:attr:`lowerings`). The fused streaming op resolves ``"auto"`` to its
    ``"xla"`` lowering on TPU too (``lowering.resolve_stream_lowering``
    says why). The fused paths
    reassociate float adds vs their oracles (``bit_exact=False``) and stay
    within the documented ADC envelope (``rel_tol=0.05``) vs ``exact``.
    """

    def __init__(self, config=None, lowering: str = "auto",
                 compiled: bool = True, autotune: bool = False):
        super().__init__(config)
        from .lowering import (
            resolve_exec_lowering, resolve_lowering, resolve_stream_lowering)

        self.compiled = bool(compiled)
        self.autotune = bool(autotune)
        self.lowering = (resolve_exec_lowering(lowering) if self.compiled
                         else resolve_lowering(lowering))
        self.stream_lowering = resolve_stream_lowering(lowering)

    @property
    def lowerings(self) -> dict:
        """The resolved lowering of each kernel op this backend reaches."""
        if self.compiled:
            return {"psram_matmul": self.lowering,
                    "mttkrp_psram": self.lowering,
                    "stream_mttkrp": self.stream_lowering}
        return {"psram_matmul": self.lowering, "mttkrp": self.lowering,
                "blocked_segment_sum": self.lowering}

    def capabilities(self) -> Capabilities:
        return Capabilities(
            executes=True, cost_model=False, matmul=True, lossy=True,
            bit_exact=False, rel_tol=0.05, prefers_csf=True,
            compiled=self.compiled, autotune=self.autotune,
            description="fused Pallas kernel family (bit-plane matmul, "
                        "quantized KR dense, fused streaming sparse)"
                        + ("" if self.compiled else " [legacy per-op]"),
        )

    def matmul(self, x, w):
        from repro.kernels.ops import psram_matmul_op

        return psram_matmul_op(
            x, w, adc_bits=self.config.adc.bits, backend=self.lowering)

    def mttkrp(self, data, factors, mode: int):
        norm = normalize_mttkrp_data(data)
        if norm.kind == "dense":
            from repro.kernels.ops import mttkrp_op, mttkrp_psram_op

            self._require("N-mode dense MTTKRP (3-mode kernel)",
                          norm.dense.ndim == 3)
            others = [d for d in range(3) if d != mode]
            xt = jnp.transpose(norm.dense, [mode] + others)
            op = mttkrp_psram_op if self.compiled else mttkrp_op
            return op(xt, factors[others[0]], factors[others[1]],
                      backend=self.lowering)
        csf = mode_csf(norm, mode)
        if self.compiled:
            from repro.kernels.ops import fused_stream_mttkrp_op

            return fused_stream_mttkrp_op(
                csf, tuple(factors), self.config,
                adc_bits=self.config.adc.bits, backend=self.stream_lowering,
                autotune=self.autotune)
        from repro.sparse.stream import stream_mttkrp_blocked

        return stream_mttkrp_blocked(
            csf, tuple(factors), self.config, backend=self.lowering)


@register("psram-mesh")
class PsramMeshBackend(Backend):
    """The streaming sparse schedule scaled past one array: shards from the
    partition planner land on the ``"array"`` axis of a 1-D device mesh,
    every device drains its shard under ``shard_map``, and a ``psum`` —
    the electrical reduction fabric — adds the partial factor outputs
    (``repro.sparse.mesh``). Dense data is accepted by COO-ifying.

    ``n_arrays=None`` spans every local device (1 in plain CPU runs — the
    mesh then degenerates to exactly the single-device schedule; force more
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``). The
    planner never splits a root fiber, so the default eager lowering is
    *bit-identical* to ``"psram-stream"`` and independent of device count
    and shard order; ``compiled=True`` runs the blocked-segment fold per
    shard (reassociated, ``bit_exact`` drops); ``lowering="fused"`` runs
    the PR 6 int8 fused chunk body. ``cost()`` prices the planned split —
    per-array counted makespan plus the fabric all-reduce — with the same
    closed forms ``"analytical"`` uses, so estimate==measured stays exact
    at mesh scale.
    """

    def __init__(self, config=None, n_arrays: int | None = None,
                 compiled: bool = False, lowering: str | None = None,
                 planner: str = "makespan", fabric=None):
        super().__init__(config)
        from repro.sparse.mesh import MESH_LOWERINGS

        self.n_arrays = None if n_arrays is None else int(n_arrays)
        self.compiled = bool(compiled)
        self.lowering = lowering or ("compiled" if compiled else "eager")
        if self.lowering not in MESH_LOWERINGS:
            raise ValueError(
                f"unknown mesh lowering {self.lowering!r}; pick one of "
                f"{MESH_LOWERINGS}")
        self.compiled = self.lowering != "eager"
        self.planner = planner
        self.fabric = fabric

    def capabilities(self) -> Capabilities:
        return Capabilities(
            executes=True, cost_model=True, matmul=False, lossy=True,
            rel_tol=0.05, prices=("sparse",), prefers_csf=True,
            bit_exact=not self.compiled, compiled=self.compiled,
            description="mesh-sharded streaming schedule (shard_map + psum "
                        f"fabric, {self.lowering} fold)",
        )

    def mttkrp(self, data, factors, mode: int):
        from repro.sparse.mesh import mesh_stream_mttkrp

        csf = mode_csf(normalize_mttkrp_data(data), mode)
        return mesh_stream_mttkrp(
            csf, tuple(factors), self.config, n_arrays=self.n_arrays,
            psram=True, adc_bits=self.config.adc.bits,
            lowering=self.lowering, planner=self.planner,
        )

    def gram(self, f):
        """All-reduced Gram — partial ``(R, R)`` Grams of the row shards
        psum'd over the array axis (CP-ALS normal equations, SPMD)."""
        from repro.sparse.mesh import mesh_gram

        return mesh_gram(f, n_arrays=self.n_arrays)

    def cost(self, workload) -> Estimate:
        from repro.core.perf_model import (
            MeshSparseMTTKRPWorkload,
            SparseMTTKRPWorkload,
            breakdown_from_counts,
        )
        from repro.core.schedule import program_energy
        from repro.sparse.mesh import mesh_counted_price

        workload = describe(workload)
        if not isinstance(workload, SparseMTTKRPWorkload):
            raise CapabilityError(
                "backend 'psram-mesh' prices fiber-length distributions "
                "(SparseMTTKRPWorkload / MeshSparseMTTKRPWorkload); use "
                "'psram-scheduled' or 'analytical' for dense descriptors"
            )
        if isinstance(workload, MeshSparseMTTKRPWorkload):
            n = workload.n_arrays
            fabric = workload.fabric or self.fabric
            out_rows = workload.reduced_rows
        else:
            n = self.n_arrays or 1
            fabric = self.fabric
            out_rows = None
        price, ps = mesh_counted_price(
            workload.fiber_lengths, workload.rank, self.config,
            n_arrays=n, fabric=fabric, planner=self.planner,
            out_rows=out_rows)
        counts = price.counts
        energy = sum((program_energy(p) for p in ps.programs[1:]),
                     program_energy(ps.programs[0]))
        return Estimate(
            backend=self.name,
            config=self.config,
            workload=workload,
            breakdown=breakdown_from_counts(self.config, counts),
            time_s=price.duration_s(self.config),
            counts=counts,
            energy=energy,
        )


@register("analytical")
class AnalyticalBackend(Backend):
    """The closed-form §V predictive model — cost-only. Asking it to execute
    raises :class:`CapabilityError` (the registry's documented error path);
    its §V-A dense breakdown equals ``psram-scheduled``'s counted cycles
    exactly (the PR 2/3 invariant, asserted in tests/test_backends.py)."""

    def capabilities(self) -> Capabilities:
        return Capabilities(
            executes=False, cost_model=True, matmul=False,
            prices=("dense", "sparse", "matmul"),
            description="closed-form §V sustained-performance model",
        )

    def cost(self, workload) -> Estimate:
        from repro.core.perf_model import (
            MeshSparseMTTKRPWorkload,
            MTTKRPWorkload,
            breakdown_from_counts,
            mesh_sparse_price,
            mttkrp_energy,
            sustained_mttkrp,
        )

        workload = describe(workload)
        if isinstance(workload, MatmulWorkload):
            # the analytical model of one matmul IS its canonical schedule
            return _program_estimate(
                self.name, self.config, _matmul_program(self.config, workload),
                workload)
        if isinstance(workload, MeshSparseMTTKRPWorkload):
            # the mesh closed form: per-array makespan (the same stream
            # counts the counted schedule walks) + the fabric all-reduce —
            # matches "psram-mesh"'s counted price exactly
            price = mesh_sparse_price(self.config, workload)
            counts = price.counts
            return Estimate(
                backend=self.name,
                config=self.config,
                workload=workload,
                breakdown=breakdown_from_counts(self.config, counts),
                time_s=price.duration_s(self.config),
                counts=counts,
                energy=None,
            )
        sb = sustained_mttkrp(self.config, workload)
        rate = sb.sustained_petaops * 1e15
        return Estimate(
            backend=self.name,
            config=self.config,
            workload=workload,
            breakdown=sb,
            time_s=2.0 * workload.macs / rate if rate > 0 else float("inf"),
            counts=None,
            energy=mttkrp_energy(self.config, workload)
            if isinstance(workload, MTTKRPWorkload) else None,
        )
