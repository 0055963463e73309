"""Mesh-sharded execution of the streaming MTTKRP — many pSRAM arrays, SPMD.

Everything below the registry ran on ONE device through PR 6; this module is
the scale-out step (ROADMAP item 2, the paper's §V single-array headline →
the system-level many-array regime of arxiv 2602.00892): the blocked-COO
partitions of :mod:`repro.sparse.partition` land on the ``"array"`` axis of
a 1-D device mesh (:func:`repro.launch.mesh.make_array_mesh`), every device
streams its own shard of the sorted nonzero stream under ``shard_map``, and
one ``psum`` plays the electrical reduction fabric that adds the per-array
partial outputs.

Numeric contracts (tests/test_mesh.py):

* The partition planner never splits a root fiber across arrays, so every
  output row is computed *entirely* on one shard — the other shards
  contribute exact zeros to its ``psum``. With the **eager** lowering
  (per-nonzero fold, the order of ``jax.ops.segment_sum``) the mesh result
  is therefore *bit-identical* to the single-device stream
  (``stream_mttkrp`` / ``mttkrp_sparse_psram``) and independent of device
  count and shard order.
* The **compiled** lowering runs the blocked-segment fold per shard
  (reassociated adds, the PR 5 envelope); the **fused** lowering runs the
  PR 6 int8 fused chunk body with its chunk-local ADC epilogue — both stay
  within the documented ADC envelope (rel 0.05) of ``"exact"``.
* Empty shards (fibers < arrays) stream all-padding blocks that scatter
  into the sacrificial row — a zero-row partition never breaks the stacked
  layout, and its program prices zero cycles.

Pricing: :func:`mesh_counted_price` walks the per-array op lists
(``count_cycles``) and adds the fabric's all-reduce through the SAME
closed form (``perf_model.allreduce_cycles``) the analytical mesh price
uses — analytical == counted stays exact at mesh scale.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.backends.base import resolve_config
from repro.core.mttkrp import cp_chain_exact, cp_chain_psram
from repro.core.psram import PsramConfig
from repro.faults import plan as _faults

from .formats import CSF
from .partition import MeshedSparseTensor, partition_csf
from .stream import _exec_blocks, _mask_partials, stream_layout

MESH_LOWERINGS = ("eager", "compiled", "fused")


def resolve_array_mesh(mesh: Mesh | None = None,
                       n_arrays: int | None = None) -> Mesh:
    """The 1-D array mesh this run executes on: pass an existing mesh (its
    leading axis is the array axis) or an array count (``None`` = every
    local device)."""
    if mesh is None:
        from repro.launch.mesh import make_array_mesh

        return make_array_mesh(n_arrays)
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"mesh sparse execution needs a 1-D mesh (one axis of arrays); "
            f"got axes {mesh.axis_names}"
        )
    if n_arrays is not None and n_arrays != mesh.devices.size:
        raise ValueError(
            f"n_arrays={n_arrays} disagrees with the {mesh.devices.size}-"
            "device mesh; pass one or the other"
        )
    return mesh


def _mesh_partition(csf: CSF, n_arrays: int, rank: int, cfg: PsramConfig,
                    planner: str) -> MeshedSparseTensor:
    """The planned split of ``csf`` over ``n_arrays``, cached on the CSF
    (immutable; CP-ALS revisits the same tensor every sweep)."""
    key = ("_mesh_partition", n_arrays, rank, cfg, planner)
    cached = csf.__dict__.get(key)
    if cached is None:
        cached = partition_csf(csf, n_arrays=n_arrays, rank=rank, config=cfg,
                               planner=planner)
        csf.__dict__[key] = cached
    return cached


# ---------------------------------------------------------------------------
# stacked shard layouts — every shard padded to the global maxima so one
# SPMD program covers all of them (empty shards become all-padding stacks)
# ---------------------------------------------------------------------------


def _eager_shard_stack(meshed: MeshedSparseTensor, out_rows: int,
                       chunk: int):
    """Stacked eager operands ``(ip, rp, vp)`` with a leading array axis:
    ``ip (A, nb, chunk, nm)`` zero-padded coordinates (gather-safe),
    ``rp (A, nb, chunk)`` scatter rows (sacrificial ``out_rows`` padding),
    ``vp (A, nb, chunk)`` zero-padded values."""
    shards = meshed.shards
    nb = max(1, max(-(-s.nnz // chunk) for s in shards))
    total = nb * chunk
    ips, rps, vps = [], [], []
    for s in shards:
        idx = np.asarray(s.expanded_indices(), dtype=np.int64)
        vals = np.asarray(s.values, dtype=np.float32)
        nm = idx.shape[1] if idx.size else len(s.shape)
        pad = total - idx.shape[0]
        mode = s.mode_order[0]
        rp = np.pad(idx[:, mode] if idx.size else np.zeros(0, np.int64),
                    (0, pad), constant_values=out_rows)
        ip = np.pad(idx if idx.size else np.zeros((0, nm), np.int64),
                    ((0, pad), (0, 0)))
        vp = np.pad(vals, (0, pad))
        ips.append(ip.reshape(nb, chunk, nm))
        rps.append(rp.reshape(nb, chunk))
        vps.append(vp.reshape(nb, chunk))
    return (jnp.asarray(np.stack(ips)), jnp.asarray(np.stack(rps)),
            jnp.asarray(np.stack(vps)))


def _blocked_shard_stack(meshed: MeshedSparseTensor, out_rows: int,
                         rows: int, exec_blocks: int):
    """Stacked compiled layouts ``(ip, vp, lp, sp, n_seg)`` with a leading
    array axis, padded to the global chunk count and segment width.

    Reuses every shard's own cached ``stream_layout``; the extra padding
    blocks an uneven (or empty) shard needs carry zero values and scatter
    exclusively into the sacrificial row, so they change no result bit —
    this is where a zero-row partition would have broken a naive stacking.
    """
    per = [stream_layout(s, rows, exec_blocks) for s in meshed.shards]
    nb = max(p[0].shape[0] for p in per)
    n_seg = max(p[4] for p in per)
    ips, vps, lps, sps = [], [], [], []
    for (ip, vp, lp, sp, ns), shard in zip(per, meshed.shards):
        e = ip.shape[1]
        padb = nb - ip.shape[0]
        ips.append(np.pad(np.asarray(ip), ((0, padb),) + ((0, 0),) * 3))
        vps.append(np.pad(np.asarray(vp), ((0, padb), (0, 0), (0, 0))))
        lps.append(np.pad(np.asarray(lp), ((0, padb), (0, 0), (0, 0))))
        s3 = np.asarray(sp).reshape(ip.shape[0], e, ns)
        s3 = np.pad(s3, ((0, padb), (0, 0), (0, n_seg - ns)),
                    constant_values=out_rows)
        sps.append(s3.reshape(nb, e * n_seg).astype(np.int32))
    return (jnp.asarray(np.stack(ips)), jnp.asarray(np.stack(vps)),
            jnp.asarray(np.stack(lps)), jnp.asarray(np.stack(sps)), n_seg)


def _faulty_values(vp):
    """Per-shard fault hook (zero-cost when no plan is armed).

    Applies the armed :class:`~repro.faults.plan.FaultPlan`'s shard faults
    — dead arrays zero their stack slice, transient spikes hit surviving
    shards — to a *copy* of the stacked values; the layouts cached on the
    CSF are never written through, so disarming restores clean runs.
    """
    plan = _faults._ACTIVE
    if plan is None or not (plan.array_loss or plan.adc_spikes):
        return vp
    if obs.enabled() and plan.array_loss:
        obs.counter("fault/arrays_lost", len(plan.dead_arrays))
    with obs.span("fault/mesh/shard_values", arrays=int(vp.shape[0]),
                  dead=len(plan.dead_arrays)):
        return jnp.asarray(_faults.corrupt_shard_values(plan, vp))


def _mesh_layout(csf: CSF, meshed: MeshedSparseTensor, lowering: str,
                 rows: int, exec_blocks: int, mesh: Mesh):
    """Per-(CSF, partition, lowering, mesh) stacked operands, cached on the
    CSF and placed once: slice ``a`` of each stack's leading array axis
    lives on the mesh's device ``a``."""
    out_rows = csf.shape[csf.mode_order[0]]
    key = ("_mesh_layout", lowering, len(meshed.shards), rows, exec_blocks,
           meshed.partitions, mesh)
    cached = csf.__dict__.get(key)
    if cached is None:
        if lowering == "eager":
            stacks = _eager_shard_stack(meshed, out_rows, rows * exec_blocks)
        else:
            *stacks, n_seg = _blocked_shard_stack(meshed, out_rows, rows,
                                                  exec_blocks)
        by_array = NamedSharding(mesh, P(mesh.axis_names[0]))
        cached = tuple(jax.device_put(a, by_array) for a in stacks)
        if lowering != "eager":
            cached += (n_seg,)
        csf.__dict__[key] = cached
    return cached


# ---------------------------------------------------------------------------
# SPMD executors
# ---------------------------------------------------------------------------


@lru_cache(maxsize=128)
def _mesh_executor(mesh: Mesh, lowering: str, mode: int, out_rows: int,
                   n_seg: int, psram: bool, adc_bits: int):
    """One jitted shard_map program per static signature (PR 5 keying:
    equal-by-value keys return the identical callable). Each device drains
    its shard's chunk stack with the selected lowering's fold and the
    ``psum`` over the array axis adds the partial outputs — the electrical
    reduction fabric."""
    axis = mesh.axis_names[0]

    def chain(i_c, v_c, factors):
        if psram:
            return cp_chain_psram(i_c, v_c, factors, mode, adc_bits)
        return cp_chain_exact(i_c, v_c, factors, mode)

    if lowering == "eager":
        def device_fn(ip, rp, vp, factors):
            ip, rp, vp = ip[0], rp[0], vp[0]

            def step(out, blk):
                i_b, r_b, v_b = blk
                return out.at[r_b].add(chain(i_b, v_b, factors)), None

            rank = factors[0].shape[-1]
            out0 = jnp.zeros((out_rows + 1, rank), jnp.float32)
            out, _ = jax.lax.scan(step, out0, (ip, rp, vp))
            return jax.lax.psum(out[:out_rows], axis)

        in_specs = (P(axis), P(axis), P(axis), P())
    elif lowering == "compiled":
        def device_fn(ip, vp, lp, sp, factors):
            ip, vp, lp, sp = ip[0], vp[0], lp[0], sp[0]
            rank = factors[0].shape[-1]

            def step(out, blk):
                i_b, v_b, l_b, s_b = blk
                parts = _mask_partials(chain(i_b, v_b, factors), l_b, n_seg)
                return out.at[s_b].add(parts.reshape(-1, rank)), None

            out0 = jnp.zeros((out_rows + 1, rank), jnp.float32)
            out, _ = jax.lax.scan(step, out0, (ip, vp, lp, sp))
            return jax.lax.psum(out[:out_rows], axis)

        in_specs = (P(axis), P(axis), P(axis), P(axis), P())
    elif lowering == "fused":
        from repro.kernels.stream_mttkrp import _chunk_partials, _rank

        def device_fn(ip, vp, lp, sp, ps):
            ip, vp, lp, sp = ip[0], vp[0], lp[0], sp[0]
            rank = _rank(ps, mode)

            def step(out, blk):
                i_b, v_b, l_b, s_b = blk
                parts = _chunk_partials(i_b, v_b, l_b, ps, mode=mode,
                                        n_seg=n_seg, adc_bits=adc_bits)
                return out.at[s_b].add(parts.reshape(-1, rank)), None

            out0 = jnp.zeros((out_rows + 1, rank), jnp.float32)
            out, _ = jax.lax.scan(step, out0, (ip, vp, lp, sp))
            return jax.lax.psum(out[:out_rows], axis)

        in_specs = (P(axis), P(axis), P(axis), P(axis), P())
    else:
        raise ValueError(
            f"unknown mesh lowering {lowering!r}; pick one of {MESH_LOWERINGS}"
        )

    return jax.jit(jax.shard_map(device_fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(), check_vma=False))


def mesh_stream_mttkrp(
    csf: CSF,
    factors: tuple,
    config: PsramConfig | None = None,
    mesh: Mesh | None = None,
    n_arrays: int | None = None,
    psram: bool = True,
    adc_bits: int = 16,
    lowering: str = "eager",
    planner: str = "makespan",
    exec_blocks: int | None = None,
) -> jax.Array:
    """One sparse MTTKRP across the array mesh: ``(out_rows, R)``.

    ``csf``'s root mode is the target mode; ``factors`` are replicated on
    every device, each device streams its planned shard, and the partial
    outputs ``psum`` into the replicated result. ``lowering`` picks the
    per-shard fold: ``"eager"`` (bit-identical to the single-device stream
    and to ``mttkrp_sparse_psram``), ``"compiled"`` (blocked-segment fold),
    or ``"fused"`` (PR 6 int8 fused chunk body). On a 1-device mesh this
    degenerates to exactly the single-device schedule.
    """
    cfg = resolve_config(config)
    mesh = resolve_array_mesh(mesh, n_arrays)
    n = mesh.devices.size
    mode = csf.mode_order[0]
    out_rows = csf.shape[mode]
    rank = int(factors[0].shape[-1])
    meshed = _mesh_partition(csf, n, rank, cfg, planner)
    rows = cfg.rows
    max_nnz = max(1, max(s.nnz for s in meshed.shards))
    eb = _exec_blocks(rows, max(1, -(-max_nnz // rows)), exec_blocks)
    layout = _mesh_layout(csf, meshed, lowering, rows, eb, mesh)
    # spans cannot cross into the jitted shard_map body; the per-shard view
    # is host-side — one span per planned shard with its nnz (the imbalance
    # the planner fought) and the device its stack slice lives on, plus the
    # execute span around the SPMD dispatch. The cycle-domain per-array
    # tracks come from obs.mesh_timeline.
    if obs.enabled():
        devs = {sh.index[0].start or 0: sh.device
                for sh in layout[0].addressable_shards}
        for i, s in enumerate(meshed.shards):
            d = devs[i]
            with obs.span(f"mesh/shard{i}/plan", nnz=s.nnz,
                          device=f"{d.platform}:{d.id}"):
                pass
            obs.counter(f"mesh/shard{i}/nnz", s.nnz)
    with obs.span("mesh/stream/execute", nnz=csf.nnz, n_arrays=n,
                  lowering=lowering, planner=planner, mode=mode):
        if lowering == "eager":
            ip, rp, vp = layout
            vp = _faulty_values(vp)
            fn = _mesh_executor(mesh, lowering, mode, out_rows, 0, psram,
                                adc_bits)
            return fn(ip, rp, vp, tuple(factors))
        ip, vp, lp, sp, n_seg = layout
        vp = _faulty_values(vp)
        if lowering == "fused":
            from repro.kernels.stream_mttkrp import stream_factor_quants

            ps = stream_factor_quants(tuple(factors), mode)
            fn = _mesh_executor(mesh, lowering, mode, out_rows, n_seg, psram,
                                adc_bits)
            return fn(ip, vp, lp, sp, ps)
        fn = _mesh_executor(mesh, lowering, mode, out_rows, n_seg, psram,
                            adc_bits)
        return fn(ip, vp, lp, sp, tuple(factors))


# ---------------------------------------------------------------------------
# all-reduced Gram matrices (the CP-ALS normal equations, SPMD)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=64)
def _gram_executor(mesh: Mesh):
    axis = mesh.axis_names[0]

    def device_fn(f):
        return jax.lax.psum(
            jax.lax.dot_general(f, f, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32),
            axis)

    return jax.jit(jax.shard_map(device_fn, mesh=mesh, in_specs=(P(axis),),
                                 out_specs=P(), check_vma=False))


def mesh_gram(f: jax.Array, mesh: Mesh | None = None,
              n_arrays: int | None = None) -> jax.Array:
    """``f.T @ f`` with the rows of ``f`` sharded over the array axis and
    the ``(R, R)`` partial Grams all-reduced — the SPMD form of the CP-ALS
    normal-equation Grams. Zero-row padding makes any row count divisible;
    the split reassociates the row reduction, so the result is allclose
    (not bit-equal) to the single-device Gram."""
    mesh = resolve_array_mesh(mesh, n_arrays)
    n = mesh.devices.size
    if n == 1:
        return f.T @ f
    rows = f.shape[0]
    pad = (-rows) % n
    fp = jnp.pad(f, ((0, pad), (0, 0)))
    return _gram_executor(mesh)(fp)


# ---------------------------------------------------------------------------
# counted mesh pricing (the measured side of estimate == measured)
# ---------------------------------------------------------------------------


def mesh_counted_price(
    fiber_lengths,
    rank: int,
    config: PsramConfig | None = None,
    n_arrays: int = 1,
    fabric=None,
    planner: str = "makespan",
    out_rows: int | None = None,
):
    """:class:`~repro.core.perf_model.MeshPrice` from the counted op lists:
    one stream program per planned partition walked by ``count_cycles``,
    plus the fabric all-reduce — the same closed form the analytical price
    adds, so the two agree exactly (tests/test_mesh.py)."""
    from repro.core.perf_model import allreduce_cycles
    from repro.core.perf_model import MeshPrice
    from repro.core.schedule import count_cycles

    from .partition import partition_fiber_lengths

    cfg = resolve_config(config)
    f = np.asarray(fiber_lengths, dtype=np.int64)
    ps = partition_fiber_lengths(f, n_arrays, rank, cfg, planner=planner)
    reduced = int((f > 0).sum()) if out_rows is None else int(out_rows)
    return MeshPrice(
        per_array=tuple(count_cycles(p) for p in ps.programs),
        reduce_cycles=allreduce_cycles(reduced, rank, n_arrays, fabric),
        n_arrays=n_arrays,
    ), ps
