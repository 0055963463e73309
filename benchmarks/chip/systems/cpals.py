"""Runner of the sparse CP-ALS cells: back-to-back sweeps of ``cp_als``.

Set-up: the tensor's nonzero pattern is a property of the configuration
(``pattern.py``, cached per checkout); ``--seed`` draws its values on the
device and the initial factors. The program builds its per-mode CSFs,
then one ``cp_als`` call of ``warm_sweeps`` sweeps compiles every program the window
runs and times a warm sweep.

Window: one ``cp_als`` call of as many sweeps as fill ``--seconds`` at the
warm sweep's pace, timed from its first MTTKRP to its return (each sweep
ends in the fit's host read).

Check: the backend's MTTKRP is watched (not changed) so that the last
sweep's inputs and outputs of every mode stay at hand. Against
``reference/cpals.py``: each mode's MTTKRP, each mode's factor update, and
the fit the program reported for its final factors.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import pattern as pattern_mod  # noqa: E402

log = harness.log


class Watch:
    """An MTTKRP of the program, with every call's start time and the last
    call's inputs and output per mode kept: the backend's ``mttkrp(data,
    factors, mode)`` or the exact ``stream_mttkrp(csf, factors)`` that
    ``cp_als`` calls for the fit."""

    def __init__(self, fn):
        self.fn = fn
        self.starts: list[tuple[float, int]] = []
        self.last: dict = {}
        self.on_first = None
        self.first = None       # the factors of the first call: the init

    def __call__(self, data, factors, *args, **kw):
        if self.on_first is not None:
            self.on_first()
            self.on_first = None
        mode = args[0] if args else data.mode_order[0]
        t = time.perf_counter()
        out = self.fn(data, factors, *args, **kw)
        self.starts.append((t, mode))
        self.last[mode] = (tuple(factors), out)
        if self.first is None:
            self.first = tuple(factors)
        return out


def make_backend(ctx):
    """The backend the configuration names, as users get it by default."""
    from repro import backends

    be = backends.get(ctx.config["backend"])
    for op, low in getattr(be, "lowerings", {}).items():
        log(f"[setup] lowering {op}: {low}")
        if ctx.require_tpu and low in ("interpret", "ref"):
            raise RuntimeError(f"{op} resolved to {low!r} on the chip")
    return be


def run(ctx) -> None:
    import jax
    import jax.numpy as jnp

    import repro.sparse.stream as stream_mod
    from repro.core.cp_als import cp_als
    from repro.sparse.formats import COO, csf_for_mode

    cfg = ctx.config
    dims, rank = tuple(cfg["dims"]), int(cfg["rank"])
    t0 = time.perf_counter()
    idx_np, digest = pattern_mod.load(cfg)
    t1 = time.perf_counter()
    log(f"[setup] pattern {digest} nnz {len(idx_np)} dims {dims} "
        f"({t1 - t0:.3f} s)")
    idx = jax.device_put(jnp.asarray(idx_np))
    vals = pattern_mod.values(harness.jax_key(ctx.seed, 1), idx, dims,
                              int(cfg["value_rank"]))
    vals.block_until_ready()
    coo = COO(indices=idx, values=vals, shape=dims)
    t2 = time.perf_counter()
    csfs = [csf_for_mode(coo, m) for m in range(len(dims))]
    t3 = time.perf_counter()
    log(f"[setup] values on device {t2 - t1:.3f} s; {len(dims)} CSFs "
        f"(program, host) {t3 - t2:.3f} s")

    be = make_backend(ctx)
    watch = Watch(be.mttkrp)
    be.mttkrp = watch
    # cp_als imports the fit's exact MTTKRP from this module at each call
    fit_watch = Watch(stream_mod.stream_mttkrp)
    stream_mod.stream_mttkrp = fit_watch
    key = harness.jax_key(ctx.seed, 2)
    kw = dict(sparse=coo, csfs=csfs, backend=be, key=key, tol=0.0)

    warm = int(ctx.traffic["warm_sweeps"])
    cp_als(None, rank, n_iter=warm, **kw)
    t4 = time.perf_counter()
    est = t4 - [t for t, m in watch.starts if m == 0][-1]
    n_sweeps = max(1, round(ctx.seconds / est))
    log(f"[setup] warm-up: {warm} sweeps (compile + run) {t4 - t3:.3f} s; warm "
        f"sweep {est:.4f} s -> {n_sweeps} sweeps in the window")
    rows = be.config.rows
    n_segs, shapes = [], []
    for m, csf in enumerate(csfs):
        layout = csf.__dict__.get(("_stream_compiled_layout", rows))
        if layout is not None:
            ip, _, _, _, n_seg = layout[1]
            n_segs.append(n_seg)
            shapes.append(tuple(ip.shape))
            log(f"[setup] mode {m}: n_seg {n_seg}, chunks {ip.shape[0]} x "
                f"{ip.shape[1]} blocks of {ip.shape[2]} nonzeros")
    ctx.observed.update(
        pattern=digest, n_seg=n_segs, layout_shapes=shapes,
        values=pattern_mod.digest(np.asarray(vals)),
        init=pattern_mod.digest(np.asarray(watch.first[-1])))

    watch.starts.clear()
    # the window opens at the first sweep's first MTTKRP, after cp_als's
    # own host preparation of the call
    watch.on_first = ctx.window_start
    ctx.trace_begin()
    state = cp_als(None, rank, n_iter=n_sweeps, **kw)
    ctx.window_end()
    stream_mod.stream_mttkrp = fit_watch.fn
    sweeps_s = ctx.window_s
    ctx.end_to_end["sweep_s"] = sweeps_s / state.iters
    ctx.attempted = state.iters
    ctx.failed = 0 if np.isfinite(state.fit) else state.iters
    ctx.observed.update(sweeps=state.iters, nnz=int(len(idx_np)), dims=dims,
                        rank=rank, fit=state.fit)
    log(f"[window] {state.iters} sweeps in {sweeps_s:.4f} s: sweep_s "
        f"{sweeps_s / state.iters!r}, fit {state.fit!r}")
    ctx.read_memory()
    check(ctx, idx, vals, watch, fit_watch, state)


def check(ctx, idx, vals, watch, fit_watch, state) -> None:
    import reference.cpals as ref

    limits = ctx.config["limits"]
    n = len(state.factors)
    mttkrp_rel = update_rel = low_mttkrp_rel = low_update_rel = 0.0
    for m in range(n):
        f_in, out = watch.last[m]
        want = ref.mttkrp(idx, vals, f_in, m)
        r = ref.rel(out, want)
        new = state.factors[m] if m == n - 1 else watch.last[m + 1][0][m]
        a_ref, _ = ref.als_update(want, f_in, m)
        u = ref.rel(new, a_ref)
        log(f"[check] mode {m}: MTTKRP rel {r!r}, update rel {u!r}")
        mttkrp_rel, update_rel = max(mttkrp_rel, r), max(update_rel, u)
        if ctx.control:
            low = ref.mttkrp(idx, vals, f_in, m, bits=4)
            low_mttkrp_rel = max(low_mttkrp_rel, ref.rel(low, want))
            low_update_rel = max(low_update_rel, ref.rel(
                ref.als_update(low, f_in, m)[0], a_ref))
    # the fit's exact MTTKRP, of the last mode at the final factors
    m = n - 1
    f_in, out = fit_watch.last[m]
    want = ref.mttkrp(idx, vals, f_in, m)
    fit_rel = ref.rel(out, want)
    fit_ref = ref.fit(idx, vals, state.factors, state.lambdas)
    log(f"[check] fit MTTKRP rel {fit_rel!r}; fit program {state.fit!r} "
        f"reference {fit_ref!r}")
    ctx.compare("mttkrp_rel", mttkrp_rel, limits["mttkrp_rel"])
    ctx.compare("update_rel", update_rel, limits["update_rel"])
    ctx.compare("fit_mttkrp_rel", fit_rel, limits["fit_mttkrp_rel"])
    if ctx.control:
        import jax.numpy as jnp

        low_fit_rel = ref.rel(
            ref.mttkrp(idx, vals, f_in, m, dtype=jnp.bfloat16), want)
        ctx.control.compare("mttkrp_rel", low_mttkrp_rel,
                            limits["mttkrp_rel"])
        ctx.control.compare("update_rel", low_update_rel,
                            limits["update_rel"])
        ctx.control.compare("fit_mttkrp_rel", low_fit_rel,
                            limits["fit_mttkrp_rel"])
