"""Tile autotuner for the fused kernel family.

The fused executors have one genuinely free performance knob each — chunk
size (``exec_blocks``) for the streaming MTTKRP, MXU tile shapes for the
dense kernels — and the best choice depends on the workload's shape, its
nonzero profile, and the array geometry. This module sweeps a small
candidate set, benchmarks each in-process (median of repeats on the real
operands), and caches the winner per :class:`TuneKey` with the PR 5 keying
discipline: keys are frozen dataclasses compared *by value*, so two
equal-by-value ``(shape, nnz-profile, PsramConfig)`` keys share one tuned
entry — and, through ``stream_mttkrp.fused_stream_executor``'s lru cache,
one compiled kernel.

Untuned runs never regress: when tuning is disabled (the default, or via
``REPRO_AUTOTUNE=0``) :func:`get_params` returns a deterministic heuristic
— the same parameters the pre-tuner code paths used — without touching the
cache. Tuned winners can be shipped: :func:`save_cache` /
:func:`load_cache` round-trip the winner table through JSON (keys
canonicalized to strings), so CI can upload the cache as an artifact and a
cold process can start warm.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import warnings

import jax

from repro import obs
from repro.core.psram import PsramConfig


@dataclasses.dataclass(frozen=True)
class TuneKey:
    """What a tuned winner is keyed by: the kernel kind, the workload shape,
    its nonzero profile (empty for dense), and the array config — all
    hashable by value, so equal-by-value keys share one entry."""

    kind: str                 # "stream" | "matmul" | "dense_mttkrp"
    shape: tuple              # workload dims (+ rank where it matters)
    profile: tuple            # bucketed nnz statistics; () for dense
    config: PsramConfig


_WINNERS: dict[TuneKey, dict] = {}


def enabled(requested: bool = True) -> bool:
    """Is tuning live? ``REPRO_AUTOTUNE=0`` force-disables (CI determinism
    escape hatch) — the heuristic default is used instead."""
    return bool(requested) and os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def nnz_profile(nnz: int, fiber_lengths=None) -> tuple:
    """Bucketed nonzero profile: (log2-nnz bucket, log2-mean-fiber bucket).

    Buckets rather than raw counts so workloads of the same scale and
    fiber irregularity share one tuned entry (retuning per exact nnz would
    make every CP-ALS sweep a cache miss)."""
    nnz_bucket = int(math.log2(max(1, int(nnz))))
    if fiber_lengths is None or len(fiber_lengths) == 0:
        return (nnz_bucket,)
    mean_fiber = float(nnz) / max(1, len(fiber_lengths))
    return (nnz_bucket, int(math.log2(max(1.0, mean_fiber))))


def heuristic(key: TuneKey) -> dict:
    """The deterministic no-tuning default per kind — what an untuned run
    executes, and the seed candidate of every sweep."""
    if key.kind == "stream":
        # ~8Ki nonzeros per scan chunk: big enough to amortize the chunk
        # dispatch, small enough that the gathered factor rows stay hot
        return {"exec_blocks": max(1, 8192 // key.config.rows)}
    if key.kind == "matmul":
        return {"bm": 128, "bn": 128, "bk": 512}
    if key.kind == "dense_mttkrp":
        return {"bi": 128, "bk": 128}
    raise ValueError(f"unknown tune kind {key.kind!r}")


def candidates(key: TuneKey) -> list[dict]:
    """The sweep set per kind (heuristic first, so ties keep the default)."""
    if key.kind == "stream":
        rows = key.config.rows
        ebs = {max(1, nnz // rows) for nnz in (4096, 8192, 16384, 32768, 65536)}
        base = heuristic(key)["exec_blocks"]
        return [{"exec_blocks": eb}
                for eb in sorted(ebs, key=lambda e: (e != base, e))]
    if key.kind == "matmul":
        return [heuristic(key)] + [
            {"bm": bm, "bn": bn, "bk": bk}
            for bm, bn, bk in ((128, 128, 128), (128, 128, 256),
                               (256, 256, 512), (64, 64, 512))
        ]
    if key.kind == "dense_mttkrp":
        return [heuristic(key)] + [
            {"bi": bi, "bk": bk}
            for bi, bk in ((64, 128), (128, 256), (256, 128), (64, 64))
        ]
    raise ValueError(f"unknown tune kind {key.kind!r}")


def _median_time(fn, repeats: int = 3, name: str = "autotune/trial/run",
                 **meta) -> float:
    """Median wall-clock of ``fn`` over ``repeats`` — timed through the
    ``obs`` stopwatch, so every trial run lands in the trace (with its
    candidate params as span args) whenever tracing is on, at no cost when
    it's off."""
    jax.block_until_ready(fn())          # warmup / compile outside the clock
    times = []
    for _ in range(repeats):
        with obs.stopwatch(name, **meta) as sw:
            jax.block_until_ready(fn())
        times.append(sw.duration_s)
    times.sort()
    return times[len(times) // 2]


def get_params(key: TuneKey, measure=None, tune: bool = False,
               repeats: int = 3) -> dict:
    """The parameters to run ``key`` with.

    Cached winner if one exists (tuned earlier or loaded); otherwise, when
    ``tune`` is live and a ``measure`` factory is given, sweep
    :func:`candidates` — ``measure(params)`` must return a nullary runner
    over the real operands — and cache the fastest. Else: the deterministic
    :func:`heuristic` (NOT cached, so a later tuned run still happens).
    """
    hit = _WINNERS.get(key) or _check_loaded(key)
    if hit is not None:
        return hit
    if not enabled(tune) or measure is None:
        return heuristic(key)
    best, best_t = None, float("inf")
    with obs.span("autotune/sweep", kind=key.kind, shape=str(key.shape),
                  candidates=len(candidates(key))):
        for params in candidates(key):
            t = _median_time(measure(params), repeats=repeats,
                             name="autotune/trial/run", kind=key.kind,
                             **params)
            if obs.enabled():
                obs.counter("autotune/trials")
            if t < best_t:
                best, best_t = params, t
    if obs.enabled():
        with obs.span("autotune/winner", kind=key.kind, shape=str(key.shape),
                      median_s=best_t, **best):
            pass
    _WINNERS[key] = best
    return best


# ------------------------------------------------------- per-kind front doors


def stream_key(csf, rank: int, config: PsramConfig) -> TuneKey:
    return TuneKey(
        kind="stream",
        shape=tuple(csf.shape) + (rank,),
        profile=nnz_profile(csf.nnz, csf.fiber_lengths()),
        config=config,
    )


def stream_params(csf, factors, config: PsramConfig, tune: bool = False,
                  adc_bits: int = 16, lowering: str = "xla") -> dict:
    """Winner/heuristic ``{"exec_blocks": n}`` for one streaming workload.

    When tuning, candidates run the *real* fused executor on the real
    layout + quantized factors (in-process, median of 3) — the winner is
    what the caller immediately reuses, so the tuning run itself warms the
    executor cache entry that production hits.
    """
    key = stream_key(csf, int(factors[0].shape[-1]), config)
    if key in _WINNERS or not enabled(tune):
        return get_params(key)

    import jax.numpy as jnp

    from repro.kernels.stream_mttkrp import (
        _LOWERING_FNS, stream_factor_quants)
    from repro.sparse.stream import stream_layout

    mode = csf.mode_order[0]
    ps = stream_factor_quants(tuple(factors), mode)
    fn = _LOWERING_FNS[lowering]

    def measure(params):
        ip, vp, lp, sp, n_seg = stream_layout(
            csf, config.rows, params["exec_blocks"])
        ip = ip.astype(jnp.int32)
        return lambda: fn(ip, vp, lp, sp, ps, mode, n_seg, adc_bits,
                          csf.shape[mode])

    return get_params(key, measure=measure, tune=True)


def matmul_key(m: int, k: int, n: int, config: PsramConfig) -> TuneKey:
    return TuneKey(kind="matmul", shape=(m, k, n), profile=(), config=config)


def dense_mttkrp_key(i: int, j: int, k: int, rank: int,
                     config: PsramConfig) -> TuneKey:
    return TuneKey(kind="dense_mttkrp", shape=(i, j, k, rank), profile=(),
                   config=config)


# ----------------------------------------------------------- cache plumbing


def cache_stats() -> tuple[int, tuple[TuneKey, ...]]:
    """(#winners, keys) — introspection for tests and benches."""
    return len(_WINNERS), tuple(_WINNERS)


def clear_autotune_cache() -> None:
    """Drop tuned winners AND the compiled fused executors they selected
    (tests; mirrored by ``core.schedule.clear_program_cache``)."""
    _WINNERS.clear()
    _LOADED.clear()
    from repro.kernels.stream_mttkrp import fused_stream_executor

    fused_stream_executor.cache_clear()


def _key_token(key: TuneKey) -> str:
    return json.dumps(
        [key.kind, list(key.shape), list(key.profile),
         dataclasses.asdict(key.config)],
        sort_keys=True)


def save_cache(path: str) -> int:
    """Write the winner table as JSON (canonical string keys); returns the
    number of entries written. Ship it with a deployment and
    :func:`load_cache` at startup to run pre-tuned."""
    with open(path, "w") as f:
        json.dump({_key_token(k): v for k, v in _WINNERS.items()}, f,
                  indent=2, sort_keys=True)
    return len(_WINNERS)


def load_cache(path: str) -> int:
    """Merge a saved winner table. Entries are matched lazily by token:
    a loaded winner is installed for a live :class:`TuneKey` the first time
    :func:`get_params` asks for it. Returns the number of entries loaded.

    A corrupt or truncated cache file is a warning, not an error: tuned
    winners are an optimization, so a damaged table must never take the
    deployment down — the heuristic defaults stay in force and 0 is
    returned. A missing file still raises (a wrong path is a caller bug).
    """
    with open(path) as f:
        try:
            loaded = json.load(f)
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            warnings.warn(
                f"autotune cache {path!r} is corrupt ({e}); ignoring it — "
                "heuristic defaults stay in force", stacklevel=2)
            return 0
    if not isinstance(loaded, dict):
        warnings.warn(
            f"autotune cache {path!r} holds {type(loaded).__name__}, not a "
            "winner table; ignoring it", stacklevel=2)
        return 0
    good = {k: v for k, v in loaded.items()
            if isinstance(k, str) and isinstance(v, dict)}
    if len(good) != len(loaded):
        warnings.warn(
            f"autotune cache {path!r}: dropped {len(loaded) - len(good)} "
            "malformed entries", stacklevel=2)
    _LOADED.update(good)
    return len(good)


_LOADED: dict[str, dict] = {}


def _check_loaded(key: TuneKey) -> dict | None:
    params = _LOADED.get(_key_token(key))
    if params is not None:
        _WINNERS[key] = params
    return params
