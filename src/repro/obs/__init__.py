"""repro.obs — tracing, metrics, and cycle-accurate virtual timelines.

One observability layer for every execution path: wall-clock spans and
counters (:mod:`~repro.obs.tracer`), schedule-IR virtual timelines in the
cycle domain (:mod:`~repro.obs.timeline`), the registry-level backend
wrapper (:mod:`~repro.obs.instrument`), and the estimate-vs-measured drift
auditor (:mod:`~repro.obs.drift`). Everything exports Chrome ``trace_event``
JSON — one file, loadable in Perfetto / ``chrome://tracing``, with the
wall-clock process next to one virtual process per array schedule.

Usage::

    from repro import obs

    obs.enable()                              # or REPRO_TRACE=1
    with obs.span("mesh/shard3/stream", nnz=12345):
        ...
    obs.counter("adc_conversions", 52)
    obs.write_trace("trace.json")

    sw = obs.stopwatch("train/step")          # times even when disabled
    with sw:
        ...
    print(sw.duration_s)

    print(obs.drift_report().table())         # estimate vs measured

Span-naming convention — ``layer/component/detail``, slash-separated, three
levels, lowercase:

* **layer** — the subsystem: ``backend``, ``schedule``, ``stream``,
  ``mesh``, ``als``, ``autotune``, ``train``, ``serve``, ``bench``,
  ``obs``, ``fault``, ``py`` (the Python runtime: ``py/gc``).
* **component** — the object or phase within it: a backend name
  (``backend/psram-stream/...``), an executor (``schedule/execute``), a
  loop phase (``als/sweep``), a tuning key (``autotune/trial``).
* **detail** — the operation or instance: ``mttkrp``, ``matmul``,
  ``gram``, ``cost``, a shard index (``mesh/shard3/stream``), an
  iteration tag.

Two levels are fine when there is no meaningful third
(``train/step``, ``serve/generate``); the first segment doubles as the
Chrome ``cat`` field, so Perfetto can filter by layer. Metadata goes in
span **args** (keyword arguments to ``span``/``stopwatch``), not in the
name — names should aggregate across calls, args should vary.

The live serving loop (:mod:`repro.serve.loop`) instruments every engine
phase under the ``serve`` layer:

* ``serve/idle`` — one per idle stretch, from the first loop iteration that
  finds nothing queued or active until work arrives;
* ``serve/enqueue`` (rid, late_ms) — the producer releasing a request;
  ``late_ms`` is the enqueue time minus the request's due time;
* ``serve/admit`` (queued) — the admission pass, holding each
  ``serve/prefill`` (rid, prompt);
* ``serve/evict`` (rid) — a preempted row;
* ``serve/step`` (batch) — one decode step, holding ``serve/offload``
  (batch: the scheduler's pricing decision), ``serve/decode/build`` (batch,
  view: the host index arrays), ``serve/decode`` (batch, view: dispatch and
  the logits' host read; its duration feeds the offload scheduler) and
  ``serve/sample`` (batch: numpy sampling and per-row bookkeeping);
* counters ``serve/admitted``, ``serve/rejected``, ``serve/preempted``:
  the admission layer's attempts and failures (tokens, prefills and steps
  are in the ``ServeReport``).

CP-ALS (:mod:`repro.core.cp_als`) records ``als/prepare`` (rank: entry to
the first sweep — the backend, the dedupe sort and norm, the initial
factors and Grams), then per sweep ``als/sweep`` (iteration, backend,
rank) and ``als/fit`` (iteration, exact), whose ``als/fit/read`` is the
host read of the fit, where the host waits on the device.

The fault-tolerance stack (:mod:`repro.faults`) instruments under the
``fault`` layer, split by phase: spans ``fault/inject/armed`` (args: seed
and per-kind fault counts, open for the whole injected extent),
``fault/abft/check`` (kind: matmul|mttkrp — the checksum drive + compare),
``fault/abft/redrive`` (tile or fiber group, attempt), ``fault/abft/
fallback`` (the fault-suppressed recompute after retries exhaust),
``fault/mesh/shard_values`` (the per-shard corruption hook), ``fault/mesh/
degraded`` and ``fault/mesh/redrive`` (dead-array recovery), ``serve/fail``
(rid, reason — deadline/preempt-limit failures); counters
``fault/injected``, ``fault/detected``, ``fault/redrives``,
``fault/recovered``, ``fault/recovery_cycles``, ``fault/arrays_lost``,
``fault/recovered_rows``, ``serve/failed``. The injection hooks follow the
same zero-cost discipline as the null span: one module-global read when no
plan is armed.

Spans, stopwatches and counters record while tracing is enabled
(``REPRO_TRACE``, :func:`enable`) or while a JAX profiler session records
(``jax.profiler.start_trace`` to ``stop_trace``); :func:`enabled` returns
that predicate. While a profiler session records, each span also enters a
``jax.profiler.TraceAnnotation`` of its name and args, so it lands on the
profiler's host plane, on the device trace's clock; Python's collector
adds a ``py/gc`` span (generation) per collection. ``backends.get``'s
auto-wrap (:mod:`~repro.obs.instrument`) follows the explicit flag alone:
a profiler session never changes which objects the program builds.

The tracer is zero-cost when not recording: ``span()`` returns a shared
no-op context manager after one profiler query, without reading a clock
(overhead asserted in tests/test_obs.py). ``stopwatch()`` always measures
and exposes ``duration_s`` — it records an event only while recording, so
hot paths that need the number (trainer watchdog, autotune trials) pay one
clock pair either way, exactly as before.
"""
from __future__ import annotations

from .tracer import (
    Stopwatch,
    Tracer,
    counter,
    disable,
    enable,
    enabled,
    get_tracer,
    span,
    stopwatch,
)

__all__ = [
    "Stopwatch",
    "Tracer",
    "counter",
    "disable",
    "drift_report",
    "enable",
    "enabled",
    "get_tracer",
    "mesh_timeline",
    "program_timeline",
    "span",
    "stopwatch",
    "summary",
    "write_trace",
]


def write_trace(path: str) -> int:
    """Write the global tracer's Chrome trace JSON; returns event count."""
    return get_tracer().write_trace(path)


def summary() -> dict:
    """Per-span-name aggregates of the global tracer."""
    return get_tracer().summary()


def program_timeline(program, pid=None, name="schedule-IR",
                     max_events=100_000):
    """Lazy front door of :func:`repro.obs.timeline.program_timeline`."""
    from .timeline import program_timeline as impl

    return impl(program, pid=pid, name=name, max_events=max_events)


def mesh_timeline(fiber_lengths, rank, config=None, n_arrays=1,
                  planner="makespan", fabric=None, out_rows=None,
                  max_events=100_000):
    """Lazy front door of :func:`repro.obs.timeline.mesh_timeline`."""
    from .timeline import mesh_timeline as impl

    return impl(fiber_lengths, rank, config=config, n_arrays=n_arrays,
                planner=planner, fabric=fabric, out_rows=out_rows,
                max_events=max_events)


def drift_report(workloads=None, config=None, wall_times=None):
    """Lazy front door of :func:`repro.obs.drift.drift_report`."""
    from .drift import drift_report as impl

    return impl(workloads=workloads, config=config, wall_times=wall_times)
