"""The tracer: nestable wall-clock spans + typed counters, exported as
Chrome ``trace_event`` JSON, and mirrored onto the JAX profiler's clock.

One process-global :class:`Tracer` instance backs the module-level front
doors in :mod:`repro.obs` (``span`` / ``stopwatch`` / ``counter``). The
design constraints, in order:

* **one recording predicate** — spans, stopwatches and counters record
  while tracing is enabled (``REPRO_TRACE`` / ``enable()``) *or* while a
  JAX profiler session is recording (``jax.profiler.start_trace`` to
  ``stop_trace``). :func:`enabled` returns that predicate.
* **the device trace's clock** — while a profiler session records, a span
  also enters a ``jax.profiler.TraceAnnotation`` of its name and args, so
  it lands on the profiler's host plane beside the device's programs. The
  in-memory event keeps the tracer's own epoch; read it for durations and
  order.
* **zero-cost when not recording** — ``span()`` is a flag check and one
  profiler query (~0.1 us) plus the return of one shared no-op context
  manager; no clock is read, no object allocated, no lock taken. The
  overhead contract is tested (tests/test_obs.py: a spanned hot loop must
  not regress vs un-spanned).
* **always-correct timing when asked** — ``stopwatch()`` reads the clock
  whether or not tracing is enabled and exposes ``duration_s`` afterwards,
  so callers that *need* the measurement (the trainer's straggler watchdog,
  the autotuner's trial timer) use one mechanism for measuring and
  recording instead of ad-hoc ``time.perf_counter`` pairs.
* **thread-safe, thread-aware** — events carry the recording thread as
  their Chrome ``tid``; nesting within a thread renders as stacked slices
  in Perfetto (``X`` events nest by ts/dur).

Enabling: ``REPRO_TRACE`` in the environment (any value but ``0``/empty)
enables tracing at import; ``enable()`` / ``disable()`` toggle it
programmatically at any point. Python's garbage collector records a
``py/gc`` span (arg: generation) per collection under the same predicate,
so a collector pause that holds the device up names itself.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time
from typing import Any

from jax.profiler import TraceAnnotation

# True only while a JAX profiler session records
_profiling = TraceAnnotation.is_enabled

# wall-clock spans record under this Chrome pid; virtual (cycle-domain)
# timelines allocate their own pids via next_pid() so the two domains sit
# in separate process groups in Perfetto
WALL_PID = 0


class _NullSpan:
    """The shared no-op context manager returned when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Stopwatch:
    """A span that always times, and records only when tracing is on.

    ``duration_s`` is valid after ``__exit__`` (and live-updating inside a
    ``with`` block via :meth:`elapsed_s`). The measured number is the
    caller's to keep — this is the one mechanism that owns wall-clock
    measurement for the trainer / serve launcher / autotuner.
    """

    __slots__ = ("tracer", "name", "args", "t0", "duration_s", "_rec",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.t0 = 0.0
        self.duration_s = 0.0
        self._rec = False
        self._ann = None

    def __enter__(self) -> "Stopwatch":
        # whether it records is decided once, here
        profiling = _profiling()
        self._rec = self.tracer.enabled or profiling
        if profiling:
            self._ann = TraceAnnotation(self.name, **self.args)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def elapsed_s(self) -> float:
        return time.perf_counter() - self.t0

    def __exit__(self, *exc):
        self.duration_s = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._rec:
            self.tracer._record(self.name, self.t0, self.duration_s,
                                self.args)
        return False


class _Span(Stopwatch):
    """A recording span (only constructed while the tracer records)."""

    __slots__ = ()


class Tracer:
    """Collects spans and counters; renders Chrome ``trace_event`` JSON."""

    def __init__(self):
        self.enabled = False          # the explicit flag (REPRO_TRACE)
        # re-entrant: a collection (the ``py/gc`` span) may start while
        # this thread holds the lock
        self._lock = threading.RLock()
        self._events: list[dict] = []
        self._counters: dict[str, float] = {}
        self._epoch = time.perf_counter()
        self._next_pid = 1
        self._gc_span: Stopwatch | None = None   # the open ``py/gc`` span

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **args: Any):
        """A nestable span context manager — the shared no-op when not
        recording (the zero-cost contract), a recording span otherwise."""
        if not (self.enabled or _profiling()):
            return _NULL_SPAN
        return _Span(self, name, args)

    def stopwatch(self, name: str, **args: Any) -> Stopwatch:
        """A span that ALWAYS measures (``duration_s`` after exit) and
        records the event only while the tracer records."""
        return Stopwatch(self, name, args)

    def counter(self, name: str, value: float = 1.0) -> None:
        """Accumulate a named counter (no-op when not recording). Integer
        values stay integers; floats stay floats — ``counters()`` returns
        whatever type accumulated."""
        if not (self.enabled or _profiling()):
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def on_gc(self, phase: str, info: dict) -> None:
        """The ``gc.callbacks`` hook: one ``py/gc`` span per collection
        (collections neither nest nor overlap), under the predicate."""
        if phase == "start":
            if self.enabled or _profiling():
                self._gc_span = Stopwatch(self, "py/gc",
                                          {"generation": info["generation"]})
                self._gc_span.__enter__()
        elif self._gc_span is not None:
            sw, self._gc_span = self._gc_span, None
            sw.__exit__(None, None, None)

    def _record(self, name: str, t0: float, dur_s: float, args: dict):
        ev = {
            "name": name,
            "ph": "X",
            "pid": WALL_PID,
            "tid": threading.get_ident() & 0xFFFF,
            "ts": (t0 - self._epoch) * 1e6,
            "dur": dur_s * 1e6,
            "cat": name.split("/", 1)[0],
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def add_events(self, events: list[dict]) -> None:
        """Inject pre-built trace events (the virtual timelines of
        :mod:`repro.obs.timeline`) regardless of the enabled flag — callers
        emitting a timeline have already opted in."""
        with self._lock:
            self._events.extend(events)

    def next_pid(self) -> int:
        """Allocate a fresh Chrome pid for a virtual-timeline process."""
        with self._lock:
            pid = self._next_pid
            self._next_pid += 1
            return pid

    # -- reading out -------------------------------------------------------

    def counters(self) -> dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def summary(self) -> dict[str, dict]:
        """Per-span-name aggregates: ``{name: {count, total_s, max_s}}`` —
        what printed summaries source instead of their own timers."""
        out: dict[str, dict] = {}
        for ev in self.events():
            if ev.get("ph") != "X":
                continue
            s = out.setdefault(ev["name"],
                               {"count": 0, "total_s": 0.0, "max_s": 0.0})
            dur = ev["dur"] / 1e6
            s["count"] += 1
            s["total_s"] += dur
            s["max_s"] = max(s["max_s"], dur)
        return out

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._counters.clear()
            self._epoch = time.perf_counter()
            self._next_pid = 1

    def to_chrome_trace(self) -> dict:
        """The full trace as a Chrome ``trace_event`` object — wall-clock
        spans (pid 0) plus any injected virtual timelines, with process
        metadata and final counter values, loadable in Perfetto /
        ``chrome://tracing``."""
        events = self.events()
        meta = [{
            "name": "process_name", "ph": "M", "pid": WALL_PID,
            "args": {"name": "wall-clock (us)"},
        }]
        counters = self.counters()
        if counters:
            # one terminal counter sample per name, on the wall-clock track
            ts = max((e["ts"] + e.get("dur", 0) for e in events
                      if e.get("pid") == WALL_PID), default=0.0)
            for cname, val in sorted(counters.items()):
                meta.append({
                    "name": cname, "ph": "C", "pid": WALL_PID, "ts": ts,
                    "args": {"value": val},
                })
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms",
                "otherData": {"producer": "repro.obs"}}

    def write_trace(self, path: str) -> int:
        """Write :meth:`to_chrome_trace` as JSON; returns the event count."""
        trace = self.to_chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])


_TRACER = Tracer()
if os.environ.get("REPRO_TRACE", "") not in ("", "0"):
    _TRACER.enabled = True
gc.callbacks.append(_TRACER.on_gc)


def get_tracer() -> Tracer:
    return _TRACER


def enable() -> None:
    _TRACER.enabled = True


def disable() -> None:
    _TRACER.enabled = False


def enabled() -> bool:
    """The recording predicate: tracing is enabled, or a JAX profiler
    session records."""
    return _TRACER.enabled or _profiling()


def span(name: str, **args):
    return _TRACER.span(name, **args)


def stopwatch(name: str, **args) -> Stopwatch:
    return _TRACER.stopwatch(name, **args)


def counter(name: str, value: float = 1.0) -> None:
    _TRACER.counter(name, value)
