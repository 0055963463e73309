"""The program's own spans (``repro.obs``), for the per-layer metrics that
read them.

The program's tracer records while a JAX profiler session records, so
after a ``--trace 1`` run it holds the spans of the traced stretch, on its
own clock (read for durations and order only). A program that records no
span there leaves every reader with nothing, and the reader returns None.
"""
from __future__ import annotations

import bisect

import numpy as np


def spans(name: str) -> list[tuple[float, float, dict]]:
    """``(start_s, dur_s, args)`` of every span called ``name``, in order
    of start."""
    from repro import obs

    out = [(e["ts"] / 1e6, e["dur"] / 1e6, e.get("args", {}))
           for e in obs.get_tracer().events()
           if e.get("ph") == "X" and e["name"] == name]
    return sorted(out, key=lambda s: s[0])


def children(parents, kids):
    """For each span of ``parents``, the spans of ``kids`` (both in order
    of start) that start inside it."""
    starts = [k[0] for k in kids]
    return [kids[bisect.bisect_left(starts, t):
                 bisect.bisect_right(starts, t + d)]
            for t, d, _ in parents]


def pct(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    return float(v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)])
