"""Reduce a profiler trace of the window to device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Its device planes (``/device:TPU:<n>``) hold one line of program runs
("XLA Modules") and one of the operations inside them ("XLA Ops"); the
host plane holds the host's spans, among them the benchmark's own
``bench/window`` around the measured window.

From these:

* ``busy_s``: per chip, the union of the intervals in which a program ran
  inside the window, averaged over the chips the cell uses;
* ``module_s(layer)`` / ``module_runs(layer)``: device time and run count
  of the programs that ``layers.json`` maps to a layer, by the jit name's
  prefix, on chip 0 (all chips run the same programs);
* ``collective_s``: time of collective operations on chip 0;
* ``top_ops``: the ten operations that took most time, as
  ``[program/op, seconds]``;
* ``idle_gaps``: the ten longest gaps between programs on chip 0, each
  named by the innermost host span that was open across it.

``reduce_events`` does the arithmetic on plain event lists, so it can be
checked on a small recorded trace (``testdata/``) without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
LAYERS = json.loads((HERE / "layers.json").read_text())
WINDOW_SPAN = "bench/window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def module_name(name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``: the program's jit name."""
    return name.split("(", 1)[0].strip()


def op_name(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``: the HLO
    instruction's name without its text."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def layer_of(module: str) -> str | None:
    for layer, prefixes in LAYERS.items():
        for p in prefixes:
            if module == p or module.startswith(p + "."):
                return layer
    return None


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    modules: dict          # module -> [seconds, runs] on chip 0
    collective_s: float
    top_ops: list
    idle_gaps: list

    def module_s(self, layer: str) -> float:
        return sum(s for m, (s, _) in self.modules.items()
                   if layer_of(m) == layer)

    def module_runs(self, layer: str) -> int:
        return sum(n for m, (_, n) in self.modules.items()
                   if layer_of(m) == layer)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(intervals):
    total, end = 0, None
    merged = []
    for a, b in sorted(intervals):
        if end is None or a > end:
            merged.append([a, b])
            end = b
        elif b > end:
            merged[-1][1] = b
            end = b
    for a, b in merged:
        total += b - a
    return total, merged


def reduce_events(events: dict, chips: int) -> Reduced:
    """``events``: {"devices": {n: {"modules": [[name, t0, dur], ...],
    "ops": [[name, t0, dur], ...]}}, "host": [[name, t0, dur], ...]},
    times in nanoseconds on one clock."""
    host = events["host"]
    win = [e for e in host if e[0] == WINDOW_SPAN]
    devs = {int(k): v for k, v in events["devices"].items()}
    if win:
        w0, w1 = win[0][1], win[0][1] + win[0][2]
    else:
        starts = [e[1] for d in devs.values() for e in d["modules"]]
        ends = [e[1] + e[2] for d in devs.values() for e in d["modules"]]
        w0, w1 = min(starts), max(ends)

    def clip(evs):
        out = []
        for name, t0, dur in evs:
            a, b = max(t0, w0), min(t0 + dur, w1)
            if b > a:
                out.append((name, a, b))
        return out

    busy = []
    for n in range(chips):
        d = devs.get(n, {"modules": [], "ops": []})
        total, _ = _union((a, b) for _, a, b in clip(d["modules"]))
        busy.append(total)
    d0 = devs.get(0, {"modules": [], "ops": []})
    mods = clip(d0["modules"])
    modules: dict = {}
    for name, a, b in mods:
        m = module_name(name)
        s = modules.setdefault(m, [0.0, 0])
        s[0] += (b - a) / 1e9
        s[1] += 1
    # each op is named by the program run that holds it; a long window
    # holds millions of ops under a few dozen names, so names are parsed
    # once each
    mods_sorted = sorted(mods, key=lambda e: e[1])
    starts = [e[1] for e in mods_sorted]
    owners = [module_name(e[0]) for e in mods_sorted]
    parsed: dict = {}                       # raw op name -> (op, collective)
    per_op: dict = {}
    coll = 0
    for name, t, dur in d0["ops"]:          # may be a one-pass iterator
        a, b = max(t, w0), min(t + dur, w1)
        if b <= a:
            continue
        i = bisect.bisect_right(starts, a) - 1
        owner = owners[i] if i >= 0 and mods_sorted[i][2] >= a else "?"
        if name not in parsed:
            op = op_name(name)
            parsed[name] = (op, any(c in op for c in COLLECTIVES))
        op, is_coll = parsed[name]
        key = (owner, op)
        per_op[key] = per_op.get(key, 0) + (b - a)
        if is_coll:
            coll += b - a
    top = [(f"{owner}/{op}", v) for (owner, op), v in
           sorted(per_op.items(), key=lambda kv: -kv[1])[:10]]
    _, merged = _union((a, b) for _, a, b in mods)
    gaps = []
    prev = w0
    for a, b in merged:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    spans = [(n, t, t + d) for n, t, d in host if n != WINDOW_SPAN]
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [s for s in spans if s[1] <= mid <= s[2]]
        name = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
            else "(no host span)"
        named.append([name, (b - a) / 1e9])
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy) / max(chips, 1) / 1e9,
        modules=modules,
        collective_s=coll / 1e9,
        top_ops=[[k, v / 1e9] for k, v in top],
        idle_gaps=named,
    )


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.duration_ns


def load_events(trace_dir: Path) -> dict:
    """The device and host events of the newest trace under ``trace_dir``.
    A device's operations come as a one-pass iterator: a long window holds
    millions of them, and the reduction needs each once."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    out = {"devices": {}, "host": [], "profile": pd}   # keeps the iterators'
    for plane in pd.planes:                              # data alive
        m = _DEVICE.match(plane.name)
        if m:
            d = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    d["modules"] = [list(e) for e in _events(line)]
                elif line.name == "XLA Ops":
                    d["ops"] = _events(line)
            out["devices"][int(m.group(1))] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events if e.duration_ns > 0)
    return out


def reduce_trace(trace_dir: Path, chips: int) -> Reduced:
    return reduce_events(load_events(trace_dir), chips)
