"""The chip benchmark's harness: one cell, one run, one JSON line.

    python3 benchmarks/chip/run.py --workload nell2.cpals --seed 7 \
        --seconds 30 --trace 0

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by name:

* ``BENCHMARK.json`` (checkout root) names the cell's configuration and
  traffic, and the metrics it reports;
* ``configs/<file>`` holds the configuration as it is run; its ``system``
  key picks the runner ``systems/<system>.py``;
* ``traffic/<traffic>.json`` holds the traffic's parameters;
* ``metrics/<name>.py`` reads one per-layer metric from the run's context
  (``read(ctx) -> float | None``); a reader that finds nothing returns None
  and the metric is left out of the line.

A runner's ``run(ctx)`` sets up, warms every shape its traffic uses, marks
the window with ``ctx.window_start()`` / ``ctx.window_end()``, reads the
device's memory peak, then checks what the window produced against the
plain reference under ``reference/``. With ``--trace 1`` the profiler
records the window and ``devtrace.py`` reduces it.

Stdout's last line is the result; stderr's last lines are each compared
number beside its limit.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

T_PROC = time.perf_counter()

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SRC = ROOT / "src"
CACHE = HERE / ".cache"


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: Path, name: str | None = None):
    """Import a file of the benchmark by path (names may hold dots), once."""
    name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def jax_key(seed: int, stream: int = 0):
    """A JAX key for ``(seed, stream)``: any whole number, its high word
    kept past 32 bits, and each use of the seed (weights, values, factors)
    draws from a stream of its own."""
    import jax

    s = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)
    return jax.random.fold_in(key, stream)


def np_rng(seed: int, stream: int = 0):
    import numpy as np

    return np.random.default_rng([int(seed) % (1 << 64), stream])


class Spec:
    """The cell's entries of BENCHMARK.json and the files they name."""

    def __init__(self, workload: str, bench=None, config=None):
        bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: "
                             f"{', '.join(cells)}")
        self.cell = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.cell["config"]]
        self.config = config or json.loads(
            (ROOT / self.config_entry["file"]).read_text())
        self.traffic = json.loads(
            (HERE / "traffic" / f"{self.cell['traffic']}.json").read_text())
        self.chips = int(self.cell["chips"])

        def ours(m):
            return "workloads" not in m or workload in m["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if ours(m)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (workload in m["workloads"]
                              if "workloads" in m else m["moves"] in e2e)]


class Checks:
    """The numbers of one correctness check, each beside its limit."""

    def __init__(self):
        self.compared: dict[str, tuple[float, float]] = {}

    def compare(self, name: str, value: float, limit: float) -> None:
        """One number; it passes at or under its limit (NaN never
        passes)."""
        self.compared[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            math.isfinite(v) and v <= lim for v, lim in self.compared.values())


class Context(Checks):
    """What a runner gets, and what it leaves for the metric readers; its
    own ``compare`` and ``correct`` are the program's check."""

    def __init__(self, spec: Spec, seed: int, seconds: float, trace: bool,
                 devices, require_tpu: bool = True):
        super().__init__()
        self.spec = spec
        self.config = spec.config
        self.traffic = spec.traffic
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.devices = devices
        self.require_tpu = require_tpu
        self.t_window0 = self.t_window1 = None
        self.end_to_end: dict[str, float] = {}   # filled by the runner
        self.observed: dict = {}                  # runner facts for readers
        self.attempted = 0
        self.failed = 0
        self.memory_peak_bytes = None
        self.trace_dir = CACHE / "trace" / spec.name
        self.device_trace = None                  # devtrace.Reduced
        self._tracing = False
        # control.py: the control's numbers, compared by the same rule
        self.control: Checks | None = None

    # -- the window -------------------------------------------------------
    def trace_begin(self) -> None:
        """With --trace 1, start the profiler just before the window, so
        that its own start-up lands outside it."""
        if self.trace and not self._tracing:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._tracing = True

    def window_start(self) -> None:
        if self.trace:
            import jax

            self.trace_begin()
            self._span = jax.profiler.TraceAnnotation("bench/window")
            self._span.__enter__()
        self.t_window0 = time.perf_counter()

    def window_end(self) -> None:
        self.t_window1 = time.perf_counter()
        if self.trace:
            import jax

            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            log(f"[trace] profiler stopped and written in "
                f"{time.perf_counter() - self.t_window1:.3f} s")

    @property
    def setup_s(self) -> float:
        return self.t_window0 - T_PROC

    @property
    def window_s(self) -> float:
        return self.t_window1 - self.t_window0

    def read_memory(self) -> None:
        """Peak bytes on the fullest chip; read before the reference runs,
        since a process's peak never falls again."""
        peaks = []
        for d in self.devices[:self.spec.chips]:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None


def _jax_setup():
    """Compile cache at a fixed path inside the checkout, every program
    kept, so that only a cell's first run in a checkout compiles."""
    path = CACHE / "jax"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def per_layer_values(ctx: Context) -> dict:
    out = {}
    for m in ctx.spec.per_layer:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(ctx: Context) -> dict:
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    out = {"correct": ctx.correct, "attempted": ctx.attempted,
           "failed": ctx.failed}
    if ctx.trace:
        red = ctx.device_trace
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["metrics"] = per_layer_values(ctx)
    else:
        out["metrics"] = {}
        for m in ctx.spec.end_to_end:
            v = ctx.setup_s if m["name"] == "setup_s" \
                else ctx.end_to_end.get(m["name"])
            if v is not None:
                out["metrics"][m["name"]] = {"value": float(v),
                                             "unit": m["unit"]}
    out["device"] = device
    if ctx.trace:
        out["breakdown"] = {"device_ops": red.top_ops,
                            "idle_gaps": red.idle_gaps}
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in ctx.compared.items()}
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description="chip benchmark: one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (SRC / "repro").is_dir():
        print(f"benchmark: no program under {SRC}; run from a checkout of "
              "the repository", file=sys.stderr)
        return 2
    spec = Spec(args.workload)
    jax = _jax_setup()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < spec.chips:
        print(f"benchmark: {args.workload} needs {spec.chips} TPU chip(s); "
              f"JAX sees {len(devices)} x {dev.platform} ({dev.device_kind})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    log(f"[setup] {args.workload}: device {dev.platform} {dev.device_kind} "
        f"x{len(devices)} (cell uses {spec.chips}); seed {args.seed}; "
        f"{args.seconds:g} s window; trace {args.trace}")
    ctx = Context(spec, args.seed, args.seconds, bool(args.trace), devices)
    return finish(ctx)


def finish(ctx: Context) -> int:
    """Run the cell's system and print the result; shared with the tests,
    which build a Context of their own off the chip."""
    system = load_module(HERE / "systems" / f"{ctx.config['system']}.py",
                         "bench_system_" + ctx.config["system"])
    system.run(ctx)
    if ctx.trace:
        from devtrace import reduce_trace

        t0 = time.perf_counter()
        red = ctx.device_trace = reduce_trace(ctx.trace_dir, ctx.spec.chips)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        log(f"[trace] window {red.window_s!r} s, busy {red.busy_s!r} s, "
            f"collectives {red.collective_s!r} s; read and reduced in "
            f"{time.perf_counter() - t0:.3f} s")
        for m, (sec, n) in sorted(red.modules.items(), key=lambda kv:
                                  -kv[1][0])[:12]:
            log(f"[trace] program {m}: {sec!r} s in {n} runs")
    line = result_line(ctx)
    for k, (v, lim) in ctx.compared.items():
        print(f"[check] {k} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    print(f"[check] correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
