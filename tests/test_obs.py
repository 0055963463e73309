"""repro.obs — tracer, virtual timelines, drift auditor, instrumentation.

Every test leaves the global tracer disabled and empty: the tracer is
process-global state, and a leaked enable would silently wrap every backend
the rest of the suite constructs.
"""
import contextlib
import gc
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import backends, obs
from repro.core.psram import PsramConfig
from repro.core.schedule import build_matmul_program, count_cycles


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs.disable()
    obs.get_tracer().clear()
    yield
    obs.disable()
    obs.get_tracer().clear()


# ------------------------------------------------------------------ tracer


def _without_gc(events):
    """The events a test made: an automatic collection may add ``py/gc``
    spans anywhere while the tracer records."""
    return [e for e in events if e["name"] != "py/gc"]


def test_span_records_events_and_counters():
    obs.enable()
    with obs.span("test/outer", k=3):
        with obs.span("test/inner"):
            pass
        obs.counter("test/widgets", 2.0)
        obs.counter("test/widgets", 1.0)
    events = _without_gc(obs.get_tracer().events())
    names = [e["name"] for e in events]
    assert names == ["test/inner", "test/outer"]  # closed in LIFO order
    outer = events[1]
    assert outer["ph"] == "X" and outer["cat"] == "test"
    assert outer["args"] == {"k": 3}
    assert outer["dur"] >= events[0]["dur"]       # outer spans the inner
    assert obs.get_tracer().counters()["test/widgets"] == pytest.approx(3.0)


def test_summary_aggregates_per_name():
    obs.enable()
    for _ in range(3):
        with obs.span("test/unit"):
            pass
    s = obs.summary()
    assert s["test/unit"]["count"] == 3
    assert s["test/unit"]["total_s"] >= s["test/unit"]["max_s"]


def test_chrome_trace_is_valid_json(tmp_path):
    obs.enable()
    with obs.span("test/one"):
        pass
    obs.counter("test/n", 5)
    path = tmp_path / "trace.json"
    n = obs.write_trace(str(path))
    trace = json.loads(path.read_text())
    assert len(trace["traceEvents"]) == n
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert {"M", "X", "C"} <= phases              # meta + spans + counters


def test_disabled_tracer_is_null_and_cheap():
    """Disabled spans are one shared no-op object — no clock reads, no
    allocation per call — and a spanned hot loop must not meaningfully
    regress vs the bare loop (absolute bound: the per-iteration overhead
    of a disabled span stays in single-digit microseconds)."""
    assert not obs.enabled()
    assert obs.span("test/x") is obs.span("test/y", a=1)   # shared singleton
    obs.counter("test/never")                               # no-op
    assert obs.get_tracer().events() == []
    assert obs.get_tracer().counters() == {}

    n = 20_000

    def plain():
        acc = 0
        for i in range(n):
            acc += i
        return acc

    def spanned():
        acc = 0
        for i in range(n):
            with obs.span("test/hot"):
                acc += i
        return acc

    assert plain() == spanned()
    t_plain = min(_once(plain) for _ in range(3))
    t_span = min(_once(spanned) for _ in range(3))
    per_iter_overhead = max(0.0, t_span - t_plain) / n
    assert per_iter_overhead < 5e-6, (
        f"disabled span costs {per_iter_overhead * 1e6:.2f}us/iter")
    assert obs.get_tracer().events() == []        # still nothing recorded


def _once(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def test_stopwatch_measures_even_when_disabled():
    assert not obs.enabled()
    with obs.stopwatch("test/sw") as sw:
        pass
    assert sw.duration_s >= 0.0
    assert obs.get_tracer().events() == []        # measured, not recorded
    obs.enable()
    with obs.stopwatch("test/sw") as sw:
        pass
    assert sw.duration_s >= 0.0
    assert [e["name"] for e in _without_gc(obs.get_tracer().events())] == [
        "test/sw"]


# ------------------------------------------------- the profiler's clock


@contextlib.contextmanager
def _profiler_session(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _host_events(path):
    """``(name, start_ns, duration_ns, stats)`` of every event on the
    profiler's host planes."""
    from jax.profiler import ProfileData

    pb = sorted(Path(path).glob("**/*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(pb))
    return [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _double(x):
    return 2 * x


def test_span_lands_on_profiler_host_plane(tmp_path):
    """Inside a profiler session (tracer flag off) a span records: on the
    host plane under its bare name with its args as stats, around the
    host events of the jitted call it holds, and in the tracer."""
    double = jax.jit(_double)
    x = jnp.ones(8)
    double(x).block_until_ready()                  # compiled outside
    assert not obs.enabled()
    with _profiler_session(tmp_path):
        assert obs.enabled()
        with obs.span("test/host", batch=3, view=128):
            double(x).block_until_ready()
    host = _host_events(tmp_path)
    spans = [e for e in host if e[0] == "test/host"]
    assert len(spans) == 1
    _, t0, dur, stats = spans[0]
    assert stats == {"batch": 3, "view": 128}
    calls = [e for e in host if e[0] == "PjitFunction(_double)"]
    assert calls
    assert all(t0 <= c[1] and c[1] + c[2] <= t0 + dur for c in calls)
    recorded = _without_gc(obs.get_tracer().events())
    assert [e["name"] for e in recorded] == ["test/host"]
    assert recorded[0]["args"] == {"batch": 3, "view": 128}


def test_nothing_records_after_stop_trace(tmp_path):
    with _profiler_session(tmp_path):
        with obs.span("test/in"):
            pass
        with obs.stopwatch("test/sw_in"):
            pass
        if obs.enabled():                           # the guard idiom counts
            obs.counter("test/n")
    assert not obs.enabled()
    assert obs.span("test/out") is obs.span("test/other", a=1)  # shared null
    with obs.span("test/out"):
        pass
    with obs.stopwatch("test/sw_out") as sw:
        pass
    assert sw.duration_s >= 0.0
    obs.counter("test/n")
    names = [e["name"] for e in _without_gc(obs.get_tracer().events())]
    assert names == ["test/in", "test/sw_in"]
    assert obs.get_tracer().counters() == {"test/n": 1.0}


def test_gc_collection_records_a_span(tmp_path):
    gc.collect()                                    # neither on: nothing
    assert obs.get_tracer().events() == []
    with _profiler_session(tmp_path):
        gc.collect()
    mine = [e for e in obs.get_tracer().events()
            if e["name"] == "py/gc" and e["args"] == {"generation": 2}]
    assert mine                        # the forced one, perhaps not alone
    assert any(e[0] == "py/gc" and e[3] == {"generation": 2}
               for e in _host_events(tmp_path))


def test_profiler_session_builds_no_instrumented_backend(tmp_path):
    from repro.obs.instrument import InstrumentedBackend

    with _profiler_session(tmp_path):
        assert obs.enabled()
        be = backends.get("exact")
    assert not isinstance(be, InstrumentedBackend)


# ---------------------------------------------------------- virtual timeline


def test_program_timeline_tracks_and_cycle_math():
    cfg = PsramConfig()
    prog = build_matmul_program(128, 300, 40, cfg)
    events = obs.program_timeline(prog, pid=7, name="unit")
    metas = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    thread_names = {e["args"]["name"] for e in metas
                    if e["name"] == "thread_name"}
    assert "store" in thread_names
    assert any(t.startswith("ch") for t in thread_names)
    assert all(e["pid"] == 7 for e in xs)
    # the rendered window never outruns the counted schedule
    counts = count_cycles(prog)
    window = counts.total_cycles / prog.repeats
    assert max(e["ts"] + e["dur"] for e in xs) <= window * prog.repeats
    json.dumps(events)                            # Perfetto-loadable


def test_program_timeline_coalesces_under_budget():
    cfg = PsramConfig()
    prog = build_matmul_program(512, 1024, 512, cfg)
    small = obs.program_timeline(prog, pid=1, max_events=200)
    n_tracks = sum(1 for e in small
                   if e["ph"] == "M" and e["name"] == "thread_name")
    # the budget is soft by one slice per track (ceil-grouping)
    assert len([e for e in small if e["ph"] == "X"]) <= 200 + n_tracks
    # aggregates carry their op/busy-cycle totals
    assert any("ops" in e.get("args", {}) for e in small if e["ph"] == "X")


def test_mesh_timeline_per_array_tracks_and_fabric():
    from repro.sparse import mesh_counted_price

    cfg = PsramConfig()
    fibers = tuple((13 * i) % 97 + 1 for i in range(64))
    rank = 16
    events = obs.mesh_timeline(fibers, rank, config=cfg, n_arrays=4)
    proc_names = {e["args"]["name"] for e in events
                  if e["ph"] == "M" and e["name"] == "process_name"}
    assert sum(1 for p in proc_names if p.startswith("array")) == 4
    assert any("fabric" in p for p in proc_names)
    price, _ = mesh_counted_price(fibers, rank, cfg, n_arrays=4)
    reduce_ev = [e for e in events
                 if e["ph"] == "X" and e["name"] == "allreduce"]
    assert len(reduce_ev) == 1
    assert reduce_ev[0]["ts"] == price.makespan_cycles
    assert reduce_ev[0]["dur"] == max(1, price.reduce_cycles)


# ------------------------------------------------------------ drift auditor


def test_drift_report_is_zero_on_paper_operating_point():
    """The estimate==measured contract: on §V-A the analytical closed forms
    and the counted schedules agree exactly — the CI gate asserts the same
    via ``python -m repro.obs.drift --fail-on-drift``."""
    report = obs.drift_report()
    assert len(report.rows) >= 4                  # dense x2, matmul, sparse, mesh
    assert report.max_drift == 0.0
    workloads = {r.workload for r in report.rows}
    assert any("mesh" in w for w in workloads)
    assert any("sparse" in w for w in workloads)
    # the table + json render without error and carry every row
    assert len(report.table().strip().splitlines()) >= len(report.rows) + 1
    payload = report.to_json()
    assert len(payload["rows"]) == len(report.rows)
    json.dumps(payload)                           # serializable as-is


def test_drift_cli_exit_codes(tmp_path, capsys):
    from repro.obs import drift

    out = tmp_path / "drift.json"
    assert drift.main(["--json", str(out), "--fail-on-drift"]) == 0
    assert json.loads(out.read_text())["max_drift"] == 0.0
    capsys.readouterr()


# ---------------------------------------------------------- instrumentation


def test_registry_wraps_backends_only_when_enabled():
    from repro.obs.instrument import InstrumentedBackend

    be = backends.get("exact")
    assert not isinstance(be, InstrumentedBackend)
    obs.enable()
    be = backends.get("exact")
    assert isinstance(be, InstrumentedBackend)
    # instances pass through unwrapped — and instrumented ones re-enter
    assert backends.get(be) is be
    inner = be.inner
    assert backends.get(inner) is inner


def test_instrumented_backend_is_transparent():
    obs.enable()
    be = backends.get("exact")
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    got = be.matmul(x, w)
    raw = be.inner.matmul(x, w)
    assert bool(jnp.all(got == raw))
    assert be.name == be.inner.name
    assert be.capabilities() == be.inner.capabilities()
    names = [e["name"] for e in obs.get_tracer().events()]
    assert "backend/exact/matmul" in names
    span = next(e for e in obs.get_tracer().events()
                if e["name"] == "backend/exact/matmul")
    assert span["args"]["m"] == 8 and span["args"]["n"] == 4


def test_executor_spans_cover_the_stack():
    obs.enable()
    cfg = PsramConfig()
    from repro.core.schedule import execute
    prog = build_matmul_program(64, 128, 32, cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    execute(prog, x, w)
    names = [e["name"] for e in obs.get_tracer().events()]
    assert "schedule/execute/matmul" in names
    assert obs.get_tracer().counters()["schedule/programs_executed"] == 1.0


def test_stream_and_mesh_spans():
    from repro.sparse import csf_for_mode, mesh_stream_mttkrp, powerlaw_coo
    from repro.sparse import stream_mttkrp

    obs.enable()
    cfg = PsramConfig()
    shape = (40, 30, 20)
    coo = powerlaw_coo(jax.random.PRNGKey(0), shape, nnz=500, rank=4,
                       alpha=1.1)
    csf = csf_for_mode(coo, 0)
    fs = tuple(jax.random.normal(jax.random.PRNGKey(d + 1), (s, 8))
               for d, s in enumerate(shape))
    stream_mttkrp(csf, fs, cfg)
    mesh_stream_mttkrp(csf, fs, cfg, n_arrays=1)
    names = [e["name"] for e in obs.get_tracer().events()]
    assert "stream/mttkrp/execute" in names
    assert "mesh/stream/execute" in names
    assert "mesh/shard0/plan" in names
    counters = obs.get_tracer().counters()
    assert counters["stream/nonzeros"] >= csf.nnz  # both paths stream
    assert counters["mesh/shard0/nnz"] == csf.nnz  # one array: whole tensor


# --------------------------------------------- serve.offload_report schema


def test_offload_report_sparse_mesh_key_schema():
    """The sparse path's mesh keys — the contract examples/ and dashboards
    read: makespan/reduce cycles and the array count, consistent with
    ``mesh_counted_price`` on the same operands."""
    from repro.serve import offload_report
    from repro.sparse import mesh_counted_price

    fibers = tuple((7 * i) % 53 + 1 for i in range(48))
    rep = offload_report(fibers, rank=16, n_arrays=2)
    assert {"makespan_cycles", "reduce_cycles", "n_arrays"} <= set(rep)
    assert rep["n_arrays"] == 2
    cfg = backends.get("psram-stream").config
    price, _ = mesh_counted_price(fibers, 16, cfg, n_arrays=2)
    assert rep["makespan_cycles"] == price.makespan_cycles
    assert rep["reduce_cycles"] == price.reduce_cycles
    assert rep["cycles"].total_cycles == price.counts.total_cycles
