"""The trace reduction, on events whose answers are worked by hand and on
a small trace recorded on the chip (``testdata/``)."""
import json
from pathlib import Path

import pytest

import devtrace

HERE = Path(__file__).resolve().parent


def test_reduce_by_hand():
    ms = 1_000_000
    events = {
        "host": [["bench/window", 0, 100 * ms],
                 ["PjitFunction(run)", 5 * ms, 90 * ms],
                 ["np.asarray", 58 * ms, 4 * ms]],
        "devices": {
            0: {"modules": [["jit_run(1)", 10 * ms, 30 * ms],
                            ["jit_run(1)", 35 * ms, 20 * ms],   # overlaps
                            ["jit__stream_exec(2)", 60 * ms, 10 * ms],
                            ["jit_other(3)", 95 * ms, 10 * ms]],  # clipped
                "ops": [["fusion.1", 10 * ms, 25 * ms],
                        ["all-reduce.3", 36 * ms, 4 * ms],
                        ["fusion.9", 60 * ms, 10 * ms]]},
            1: {"modules": [["jit_run(1)", 0, 50 * ms]], "ops": []},
        },
    }
    red = devtrace.reduce_events(events, chips=2)
    assert red.window_s == pytest.approx(0.1)
    # chip 0 busy: [10, 55] + [60, 70] + [95, 100] = 60 ms; chip 1: 50 ms
    assert red.busy_s == pytest.approx(0.055)
    assert red.module_s("stream_mttkrp") == pytest.approx(0.050)
    assert red.module_runs("stream_mttkrp") == 2
    assert red.module_s("fit") == pytest.approx(0.010)
    assert red.collective_s == pytest.approx(0.004)
    assert red.top_ops[0] == ["jit_run/fusion.1", pytest.approx(0.025)]
    # gaps on chip 0: [0, 10], [55, 60], [70, 95]; the one across 57.5 ms
    # is named by the innermost host span open there
    gaps = {round(s, 6): n for n, s in red.idle_gaps}
    assert set(gaps) == {0.010, 0.005, 0.025}
    assert gaps[0.005] == "PjitFunction(run)"
    assert red.idle_share == pytest.approx(0.45)


RECORDED = sorted((HERE / "testdata").glob("trace_*.json"))


def _sweep_line(events, w0, w1):
    """Busy nanoseconds by counting open programs at each boundary: a
    second way to the union the reducer takes."""
    marks = []
    for _, t, d in events:
        a, b = max(t, w0), min(t + d, w1)
        if b > a:
            marks += [(a, 1), (b, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(marks):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_trace(path):
    events = json.loads(path.read_text())
    red = devtrace.reduce_events(events, chips=1)
    mods = events["devices"]["0"]["modules"]
    win = [e for e in events["host"] if e[0] == devtrace.WINDOW_SPAN]
    w0 = win[0][1] if win else min(e[1] for e in mods)
    w1 = w0 + win[0][2] if win else max(e[1] + e[2] for e in mods)
    assert red.window_s == pytest.approx((w1 - w0) / 1e9)
    assert red.busy_s == pytest.approx(_sweep_line(mods, w0, w1) / 1e9)
    assert 0 < red.busy_s <= red.window_s
    per_module = sum(s for s, _ in red.modules.values())
    assert per_module >= red.busy_s * (1 - 1e-9)     # overlaps count twice
    assert len(red.top_ops) <= 10 and len(red.idle_gaps) <= 10
