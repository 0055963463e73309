"""The readers of the program's spans, on a tracer built by hand."""
import pytest

import harness
from repro import obs
from repro.obs.tracer import Tracer

READERS = ("loop_idle.code", "intake_late_p95_ms.code",
           "decode_host_ms.code", "decode_gap_p95_ms.code",
           "prepare_s.cpals")


def reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py")


def span(name, t_ms, dur_ms, **args):
    ev = {"name": name, "ph": "X", "pid": 0, "tid": 0, "ts": 1e3 * t_ms,
          "dur": 1e3 * dur_ms, "cat": name.split("/", 1)[0]}
    if args:
        ev["args"] = args
    return ev


@pytest.fixture
def tracer(monkeypatch):
    t = Tracer()
    monkeypatch.setattr(obs, "get_tracer", lambda: t)
    return t


@pytest.fixture
def ctx(tiny):
    c = tiny("granite8b.code", 1)
    c.t_window0, c.t_window1 = 10.0, 11.0          # a 1 s window
    return c


def test_readers_find_nothing_in_an_empty_tracer(tracer, ctx):
    for name in READERS:
        assert reader(name).read(ctx) is None


def test_serve_readers(tracer, ctx):
    # idle 0-100 ms; steps at 100 (10 ms) and 130 (10 ms); idle 140-500;
    # a step at 500 (10 ms) and one at 600 (20 ms): gaps 20 and 90 ms,
    # the 360 ms across the idle stretch left out
    ev = [span("serve/idle", 0, 100), span("serve/idle", 140, 360)]
    for t, d, dec in ((100, 10, 8), (130, 10, 9), (500, 10, 7),
                      (600, 20, 16)):
        ev += [span("serve/step", t, d, batch=2),
               span("serve/decode", t + 1, dec, batch=2, view=64)]
    ev += [span("serve/enqueue", 100 * i, 0.01, rid=i, late_ms=float(i))
           for i in range(1, 21)]
    tracer.add_events(ev)
    assert reader("loop_idle.code").read(ctx) == pytest.approx(46.0)
    assert reader("intake_late_p95_ms.code").read(ctx) == 19.0
    # host time per step: 2, 1, 3, 4 ms
    assert reader("decode_host_ms.code").read(ctx) == pytest.approx(2.5)
    assert reader("decode_gap_p95_ms.code").read(ctx) == pytest.approx(90.0)
    assert reader("prepare_s.cpals").read(ctx) is None


def test_prepare_reads_the_last_call(tracer, ctx):
    tracer.add_events([span("als/prepare", 0, 2500.0, rank=32),
                       span("als/sweep", 2600, 1300.0),
                       span("als/prepare", 5000, 4200.0, rank=32)])
    assert reader("prepare_s.cpals").read(ctx) == pytest.approx(4.2)
    assert reader("loop_idle.code").read(ctx) is None
