"""The harness's own contract: which metrics a cell reports, the result
line's keys, seeds past 32 bits, and what counts as correct."""
import json
import math

import harness


def test_cells_report_their_metrics():
    for w in ("nell2.cpals", "granite8b.code"):
        spec = harness.Spec(w)
        e2e = {m["name"] for m in spec.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.per_layer
        assert all(m["moves"] in e2e for m in spec.per_layer)
        for m in spec.per_layer:
            assert (harness.HERE / "metrics" / f"{m['name']}.py").exists()


def test_result_line_keys(tiny):
    ctx = tiny("nell2.cpals", 1)
    ctx.t_window0, ctx.t_window1 = harness.T_PROC + 2.0, harness.T_PROC + 3.0
    ctx.end_to_end["sweep_s"] = 0.5
    ctx.compare("mttkrp_rel", 0.01, 0.02)
    line = harness.result_line(ctx)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["metrics"]["sweep_s"] == {"value": 0.5, "unit": "s"}
    assert line["metrics"]["setup_s"]["value"] == 2.0
    assert line["correct"] is True
    json.dumps(line)


def test_correct_needs_every_number_within_its_limit(tiny):
    ctx = tiny("nell2.cpals", 1)
    assert not ctx.correct                      # nothing compared yet
    ctx.compare("a", 0.5, 1.0)
    assert ctx.correct
    ctx.compare("b", math.nan, 1.0)
    assert not ctx.correct


def test_large_seeds_draw_apart():
    import jax

    keys = {tuple(jax.random.key_data(harness.jax_key(s)).tolist())
            for s in (5, 5 + 2**32, 2**31 + 5, -5)}
    assert len(keys) == 4
