"""The tensor's nonzero pattern belongs to the configuration: two seeds
decompose the same coordinates through the same compiled shapes, with
different values and initial factors."""
import harness


def test_seeds_share_pattern_not_values(tiny):
    seen = []
    for seed in (3, 2**31 + 11):
        ctx = tiny("nell2.cpals", seed, seconds=0.2)
        harness.finish(ctx)
        assert ctx.correct
        seen.append(ctx.observed)
    a, b = seen
    assert a["pattern"] == b["pattern"] and a["nnz"] == b["nnz"]
    assert a["n_seg"] == b["n_seg"] and len(a["n_seg"]) == 3
    assert a["layout_shapes"] == b["layout_shapes"]
    assert a["values"] != b["values"]
    assert a["init"] != b["init"]


def test_pattern_cache_round_trip(tmp_path):
    import pattern

    pattern.CACHE = tmp_path
    cfg = {"name": "t", "dims": [7, 5, 9], "nnz": 200, "pattern_seed": 0,
           "alpha": 1.1}
    drawn, d1 = pattern.load(cfg)
    loaded, d2 = pattern.load(cfg)
    assert d1 == d2 and (drawn == loaded).all()
    assert len({tuple(r) for r in drawn}) == len(drawn)   # duplicates merged
