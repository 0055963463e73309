"""Tests of the chip benchmark's own code, on the CPU at tiny sizes:
``PYTHONPATH=src python -m pytest benchmarks/chip -q``."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import json  # noqa: E402

import pytest  # noqa: E402

ROOT = HERE.parents[1]


class CpuDevice:
    """The CPU standing in for the chip: the runners read a memory limit."""
    platform = "cpu"
    device_kind = "cpu"

    def memory_stats(self):
        return {"bytes_limit": 600 * 2**20, "peak_bytes_in_use": 0}


def tiny_context(workload: str, seed: int, seconds: float, tmp_path):
    """A Context for ``workload`` at a tiny size of its configuration and
    traffic, on the CPU, with no compile cache and the pattern cache in
    ``tmp_path``."""
    import harness
    import pattern

    pattern.CACHE = tmp_path / "patterns"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / entry["file"]).read_text())
    if cfg["system"] == "cpals":
        cfg.update(dims=[40, 30, 50], nnz=3000, rank=8, value_rank=8,
                   name="tiny")
    else:
        small = dict(num_hidden_layers=2, hidden_size=64,
                     intermediate_size=128, num_attention_heads=4,
                     num_key_value_heads=2, head_dim=16, vocab_size=512)
        cfg.update(small)
        cfg["runtime"] = dict(d_model=64, d_ff=128, n_heads=4, n_kv_heads=2,
                              head_dim=16, vocab_size=512)
    spec = harness.Spec(workload, bench=bench, config=cfg)
    t = spec.traffic
    if cfg["system"] == "serve":
        t.update(rate_rps=20.0, prompt={"min": 16, "max": 120, "tail": 1.8},
                 output={"min": 4, "max": 16, "tail": 1.5},
                 loop={"max_batch": 4, "page_size": 8, "min_bucket": 16},
                 check_tokens=150)
    return harness.Context(spec, seed, seconds, False, [CpuDevice()],
                           require_tpu=False)


@pytest.fixture
def tiny(tmp_path):
    return lambda workload, seed, seconds=0.5: tiny_context(
        workload, seed, seconds, tmp_path)
