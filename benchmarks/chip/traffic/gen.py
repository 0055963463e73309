"""The one traffic generator of the serving cells; a mix is a JSON file.

Copied from the program's ``serve/traffic.py`` (seeded Poisson arrivals,
bounded-Pareto lengths) with one change of substance: the sizes and the
arrival times are a property of the mix. ``shape_seed`` in the mix's file
draws the (prompt, output) lengths and the gaps between arrivals once, in
their order; ``--seed`` draws only the prompts' token ids. Order matters
as much as the set: the tail of time to first token depends on which long
prompts arrive close together, so a seed that reshuffled them would
change the work from run to run.

A mix's keys:

* ``arrival``: ``"poisson"``, at ``rate_rps``;
* ``prompt`` / ``output``: ``{"min", "max", "tail"}``, lengths
  ``min * (1 + Lomax(tail))`` clipped to ``[min, max]``;
* ``shape_seed``: the draw of sizes and gaps;
* ``temperature``: 0 for greedy decoding.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    arrival_s: float          # due time, seconds after the window opens
    prompt: np.ndarray        # (prompt_len,) int32
    decode_len: int

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def bounded_pareto(rng, n: int, lo: int, hi: int, alpha: float):
    draw = lo * (1.0 + rng.pareto(alpha, size=n))
    return np.clip(draw.astype(np.int64), lo, hi)


def poisson_gaps(rng, n: int, rate: float):
    return rng.exponential(1.0 / rate, size=n)


def shapes(mix: dict, seconds: float):
    """The mix's fixed arrival gaps (seconds) and (prompt, output) lengths
    for a window of ``seconds``: every gap whose arrival falls inside it."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    rng = np.random.default_rng(mix["shape_seed"])
    rate = float(mix["rate_rps"])
    gaps = poisson_gaps(rng, int(rate * seconds * 3) + 64, rate)
    n = int(np.searchsorted(np.cumsum(gaps), seconds, side="right"))
    gaps = gaps[:n]
    p, o = mix["prompt"], mix["output"]
    prompts = bounded_pareto(rng, n, p["min"], p["max"], p["tail"])
    outputs = bounded_pareto(rng, n, o["min"], o["max"], o["tail"])
    return gaps, prompts, outputs


def generate(mix: dict, rng, seconds: float, vocab: int) -> list[Request]:
    """The run's requests: the mix's shapes, prompt token ids from ``rng``
    in ``[2, vocab)``."""
    gaps, prompts, outputs = shapes(mix, seconds)
    arrivals = np.cumsum(gaps)
    return [Request(rid=i, arrival_s=float(arrivals[i]),
                    prompt=rng.integers(2, vocab, size=int(prompts[i]),
                                        dtype=np.int32),
                    decode_len=int(outputs[i]))
            for i in range(len(gaps))]
