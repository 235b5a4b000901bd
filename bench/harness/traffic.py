"""The one traffic generator. A mix is a data file of parameters
(`bench/traffic/<mix>.json`); this module turns it into requests.

Every seed serves the same sizes and gaps in the same order: they are
drawn from the mix's own `population_seed`, and the run's seed draws only
the token ids (and the weights). Which request meets which decides the
tails and the work a 51 s window sees: permuting the order by the seed
moved them by 25-55% from seed to seed on the chip.

Lognormal lengths and Poisson gaps follow `repro.serving.workload`
(`poisson`), copied here so that the yardstick does not move with the
program.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass
class Req:
    """One request the generator will send."""
    idx: int
    due: float             # seconds after the stream's start
    prompt_len: int
    output_len: int
    part: int = 0

    def tokens(self, seed: int, vocab: int) -> List[int]:
        rng = np.random.default_rng([seed, 2, self.part, self.idx])
        return rng.integers(0, vocab, self.prompt_len).tolist()


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def _gaps(arr: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n inter-arrival gaps (seconds) of the mix's arrival process."""
    proc = arr["process"]
    if proc == "poisson":
        return rng.exponential(1.0 / arr["rate"], n)
    if proc == "backlog":
        return np.zeros(n)
    raise ValueError(f"unknown arrival process {proc!r}")


WINDOW, RAMP, DRAIN = 0, 1, 2   # the parts of an open-loop stream


def stream(mix: dict, n: int, part: int,
           span: Optional[float] = None) -> List[Req]:
    """`n` requests of stream `part`: sizes and gaps from
    `population_seed` and `part`, the same for every run seed. With
    `span`, the gaps are scaled so that the n requests fall due over
    exactly `span` seconds."""
    pop = np.random.default_rng([mix["population_seed"], part])
    li = _lengths(mix["prompt"], n, pop)
    lo = _lengths(mix["output"], n, pop)
    gaps = _gaps(mix["arrivals"], n, pop)
    due = np.concatenate([[0.0], np.cumsum(gaps[1:])])
    if span is not None and n > 1:
        due = due * (span * (n - 1) / n) / due[-1]
    return [Req(i, float(due[i]), int(li[i]), int(lo[i]), part)
            for i in range(n)]


def open_loop(mix: dict, seconds: float):
    """(ramp, window, drain) of an open loop: the window's
    round(rate * seconds) requests fall due over exactly `seconds`, so
    every seed judges the same sizes over the same span; the ramp's and
    the drain's arrivals keep the rate before and after it."""
    rate = mix["arrivals"]["rate"]
    n = max(2, round(rate * seconds))
    ramp_n = max(1, round(rate * mix["ramp_seconds"]))
    drain_n = int(rate * mix["drain_limit_s"] * 1.5) + 8
    return (stream(mix, ramp_n, RAMP, span=mix["ramp_seconds"]),
            stream(mix, n, WINDOW, span=seconds),
            stream(mix, drain_n, DRAIN))
