"""The one traffic generator: a cell's traffic file in, a request stream out.

A cell file (``cells/<cell>.json``) describes its traffic as data; this
module turns it into arrivals and request sizes from ``--seed``.  Every
seed draws the same multiset of sizes and inter-arrival gaps -- stratified
quantiles of the stated distributions -- and the seed only chooses their
order and the request contents.  So two seeds offer the same work, and the
run-to-run spread measures the system, not the draw.

Keys a cell file may give:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the system does)
  or ``"closed"`` (``clients`` callers, each sending its next request when
  its last one returns).
* ``arrivals`` (open loop): ``{"kind": "poisson", "rate_rps": r}`` or
  ``{"kind": "bursty", "rate_lo_rps", "rate_hi_rps", "mean_calm_s",
  "mean_burst_s"}`` (a Markov-modulated Poisson process: calm spells and
  bursts with exponential dwell times).
* ``mix``: ``[[workload, weight], ...]``, the share of each named model of
  the configuration (one-model configurations leave it out).
* ``prompt_len``: ``{"values": [...], "weights": [...]}``; ``output_len``:
  ``{"kind": "loguniform", "low", "high"}`` or ``{"kind": "fixed",
  "value"}`` (token configurations).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

# Requests drawn per stratified block in a closed loop, whose length is not
# known in advance: every block holds the stated shares exactly.
BLOCK = 1024


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use of the seed (any size of seed)."""
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def stratified_counts(weights: Sequence[float], n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` draws over ``weights``."""
    w = np.asarray(weights, np.float64)
    if n < 0 or w.ndim != 1 or not len(w) or (w < 0).any() or w.sum() <= 0:
        raise ValueError(f"bad weights {weights!r} or count {n}")
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return counts


def stratified_choice(values: Sequence, weights: Sequence[float], n: int,
                      rng: np.random.Generator) -> np.ndarray:
    """``n`` values in the stated shares, in an order drawn from ``rng``."""
    out = np.repeat(np.asarray(values), stratified_counts(weights, n))
    rng.shuffle(out)
    return out


def exponential_gaps(rate: float, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate``: the
    exponential's quantiles at (i + 1/2)/n, shuffled."""
    if rate <= 0 or n < 1:
        raise ValueError("exponential_gaps needs rate > 0 and n >= 1")
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    rng.shuffle(gaps)
    return gaps


def bursty_times(rate_lo: float, rate_hi: float, mean_calm_s: float,
                 mean_burst_s: float, seconds: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Arrival times in [0, seconds) of a Markov-modulated Poisson process:
    calm spells at ``rate_lo`` and bursts at ``rate_hi``, exponential dwell
    times.  Flips are memoryless, so dropping the partial gap at a flip
    keeps the process exact."""
    if min(rate_lo, rate_hi, mean_calm_s, mean_burst_s) <= 0:
        raise ValueError("bursty arrivals need positive rates and dwells")
    ts: List[float] = []
    t, burst = 0.0, False
    state_end = rng.exponential(mean_calm_s)
    while True:
        gap = rng.exponential(1.0 / (rate_hi if burst else rate_lo))
        if t + gap > state_end:
            t = state_end
            burst = not burst
            state_end = t + rng.exponential(
                mean_burst_s if burst else mean_calm_s)
            continue
        t += gap
        if t >= seconds:
            return np.asarray(ts)
        ts.append(t)


def arrival_times(spec: Dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds after the window opens) of an open loop."""
    rng = rng_for(seed, 1)
    kind = spec["kind"]
    if kind == "poisson":
        n = max(1, int(round(spec["rate_rps"] * seconds)))
        return np.cumsum(exponential_gaps(spec["rate_rps"], n, rng))
    if kind == "bursty":
        return bursty_times(spec["rate_lo_rps"], spec["rate_hi_rps"],
                            spec["mean_calm_s"], spec["mean_burst_s"],
                            seconds, rng)
    raise ValueError(f"unknown arrival kind {kind!r}")


def loguniform_quantiles(low: float, high: float, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return np.exp(math.log(low) + u * (math.log(high) - math.log(low)))


class Stream:
    """Sizes of a request stream, drawn ``block`` requests at a time; each
    block holds the stated shares and quantiles exactly, in its own order.
    An open loop passes its whole length as the block."""

    def __init__(self, cell: Dict, seed: int, block: int = BLOCK) -> None:
        self.cell, self.seed, self.block = cell, seed, block
        self._blocks: Dict[int, Dict[str, np.ndarray]] = {}

    def _get(self, key: str, i: int) -> int:
        k, j = divmod(i, self.block)
        if k not in self._blocks:
            self._blocks[k] = draw_block(self.cell, self.block,
                                         rng_for(self.seed, 100 + k))
        return int(self._blocks[k][key][j])

    def workload(self, i: int) -> int:
        """Index into the cell's ``mix`` of request ``i``."""
        return self._get("workload", i) if "mix" in self.cell else 0

    def prompt_len(self, i: int) -> int:
        return self._get("prompt_len", i)

    def output_len(self, i: int) -> int:
        return self._get("output_len", i)


def draw_block(cell: Dict, n: int, rng: np.random.Generator
               ) -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    if "mix" in cell:
        out["workload"] = stratified_choice(
            np.arange(len(cell["mix"])), [w for _, w in cell["mix"]], n, rng)
    if "prompt_len" in cell:
        pl = cell["prompt_len"]
        out["prompt_len"] = stratified_choice(pl["values"], pl["weights"],
                                              n, rng)
    if "output_len" in cell:
        out["output_len"] = output_lengths(cell["output_len"], n, rng)
    return out


def output_lengths(spec: Dict, n: int, rng: np.random.Generator
                   ) -> np.ndarray:
    if spec["kind"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["kind"] == "loguniform":
        out = np.rint(loguniform_quantiles(spec["low"], spec["high"], n))
        rng.shuffle(out)
        return out.astype(np.int64)
    raise ValueError(f"unknown output_len kind {spec['kind']!r}")
