"""The one traffic generator: a mix is a data file, this turns it into work.

A mix (``perfbench/traffic/<name>.json``) gives distributions and an arrival
rule; nothing here knows a mix by name. Every seed gets the *same set* of sizes
and inter-arrival gaps — the distribution's own quantiles, so the set has the
distribution's shape exactly — in another order, and other token ids. A run's
work therefore does not depend on the luck of the seed; only its order does.

Imports numpy and the standard library only: the load generator is a process
of its own and must not touch JAX (one process holds the chip).
"""

from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import Any, Dict, List, Sequence

import numpy as np

_NORMAL = statistics.NormalDist()


def load_mix(path: str | Path) -> Dict[str, Any]:
    with open(path) as fh:
        mix = json.load(fh)
    for key in ("kind", "pool"):
        if key not in mix:
            raise ValueError(f"traffic mix {path} lacks {key!r}")
    return mix


def quantile_set(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole sizes at the mid-quantiles of ``dist``, clipped to its range.

    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}`` or
    ``{"dist": "fixed", "value": v}``.
    """
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["value"]), dtype=np.int64)
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    qs = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(q)) for q in qs])
    sizes = np.rint(dist["median"] * np.exp(dist["sigma"] * z)).astype(np.int64)
    return np.clip(sizes, int(dist["min"]), int(dist["max"]))


def size_pool(mix: Dict[str, Any]) -> np.ndarray:
    """The mix's fixed ``(pool, 2)`` table of (prompt, output) sizes.

    Prompt and output quantiles are paired by a permutation drawn from the
    mix's own ``pairing_seed``, not from the run's seed, so the table is the
    same in every run of the mix.
    """
    n = int(mix["pool"])
    prompts = quantile_set(mix["prompt_tokens"], n)
    outputs = quantile_set(mix["output_tokens"], n)
    pairing = np.random.default_rng(int(mix.get("pairing_seed", 0))).permutation(n)
    return np.stack([prompts, outputs[pairing]], axis=1)


def _order(seed: int, epoch: int, n: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 0x0DE4, int(epoch)]).permutation(n)


class RequestStream:
    """Requests of one run, by index: sizes from the pool in the seed's order
    (reshuffled each time the pool is used up), token ids from the seed."""

    def __init__(self, mix: Dict[str, Any], seed: int, vocab_size: int) -> None:
        self.mix = mix
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.pool = size_pool(mix)
        self._orders: Dict[int, np.ndarray] = {}

    def sizes(self, index: int) -> tuple[int, int]:
        n = len(self.pool)
        epoch, slot = divmod(int(index), n)
        if epoch not in self._orders:
            self._orders[epoch] = _order(self.seed, epoch, n)
        prompt, output = self.pool[self._orders[epoch][slot]]
        return int(prompt), int(output)

    def prompt(self, index: int) -> List[int]:
        length, _ = self.sizes(index)
        rng = np.random.default_rng([self.seed, 0x70C5, int(index)])
        return rng.integers(0, self.vocab_size, length).tolist()

    def request(self, index: int) -> Dict[str, Any]:
        _, output = self.sizes(index)
        return {"index": int(index), "prompt_ids": self.prompt(index), "max_new_tokens": output}


def closed_index(client: int, turn: int, clients: int) -> int:
    """Request index of a closed-loop client's ``turn``-th request."""
    return int(turn) * int(clients) + int(client)


def first_turn_cut(client_rank: int, clients: int, output: int) -> int:
    """A client's first answer is cut to a uniform share of its drawn length,
    so that clients which start together do not finish together."""
    return max(1, math.ceil(output * (client_rank + 1) / clients))


def client_ranks(seed: int, clients: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 0xC1E7]).permutation(clients)


def _segment_arrivals(rate: float, length_s: float, burst: Any, rng: np.random.Generator) -> np.ndarray:
    """``round(rate * length_s)`` arrivals in ``[0, length_s)``: the exponential
    distribution's mid-quantile gaps in the generator's order, each arrival at
    the start of its gap, scaled so that the gaps fill the segment exactly.
    With a burst the same arrivals are warped in time: inside a burst the rate
    is ``factor`` times the rate outside, the segment's count unchanged."""
    n = int(round(rate * length_s))
    if n <= 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps[rng.permutation(n)]
    starts = (np.cumsum(gaps) - gaps) / gaps.sum()  # in [0, 1): shares of the segment's arrivals
    if not burst:
        return starts * length_s
    grid = np.linspace(0.0, length_s, 4096)
    in_burst = (grid % float(burst["every_s"])) < float(burst["len_s"])
    intensity = np.where(in_burst, float(burst["factor"]), 1.0)
    cumulative = np.concatenate([[0.0], np.cumsum((intensity[1:] + intensity[:-1]) / 2)])
    return np.interp(starts, cumulative / cumulative[-1], grid)


def arrival_times(mix: Dict[str, Any], seed: int, seconds: float) -> np.ndarray:
    """Due times (seconds from the start of offered load) of an open-loop mix:
    the ramp's arrivals in ``[0, ramp_s)``, then the window's in
    ``[ramp_s, ramp_s + seconds)``.

    A Poisson-like schedule whose set of gaps, and whose number of arrivals in
    the ramp and in the window, are the same on every seed; the seed orders the
    gaps. ``"burst"`` (``{"every_s", "len_s", "factor"}``) multiplies the rate
    inside recurring bursts, the mean rate staying ``rate_per_s``.
    """
    arrival = mix["arrival"]
    rate, ramp_s = float(arrival["rate_per_s"]), float(arrival.get("ramp_s", 0.0))
    burst = arrival.get("burst")
    ramp = _segment_arrivals(rate, ramp_s, burst, np.random.default_rng([int(seed), 0xA880]))
    window = _segment_arrivals(rate, float(seconds), burst, np.random.default_rng([int(seed), 0xA881]))
    return np.concatenate([ramp, ramp_s + window])


def documents(mix: Dict[str, Any], seed: int, vocab_size: int, count: int) -> List[np.ndarray]:
    """``count`` training documents: lengths cycle through the mix's quantile
    set in the seed's order, token ids uniform from the seed."""
    n = int(mix["pool"])
    lengths = quantile_set(mix["document_tokens"], n)
    rng = np.random.default_rng([int(seed), 0xD0C5])
    docs: List[np.ndarray] = []
    epoch = 0
    while len(docs) < count:
        for slot in _order(seed, epoch, n):
            if len(docs) == count:
                break
            docs.append(rng.integers(0, vocab_size, int(lengths[slot]), dtype=np.int32))
        epoch += 1
    return docs


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least ``q`` percent of the sample at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def tail_mean(values: Sequence[float], share: float) -> float:
    """Mean of the largest ``share`` (0..1) of the sample, at least one value:
    a tail that moves a little when the sample does, where a percentile of
    values that come in steps jumps a whole step."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("tail mean of an empty sample")
    count = max(1, math.ceil(share * len(ordered)))
    return float(sum(ordered[-count:]) / count)
