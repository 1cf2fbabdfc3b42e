"""The general traffic generator: every mix is a data file it reads.

Sizes and gaps are *stratified*: a mix of ``count`` requests takes the
distribution's quantiles at (i + 1/2) / count, dealt out in one fixed
order (``SCHEDULE``).  The seed draws the token ids, never the sizes or
their order: a serving cell near its capacity is so sensitive to which
long prompt comes when that seeds dealing the same sizes in other
orders spread by 36-40 % (serve.mamba2.chat, PERF.md), far more than
two runs of one seed (under 0.1 %).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Sequence, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()
SCHEDULE = 0        # the seed of the fixed order of sizes and gaps


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A numpy generator for any whole-number seed (also past 32 bits)."""
    return np.random.default_rng([seed % (1 << 64), stream])


def jax_key(seed: int, stream: int = 0):
    """A JAX PRNG key for any whole-number seed: its low and high 32 bits
    both enter the key."""
    import jax
    seed %= 1 << 64
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def quantile(dist: Dict, q: float) -> float:
    """Inverse CDF of a length distribution, clipped to its bounds."""
    kind = dist["dist"]
    if kind == "lognormal":
        value = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    elif kind == "uniform":
        value = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "fixed":
        value = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(value, dist.get("min", value)), dist.get("max", value))


def stratified_lengths(dist: Dict, count: int, rng: np.random.Generator,
                       offset: float = 0.5) -> np.ndarray:
    """``count`` lengths at the quantiles (i + offset) / count, permuted."""
    qs = (np.arange(count) + offset) / count
    values = np.array([int(round(quantile(dist, q))) for q in qs], np.int64)
    return rng.permutation(values)


def stratified_gaps(rate: float, count: int,
                    rng: np.random.Generator) -> np.ndarray:
    """Poisson inter-arrival gaps: exponential quantiles, permuted."""
    qs = (np.arange(count) + 0.5) / count
    return rng.permutation(-np.log1p(-qs) / rate)


@dataclasses.dataclass
class Request:
    """One request of a serving mix; ``arrival`` is seconds into the
    window (open loop) and ``client`` the sender (closed loop)."""
    index: int
    arrival: float
    prompt: np.ndarray
    max_new_tokens: int
    client: int = -1


def _prompt(rng: np.random.Generator, length: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(length), dtype=np.int32)


def open_loop(mix: Dict, rate: float, seconds: float, vocab: int,
              seed: int) -> List[Request]:
    """Requests due in a window of ``seconds`` at ``rate`` per second."""
    count = max(int(math.ceil(rate * seconds)), 1)
    order = rng_for(SCHEDULE, 1)
    arrivals = np.cumsum(stratified_gaps(rate, count, order))
    prompts = stratified_lengths(mix["prompt"], count, order)
    outputs = stratified_lengths(mix["output"], count, order)
    rng = rng_for(seed, 1)
    return [Request(i, float(arrivals[i]), _prompt(rng, prompts[i], vocab),
                    int(outputs[i]))
            for i in range(count) if arrivals[i] < seconds]


def closed_loop(mix: Dict, clients: int, rounds: int, vocab: int,
                seed: int) -> List[List[Request]]:
    """Per client, the requests it sends one after another.  Round r
    covers the whole distribution at the quantiles (i + (r + 1/2) /
    rounds) / clients, dealt over the clients in the fixed order, so every
    round, the first included, is the same work for every seed."""
    order = rng_for(SCHEDULE, 2)
    rng = rng_for(seed, 2)
    queues: List[List[Request]] = [[] for _ in range(clients)]
    index = 0
    for r in range(rounds):
        offset = (r + 0.5) / rounds
        prompts = stratified_lengths(mix["prompt"], clients, order, offset)
        outputs = stratified_lengths(mix["output"], clients, order, offset)
        for c in range(clients):
            queues[c].append(Request(index, 0.0,
                                     _prompt(rng, prompts[c], vocab),
                                     int(outputs[c]), client=c))
            index += 1
    return queues


def call_rounds(calls: Sequence[Tuple], seed: int, rounds: int
                ) -> List[Tuple]:
    """A call schedule with every entry once per round, each round in a
    seeded order."""
    rng = rng_for(seed, 3)
    out: List[Tuple] = []
    for _ in range(rounds):
        out.extend(calls[i] for i in rng.permutation(len(calls)))
    return out
