"""One general generator for every traffic mix under `perfbench/traffic/`.

A mix is a JSON file of parameters. Requests come in blocks of `block`.
Every block holds the same multiset of (prompt length, output length,
gap to the next arrival): the (i + 1/2)/N quantiles of
the mix's distributions, paired by a fixed permutation, in an order
within the block that is drawn once and is the same for every seed. The
seed draws the prompt token ids (and the harness draws the weights from
it). Every request is served greedily, so that every served token can be
checked against the reference. So every run serves the same sequence of work, whatever
seed it is given. (With the order drawn from the seed, the 95th percentile
of the time per output token of qwen3-1.7b.chat-backlog on one TPU v5e
ranged over 17% between six seeds, and by at most 6% between two runs of
one seed.)

Prompt lengths are rounded up to the mix's fixed set of `buckets`, so the
programs a run can reach are known before it starts.

Arrivals:
  backlog  every request is due at t = 0;
  poisson  exponential gaps at `rate` requests per second (the cell gives
           the rate), stratified like the lengths. The arithmetic is that
           of `Trace.poisson` in the program's `core/workload.py`:
           arrival times are the running sum of the gaps.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

# Fixed pairing of the quantiles inside a block, and fixed order of each
# block: the same for every seed.
_PAIRING_SEED = 20231203
_ORDER_SEED = 20231204


@dataclass
class GenRequest:
    index: int            # position in the stream
    due: float            # seconds after the schedule starts
    prompt: List[int]
    out_len: int


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    nd = NormalDist()
    p = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in p])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.round(v), spec["min"], spec["max"]).astype(np.int64)


def round_up_to_bucket(n: int, buckets: List[int]) -> int:
    for b in sorted(buckets):
        if n <= b:
            return b
    return max(buckets)


def block_template(mix: dict) -> List[tuple]:
    """The multiset every block holds: (prompt, out, gap_quantile)
    tuples in their fixed pairing order. gap_quantile is the exponential
    quantile for a unit rate (scaled by 1/rate for poisson arrivals)."""
    n = int(mix["block"])
    buckets = mix["prompt"]["buckets"]
    prompts = [round_up_to_bucket(int(v), buckets)
               for v in _lognormal_quantiles(mix["prompt"], n)]
    outs = [int(v) for v in _lognormal_quantiles(mix["output"], n)]
    fixed = np.random.default_rng(_PAIRING_SEED)
    out_perm = fixed.permutation(n)
    gap_perm = fixed.permutation(n)
    p = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-p)                     # exponential quantiles, rate 1
    return [(prompts[i], outs[out_perm[i]], float(gaps[gap_perm[i]]))
            for i in range(n)]


def stream(mix: dict, seed: int, vocab: int,
           rate: Optional[float] = None) -> Iterator[GenRequest]:
    """Endless request stream of `mix` for `seed` (blocks made lazily)."""
    kind = mix["arrivals"]
    if kind not in ("backlog", "poisson"):
        raise ValueError(f"unknown arrivals {kind!r}")
    if kind == "poisson" and not (rate and rate > 0):
        raise ValueError("poisson arrivals need a rate > 0")
    template = block_template(mix)
    order_rng = np.random.default_rng(_ORDER_SEED)
    token_rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    t = 0.0
    index = 0
    while True:
        for j in order_rng.permutation(len(template)):
            prompt_len, out_len, gap = template[j]
            if kind == "poisson":
                t += gap / rate
            yield GenRequest(
                index=index, due=t,
                prompt=token_rng.integers(0, vocab, size=prompt_len)
                .tolist(),
                out_len=out_len)
            index += 1


def take(mix: dict, seed: int, vocab: int, n: int,
         rate: Optional[float] = None) -> List[GenRequest]:
    it = stream(mix, seed, vocab, rate)
    return [next(it) for _ in range(n)]
