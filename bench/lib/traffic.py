"""The one traffic generator: turns a mix's parameters (a data file
under ``bench/traffic/``) into requests.

Lengths are a fixed set, the same for every seed: ``n`` stratified
quantiles, at (i + 0.5) / n, of a lognormal with the given mean and
sigma, rounded and clipped. Prompt and output lengths are paired once,
the same way for every seed and cycle. The seed decides only the order
of the requests and their token ids. So two seeds do the same work in
another order (the paged decode step's time follows each row's context,
so a pairing drawn by the seed would change the work), and a warm-up
with the same lengths builds every shape the window uses.
"""

from __future__ import annotations

import math
import statistics
from typing import List, NamedTuple

import numpy as np


class Request(NamedTuple):
    prompt: List[int]
    max_new: int


def lengths(spec: dict, n: int) -> List[int]:
    """``n`` stratified lengths of a {"dist": "lognormal", "mean",
    "sigma", "min", "max"} spec, ascending."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    s = float(spec["sigma"])
    mu = math.log(spec["mean"]) - s * s / 2
    nd = statistics.NormalDist()
    out = [round(math.exp(mu + s * nd.inv_cdf((i + 0.5) / n)))
           for i in range(n)]
    return [min(max(x, spec["min"]), spec["max"]) for x in out]


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, *stream])


def pairs(mix: dict) -> List[tuple]:
    """The burst's (prompt, output) lengths: the i-th prompt quantile
    with the output quantiles in one fixed permutation, drawn from a
    stream that no seed reaches."""
    n = mix["burst"]
    outputs = lengths(mix["output_len"], n)
    return list(zip(lengths(mix["prompt_len"], n),
                    [outputs[i] for i in rng(0, 2 ** 32 + 2).permutation(n)]))


def burst(mix: dict, vocab: int, seed: int, cycle: int) -> List[Request]:
    """The requests of one burst: the mix's fixed pairs, ordered by the
    seed, with token ids uniform over the vocabulary."""
    fixed = pairs(mix)
    r = rng(seed, cycle)
    return [Request(r.integers(0, vocab, fixed[i][0]).tolist(), fixed[i][1])
            for i in r.permutation(len(fixed))]


def warmup_burst(mix: dict, vocab: int) -> List[Request]:
    """The window's prompt lengths with short outputs: every shape the
    window builds, in a fraction of its time."""
    n = mix["burst"]
    r = rng(0, 2 ** 32)
    return [Request(r.integers(0, vocab, p).tolist(), mix["warmup_output_len"])
            for p in lengths(mix["prompt_len"], n)]
