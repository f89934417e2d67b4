"""The one generator every traffic mix goes through.

A mix is a JSON file of parameters under ``traffic/``. Training mixes
describe the job (batch, sequence, quantum, learning rate and warm-up,
preemption period) and need no generation. Serving mixes describe an open loop: a Poisson
rate and the distributions of prompt and output lengths.

Every seed gets the same schedule: lengths are the distribution's
quantiles at (i + 0.5) / n and the gaps between arrivals the
exponential's, each put in a low-discrepancy (Halton) order, bases 2, 3
and 5, so that no stretch of the window gathers the long requests or the
short gaps; the run's seed draws the prompt tokens (and the weights). So
neither the work nor the arrivals change with the seed, and the spread
between runs is the system's own. Bursts are a mix of their own.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass
class Request:
    due_s: float          # offset from the window's start
    prompt: List[int]
    max_new: int


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """Integer lengths at the quantiles (i + 0.5) / n of ``dist``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def walk_batch(data: Dict, step: int, batch: int, seq: int, vocab: int):
    """The training job's batch of ``step``: the affine-modular walk
    x[t+1] = (a x[t] + b) mod vocab, each token replaced by a uniform one
    with probability ``noise``, that ``data`` states with its seed, as
    (tokens, labels), each (batch, seq) int32. A pure function of
    (``data``, step)."""
    rng = np.random.RandomState((data["seed"] * 1_000_003 + step) % (2 ** 31))
    full = np.zeros((batch, seq + 1), np.int64)
    full[:, 0] = rng.randint(0, vocab, batch)
    noise_mask = rng.rand(batch, seq) < data["noise"]
    noise_tok = rng.randint(0, vocab, (batch, seq))
    for t in range(seq):
        nxt = (data["a"] * full[:, t] + data["b"]) % vocab
        full[:, t + 1] = np.where(noise_mask[:, t], noise_tok[:, t], nxt)
    return full[:, :-1].astype(np.int32), full[:, 1:].astype(np.int32)


def halton_order(n: int, base: int) -> np.ndarray:
    """A permutation of range(n) whose successive picks spread evenly: the
    ranks of the first n points of the van der Corput sequence in
    ``base``."""
    def radical_inverse(k: int) -> float:
        x, d = 0.0, 1.0
        while k:
            d *= base
            k, r = divmod(k, base)
            x += r / d
        return x
    return np.argsort([radical_inverse(k + 1) for k in range(n)],
                      kind="stable")


def n_requests(mix: Dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def open_loop(mix: Dict, seconds: float, vocab: int,
              rng: np.random.RandomState) -> List[Request]:
    """Requests due over ``[0, seconds)`` at the mix's rate; ``rng`` (the
    run's seed) draws only the prompt tokens."""
    n = n_requests(mix, seconds)
    u = (np.arange(n) + 0.5) / n
    gaps = (-np.log1p(-u) / mix["rate_per_s"])[halton_order(n, 2)]
    due = seconds * (np.cumsum(gaps) - gaps) / gaps.sum()
    prompt_len = quantiles(mix["prompt"], n)[halton_order(n, 3)]
    out_len = quantiles(mix["output"], n)[halton_order(n, 5)]
    return [Request(float(d), [int(t) for t in rng.randint(1, vocab, p)],
                    int(o))
            for d, p, o in zip(due, prompt_len, out_len)]
