"""The one traffic generator: a workload file's parameters + a seed -> inputs.

Every seed gets the SAME multiset of request sizes and inter-arrival gaps,
in another order and with other token ids, so that runs of different seeds
do the same amount of work. The multiset comes from the workload file alone
(sizes apportioned by the weights; exponential gaps from a fixed stream,
rescaled to the rate); ``--seed`` permutes it and draws the token ids.

Serving workload keys (``kind: serve``):
  rate_rps        offered load, requests / second, fixed in the file
  prompt_lens, prompt_weights, gen_lens, gen_weights
                  length grids and their relative weights

Training workload keys (``kind: train``): batch, seq, n_batches.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    t: float                 # seconds after the window opens
    prompt: np.ndarray       # [S] int32
    max_new_tokens: int


def _seed32(seed: int, salt: int) -> int:
    # numpy's RandomState takes 32 bits; --seed may hold more
    return (int(seed) * 2654435761 + salt * 40503 + (int(seed) >> 32)) \
        % (2**32)


def apportion(values, weights, n: int) -> np.ndarray:
    """``n`` items of ``values`` in the proportions of ``weights``, by
    largest remainder: the same multiset for every seed."""
    w = np.asarray(weights, float)
    quota = w / w.sum() * n
    counts = np.floor(quota).astype(int)
    order = np.argsort(-(quota - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return np.repeat(np.asarray(values), counts)


def gaps(n: int, rate: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps (a Poisson process), the same
    for every seed, rescaled so that they span exactly n / rate."""
    g = np.random.RandomState(1).exponential(1.0 / rate, n)
    return g * (n / rate) / g.sum()


def serve_requests(w: dict, vocab: int, seed: int,
                   seconds: float) -> List[Req]:
    rate = float(w["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.RandomState(_seed32(seed, 2))
    g = gaps(n, rate)[rng.permutation(n)]
    plens = apportion(w["prompt_lens"], w["prompt_weights"],
                      n)[rng.permutation(n)]
    glens = apportion(w["gen_lens"], w["gen_weights"], n)[rng.permutation(n)]
    # the first request is due at 0 and the last before `seconds`
    t = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return [Req(float(t[i]),
                rng.randint(0, vocab, (int(plens[i]),)).astype(np.int32),
                int(glens[i])) for i in range(n)]


def train_batches(w: dict, vocab: int, seed: int) -> np.ndarray:
    """[n_batches, batch, seq] int32 token ids."""
    rng = np.random.RandomState(_seed32(seed, 3))
    return rng.randint(0, vocab, (int(w["n_batches"]), int(w["batch"]),
                                  int(w["seq"]))).astype(np.int32)
