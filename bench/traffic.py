"""Inputs made from the seed: serving waves and training batches.

A serving mix is a data file's ``traffic`` parameters:

    {"wave": 32,
     "prompt": {"dist": "lognormal", "median": 1500, "sigma": 0.8, "min": 256, "max": 4000},
     "output": {"dist": "uniform", "min": 16, "max": 64}}

Every wave holds the same multiset of (prompt length, output length) pairs:
the distributions' quantiles at (i + 1/2) / wave, paired by one fixed
shuffle, and submitted in an order fixed for each wave's index. The seed sets
only the prompts' token ids, so every seed asks for the same work in the same
order: which requests share the slot pool, and so each request's time per
token, does not move with the seed. Decoding is greedy.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

# Pairs prompt quantiles with output quantiles and orders each wave; fixed,
# not seeded, so that every seed's waves are the same work.
_PAIRING_SEED = 20240731


def quantiles(spec, n):
    """n lengths at the distribution's quantiles (i + 1/2) / n, clipped."""
    ps = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        vals = [spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(p)) for p in ps]
    elif spec["dist"] == "uniform":
        vals = [spec["min"] + p * (spec["max"] - spec["min"]) for p in ps]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(max(round(v), spec["min"]), spec["max"])) for v in vals]


def wave_sizes(traffic):
    """The wave's (prompt length, output length) pairs, in a fixed order."""
    n = traffic["wave"]
    prompts = quantiles(traffic["prompt"], n)
    outputs = quantiles(traffic["output"], n)
    perm = np.random.default_rng(_PAIRING_SEED).permutation(n)
    return [(prompts[i], outputs[j]) for i, j in zip(range(n), perm)]


def wave(traffic, vocab, seed, index):
    """Wave ``index`` of a run seeded ``seed``: [(prompt ids, output length)]
    in the order they are submitted."""
    sizes = wave_sizes(traffic)
    order = np.random.default_rng([_PAIRING_SEED, index]).permutation(len(sizes))
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    return [(rng.integers(0, vocab, sizes[i][0], dtype=np.int32), sizes[i][1])
            for i in order]


def train_batch(seed, step, batch, seq_len, vocab):
    """{"tokens", "labels"} of training step ``step``: uniform token ids,
    every row its own."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    toks = rng.integers(0, vocab, (batch, seq_len + 1), dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


class TrainFeed:
    """Hands the trainer its batches (``next_batch``), step by step."""

    def __init__(self, seed, batch, seq_len, vocab):
        self.seed, self.batch, self.seq_len, self.vocab = seed, batch, seq_len, vocab
        self.step = 0

    def next_batch(self):
        b = train_batch(self.seed, self.step, self.batch, self.seq_len, self.vocab)
        self.step += 1
        return b
