"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

A mix is a ``closed`` loop: ``clients`` callers, each sending its next
request when the last one is answered. It states the distributions of
prompt and output lengths.

Sizes come in blocks of ``block`` requests. Every block holds the same
multiset, stratified quantiles of the stated distributions, in an order
drawn once from the mix's own ``order_seed``: every run seed gives the
same sizes in the same order, so runs of different seeds do the same
work. The run seed draws the token ids (uniform over the vocabulary, id
0 left out), as it draws the weights.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: loop must be closed")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any seed >= 0."""
    return np.random.default_rng([int(seed), int(stream)])


def length_block(spec: dict, block: int) -> np.ndarray:
    """The multiset of one block's lengths, in ascending order.

    ``{"values": [...], "weights": [...]}``: each value for its share of
    the block (largest remainders); ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}``: stratified quantiles, rounded, clipped."""
    if "values" in spec:
        w = np.asarray(spec["weights"], np.float64)
        exact = w / w.sum() * block
        n = np.floor(exact).astype(int)
        for i in np.argsort(-(exact - n))[: block - n.sum()]:
            n[i] += 1
        return np.repeat(np.asarray(spec["values"], np.int64), n)
    if spec.get("dist") != "lognormal":
        raise ValueError(f"unknown length spec {spec}")
    q = (np.arange(block) + 0.5) / block
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


@dataclass
class Req:
    idx: int
    prompt: List[int]
    max_new_tokens: int


def requests(mix: dict, vocab: int, seed: int, n: int) -> List[Req]:
    """The first ``n`` requests of ``mix`` for ``seed``."""
    block = int(mix["block"])
    order = rng_for(mix["order_seed"], 1)
    ids = rng_for(seed, 1)
    out: List[Req] = []
    base_p = length_block(mix["prompt_len"], block)
    base_o = length_block(mix["output_len"], block)
    while len(out) < n:
        p = order.permutation(base_p)
        o = order.permutation(base_o)
        for j in range(min(block, n - len(out))):
            prompt = ids.integers(1, vocab, int(p[j])).tolist()
            out.append(Req(len(out), prompt, int(o[j])))
    return out
