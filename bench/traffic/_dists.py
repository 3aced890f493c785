"""Request shapes shared by the generators.

A mix fixes a pool of request sizes (prompt lengths, output lengths,
temperatures, arrival gaps) drawn once from the mix's own `shapes_seed`;
the run's seed only orders them and draws the prompt tokens. Every seed
therefore offers the same set of sizes and arrivals in another order.

The order is stratified: each of the four columns is cut by rank into
`stratify` strata of equal size, and every run of `stratify` consecutive
requests (a block) takes one value of each column from each stratum. So
whatever the seed, any whole number of blocks carries the same share of
every quantile of every column, and a window that sees a few blocks sees
about the same amount of work. The seed picks which value of a stratum
comes, the order inside a block, and how the columns pair up.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """n lengths from {"dist": "lognormal", "median", "sigma", "min", "max"}
    or {"dist": "uniform", "min", "max"} (both ends included)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, size=n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


@dataclasses.dataclass
class Shape:
    prompt_len: int
    max_new: int
    temperature: float
    gap_s: float = 0.0        # open loop: time since the previous arrival


def stratified(values: np.ndarray, block: int,
               rng: np.random.Generator) -> np.ndarray:
    """`values` in an order in which every run of `block` consecutive
    entries holds one value from each of `block` rank strata of equal
    size; which member of a stratum, and the order inside a block, come
    from `rng`."""
    n = len(values)
    if block < 1 or n % block:
        raise ValueError(f"pool {n} is not a whole number of blocks of "
                         f"{block}")
    strata = np.argsort(values, kind="stable").reshape(block, n // block)
    blocks = rng.permuted(rng.permuted(strata, axis=1).T, axis=1)
    return values[blocks.reshape(-1)]


def shape_pool(params: dict, run_seed: int) -> list[Shape]:
    """The mix's fixed pool of sizes, in the stratified order `run_seed`
    gives (module doc; `stratify` defaults to 1, a plain shuffle)."""
    n = int(params["pool"])
    rng = np.random.default_rng(int(params["shapes_seed"]))
    prompts = draw_lengths(rng, params["prompt_len"], n)
    outs = draw_lengths(rng, params["output_len"], n)
    greedy = rng.random(n) < float(params.get("greedy_share", 1.0))
    temp = np.where(greedy, 0.0, float(params.get("temperature", 0.0)))
    rate = params.get("rate_rps")
    gaps = rng.exponential(1.0 / rate, n) if rate else np.zeros(n)
    block = int(params.get("stratify", 1))
    order = np.random.default_rng(run_seed)
    cols = [stratified(c, block, order) for c in (prompts, outs, temp, gaps)]
    return [Shape(int(p), int(o), float(t), float(g))
            for p, o, t, g in zip(*cols)]


@dataclasses.dataclass
class Send:
    """One request as the client sends it; `due` is its scheduled time."""
    rid: int
    prompt: np.ndarray
    max_new: int
    temperature: float
    seed: int
    due: float


class Tokens:
    """Prompt token ids from the run's seed, one stream per request."""

    def __init__(self, run_seed: int, vocab: int):
        self.run_seed, self.vocab = int(run_seed), int(vocab)

    def prompt(self, rid: int, n: int) -> np.ndarray:
        rng = np.random.default_rng((self.run_seed, 1, rid))
        return rng.integers(0, self.vocab, size=n).astype(np.int32)

    def seed(self, rid: int) -> int:
        return int(np.random.default_rng((self.run_seed, 2, rid))
                   .integers(0, 2**31 - 1))
