"""Traffic mixes: ``<mix>.json`` parameter files read by one generator per
``kind`` (``gen_<kind>.py``). A mix is data only; adding one adds a file.

A generator returns a ``Source``: the driver asks it which requests are due
(``pop_due``) and when the next one is (``next_due``). Sizes are stratified:
every seed gets the same multiset of lengths (quantiles of the stated
distributions), in an order and with token ids drawn from the seed, so two
seeds differ in content and not in the amount of work; ``banded_order``
also keeps each position's size within one band for every seed.
"""
from __future__ import annotations

import importlib
import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass
class Item:
    due: float  # seconds on the driver's clock (0 = traffic start)
    prompt: List[int]
    max_new: int


def load(name: str) -> dict:
    return json.loads((HERE / f"{name}.json").read_text())


def make(spec: dict, **kw):
    """The generator ``gen_<spec['kind']>.py`` builds the source."""
    return importlib.import_module(f"bench.traffic.gen_{spec['kind']}").make(spec, **kw)


def lognormal_quantiles(median: float, p90: float, n: int) -> np.ndarray:
    """n values at the mid-quantiles (i + 0.5) / n of the lognormal with this
    median and 90th percentile."""
    mu, sigma = math.log(median), max(math.log(p90 / median) / 1.2816, 1e-6)
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.exp(mu + sigma * z)


def band_order(kind: str, bands: int) -> np.ndarray:
    """A fixed order in which to visit ``bands`` bands so that every short
    stretch spans the whole range: ``bitrev`` (bit-reversed indices;
    ``bands`` a power of two) or ``golden`` (ranks of i x 0.618 mod 1). The
    two are uncorrelated, so prompt and output lengths stay independent."""
    if kind == "bitrev":
        k = bands.bit_length() - 1
        if bands != 1 << k:
            raise ValueError(f"bitrev needs a power of two, not {bands}")
        return np.asarray([int(format(i, f"0{k}b")[::-1] or "0", 2) for i in range(bands)])
    if kind == "golden":
        return np.argsort(np.argsort((np.arange(bands) * 0.6180339887498949) % 1.0))
    raise ValueError(f"unknown band order {kind!r}")


def banded_order(values: np.ndarray, rng, bands: int, order: np.ndarray) -> np.ndarray:
    """The values with item i taken from band ``order[i % bands]`` of
    ``bands`` equal bands of the sorted values (``len(values)`` a multiple
    of ``bands``); ``rng`` picks which member of the band. Every seed then
    gets the same sizes at nearly the same positions, and any stretch of a
    backlog about the same work."""
    v = np.sort(values).reshape(bands, -1)
    v = np.stack([rng.permutation(b) for b in v])
    i = np.arange(len(values))
    return v[np.asarray(order)[i % bands], i // bands]


def round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def tokens(seed: int, tag, n: int, vocab: int) -> List[int]:
    """Token ids of one prompt piece, from (seed, tag) alone."""
    rng = np.random.default_rng([int(seed), *tag])
    return rng.integers(1, vocab, int(n)).tolist()
