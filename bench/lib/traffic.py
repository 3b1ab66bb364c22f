"""Traffic generators: everything a mix file can ask for, drawn from a seed.

A mix file (`bench/traffic/<mix>.json`) names its driver and gives the
parameters below; nothing here knows a cell by name.

- `stratified_lengths`: output lengths from a clipped lognormal, one per
  equal-probability stratum.  The offset inside each stratum is drawn from
  a stream keyed by the batch index alone, so every seed serves the same
  set of lengths and the seed only changes which row gets which.
- `prompts`: uniformly random token ids, drawn from the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np

# Streams of the generators, so that no two of them share draws.
_LENGTHS, _PROMPTS, _ORDER, _SAMPLE = 0, 1, 2, 4


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream])


def stratified_lengths(spec: dict, batch_index: int, strata: int) -> List[int]:
    """``strata`` lengths, one from each equal-probability stratum of the
    lognormal ``spec`` (median, sigma, min, max), sorted ascending."""
    off = np.random.default_rng([_LENGTHS, batch_index]).random(strata)
    nd = NormalDist(math.log(spec["median"]), spec["sigma"])
    out = []
    for i in range(strata):
        u = min(max((i + off[i]) / strata, 1e-12), 1 - 1e-12)
        x = round(math.exp(nd.inv_cdf(u)))
        out.append(int(min(max(x, spec["min"]), spec["max"])))
    return sorted(out)


def batch_lengths(spec: dict, seed: int, batch_index: int, batch: int) -> List[int]:
    """The lengths of one batch in the row order the seed gives them."""
    lens = stratified_lengths(spec, batch_index, batch)
    perm = rng(seed, _ORDER, batch_index).permutation(batch)
    return [lens[i] for i in perm]


def prompts(seed: int, batch_index: int, batch: int, length: int,
            vocab: int) -> np.ndarray:
    return rng(seed, _PROMPTS, batch_index).integers(
        0, vocab, size=(batch, length), dtype=np.int32)


def sample(seed: int, population: int, k: int, must: int | None = None) -> List[int]:
    """``k`` distinct indices of ``range(population)``, drawn from the
    seed; ``must`` is always among them."""
    g = rng(seed, _SAMPLE)
    idx = [int(i) for i in g.permutation(population)]
    if must is not None:
        idx.remove(must)
        idx.insert(0, must)
    return sorted(idx[:k])
