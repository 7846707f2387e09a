"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and makes its requests from the seed.

Every seed gets the same sizes, in another order: lengths are the
quantiles of their laws, laid out in blocks of ``block`` requests, and the
seed only permutes each block.  A block is smaller than the requests a
window finishes, so every window carries the same mix of work, and seeds
differ in order and token content alone.

Parameters (all but ``prompt`` and ``output`` optional):

* ``arrivals``: ``{"kind": "backlog", "depth_per_lane": k}`` keeps the
  engine's queue at least k x lanes deep (the only kind so far).
* ``prompt`` / ``output``: log-normal lengths, ``{"median", "sigma",
  "min", "max"}``.
* ``block``: requests per permuted block (default 8).
* ``lead_in_s``: traffic run before the window opens (counted in set-up).

Keys a mix file carries beyond these (its ``source``, what it ``assumed``)
document it and are not read.
"""
from __future__ import annotations

import json
import math
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_STD_NORMAL = statistics.NormalDist()


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def lognormal_quantiles(law: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a clipped log-normal law."""
    mu, sig = math.log(law["median"]), law["sigma"]
    q = [(j + 0.5) / n for j in range(n)]
    x = [math.exp(mu + sig * _STD_NORMAL.inv_cdf(p)) for p in q]
    return np.clip(np.round(x), law["min"], law["max"]).astype(np.int64)


class Traffic:
    """Requests of one mix under one seed: ``request(i)``."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.seed, self.vocab = int(seed), vocab
        self.block = int(spec.get("block", 8))
        self._prompt = lognormal_quantiles(spec["prompt"], self.block)
        self._output = lognormal_quantiles(spec["output"], self.block)
        arr = spec.get("arrivals", {"kind": "backlog", "depth_per_lane": 2})
        if arr["kind"] != "backlog":
            raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
        self.depth_per_lane = int(arr.get("depth_per_lane", 2))
        self._perm: dict[int, tuple] = {}

    def _block_perm(self, b: int) -> tuple:
        if b not in self._perm:
            rng = np.random.default_rng([self.seed, 0, b])
            self._perm[b] = (rng.permutation(self.block),
                             rng.permutation(self.block))
        return self._perm[b]

    def lengths(self, i: int) -> tuple[int, int]:
        """(prompt length, output length) of request i."""
        pp, po = self._block_perm(i // self.block)
        j = i % self.block
        return int(self._prompt[pp[j]]), int(self._output[po[j]])

    def request(self, i: int) -> tuple[list[int], int]:
        """(prompt tokens, max_new) of request i."""
        n, out = self.lengths(i)
        rng = np.random.default_rng([self.seed, 2, i])
        return rng.integers(2, self.vocab, size=n).tolist(), out
