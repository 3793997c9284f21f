"""Traffic from a mix file and a seed.

One general generator reads every mix (``bench/traffic/<mix>.json``).  A
mix states the loop (``closed`` with a number of clients, or ``open`` at a
rate in requests per second), the prompt and output length distributions
(lognormal by median and sigma, clipped) and the vocabulary draw.

The seed changes the token ids (and, in the harness, the weights), not
the work: lengths come in blocks of ``block`` requests, every block holds
the same quantile-spaced lengths in a shuffled order, and open-loop gaps
are drawn the same way from the exponential distribution.  The shuffle
depends on the block's index and not on the seed: in a closed loop whose
pool admits only some of the clients at once, the order decides which
prompts are ingested first, and a seed-shuffled order changed the work of a
window from seed to seed.

A closed loop may start ``"first_wave": "residual"``: each client's first
request keeps its prompt but only a quantile-spaced share of its output
(``(k + 0.5) / clients`` for the ``k``-th in a fixed shuffled order), as a
request already in progress would.  Its clients then retire one after
another from the first ticks on, and not as one wave.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tags]))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_block(dist: dict, n: int) -> np.ndarray:
    """``n`` quantile-spaced draws of a clipped lognormal, as integers."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    x = np.clip(np.rint(x), int(dist["min"]), int(dist["max"]))
    return x.astype(np.int64)


def exponential_block(n: int) -> np.ndarray:
    """``n`` quantile-spaced unit-mean exponential draws."""
    return -np.log1p(-_quantiles(n))


@dataclasses.dataclass(frozen=True)
class Req:
    index: int            # position in the seed's stream
    due: float            # seconds after the window opens (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int


class Traffic:
    """The request stream of one mix under one seed.  ``request(i)`` is the
    same for a given (mix, seed) however many requests are taken."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop must be 'open' or 'closed': {self.loop!r}")
        # knobs a mix may state but this generator does not implement yet
        # must sit at their neutral values, never be silently ignored
        for key, neutral in (("think_s", 0), ("eos", False),
                             ("shared_prefix", 0)):
            if mix.get(key, neutral) != neutral:
                raise ValueError(f"mix key {key}={mix[key]!r} is not "
                                 f"implemented by the generator")
        for part in ("prompt", "output"):
            if mix[part].get("dist", "lognormal") != "lognormal":
                raise ValueError(f"{part} dist {mix[part]['dist']!r} is not "
                                 f"implemented by the generator")
        self.block = int(mix.get("block", 16))
        self._p = lognormal_block(mix["prompt"], self.block)
        self._o = lognormal_block(mix["output"], self.block)
        self._g = exponential_block(self.block)
        self._first = {}
        if self.loop == "closed" and mix.get("first_wave") == "residual":
            n = int(mix["clients"])
            self._first = dict(enumerate(_rng(0, 4).permutation(
                _quantiles(n))))
        self._blocks: Dict[int, tuple] = {}
        self._due: List[float] = [0.0]

    def _block(self, b: int):
        if b not in self._blocks:
            r = _rng(0, 1, b)
            self._blocks[b] = (r.permutation(self._p), r.permutation(self._o),
                               r.permutation(self._g))
        return self._blocks[b]

    def lengths(self, i: int):
        p, o, _ = self._block(i // self.block)
        plen, olen = int(p[i % self.block]), int(o[i % self.block])
        if i in self._first:
            olen = max(1, int(round(olen * self._first[i])))
        return plen, olen

    def prompt_lengths(self) -> List[int]:
        """Every prompt length the mix sends (each block holds them all)."""
        return sorted({int(x) for x in self._p})

    def due(self, i: int) -> float:
        """Open loop: seconds after the traffic starts at which request
        ``i`` falls due (Poisson at ``rate``, stratified as above)."""
        rate = float(self.mix["rate"])
        while len(self._due) <= i + 1:
            j = len(self._due) - 1
            _, _, g = self._block(j // self.block)
            self._due.append(self._due[-1] + g[j % self.block] / rate)
        return self._due[i + 1]

    def request(self, i: int) -> Req:
        plen, olen = self.lengths(i)
        tok = _rng(self.seed, 2, i).integers(0, self.vocab, size=plen,
                                             dtype=np.int32)
        due = self.due(i) if self.loop == "open" else math.nan
        return Req(index=i, due=due, prompt=tok, max_new=olen)
