"""Traffic generation from a mix file and a seed.

A mix is a JSON file of parameters (``bench/traffic/<name>.json``):

``kind``        ``open_loop`` — requests are due on a schedule whatever the
                server does (independent users), or ``backlog`` — a
                saturated queue of offline work.
``arrivals``    open loop only: ``{"process": "poisson", "rate_per_s": r}``.
``prompt_len``, ``output_len``
                ``{"dist": "lognormal", "median": m, "sigma": s, "min": lo,
                "max": hi}``.
``set_size``    the number of distinct (prompt, output) sizes and
                inter-arrival gaps.
``pairing``     ``seed`` (the default): each pass pairs prompt and output
                sizes afresh; ``fixed``: the pairs are drawn once for the
                mix, the same under every seed.

Every seed gets the same multiset of sizes and gaps, in its own order: the
sizes are the distribution's quantiles at (i + 1/2) / set_size and the gaps
the exponential's, scaled so that their mean is 1 / rate_per_s, and the
seed shuffles them.  The sequence repeats the set, each pass in a fresh
order.  An open-loop schedule is laid so that a pass begins half the
shortest gap before ``ramp_s``, where the window opens: a window of
set_size / rate_per_s seconds, or a whole number of them, then holds whole
passes, and every seed offers it the same requests (with ``fixed``
pairing) in another order and with other token ids.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One generated request: due time in seconds from the schedule's
    start (0 for a backlog), prompt token ids and output length."""
    index: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def _quantiles(dist: Dict, n: int) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    p = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(q)) for q in p])
    v = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def _gaps(arr: Dict, n: int) -> np.ndarray:
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate_per_s"])
    if rate <= 0:
        raise ValueError(f"rate_per_s must be > 0, got {rate}")
    p = (np.arange(n) + 0.5) / n
    g = -np.log1p(-p)
    return g / (g.mean() * rate)


class Traffic:
    """The request sequence of one mix under one seed.  ``take(n)`` and
    ``until(t)`` extend it deterministically."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.kind = mix["kind"]
        if self.kind not in ("open_loop", "backlog"):
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        self.vocab = int(vocab)
        self.n_set = int(mix["set_size"])
        self._rng = np.random.default_rng(seed)
        self._plen = _quantiles(mix["prompt_len"], self.n_set)
        self._olen = _quantiles(mix["output_len"], self.n_set)
        pairing = mix.get("pairing", "seed")
        if pairing not in ("seed", "fixed"):
            raise ValueError(f"unknown pairing {pairing!r}")
        self._pairs = np.random.default_rng(0).permutation(self.n_set) \
            if pairing == "fixed" else None
        self.reqs: List[Req] = []
        self._gaps = None
        self._t = 0.0                   # due time of the last request
        if self.kind == "open_loop":
            self._gaps = _gaps(mix["arrivals"], self.n_set)
            pass_s = float(self._gaps.sum())
            start = float(mix["ramp_s"]) - 0.5 * float(self._gaps.min())
            self._t = start - np.ceil(start / pass_s) * pass_s

    def _extend(self) -> None:
        """Append one pass over the size set in a fresh order (of an
        open-loop schedule's first pass, the requests due from 0 on)."""
        order_p = self._rng.permutation(self.n_set)
        order_o = self._rng.permutation(self.n_set) \
            if self._pairs is None else self._pairs[order_p]
        gaps = None if self._gaps is None \
            else self._gaps[self._rng.permutation(self.n_set)]
        for j in range(self.n_set):
            if gaps is not None:
                self._t += float(gaps[j])
                if self._t < 0:
                    continue
            n = int(self._plen[order_p[j]])
            prompt = self._rng.integers(0, self.vocab, n, dtype=np.int32)
            self.reqs.append(Req(len(self.reqs), self._t, prompt,
                                 int(self._olen[order_o[j]])))

    def take(self, n: int) -> List[Req]:
        while len(self.reqs) < n:
            self._extend()
        return self.reqs[:n]

    def until(self, t_s: float) -> List[Req]:
        """Every request due before ``t_s`` (open loop)."""
        while not self.reqs or self.reqs[-1].due_s < t_s:
            self._extend()
        return [r for r in self.reqs if r.due_s < t_s]
