"""The one traffic generator: a mix file's parameters + a seed -> requests.

A mix (``bench/mixes/<name>.json``) is a closed batch of ``batch`` rows
whose queue never empties: every request is queued at once, and a row
that finishes takes the next. It gives the prompt and output length
distributions (lognormal with a median, a sigma and clips).

Every seed gets the same work in another order. Requests come in blocks
of ``STRATA``: within a block the prompt and output lengths are the
``STRATA`` evenly spaced quantiles of their distributions, and the seed
only permutes them (and draws the tokens).

The window opens on the batch's steady state, not on a batch that has
just started. In a closed batch that has run for long, the request a row
holds is drawn in proportion to its output length, and it is a uniform
share of the way through its output. :func:`steady_starts` takes
``batch`` evenly spaced points of that distribution over the output
strata; these are the same for every seed. The first ``batch`` requests
start at those points: the tokens they have already produced are drawn
as part of their prompt (``Req.progress`` of them) and ``max_new`` is
what is left. The rest start from the beginning.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

STRATA = 16


@dataclasses.dataclass
class Req:
    uid: int
    due: float              # seconds after the traffic starts
    prompt: np.ndarray      # int32 [P + progress]
    max_new: int
    progress: int = 0       # output tokens already produced (in ``prompt``)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` stratum lengths of a lognormal length spec, clipped."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def steady_starts(outputs: np.ndarray, n: int) -> List[Tuple[int, int]]:
    """``n`` (output length, tokens already produced) points of a closed
    batch's steady state over the output strata ``outputs``: every token
    position of every stratum is equally likely, so a stratum is picked
    in proportion to its length and a position within it uniformly. At
    least 2 tokens are left to produce."""
    o = np.sort(np.asarray(outputs, np.int64))
    ends = np.cumsum(o)
    out = []
    for q in _quantiles(n) * ends[-1]:
        j = int(np.searchsorted(ends, q, side="right"))
        age = int(q - (ends[j - 1] if j else 0))
        out.append((int(o[j]), min(age, int(o[j]) - 2)))
    return out


def generate(mix: Dict, seed: int, vocab: int, n: int) -> List[Req]:
    """The first ``n`` requests of the mix for ``seed``."""
    rng = np.random.default_rng([int(seed), 0])
    p_len = lengths(mix["prompt"], STRATA)
    o_len = lengths(mix["output"], STRATA)
    starts = steady_starts(o_len, mix["batch"])
    out: List[Req] = []
    for b in range(math.ceil(n / STRATA)):
        pp, oo = rng.permutation(p_len), rng.permutation(o_len)
        ss = rng.permutation(len(starts))
        for j in range(STRATA):
            uid = b * STRATA + j
            if uid >= n:
                break
            o, done = int(oo[j]), 0
            if uid < len(starts):
                o, done = starts[ss[uid]]
            body = rng.integers(0, vocab, int(pp[j]) + done, dtype=np.int32)
            out.append(Req(uid, 0.0, body, o - done, done))
    return out


def max_prompt(mix: Dict) -> int:
    return mix["prompt"]["max"]


def max_new(mix: Dict) -> int:
    return mix["output"]["max"]


def max_start_prompt(mix: Dict) -> int:
    """The longest prompt a steady-state start can install."""
    o_len = lengths(mix["output"], STRATA)
    return max_prompt(mix) + max(d for _, d in steady_starts(o_len,
                                                              mix["batch"]))
