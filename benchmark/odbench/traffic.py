"""The one traffic generator: a mix is a data file, this is all the code.

A mix (``benchmark/traffic/<mix>.json``) has a ``kind`` and parameters:

``diloco_rounds``  training: ``local_steps``, ``seq_length``, ``global_batch``,
                   ``accum``; batches are consecutive-token ramps.
``open_loop``      serving, independent users: ``rate_per_s`` (Poisson
                   arrivals), ``prompt_tokens`` and ``output_tokens`` (a
                   length distribution each).
``closed_loop``    serving, callers that wait, one per slot: the two length
                   distributions.

Every seed gets the same *set* of lengths and gaps -- the stratified
quantiles of the distribution, so the set depends on the count alone -- in an
order of its own, and token values of its own. So no seed changes the amount
of work, only its arrangement.
"""

from __future__ import annotations

import dataclasses
import json
import math
import statistics

import numpy as np

# token ids below this are left to special tokens, as the program's own
# drivers do (chip_smoke.py draws from [3, vocab))
FIRST_TOKEN = 3
_NORMAL = statistics.NormalDist()


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if "kind" not in mix:
        raise ValueError(f"{path}: a traffic mix needs a 'kind'")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any whole-number seed."""
    return np.random.default_rng([abs(int(seed)), stream])


def jax_seed(seed: int) -> int:
    """``--seed`` folded into what a 32-bit ``jax.random.key`` takes."""
    return abs(int(seed)) % (2**31 - 1)


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_set(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, ascending:
    ``{"dist": "const", "value": v}``, ``{"dist": "uniform", "min", "max"}``
    or ``{"dist": "lognormal", "median", "sigma", "min", "max"}``."""
    dist = spec["dist"]
    u = _strata(n)
    if dist == "const":
        out = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        out = spec["min"] + u * (spec["max"] + 1 - spec["min"])
        out = np.floor(out)
    elif dist == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        out = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    if "min" in spec:
        out = np.clip(out, spec["min"], spec["max"])
    return np.round(out).astype(np.int64)


def gap_set(mix: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps of mean ``1 / rate_per_s``: the stratified
    quantiles of the exponential (Poisson arrivals)."""
    gaps = -np.log1p(-_strata(n))
    # the set's mean is exactly 1/rate, so the arrivals fill the window
    return gaps / gaps.mean() / float(mix["rate_per_s"])


@dataclasses.dataclass
class Arrival:
    due_s: float  # offset from the window's start (open loop), else 0
    prompt: list
    max_new_tokens: int


def requests(mix: dict, n: int, vocab: int, seed: int) -> list:
    """``n`` requests of a serving mix for ``seed``: the fixed sets of
    prompt and output lengths, each in the seed's own order."""
    order, values = rng_for(seed, 1), rng_for(seed, 6)
    prompt_lens = order.permutation(length_set(mix["prompt_tokens"], n))
    output_lens = order.permutation(length_set(mix["output_tokens"], n))
    return [
        Arrival(0.0, values.integers(FIRST_TOKEN, vocab, int(p)).tolist(), int(o))
        for p, o in zip(prompt_lens, output_lens)
    ]


def open_loop(mix: dict, seconds: float, vocab: int, seed: int) -> list:
    """The arrivals of ``seconds`` of an open loop, each with its due time."""
    n = max(1, round(float(mix["rate_per_s"]) * seconds))
    reqs = requests(mix, n, vocab, seed)
    gaps = rng_for(seed, 2).permutation(gap_set(mix, n))
    due = np.cumsum(gaps) - gaps[0]  # the first is due at the window's start
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs


def ramp_batch(rng: np.random.Generator, vocab: int, batch: int, seq: int):
    """One training batch of the learnable deterministic stream
    (consecutive-token ramps), as ``chip_smoke.py:ramp_batch`` draws it."""
    starts = rng.integers(0, vocab, (batch, 1))
    ids = ((starts + np.arange(seq)) % vocab).astype(np.int32)
    return ids, ids.copy()
