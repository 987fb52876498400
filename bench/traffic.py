"""One generator for every traffic mix: a mix is a data file of parameters.

A mix file (``bench/traffic/<name>.json``) gives the length distributions
and the arrival process; the cell file gives the offered rate and how
long the schedule runs before the measured window opens (``preroll_s``),
so that the window finds the slots as full as the load keeps them.  The
pre-roll and the window are two parts of the schedule; at the same rate
and lengths each part has the same *set* of prompt lengths, answer
lengths and inter-arrival gaps for every seed (each the stratified
quantiles of its distribution), so the work due in the window is fixed.
The seed draws the order of each set and the token ids.

Arrivals are Poisson: the gaps are the exponential quantiles in a plain
random order, so bursts come and go as they do for independent users.
The lengths are spread: each part is cut into runs of ``PER_SEGMENT``
consecutive requests, and each run takes every g-th value of the sorted
set (a different residue for each run, in a random order), so that every
stretch carries a mix of lengths like the whole.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Scheduled:
    """One request of the schedule: due ``due_s`` seconds after the window
    opens (before it, in the pre-roll), with ``prompt`` token ids and ``max_new`` tokens to generate."""
    rid: int
    due_s: float                      # negative in the pre-roll
    prompt: list
    max_new: int


PER_SEGMENT = 8


def _spread(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``values`` in a seeded order in which each run of about
    ``PER_SEGMENT`` consecutive entries spans the whole distribution."""
    v = np.sort(values)
    g = max(1, len(v) // PER_SEGMENT)
    return np.concatenate([rng.permutation(v[r::g])
                           for r in rng.permutation(g)])


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws, (i + 0.5) / n, of one length distribution."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of a (possibly > 32-bit) seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def n_requests(rate: float, seconds: float) -> int:
    return max(1, int(round(rate * seconds)))


def _part(mix: dict, rate: float, seconds: float,
          order: np.random.Generator):
    """Due times in ``[0, seconds)``, prompt and answer lengths of one part
    of a schedule: ``rate * seconds`` requests whose gaps fill it."""
    n = n_requests(rate, seconds)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps = order.permutation(gaps * (seconds / gaps.sum()))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return (due, _spread(_quantiles(mix["prompt_len"], n), order),
            _spread(_quantiles(mix["answer_len"], n), order))


def bucket_set(mix: dict, rate: float, seconds: float, preroll: float,
               bucket) -> list:
    """The prompt buckets every seed's schedule uses (``bucket`` maps a
    prompt length to its bucket) — the shapes set-up has to warm."""
    return sorted({int(bucket(int(p))) for part in (preroll, seconds) if part
                   for p in _quantiles(mix["prompt_len"],
                                       n_requests(rate, part))})


def schedule(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int, preroll: float = 0.0) -> list[Scheduled]:
    """The open-loop schedule of one run, ordered by due time: ``rate *
    preroll`` requests over the ``preroll`` seconds before the window
    opens, then ``rate * seconds`` over the window.  Each part has its own
    fixed set of lengths and gaps, so the work due in the window is the
    same for every seed.  Token ids are drawn uniformly from ``[0,
    vocab)``."""
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrival process {mix.get('arrivals')!r}")
    if mix.get("shared_prefix", 0):
        raise ValueError("shared prefixes are not generated yet")
    order = rng_for(seed, 0)
    due, prompts, answers = _part(mix, rate, seconds, order)
    if preroll:
        d, p, a = _part(mix, rate, preroll, order)
        due = np.concatenate([d - preroll, due])
        prompts = np.concatenate([p, prompts])
        answers = np.concatenate([a, answers])
    ids = rng_for(seed, 1)
    return [Scheduled(rid=i, due_s=float(due[i]),
                      prompt=ids.integers(0, vocab, int(prompts[i])).tolist(),
                      max_new=int(answers[i]))
            for i in range(len(due))]


def longest_need(mix: dict) -> tuple[int, int]:
    """(longest prompt, longest answer) the mix can draw."""
    return int(mix["prompt_len"]["max"]), int(mix["answer_len"]["max"])
