"""One general generator for every serving mix: a ``traffic/<mix>.json`` of
parameters in, a schedule of requests out.

Sizes are the distribution's quantiles at ``(i + 0.5) / n`` and
inter-arrival gaps the exponential's, put in one fixed random order: every
run of a mix offers the same requests at the same times, and the seed draws
only the token ids (and, elsewhere, the weights).  A cell below the knee
holds a few dozen requests in a window; a seed that reordered them would
move its latency tail more than any change to the program could.  Times are
seconds from the window's start; a request is measured from when it was
*due*, never from when the generator got round to submitting it.

Groups of requests, each optional in a mix:

* ``in_flight``: one request per slot, admitted during set-up, each with a
  share of its output still to come, so the window opens with the slots
  full and staggered;
* ``backlog``: requests already waiting when the window opens (due at 0);
* ``preroll_s``: arrivals before the window, so it opens in steady state;
* the window's own arrivals, ``rate_per_s`` over ``--seconds``.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    rid: int
    due: float               # seconds from the window's start; -inf: set-up
    prompt: np.ndarray       # int32 token ids
    max_new: int
    group: str               # in_flight | backlog | preroll | window


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``spec``."""
    if n <= 0:
        return np.zeros(0, np.int64)
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + np.floor(q * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def gaps(rate: float, n: int, span: float) -> np.ndarray:
    """``n`` exponential gaps at quantiles ``(i + 0.5) / n``, scaled so the
    ``n`` arrivals fill ``span`` seconds (the last due half a gap before its
    end)."""
    if n <= 0:
        return np.zeros(0)
    g = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return g * (span - 0.5 / rate) / g.sum()


ORDER_SEED = 0x7AFF1C


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run, in rid order (rids count from 0)."""
    rng = np.random.default_rng(ORDER_SEED)
    tok_rng = np.random.default_rng(np.random.SeedSequence([int(seed),
                                                            ORDER_SEED]))
    rate = float(mix["rate_per_s"])
    pre = float(mix.get("preroll_s", 0.0))
    n_fly = int(mix["n_slots"]) if mix.get("in_flight") else 0
    n_pre = int(round(rate * pre))
    n_win = int(round(rate * seconds))
    groups = [  # (group, due times)
        ("in_flight", np.full(n_fly, -math.inf)),
        ("backlog", np.zeros(int(mix.get("backlog", 0)))),
        ("preroll", -pre + np.cumsum(rng.permutation(gaps(rate, n_pre, pre)))),
        ("window", np.cumsum(rng.permutation(gaps(rate, n_win, seconds))))]
    items, rid = [], 0
    for name, dues in groups:
        n = len(dues)
        p_len = rng.permutation(lengths(mix["prompt_len"], n))
        out = lengths(mix["output_len"], n)
        if name == "in_flight":
            # the output still to come of a request caught mid-stream: a
            # fixed share of each length, the longest paired with the least
            frac = (np.arange(n)[::-1] + 0.5) / n
            out = np.maximum(1, np.round(out * frac)).astype(np.int64)
        out = rng.permutation(out)
        toks = tok_rng.integers(0, vocab, int(p_len.sum()), dtype=np.int32)
        at = 0
        for due, pl, mn in zip(dues, p_len, out):
            items.append(Item(rid, float(due), toks[at:at + pl], int(mn),
                              name))
            at += pl
            rid += 1
    return items


def check_fits(mix: dict) -> None:
    """Every request fits the slot's cache."""
    need = int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])
    if need > int(mix["max_len"]):
        raise ValueError(f"prompt + output up to {need} tokens exceeds "
                         f"max_len {mix['max_len']}")
