"""The one traffic generator: reads a mix file, gives a request schedule.

Every seed gets the same multiset of sizes and inter-arrival gaps, in
another order: lengths are the stratified quantiles of the mix's
distribution, gaps the stratified quantiles of an exponential, and the
seed orders them and draws the prompt tokens.  The order is itself
stratified: each run of ``strata`` consecutive requests holds one value
from each of ``strata`` equal slices of the sorted values, shuffled by
the seed within the run.  Two seeds then offer the same work, and every
stretch of the schedule about the same share of it, so their runs differ
by ordering within a run of ``strata`` requests alone.

Mix keys (``traffic/<mix>.json``):

* ``loop``: ``"open"`` (requests due on a schedule, whatever the system
  does) or ``"closed"`` (``clients`` callers, each sending its next
  request when its last one completes);
* ``rate_per_s`` (open), ``clients`` and ``requests_per_client``
  (closed);
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` in tokens;
* ``ramp_s``: seconds of traffic before the window opens (open loop), so
  the window starts at steady occupancy;
* ``strata``: the run length of the stratified order (1: a plain
  shuffle).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Item:
    uid: int
    due: float            # seconds from window open (open loop); unused closed
    prompt: np.ndarray    # (P,) int32 token ids
    max_new: int
    client: int = -1


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped length distribution."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    x = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(p)) for p in q]
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def stratified_order(values: np.ndarray, k: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``values`` (sorted) in an order whose every run of ``k`` entries
    takes one value from each of ``k`` equal slices, shuffled in the
    run."""
    n = len(values)
    m = -(-n // k)
    out = []
    for b in range(m):
        idx = [j * m + b for j in range(k) if j * m + b < n]
        out.extend(values[i] for i in rng.permutation(idx))
    return np.asarray(out)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, int(n), dtype=np.int32)


def _segment(mix: dict, rng: np.random.Generator, n: int, t0: float,
             span: float):
    """``n`` (due, prompt length, output length) over [t0, t0 + span)."""
    k = int(mix.get("strata", 1))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    due = np.cumsum(stratified_order(gaps, k, rng))
    # fixed span: the n-th request is due half a gap before its end
    due = t0 + due * (span * (n - 0.5) / n) / due[-1]
    p_len = stratified_order(lengths(mix["prompt"], n), k, rng)
    o_len = stratified_order(lengths(mix["output"], n), k, rng)
    return list(zip(due, p_len, o_len))


def open_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                  rate: float = None) -> List[Item]:
    """Requests due over [-ramp_s, seconds), Poisson-like at ``rate``.

    The ramp and the window are drawn apart, so the window always holds
    the same number of requests and the same multiset of sizes."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    ramp = float(mix.get("ramp_s", 0.0))
    rng = _rng(seed, 1)
    sched = []
    if ramp > 0:
        sched += _segment(mix, rng, max(1, int(round(rate * ramp))), -ramp,
                          ramp)
    sched += _segment(mix, rng, max(1, int(round(rate * seconds))), 0.0,
                      float(seconds))
    trng = _rng(seed, 2)
    return [Item(uid=i, due=float(d), prompt=_tokens(trng, p, vocab),
                 max_new=int(o)) for i, (d, p, o) in enumerate(sched)]


def closed_clients(mix: dict, seed: int, vocab: int) -> List[List[Item]]:
    """Per-client request sequences; first budgets are staggered.

    Client ``c``'s first request keeps ``(c + 1) / clients`` of its drawn
    output budget (at least the mix's minimum), so completions spread out
    instead of arriving in waves."""
    n_c = int(mix["clients"])
    per = int(mix["requests_per_client"])
    n = n_c * per
    k = int(mix.get("strata", 1))
    rng = _rng(seed, 1)
    p_len = stratified_order(lengths(mix["prompt"], n), k, rng)
    o_len = stratified_order(lengths(mix["output"], n), k, rng)
    trng = _rng(seed, 2)
    out: List[List[Item]] = [[] for _ in range(n_c)]
    for i in range(n):
        c = i % n_c
        m = int(o_len[i])
        if not out[c]:
            m = max(int(mix["output"]["min"]),
                    int(round(m * (c + 1) / n_c)))
        out[c].append(Item(uid=i, due=0.0, prompt=_tokens(trng, p_len[i],
                                                          vocab),
                           max_new=m, client=c))
    return out


def extents(mix: dict):
    """(shortest prompt, longest prompt, longest output) a run can send."""
    return (int(mix["prompt"]["min"]), int(mix["prompt"]["max"]),
            int(mix["output"]["max"]))
