"""One general load generator. A traffic mix is a data file of parameters
(``traffic/<name>.json``); nothing here names a cell.

The loop is open or closed by the file (``arrivals.loop``). Open: arrivals
at ``arrivals.rate_per_s``, whatever the engine does, for a cell below
capacity or one that offers a rate. Closed: ``arrivals.clients`` callers,
each of which sends its next conversation the moment its last answer is
whole, so that a replica run full stays full at any engine's speed; the
list is then consumed as far as the engine gets.

The work a seed offers is fixed: the file gives one multiset of (prompt,
answer) lengths, the cross product of two quantile grids, and (open loop)
one multiset of gaps between arrivals, the quantile grid of the exponential
distribution at the file's rate. ``--seed`` chooses their order, and the
token ids. The order is balanced: every run of ``m`` consecutive arrivals
(``m`` the grid's side) holds each prompt length, each answer length and one
gap of each of ``m`` strata exactly once, so any window of a run sees the
same mix whatever the seed, and a pass of ``m*m`` arrivals is the whole
multiset.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Arrival:
    due: float | None          # seconds after the generator starts; None in
                               # a closed loop: when a client comes free
    prompt: np.ndarray         # int32 token ids
    max_new: int


def length_pairs(lengths: dict, n: int, rng: np.random.Generator) -> list:
    """``n`` (prompt, answer) pairs: passes over the cross product of the two
    grids, each pass a Latin square cut into balanced blocks."""
    prompts, answers = list(lengths["prompt"]), list(lengths["answer"])
    m = len(prompts)
    if len(answers) != m:
        raise ValueError("prompt and answer grids differ in length")
    out = []
    while len(out) < n:
        sigma, tau = rng.permutation(m), rng.permutation(m)
        for b in rng.permutation(m):
            for j in rng.permutation(m):
                out.append((prompts[sigma[(tau[j] + b) % m]], answers[j]))
    return out[:n]


def gaps(rate_per_s: float, m: int, n: int, rng: np.random.Generator) -> list:
    """``n`` gaps between arrivals: passes over the ``m*m``-point quantile
    grid of the exponential distribution with mean ``1/rate`` (a Poisson
    process), one gap of each of ``m`` strata (by size) in every block of
    ``m``."""
    total = m * m
    grid = [-math.log(1.0 - (k + 0.5) / total) / rate_per_s
            for k in range(total)]
    strata = [grid[s * m:(s + 1) * m] for s in range(m)]
    out = []
    while len(out) < n:
        picks = [rng.permutation(m) for _ in range(m)]   # per stratum
        for b in range(m):
            for s in rng.permutation(m):
                out.append(strata[s][picks[s][b]])
    return out[:n]


def schedule(traffic: dict, seed_rng: np.random.Generator, horizon_s: float,
             vocab: int) -> list:
    """Every arrival of a run: a backlog due at once, then the rest of the
    list. Open loop: ``ramp.backlog`` requests, then arrivals at
    ``arrivals.rate_per_s`` until ``horizon_s``. Closed loop: the backlog is
    the ``arrivals.clients`` themselves, and the rest has no due time (a
    client sends it when its last answer is whole); it is as long as an
    engine that finishes ``arrivals.ceiling_per_s`` requests a second would
    consume by ``horizon_s``. The backlog is a snapshot of the steady state,
    so that the ramp need not outlast the longest answer. As many of it as the
    engine has slots are requests under way: answers drawn in proportion to
    their length (a long answer holds its slot for longer), each at an age
    spread evenly from none to all, the answer cut to what is left of it and
    the prompt lengthened by the tokens the request would already hold (up to
    the grid's longest prompt, which the engine's largest bucket takes). The
    rest of it waits for a slot, so it is whole."""
    arr = traffic["arrivals"]
    closed = arr.get("loop", "open") == "closed"
    m = len(traffic["lengths"]["prompt"])
    if closed:
        backlog = int(arr["clients"])
        n = backlog + int(math.ceil(float(arr["ceiling_per_s"]) * horizon_s))
    else:
        rate = float(arr["rate_per_s"])
        backlog = int(traffic["ramp"].get("backlog", 0))
        n = backlog + int(math.ceil(rate * horizon_s * 1.05)) + m
    pairs = length_pairs(traffic["lengths"], n, seed_rng)
    between = None if closed else gaps(rate, m, n, seed_rng)
    under_way = min(backlog, int(traffic["engine"]["n_slots"]))
    if under_way:
        answers = sorted(traffic["lengths"]["answer"])
        share = np.cumsum(answers) / float(sum(answers))
        by_length = [answers[min(m - 1, int(np.searchsorted(
            share, (k + 0.5) / under_way)))]
            for k in seed_rng.permutation(under_way)]
        left = (seed_rng.permutation(under_way) + 0.5) / under_way
        longest = max(traffic["lengths"]["prompt"])
    out, t = [], 0.0
    for i, (p_len, a_len) in enumerate(pairs):
        if i < under_way:
            a_len = max(1, math.ceil(by_length[i] * left[i]))
            p_len = min(longest, p_len + by_length[i] - a_len)
        elif i >= backlog and closed:
            t = None
        elif i >= backlog:
            t += between[i]
            if t > horizon_s:
                break
        out.append(Arrival(
            due=t, max_new=int(a_len),
            prompt=seed_rng.integers(0, vocab, int(p_len), dtype=np.int32)))
    return out
