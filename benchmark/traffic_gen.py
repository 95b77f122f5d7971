"""The one traffic generator: a data file of parameters in, a schedule out.

A traffic mix is ``benchmark/traffic/<name>.json``; this module is the only
code that reads it.  Lengths and arrival gaps are STRATIFIED: each quantity
is a block of ``block`` values at the quantiles ``(i + 0.5) / block`` of its
distribution; a shuffle orders each block (and so pairs prompts with
outputs).  Blocks repeat, each reshuffled.

The shuffles come from the file's own ``schedule_seed``, never from
``--seed``: every run of a cell offers the same lengths at the same due
times in the same order, and ``--seed`` draws the token ids (and, in the
driver, the weights).  With the order drawn from the run's seed, the p90 of
time to first token over the ~100 requests of a window differed by +-10%
between seeds and by 3% between two runs of one seed, although every seed
offered the same multiset (PERF.md, Findings): which requests collide is
the schedule's doing, not the program's.  Another ``schedule_seed`` is
another traffic mix, in a file of its own.

The draws are a copy of the sound part of ``tfmesos_tpu/fleet/workload.py``
(``_clamped_lognormal``: lognormal about a median, rounded, clamped), turned
from random variates into quantiles.  The program's copy may change later;
the yardstick's may not.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def quantile_values(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` values of ``spec``'s distribution at the quantiles
    ``(i + 0.5) / n``, ascending.  Lengths (all but exponential gaps) are
    rounded, clamped to ``[min, max]`` and, with ``"quantum"``, rounded to
    its multiples first."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif dist == "uniform":
        v = float(spec["min"]) + q * (float(spec["max"]) - float(spec["min"]))
    elif dist == "exponential":
        v = -float(spec["mean"]) * np.log1p(-q)
        # the quantile midpoints under-weigh the tail: keep the stated mean
        v *= float(spec["mean"]) / float(v.mean())
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if dist == "exponential":       # gaps: seconds, not lengths
        return v
    quantum = int(spec.get("quantum", 1))
    v = np.round(v / quantum) * quantum
    lo = spec.get("min", 1)
    hi = spec.get("max", None)
    v = np.clip(v, lo, hi)
    return v.astype(np.int64)


def stratified(spec: Dict[str, Any], n: int, block: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` values: whole blocks of the ``block`` quantile values, each
    block shuffled on its own, cut to ``n``."""
    base = quantile_values(spec, block)
    out = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


@dataclasses.dataclass
class Planned:
    """One request of the schedule.  ``due_s`` is seconds after the
    generator starts (0.0 for every request of a backlog)."""
    index: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    group: int          # requests that share a prompt prefix share a group


@dataclasses.dataclass
class Schedule:
    kind: str                   # "open_loop" | "backlog"
    ramp_s: float
    grace_s: float
    requests: List[Planned]


def make_schedule(traffic: Dict[str, Any], seed: int, seconds: float,
                  vocab_size: int) -> Schedule:
    """The whole run's requests, computed before anything is sent.

    ``open_loop``: exponential gaps at ``rate_rps`` (stratified like the
    lengths), enough to cover ramp + window + grace.  ``backlog``:
    ``arrivals.requests`` requests, all due at 0.

    ``repeat: {times, stride}`` asks every prompt ``times`` times, each time
    with another ``suffix``, the copies ``stride`` requests apart: a block of
    the schedule is then ``block * times`` requests.
    """
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 0x7fbe])
    tok_rng = np.random.default_rng([int(seed), 0x70c5])
    arr = traffic["arrivals"]
    kind = arr["kind"]
    ramp_s = float(traffic.get("ramp_s", 0.0))
    grace_s = float(traffic.get("grace_s", 0.0))
    block = int(traffic.get("block", 64))
    rep = traffic.get("repeat") or {"times": 1, "stride": 1}
    times, stride = int(rep["times"]), int(rep["stride"])
    if block % stride:
        raise ValueError("repeat.stride must divide block")
    if kind == "open_loop":
        rate = float(arr["rate_rps"])
        horizon = ramp_s + float(seconds) + grace_s
        n = int(math.ceil(rate * horizon * 1.05)) + block
    elif kind == "backlog":
        n = int(arr["requests"])
    else:
        raise ValueError(f"unknown arrivals.kind {kind!r}")
    n_prompts = -(-n // times)
    n_prompts = -(-n_prompts // block) * block
    n = n_prompts * times
    plen = stratified(traffic["prompt"], n_prompts, block, rng)
    olen = stratified(traffic["output"], n, block, rng)
    slen = (stratified(traffic["suffix"], n, block, rng)
            if traffic.get("suffix") else np.zeros(n, np.int64))
    if kind == "open_loop":
        gaps = stratified({"dist": "exponential", "mean": 1.0 / rate},
                          n, block, rng)
        due = np.cumsum(gaps)
    else:
        due = np.zeros(n)
    # order: prompts in groups of `stride`; a group is emitted `times`
    # times in a row, so the copies of one prompt lie `stride` apart
    order: List[int] = []
    for g0 in range(0, n_prompts, stride):
        for _ in range(times):
            order.extend(range(g0, g0 + stride))
    bodies = [tok_rng.integers(0, vocab_size, int(L), dtype=np.int32)
              for L in plen]
    requests = []
    for i, p in enumerate(order):
        prompt = bodies[p]
        if slen[i]:
            prompt = np.concatenate(
                [prompt, tok_rng.integers(0, vocab_size, int(slen[i]),
                                          dtype=np.int32)])
        requests.append(Planned(index=i, due_s=float(due[i]), prompt=prompt,
                                max_new_tokens=int(olen[i]), group=p))
    return Schedule(kind=kind, ramp_s=ramp_s, grace_s=grace_s,
                    requests=requests)
