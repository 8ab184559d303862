"""The one traffic generator: reads a mix's data file and a seed.

Every seed gets the same sizes and arrival gaps, in another order.  Gaps
and lengths are the quantiles of the mix's distributions at evenly spaced
probabilities, shuffled once into a fixed sequence; the seed picks where in
that sequence its window starts (a rotation) and draws the prompt contents.
So two seeds do the same work with the same bursts, at other times.

Two kinds of mix:

* ``requests``: open-loop arrivals of served requests (prompt tokens and a
  number of tokens to generate);
* ``churn``: open-loop arrivals of placement workloads, each matched by the
  departure of a uniformly drawn live workload, on a control plane.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Sequence, Tuple

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of one seed (any size)."""
    return np.random.default_rng([stream, seed])


#: the seed of the fixed shuffles every run's sequence is a rotation of
PATTERN_SEED = 0


def _rotation(seed: int, stream: int, n: int) -> int:
    return int(rng_for(seed, stream).integers(n))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def exp_gaps(rng: np.random.Generator, n: int, span_s: float) -> np.ndarray:
    """n exponential inter-arrival gaps summing to ``span_s``, shuffled."""
    g = -np.log1p(-_quantiles(n))
    g *= span_s / g.sum()
    return rng.permutation(g)


def lognormal_lengths(rng: np.random.Generator, n: int, spec: dict) -> np.ndarray:
    """n lengths from a clipped lognormal given by median and sigma."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)


# ---------------------------------------------------------------------------
# served requests
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Req:
    rid: str
    due_s: float
    prompt: Tuple[int, ...]
    max_new_tokens: int
    in_window: bool


def _request_block(mix: dict, seed: int, stream: int, start_s: float,
                   span_s: float, vocab: int, prefix: str,
                   in_window: bool) -> List[Req]:
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * span_s)))
    pattern = rng_for(PATTERN_SEED, stream)
    gaps = exp_gaps(pattern, n, span_s)
    plens = lognormal_lengths(pattern, n, mix["prompt_len"])
    olens = lognormal_lengths(pattern, n, mix["output_len"])
    off = _rotation(seed, stream, n)
    gaps, plens, olens = (np.roll(a, -off) for a in (gaps, plens, olens))
    due = start_s + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    rng = rng_for(seed, stream + 10)
    out = []
    for i in range(n):
        prompt = rng.integers(1, vocab, int(plens[i]))
        out.append(Req(f"{prefix}{i}", float(due[i]), tuple(int(t) for t in prompt),
                       int(olens[i]), in_window))
    return out


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             tail_s: float) -> List[Req]:
    """Requests due in ``[0, seconds)`` (counted), then ``tail_s`` more
    seconds at the same rate that keep the load on while the counted ones
    finish (not counted)."""
    if mix["kind"] != "requests":
        raise ValueError(f"not a request mix: {mix['kind']!r}")
    win = _request_block(mix, seed, 1, 0.0, seconds, vocab, "r", True)
    tail = _request_block(mix, seed, 2, float(seconds), tail_s, vocab, "x", False)
    return win + tail


def warmup_prompt_lengths(mix: dict) -> List[int]:
    """Every power-of-two prefill bucket the mix's prompts can fall in."""
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    out, b = [], 1
    while b < hi:
        b *= 2
        if b >= lo:
            out.append(b)
    return out


# ---------------------------------------------------------------------------
# placement churn
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Event:
    """One due event: ``arrive`` (workloads as (wid, profile id)), ``depart``
    (``pick`` in [0, 1) selects the live workload), or ``compact``."""

    due_s: float
    kind: str
    workloads: Tuple[Tuple[str, int], ...] = ()
    pick: float = 0.0


def churn(mix: dict, seed: int, seconds: float, compact_first_s: float,
          compact_period_s: float) -> List[Event]:
    """Events due in ``[0, seconds)`` in trace order.  A compaction due
    with an arrival runs before it, so the one at a window's start always
    compacts the configuration's snapshot, whatever the seed."""
    if mix["kind"] != "churn":
        raise ValueError(f"not a churn mix: {mix['kind']!r}")
    pattern = rng_for(PATTERN_SEED, 3)
    n = max(1, int(round(float(mix["arrival_rate_per_s"]) * seconds)))
    gaps = exp_gaps(pattern, n, seconds)
    lo, hi = mix["burst_size"]
    n_burst = int(round(mix["burst_prob"] * n))
    sizes = [lo + i % (hi - lo + 1) for i in range(n_burst)] + [1] * (n - n_burst)
    sizes = pattern.permutation(np.array(sizes, np.int64))
    total = int(sizes.sum())
    pool: Sequence[int] = mix["profile_pool"]
    pids = pattern.permutation(np.array([pool[i % len(pool)] for i in range(total)]))
    picks = pattern.permutation(_quantiles(total))
    starts = np.concatenate([[0], np.cumsum(sizes)])
    blocks = [(gaps[i], pids[starts[i]:starts[i + 1]], picks[starts[i]:starts[i + 1]])
              for i in range(n)]
    off = _rotation(seed, 3, n)
    blocks = blocks[off:] + blocks[:off]

    events: List[Event] = []
    k, t = 0, 0.0
    for gap, b_pids, b_picks in blocks:
        ws = tuple((f"t{k + j}", int(p)) for j, p in enumerate(b_pids))
        events.append(Event(t, "arrive", workloads=ws))
        for p in b_picks:
            events.append(Event(t, "depart", pick=float(p)))
        k += len(ws)
        t += float(gap)
    t = compact_first_s
    while t < seconds:
        events.append(Event(float(t), "compact"))
        t += compact_period_s
    order = {"compact": 0, "arrive": 1, "depart": 2}
    events.sort(key=lambda e: (e.due_s, order[e.kind]))
    return events


def pick_index(pick: float, n_live: int) -> int:
    return min(int(math.floor(pick * n_live)), n_live - 1)

