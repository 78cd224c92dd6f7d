"""The one generator of requests, read from a traffic mix's parameters.

Every distribution is drawn by stratified quantiles: ``n`` draws take the
quantiles at ``(i + 0.5) / n``, in an order shuffled by the mix's own
``schedule_seed``.  The run's seed draws the token ids.  Every seed
therefore gets the same lengths at the same times: an open-loop tail
over some tens of requests swings with the order of the arrivals far
more than with anything else (the order alone moved a 95th percentile
of time to first token between 0.4 and 4.8 s on one v5e), and a seed
must not change the work.

A mix is one of two kinds:

- ``open``: independent arrivals at ``rate_per_s`` (exponential gaps),
  each request due at its arrival whether or not earlier ones finished.
  With ``documents``, arrivals are documents, each asked several times;
  an ask is the document plus a question, and the asks of one document
  follow each other by exponential gaps.  ``"shared": false`` gives
  every ask a document of its own with the same lengths and schedule.
- ``backlog``: ``requests`` requests, all due at time 0.

Lengths are clipped to ``[min, max]`` and rounded up to ``round_to``, so
that warm-up can cover every prompt shape.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the front end sees it."""

    rid: int
    due_s: float
    prompt: List[int]
    max_new: int
    doc: Optional[int] = None     # the document an ask is about


def _quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    dist = spec.get("dist", "lognormal")
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "choice":
        c = spec["choices"]
        v = np.array([c[min(int(x * len(c)), len(c) - 1)] for x in u])
    elif dist == "exponential":
        v = -spec["mean"] * np.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return v


def draw(spec: dict, n: int, rng: np.random.Generator,
         integer: bool = True) -> np.ndarray:
    """``n`` stratified draws of ``spec``, in an order set by ``rng``."""
    u = (np.arange(n) + 0.5) / n
    v = _quantile(spec, u)
    if integer:
        lo, hi = spec.get("min", 1), spec.get("max", None)
        v = np.clip(np.ceil(v), lo, hi if hi is not None else np.inf)
        step = spec.get("round_to", 1)
        v = (np.ceil(v / step) * step).astype(np.int64)
    return rng.permutation(v)


def grid(spec: dict) -> List[int]:
    """Every value a length spec can take (its clipped, rounded range)."""
    if spec.get("dist") == "choice":
        return sorted(set(int(c) for c in spec["choices"]))
    step = spec.get("round_to", 1)
    lo = int(math.ceil(spec["min"] / step) * step)
    return list(range(lo, int(spec["max"]) + 1, step))


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    # ids 0 and 1 are left out: they are the usual pad and eos ids
    return rng.integers(2, vocab, int(n)).tolist()


def generate(mix: dict, seconds: float, seed: int, vocab: int) -> List[Req]:
    """The requests of one run, sorted by due time: sizes and arrivals
    from the mix's ``schedule_seed``, token ids from ``seed``."""
    rng = np.random.default_rng([int(mix["schedule_seed"]), 0x7261666669])
    ids = np.random.default_rng([int(seed), 0x746F6B656E])
    if mix["kind"] == "backlog":
        n = int(mix["requests"])
        prompts = draw(mix["prompt"], n, rng)
        outs = draw(mix["output"], n, rng)
        return [Req(i, 0.0, _tokens(ids, p, vocab), int(o))
                for i, (p, o) in enumerate(zip(prompts, outs))]
    if mix["kind"] != "open":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    rate = float(mix["rate_per_s"])
    docs = mix.get("documents")
    if docs is None:
        n = max(int(round(rate * seconds)), 1)
        gaps = draw({"dist": "exponential", "mean": 1.0 / rate}, n, rng,
                    integer=False)
        due = np.cumsum(gaps) - gaps[0]
        prompts = draw(mix["prompt"], n, rng)
        outs = draw(mix["output"], n, rng)
        return [Req(i, float(t), _tokens(ids, p, vocab), int(o))
                for i, (t, p, o) in enumerate(zip(due, prompts, outs))]
    # documents asked several times: arrivals are documents
    asks = docs["asks"]
    mean_asks = (asks["min"] + asks["max"]) / 2.0
    d = max(int(round(rate * seconds / mean_asks)), 1)
    doc_gaps = draw({"dist": "exponential", "mean": mean_asks / rate}, d,
                    rng, integer=False)
    starts = np.cumsum(doc_gaps) - doc_gaps[0]
    doc_lens = draw(docs["length"], d, rng)
    n_asks = draw({"dist": "choice",
                   "choices": list(range(asks["min"], asks["max"] + 1))},
                  d, rng)
    total = int(n_asks.sum())
    ask_gaps = draw({"dist": "exponential", "mean": docs["ask_gap_mean_s"]},
                    total, rng, integer=False)
    questions = draw(docs["question"], total, rng)
    outs = draw(mix["output"], total, rng)
    shared = bool(docs.get("shared", True))
    raw, k = [], 0
    for j in range(d):
        doc_tokens = _tokens(ids, doc_lens[j], vocab)
        t = starts[j]
        for a in range(int(n_asks[j])):
            if a:
                t += ask_gaps[k]
            body = doc_tokens if shared else _tokens(ids, doc_lens[j], vocab)
            prompt = body + _tokens(ids, questions[k], vocab)
            raw.append((float(t), prompt, int(outs[k]), j))
            k += 1
    raw.sort(key=lambda r: r[0])
    return [Req(i, t, p, o, j) for i, (t, p, o, j) in enumerate(raw)]


def prompt_lengths(mix: dict) -> List[int]:
    """Every prompt length the mix can send (what warm-up must cover)."""
    docs = mix.get("documents")
    if docs is None:
        return grid(mix["prompt"])
    return sorted({a + b for a in grid(docs["length"])
                   for b in grid(docs["question"])})


def longest(mix: dict) -> int:
    """The most positions one request can reach: prompt plus output."""
    return max(prompt_lengths(mix)) + max(grid(mix["output"]))
