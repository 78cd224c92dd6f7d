"""End-to-end metric arithmetic over the requests of one run.

Times are host-clock seconds from the start of the measured window.  A
percentile is taken over every request in the set (a request that never
finished counts at the time the run gave up on it), and a rate over the
whole window, so a stall anywhere in the window moves both.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    """What the front end saw of one request."""

    rid: int
    due: float                       # when it was due to be sent
    prompt_len: int
    max_new: int
    queued: Optional[float] = None   # when the loop noticed it was due
    admit_start: Optional[float] = None
    first: Optional[float] = None    # delivery of its first token
    last: Optional[float] = None     # delivery of its latest token
    n: int = 0                       # tokens delivered
    n_in_window: int = 0             # tokens delivered before the close
    shared_pages: int = 0            # prompt pages it found shared
    failed: bool = False
    done: bool = False

    def deliver(self, t: float, k: int, window_s: float) -> None:
        if k <= 0:
            return
        if self.first is None:
            self.first = t
        self.last = t
        self.n += k
        if t < window_s:
            self.n_in_window += k


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_s(records: List[Record], give_up: float) -> List[float]:
    """Due time to first token, for every request of the set."""
    return [(r.first if r.first is not None else give_up) - r.due
            for r in records]


def tpot_s(records: List[Record]) -> List[float]:
    """(last delivery - first delivery) / (n - 1), per request of two or
    more tokens."""
    return [(r.last - r.first) / (r.n - 1) for r in records
            if r.n >= 2 and r.first is not None]


def output_tok_s(records: List[Record], window_s: float) -> float:
    return sum(r.n_in_window for r in records) / window_s


def summarize(records: List[Record], window_s: float, give_up: float,
              names) -> dict:
    """The end-to-end metrics ``names`` asks for, in their units."""
    out = {}
    for name in names:
        if name == "ttft_p95_ms":
            out[name] = 1e3 * percentile(ttft_s(records, give_up), 95)
        elif name == "tpot_p95_ms":
            out[name] = 1e3 * percentile(tpot_s(records), 95)
        elif name == "output_tok_s":
            out[name] = output_tok_s(records, window_s)
    return out
