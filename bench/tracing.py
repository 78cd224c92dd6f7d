"""Reduce a profiler trace of the measured window to device times.

The harness wraps its window in a ``bench.window`` annotation and each of
its calls into the engine in ``bench.admit`` / ``bench.step`` /
``bench.sync`` / ``bench.wait``, all on the profiler's own clock.  From
the trace this module takes:

- busy time: the union of the intervals in which a device operation ran,
  inside the window, averaged over the devices in the trace;
- device time by XLA module, grouped into categories by a part of the
  module's name (an operation belongs to the module execution it starts
  in; what belongs to none, or to an unnamed module, is ``other``);
- the longest idle gaps, each named by the host annotation that covers
  most of it, and the device operations that took most time.

The device's clock in the trace may stand a little off the host's (1.4
ms in a trace of one v5e).  The host spans are therefore moved onto the
device's clock by the distance from the window's first call into the
engine to the first device operation: in a traced run nothing runs on
the device between the start of the trace and the window.

:func:`read_xspace` turns an ``.xplane.pb`` into plain tuples, and
:func:`reduce` works on those alone, so that it can be checked on a
small trace without a chip.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

WINDOW = "bench.window"
HOST_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def read_xspace(path: str) -> dict:
    """``{"devices": [{"ops": [...], "modules": [...]}, ...],
    "host": [...]}`` from one ``.xplane.pb``; every entry an Interval."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def categorize(name: str, categories: Dict[str, str]) -> str:
    for cat, part in categories.items():
        if part in name:
            return cat
    return "other"


def reduce(trace: dict, categories: Dict[str, str]) -> dict:
    """Device busy and module times inside the ``bench.window`` span.

    Returns seconds: ``busy_s``, ``window_s``, ``modules`` (category ->
    ``{"device_s", "count"}``), ``idle_gaps`` and ``device_ops`` (lists of
    ``[name, seconds]``, longest first, at most 10 each)."""
    spans = [(s, e) for n, s, e in trace["host"] if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(spans)}")
    if not trace["devices"] or not any(d["ops"] for d in trace["devices"]):
        raise ValueError("the trace holds no device operation")
    lo, hi = spans[0]
    calls = [s for n, s, e in trace["host"]
             if n != WINDOW and lo <= s < hi]
    first_op = min(s for d in trace["devices"] for _, s, _ in d["ops"])
    shift = first_op - min(calls) if calls else 0.0
    lo, hi = lo + shift, hi + shift
    host = [(n, s + shift, e + shift) for n, s, e in trace["host"]
            if n != WINDOW]
    n_dev = len(trace["devices"])
    busy_total = 0.0
    modules = defaultdict(lambda: {"device_s": 0.0, "count": 0})
    op_time = defaultdict(float)
    first_busy = None
    for dev in trace["devices"]:
        ops = sorted(_clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
        merged = union(ops)
        busy_total += _length(merged)
        if first_busy is None:
            first_busy = merged
        mods = sorted((s, e, module_name(n)) for n, s, e in dev["modules"]
                      if lo <= s < hi)
        starts = [m[0] for m in mods]
        per_cat = defaultdict(list)
        for name, s, e in dev["ops"]:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            i = bisect.bisect_right(starts, s) - 1
            owner = mods[i][2] if i >= 0 and s < mods[i][1] else ""
            per_cat[categorize(owner, categories) if owner
                    else "other"].append((s, e))
            op_time[f"{owner or 'none'}/{op_name(name)}"] += (e - s) / 1e9
        for cat, ivs in per_cat.items():
            modules[cat]["device_s"] += _length(union(ivs)) / 1e9 / n_dev
        for s, e, name in mods:
            modules[categorize(name, categories)]["count"] += 1
    for cat in modules:
        modules[cat]["count"] /= n_dev
    gaps = []
    prev = lo
    for s, e in (first_busy or []) + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        best, cover = "host:unannotated", 0.0
        for n, hs, he in host:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = n, c
        named.append([best, (e - s) / 1e9])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy_total / n_dev / 1e9,
            "window_s": (hi - lo) / 1e9,
            "modules": {k: dict(v) for k, v in modules.items()},
            "idle_gaps": named,
            "device_ops": [[k, v] for k, v in ops_top]}
