"""Reduce the program's own spans and scopes in a trace of the window.

:mod:`bench.tracing` times the engine from outside: the harness's
``bench.*`` spans and device time by XLA module.  This module reads what
the program itself writes into the same trace:

- the serving layer's spans (``serve.admit`` and its children,
  ``serve.step``, ``serve.sync`` and its children; the names are listed
  in ``src/repro/serve/spans.py``), on the profiler's host clock;
- the model's ``jax.named_scope`` path of every tick operation, from the
  tick program's optimized HLO (``BatchedEngine.tick_hlo_text``): each
  instruction's ``metadata={op_name=...}`` names the scopes it was
  traced in, and the instruction's name is the operation's name in the
  trace.

Host spans move onto the device clock by the shift that
:func:`bench.tracing.reduce` applies, computed the same way, so both
modules see the spans at the same times.  :func:`reduce` returns keys
that :func:`bench.tracing.reduce` does not, and changes none of its.
"""
from __future__ import annotations

import bisect
import re
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench.tracing import (_DEVICE_PLANE, HOST_PREFIX, WINDOW, Interval,
                           _length, categorize, module_name, op_name, union)

PROGRAM_PREFIX = "serve."
ADMIT = "serve.admit"
ADMIT_PREFILL = "serve.admit.prefill"
STEP = "serve.step"
#: the model's named scopes (``models/transformer.py``); an operation
#: takes the innermost one on its path, or ``NO_SCOPE``
SCOPES = frozenset({"embed", "attention", "kv_write", "kv_read", "mlp",
                    "head"})
NO_SCOPE = "none"
#: operations whose time is that of the operations they contain
CONTAINERS = frozenset({"while", "conditional", "call"})
UNANNOTATED = "host:unannotated"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CONTAINER_NAME = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def read_xspace(path: str) -> dict:
    """What :func:`bench.tracing.read_xspace` returns, from the same one
    parse of the file, with the program's ``serve.*`` spans under
    ``program``; ``host`` keeps the harness's spans alone."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: List[dict] = []
    host: List[Interval] = []
    program: List[Interval] = []
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
                for e in line.events:
                    name = e.name
                    if name.startswith(HOST_PREFIX):
                        host.append((name, e.start_ns, e.end_ns))
                    elif name.startswith(PROGRAM_PREFIX):
                        program.append((name, e.start_ns, e.end_ns))
    return {"devices": devices, "host": host, "program": program}


def scope_map(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (innermost model scope or ``none``, opcode),
    for every instruction of an HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        path = _OP_NAME.search(rest)
        scope = NO_SCOPE
        if path:
            for part in reversed(path.group(1).split("/")):
                if part in SCOPES:
                    scope = part
                    break
        out[name] = (scope, op.group(1) if op else "")
    return out


def _is_container(name: str, scopes: Dict[str, Tuple[str, str]]) -> bool:
    if name in scopes:
        return scopes[name][1] in CONTAINERS
    return bool(_CONTAINER_NAME.match(name))


def _window(trace: dict) -> Tuple[float, float, float]:
    """(start, end, shift): the window on the device clock, and what host
    times add to get there (as :func:`bench.tracing.reduce` takes it)."""
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
    return lo + shift, hi + shift, shift


def _owned_ops(dev: dict, lo: float, hi: float, categories: Dict[str, str]):
    """(category, op name, start, end) of each operation inside the
    window, by the module execution it starts in."""
    mods = sorted((s, e, module_name(n)) for n, s, e in dev["modules"]
                  if lo <= s < hi)
    starts = [m[0] for m in mods]
    out = []
    for name, s, e in dev["ops"]:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        i = bisect.bisect_right(starts, s) - 1
        owner = mods[i][2] if i >= 0 and s < mods[i][1] else ""
        out.append((categorize(owner, categories) if owner else "other",
                    op_name(name), s, e))
    return out


def _tick_scopes(owned, scopes) -> Tuple[Dict[str, float], float, float]:
    """Self time of the tick's operations by scope (containers left out),
    and the seconds found and not found in ``scopes``."""
    by_scope: Dict[str, float] = defaultdict(float)
    found = missing = 0.0
    for cat, name, s, e in owned:
        if cat != "tick" or _is_container(name, scopes):
            continue
        if name in scopes:
            by_scope[scopes[name][0]] += (e - s) / 1e9
            found += (e - s) / 1e9
        else:
            missing += (e - s) / 1e9
    return by_scope, found, missing


def _admit_device(owned, admits) -> dict:
    """Device time inside each admission span, split into the prefill
    module and the rest, and the span's time with the device idle.  One
    pass over the operations: admission spans never overlap, so each
    operation meets only the spans between its start and its end."""
    admits = sorted(admits)
    ends = [b for _, b in admits]
    pieces = [([], []) for _ in admits]      # (prefill, other) per span
    for cat, _, s, e in owned:
        k = bisect.bisect_right(ends, s)
        while k < len(admits) and admits[k][0] < e:
            a, b = admits[k]
            pieces[k][cat != "prefill"].append((max(s, a), min(e, b)))
            k += 1
    prefill = other = idle = 0.0
    for (a, b), (pre, rest) in zip(admits, pieces):
        prefill += _length(union(pre))
        other += _length(union(rest))
        idle += (b - a) - _length(union(pre + rest))
    return {"prefill_s": prefill / 1e9, "other_s": other / 1e9,
            "idle_s": idle / 1e9}


def _idle_by_span(busy, spans, lo, hi) -> Dict[str, float]:
    """Every idle stretch of the window, each piece given to the
    innermost span over it (the one that started last)."""
    gaps, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    out: Dict[str, float] = defaultdict(float)
    stack: list = []
    k = 0
    for gs, ge in gaps:
        points = [gs] + cuts[bisect.bisect_right(cuts, gs):
                             bisect.bisect_left(cuts, ge)] + [ge]
        for a, b in zip(points, points[1:]):
            while k < len(spans) and spans[k][1] <= a:
                stack.append(spans[k])
                k += 1
            stack = [x for x in stack if x[2] > a]
            out[stack[-1][0] if stack else UNANNOTATED] += (b - a) / 1e9
    return dict(out)


def _span_self(spans) -> Dict[str, float]:
    """Each span's duration less the part its child spans cover, summed
    by name."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    covered = [0.0] * len(spans)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = spans[stack[-1]]
            covered[stack[-1]] += max(min(e, p[2]) - s, 0.0)
        stack.append(i)
    out: Dict[str, float] = defaultdict(float)
    for (name, s, e), c in zip(spans, covered):
        out[name] += (e - s - c) / 1e9
    return dict(out)


def reduce(trace: dict, categories: Dict[str, str],
           scopes: Optional[Dict[str, Tuple[str, str]]] = None) -> dict:
    """The program's spans and scopes inside the ``bench.window`` span.

    ``trace`` is what :func:`read_xspace` returns; ``scopes`` what
    :func:`scope_map` makes of the tick's HLO.  Returns, in seconds
    unless named otherwise:

    - ``tick_scopes`` (with ``scopes``): ``{"seconds": {scope: s},
      "ticks": n}``, the self time of the tick's operations by innermost
      model scope (``none`` where no scope is on the path: the scan's
      slicing and restacking and XLA's own copies), containers such as
      ``while`` left out; and ``tick_scope_coverage``, the share of that
      time whose operation the HLO names;
    - ``admit_device``: inside the ``serve.admit`` spans, device time of
      the prefill module (``prefill_s``) and of everything else
      (``other_s``), the time with the device idle (``idle_s``), and the
      counts of ``serve.admit`` and ``serve.admit.prefill`` spans.  Every
      device operation inside an admission span is admission's: the
      front end syncs every tick before it admits, and admission ends in
      a blocking fetch of the first tokens;
    - ``idle_by_span``: every idle stretch of the window, each piece
      given to the innermost program or harness span over it, else to
      ``host:unannotated``; the values sum to the window's idle time;
    - ``span_self_s``: host time of each span name less the part its
      child spans cover;
    - ``clock_check_ms``: ``{"min", "median", "pairs"}`` of the k-th tick
      module's start less the k-th ``serve.step`` start, after the shift.
      A right shift puts the minimum at or just above 0.

    Device quantities are averaged over the devices in the trace, as
    :func:`bench.tracing.reduce` averages them; idle time is that of the
    first device, as its gaps are."""
    lo, hi, shift = _window(trace)
    moved = [(n, s + shift, e + shift)
             for n, s, e in trace["host"] + trace.get("program", [])]
    inside = [x for x in moved if lo <= x[1] < hi]
    calls = [x for x in inside if x[0] != WINDOW]
    n_dev = len(trace["devices"])
    admits = [(s, min(e, hi)) for n, s, e in calls if n == ADMIT]
    by_scope: Dict[str, float] = defaultdict(float)
    found = missing = 0.0
    admit = {"prefill_s": 0.0, "other_s": 0.0, "idle_s": 0.0}
    busy0 = None
    ticks: List[float] = []
    for dev in trace["devices"]:
        owned = _owned_ops(dev, lo, hi, categories)
        if busy0 is None:
            busy0 = union([(s, e) for _, _, s, e in owned])
            ticks = sorted(s for n, s, e in dev["modules"] if lo <= s < hi
                           and categorize(module_name(n), categories)
                           == "tick")
        if scopes is not None:
            d, f, m = _tick_scopes(owned, scopes)
            for k, v in d.items():
                by_scope[k] += v / n_dev
            found += f
            missing += m
        for k, v in _admit_device(owned, admits).items():
            admit[k] += v / n_dev
    admit["calls"] = len(admits)
    admit["prefill_spans"] = sum(1 for x in calls if x[0] == ADMIT_PREFILL)
    out = {"admit_device": admit,
           "idle_by_span": _idle_by_span(busy0 or [], calls, lo, hi),
           "span_self_s": _span_self(inside)}
    if scopes is not None:
        out["tick_scopes"] = {"seconds": dict(by_scope), "ticks": len(ticks)}
        total = found + missing
        out["tick_scope_coverage"] = found / total if total else None
    steps = sorted(s for n, s, e in calls if n == STEP)
    diffs = [(t - s) / 1e6 for t, s in zip(ticks, steps)]
    if diffs:
        out["clock_check_ms"] = {"min": min(diffs),
                                 "median": statistics.median(diffs),
                                 "pairs": len(diffs)}
    return out
