"""Find a cell's configuration, traffic, metrics and reference by name.

Nothing here knows one cell from another: a later change adds a cell by
adding files (``configs/<name>.json``, ``traffic/<name>.json``,
``metrics/<name>.py``, ``reference/<family>.py``) and entries in
``BENCHMARK.json``, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

#: the directory that holds this package's data files
ROOT = os.path.dirname(os.path.abspath(__file__))
#: the checkout the benchmark runs from
REPO = os.path.dirname(ROOT)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(repo: str = REPO) -> dict:
    return _json(os.path.join(repo, "BENCHMARK.json"))


def workload(name: str, bench: dict) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "configs", _checked(name) + ".json"))


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "traffic", _checked(name) + ".json"))


def _module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, root: str = ROOT):
    """The reader of one per-layer metric: a module with ``UNIT`` and
    ``read(ctx)``, which returns a number or None when it finds nothing."""
    path = os.path.join(root, "metrics", _checked(name) + ".py")
    return _module(path, "bench_metric_" + re.sub(r"\W", "_", name))


def load_reference(family: str, root: str = ROOT):
    path = os.path.join(root, "reference", _checked(family) + ".py")
    return _module(path, "bench_reference_" + re.sub(r"\W", "_", family))


def per_layer_for(cell: str, bench: dict) -> list:
    """The per-layer metrics a cell reports: those that list it, and those
    without a ``workloads`` key whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(cell, bench)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def end_to_end_for(cell: str, bench: dict) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]
