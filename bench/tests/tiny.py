"""A benchmark tree at CPU size, for the benchmark's own tests.

:func:`make_tree` lays out a scratch checkout: a ``BENCHMARK.json`` with
tiny cells, the real metric readers and reference, and the tiny
configuration and traffic files from ``data/``.  The harness finds all
of it by name, as it finds the real cells.
"""
from __future__ import annotations

import json
import os
import shutil

from bench import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

CELLS = {"tiny.chat": "tiny-chat", "tiny.docs": "tiny-docs",
         "tiny.backlog": "tiny-backlog"}


def make_tree(tmp: str) -> tuple:
    """(repo, root) of a scratch benchmark holding the tiny cells."""
    repo = os.path.join(tmp, "repo")
    root = os.path.join(repo, "bench")
    for sub in ("metrics", "reference"):
        shutil.copytree(os.path.join(spec.ROOT, sub),
                        os.path.join(root, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(root, "configs"))
    os.makedirs(os.path.join(root, "traffic"))
    shutil.copy(os.path.join(DATA, "tiny.json"),
                os.path.join(root, "configs", "tiny.json"))
    for mix in CELLS.values():
        shutil.copy(os.path.join(DATA, mix + ".json"),
                    os.path.join(root, "traffic", mix + ".json"))
    bench = spec.load_benchmark()
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": n, "config": "tiny", "traffic": t,
                           "chips": 1, "why": "test"}
                          for n, t in CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(CELLS) if m["name"] != "ttft_p95_ms" \
                else ["tiny.chat", "tiny.docs"]
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return repo, root
