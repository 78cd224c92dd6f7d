"""Whole runs at CPU size: the harness past its look for a chip, with the
timed path sound and with it broken underneath; the command without a
chip; and cells found by name in files dropped in beside the others."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness, spec
from bench.tests.tiny import make_tree

SEED = 2**33 + 17


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(autouse=True)
def _no_checkout_cache(monkeypatch, tmp_path):
    # keep the tests' compiled programs out of the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def run(tree, cell="tiny.chat", seconds=1.0):
    repo, root = tree
    return harness.run_cell(cell, SEED, seconds, False, require_tpu=False,
                            repo=repo, root=root)


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.docs", "tiny.backlog"])
def test_sound_run_is_correct(tree, cell):
    out = run(tree, cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    names = {m["name"] for m in spec.end_to_end_for(
        cell, spec.load_benchmark(tree[0]))}
    assert set(out["metrics"]) == names
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_requests_due_at_the_close_are_served_in_the_drain(tree,
                                                          monkeypatch):
    """The loop is inside a tick when the window closes: every request
    that fell due meanwhile is admitted in the drain, not failed."""
    orig = harness.FrontEnd.tick

    def slow_first_tick(self, cfg):
        if self.in_window and not self.tick_work:
            time.sleep(1.1)
        orig(self, cfg)

    monkeypatch.setattr(harness.FrontEnd, "tick", slow_first_tick)
    out = run(tree)
    assert out["notes"]["queue_at_close"] > 1
    assert out["failed"] == 0 and out["correct"], out["checks"]


def _state_unchanged(orig):
    def tick(self, params, tokens, live, remaining, cache):
        out = orig(self, params, tokens, live, remaining, cache)
        return out[:3] + (cache,) + out[4:]
    return tick


def _half_batch(orig):
    def tick(self, params, tokens, live, remaining, cache):
        out = orig(self, params, tokens, live, remaining, cache)
        half = tokens.shape[0] // 2
        nxt = out[0].at[:half].set(tokens[:half])
        return (nxt,) + out[1:]
    return tick


def _token_altered(orig):
    def tick(self, params, tokens, live, remaining, cache):
        out = orig(self, params, tokens, live, remaining, cache)
        nxt = out[0].at[0].set((out[0][0] + 1) % 512)
        return (nxt,) + out[1:]
    return tick


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_broken_timed_path_is_not_correct(tree, monkeypatch, fault):
    from repro.serve import BatchedEngine
    monkeypatch.setattr(BatchedEngine, "_tick_impl",
                        fault(BatchedEngine._tick_impl))
    out = run(tree)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_command_without_a_chip_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "run.py"), "--workload",
         "granite-8b.chat", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_files_dropped_in_are_found_by_name(tree, tmp_path):
    repo, root = tree
    with open(os.path.join(root, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-wide"
    cfg["intermediate_size"] = 192
    with open(os.path.join(root, "configs", "tiny-wide.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "traffic", "tiny-slow.json"), "w") as f:
        json.dump({"kind": "open", "schedule_seed": 3, "rate_per_s": 4.0,
                   "prompt": {"dist": "uniform", "min": 16, "max": 16},
                   "output": {"dist": "uniform", "min": 4, "max": 4}}, f)
    with open(os.path.join(root, "metrics", "requests_seen.py"), "w") as f:
        f.write('UNIT = "requests"\n\n\ndef read(ctx):\n'
                '    return len(ctx.records)\n')
    assert spec.load_config("tiny-wide", root)["intermediate_size"] == 192
    assert spec.load_traffic("tiny-slow", root)["rate_per_s"] == 4.0
    assert spec.load_metric("requests_seen", root).UNIT == "requests"
    bench = spec.load_benchmark(repo)
    bench["workloads"].append({"name": "tiny-wide.slow", "config":
                               "tiny-wide", "traffic": "tiny-slow",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "requests_seen", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine admission",
                               "moves": "output_tok_s"})
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = harness.run_cell("tiny-wide.slow", SEED, 1.0, False,
                           require_tpu=False, repo=repo, root=root)
    assert out["correct"]
    assert out["attempted"] == 4
    assert [m["name"] for m in spec.per_layer_for("tiny-wide.slow", bench)] \
        == ["requests_seen"]
    with pytest.raises(FileNotFoundError):
        spec.load_traffic("no-such-mix", root)
    with pytest.raises(ValueError):
        spec.load_config("../configs/tiny", root)
