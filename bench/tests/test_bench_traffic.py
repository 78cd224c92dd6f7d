"""The traffic generator: deterministic per seed, the same work for every
seed, and the stated distributions, grids and sharing."""
import json
import os

import numpy as np
import pytest

from bench import spec, traffic
from bench.tests.tiny import DATA

VOCAB = 49152


def mix(name):
    return spec.load_traffic(name)


@pytest.mark.parametrize("name", ["chat", "doc-qa", "doc-qa-unshared",
                                  "decode"])
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 20.0, 2**40 + 3, VOCAB)
    b = traffic.generate(mix(name), 20.0, 2**40 + 3, VOCAB)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("name", ["chat", "doc-qa", "decode"])
def test_seeds_share_the_schedule_not_the_tokens(name):
    a = traffic.generate(mix(name), 20.0, 11, VOCAB)
    b = traffic.generate(mix(name), 20.0, 2**40 + 12, VOCAB)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert [r.prompt[:8] for r in a] != [r.prompt[:8] for r in b]


def test_schedule_seed_orders_the_same_sizes():
    m = mix("chat")
    other = dict(m, schedule_seed=m["schedule_seed"] + 1)
    a = traffic.generate(m, 20.0, 11, VOCAB)
    b = traffic.generate(other, 20.0, 11, VOCAB)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_chat_lengths_follow_distribution_and_grid():
    m = mix("chat")
    reqs = traffic.generate(m, 60.0, 5, VOCAB)
    lens = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    assert set(lens) <= set(traffic.grid(m["prompt"]))
    assert lens.min() >= 128 and lens.max() <= 2048
    assert np.all(lens % 256 == 0)
    assert outs.min() >= 16 and outs.max() <= 512
    # medians of the stated lognormals, after rounding up to the grid
    assert np.median(outs) == pytest.approx(128, rel=0.05)
    assert 512 <= np.median(lens) <= 768
    # Poisson arrivals at the stated rate over the window
    assert len(reqs) == round(m["rate_per_s"] * 60.0)
    gaps = np.diff([r.due_s for r in reqs])
    assert np.mean(gaps) == pytest.approx(1.0 / m["rate_per_s"], rel=0.1)
    assert all(g >= 0 for g in gaps)


def test_documents_are_asked_again_and_shared():
    m = mix("doc-qa")
    reqs = traffic.generate(m, 30.0, 9, VOCAB)
    by_doc = {}
    for r in reqs:
        by_doc.setdefault(r.doc, []).append(r)
    asks = [len(v) for v in by_doc.values()]
    assert min(asks) >= 1 and max(asks) <= 5
    grid = traffic.prompt_lengths(m)
    for group in by_doc.values():
        for r in group:
            assert len(r.prompt) in grid
        bodies = {tuple(r.prompt[:1024]) for r in group}
        assert len(bodies) == 1            # one document, asked again


def test_unshared_documents_have_the_same_lengths_and_schedule():
    a = traffic.generate(mix("doc-qa"), 30.0, 4, VOCAB)
    b = traffic.generate(mix("doc-qa-unshared"), 30.0, 4, VOCAB)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    firsts = [tuple(r.prompt[:1024]) for r in b]
    assert len(set(firsts)) == len(firsts)


def test_backlog_is_due_at_once():
    m = mix("decode")
    reqs = traffic.generate(m, 30.0, 1, VOCAB)
    assert len(reqs) == m["requests"]
    assert all(r.due_s == 0.0 for r in reqs)
    assert {len(r.prompt) for r in reqs} <= {128, 256}
    assert all(512 <= r.max_new <= 1024 for r in reqs)


def test_prompt_lengths_cover_every_prompt():
    for name in ("chat", "doc-qa", "decode", "doc-qa-unshared"):
        m = mix(name)
        lens = set(traffic.prompt_lengths(m))
        for r in traffic.generate(m, 30.0, 21, VOCAB):
            assert len(r.prompt) in lens
            assert len(r.prompt) + r.max_new <= traffic.longest(m)


def test_token_ids_leave_out_pad_and_eos():
    for r in traffic.generate(mix("chat"), 5.0, 3, 512):
        assert min(r.prompt) >= 2 and max(r.prompt) < 512


def test_tiny_mixes_load():
    for f in os.listdir(DATA):
        if f.endswith(".json"):
            with open(os.path.join(DATA, f)) as fh:
                assert isinstance(json.load(fh), dict)
