"""The plain reference against the engine, under both parameter layouts,
and the float8 control against the reference (CPU, tiny widths)."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, spec
from bench.tests.tiny import DATA
from bench.weights import make_params

REF = spec.load_reference("dense")


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(DATA, "tiny.json")) as f:
        cfg = json.load(f)
    from repro.models import build_model
    model = build_model(harness.model_config(cfg),
                        harness.parallel_config())
    return cfg, model, make_params(model, 2**35 + 1)


def concatenated(params):
    """The same weights in the program's concatenated layout."""
    blocks = dict(params["blocks"])
    attn = dict(blocks["attn"])
    attn["wqkv"] = jnp.concatenate([attn.pop("wq"), attn.pop("wk"),
                                    attn.pop("wv")], axis=-1)
    mlp = dict(blocks["mlp"])
    mlp["wig"] = jnp.concatenate([mlp.pop("wi"), mlp.pop("wg")], axis=-1)
    blocks.update(attn=attn, mlp=mlp)
    return dict(params, blocks=blocks)


def layout(params, name):
    return params if name == "split" else concatenated(params)


@pytest.mark.parametrize("name", ["split", "concat"])
def test_reference_matches_prefill_logits(tiny, name):
    cfg, model, params = tiny
    params = layout(params, name)
    prompt = np.random.default_rng(0).integers(2, 512, 40).tolist()
    got, _ = model.prefill(params, {"tokens": jnp.asarray([prompt])})
    ref = REF.logits(params, cfg, prompt, [len(prompt) - 1])
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4 * scale


@pytest.mark.parametrize("name", ["split", "concat"])
def test_engine_serves_the_reference_tokens(tiny, name):
    cfg, model, params = tiny
    params = layout(params, name)
    from repro.serve import BatchedEngine, Request, ServeConfig
    engine = BatchedEngine(model, params, ServeConfig(
        batch_slots=2, max_seq_len=64, eos_id=-1, page_size=16))
    rng = np.random.default_rng(1)
    doc = rng.integers(2, 512, 32).tolist()
    reqs = [Request(rid=i, prompt=doc + rng.integers(2, 512, 8).tolist(),
                    max_new_tokens=12) for i in range(3)]
    engine.run(reqs)
    assert engine.pool.shared_hits > 0       # the document's pages shared
    for r in reqs:
        g = check.gaps(REF, params, cfg, r.prompt, r.generated)
        assert len(g) == 12
        assert float(g.max()) < 1e-4


def test_float8_control_departs_from_the_reference(tiny):
    cfg, model, params = tiny
    prompt = np.random.default_rng(2).integers(2, 512, 48).tolist()
    served = np.random.default_rng(3).integers(2, 512, 24).tolist()
    g = check.gaps(REF, params, cfg, prompt, served, control=True)
    assert float(g.max()) > cfg["limits"]["logit_gap"]
    exact = REF.logits(params, cfg, prompt, [47])
    low = REF.logits(params, cfg, prompt, [47], quant="fp8")
    assert float(jnp.max(jnp.abs(low - exact))) > 1e-3


def test_sample_takes_the_longest_and_a_shared_one():
    class R:
        def __init__(self, rid, n, shared):
            self.rid, self.generated, self.shared_pages = rid, [0] * n, shared
    pool = [R(i, 10 + i, i == 3) for i in range(20)]
    picked = check.sample(pool, np.random.default_rng(0), min_tokens=60)
    assert picked[0].rid == 19 and picked[1].rid == 3
    assert sum(len(r.generated) for r in picked) >= 60
    assert len({r.rid for r in picked}) == len(picked)
