"""Real-mesh checks for the collective cost model and the qkv sharding
rule (ISSUE 10).  These need more than one device, so a single
subprocess probe runs under ``REPRO_SIM_DEVICES=4`` (the hostdev helper
installs ``--xla_force_host_platform_device_count`` before jax wakes
up) and reports JSON; the tests here pin its numbers:

- ``ambient_mesh_axes`` falls back to a mesh entered with
  ``jax.set_mesh`` (not just the contextvar), so ``"auto"`` retargets to
  the TP twin inside a plain ``with jax.set_mesh(mesh):`` block.
- Acceptance: ``roofline.analysis.parse_collectives`` on a really
  lowered TP program reports exactly the wire bytes
  ``core.dialect.collective_cost`` models (``collective_bytes``) — the
  ring formulas agree on both the column-parallel all-gather and the
  row-parallel all-reduce, payload for payload.
- The ``qkv_heads`` rule is layout-neutral: prefill logits with the
  persisted [wq|wk|wv] concat sharded over the model axis match the
  meshless reference; when a segment's head count does not divide the
  axis the rule replicates instead (never a wrong answer).
"""
import json
import os
import subprocess
import sys

import pytest

import repro
from repro.core.dialect import collective_cost, get_dialect

M, K, N = 128, 512, 1024
ITEM = 4                                   # float32

_PROBE = """
import json
from repro.launch.hostdev import ensure_host_devices
installed = ensure_host_devices()
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

out = {"installed": installed, "n_devices": jax.device_count()}

from repro.core.registry import (AUTO_POLICY, REGISTRY, ambient_mesh_axes,
                                 tp_axis_size)
from repro.kernels import ops  # noqa: F401  (installs every variant)
from repro.roofline.analysis import parse_collectives
from repro.launch.mesh import make_ctx, make_mesh

mesh4 = make_mesh((4,), ("model",))
out["ambient_no_mesh"] = ambient_mesh_axes()
with jax.set_mesh(mesh4):
    out["ambient"] = ambient_mesh_axes()
    out["tp"] = tp_axis_size()
    out["auto_kernel"] = REGISTRY.select(
        "gemm", AUTO_POLICY,
        shape=dict(m=128, n=4096, k=4096)).contract.kernel
out["auto_kernel_no_mesh"] = REGISTRY.select(
    "gemm", AUTO_POLICY, shape=dict(m=128, n=4096, k=4096)).contract.kernel

# --- the two TP matmul strategies the twins model, really lowered ---
M, K, N = __M__, __K__, __N__
x = jnp.ones((M, K), jnp.float32)
w = jnp.ones((K, N), jnp.float32)

col = jax.shard_map(                  # column-parallel: all-gather the output
    lambda x, w: jax.lax.all_gather(x @ w, "model", axis=1, tiled=True),
    mesh=mesh4, in_specs=(P(None, None), P(None, "model")),
    out_specs=P(None, None), check_vma=False)
out["col"] = parse_collectives(
    jax.jit(col).lower(x, w).compile().as_text(), 4)

row = jax.shard_map(                  # row-parallel: all-reduce the partials
    lambda x, w: jax.lax.psum(x @ w, "model"),
    mesh=mesh4, in_specs=(P(None, "model"), P("model", None)),
    out_specs=P(None, None), check_vma=False)
out["row"] = parse_collectives(
    jax.jit(row).lower(x, w).compile().as_text(), 4)

# --- qkv_heads layout equivalence ---
from repro.models import build_model
from repro.models.config import ModelConfig, ParallelConfig
from repro.parallel.sharding import sanitize_tree, tree_shardings

cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                  dtype="float32")
par = ParallelConfig(remat="none")
ref_model = build_model(cfg, par)
params = ref_model.init_params(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2, cfg.vocab_size)
ref_logits = np.asarray(ref_model.prefill(params, {"tokens": toks})[0])

for shape in [(2, 2), (1, 4)]:
    mesh = make_mesh(shape, ("data", "model"))
    ctx = make_ctx(mesh, par, cfg)
    t = shape[1]
    out[f"qkv_shardable_{t}way"] = ctx.qkv_heads_shardable
    out[f"qkv_spec_{t}way"] = str(ctx.spec(("embed", "qkv_heads")))
    model_tp = build_model(cfg, par, ctx)
    sh = sanitize_tree(tree_shardings(ctx, model_tp.param_specs()), params)
    p_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, s) if s is not None else x,
        params, sh,
        is_leaf=lambda v: v is None or not isinstance(v, (dict, list)))
    with jax.set_mesh(mesh):
        lg = np.asarray(model_tp.prefill(p_sh, {"tokens": toks})[0])
    out[f"qkv_maxdiff_{t}way"] = float(np.abs(lg - ref_logits).max())

print("PROBE_JSON " + json.dumps(out))
""".replace("__M__", str(M)).replace("__K__", str(K)) \
    .replace("__N__", str(N))


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    """One 4-device subprocess; every test reads its JSON report."""
    script = tmp_path_factory.mktemp("mesh_probe") / "probe.py"
    script.write_text(_PROBE)
    env = dict(os.environ)
    env["REPRO_SIM_DEVICES"] = "4"
    env["PYTHONPATH"] = os.path.dirname(repro.__path__[0])
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines()
            if l.startswith("PROBE_JSON ")][-1]
    return json.loads(line[len("PROBE_JSON "):])


class TestAmbientMesh:
    def test_hostdev_installs_four_devices(self, probe):
        assert probe["installed"] == 4 and probe["n_devices"] == 4

    def test_entered_mesh_is_the_ambient_fallback(self, probe):
        """No contextvar set: a plain ``jax.set_mesh`` block is enough for
        the registry to see the axes (and nothing leaks outside it)."""
        assert probe["ambient_no_mesh"] == {}
        assert probe["ambient"] == {"model": 4}
        assert probe["tp"] == 4

    def test_auto_retargets_inside_real_mesh_context(self, probe):
        """Tentpole, end to end: the same select() call answers the TP
        twin inside the mesh and the replicated base outside it."""
        assert probe["auto_kernel"] == "gemm_tp"
        assert probe["auto_kernel_no_mesh"] == "gemm"


class TestParsedVsModeledCollectives:
    """Acceptance: parse_collectives on the lowered program reports the
    bytes collective_cost models — exactly, not within tolerance: both
    sides implement the same ring formulas on the same payload."""

    def test_column_parallel_all_gather_bytes_match(self, probe):
        recs = [r for r in probe["col"] if r["op"] == "all-gather"]
        assert len(recs) == 1
        modeled = collective_cost("all_gather", M * N * ITEM, 4,
                                  get_dialect("tpu-v5e"))
        assert int(recs[0]["wire_bytes"]) == modeled.wire_bytes
        assert int(recs[0]["result_bytes"]) == modeled.payload_bytes
        assert recs[0]["group_size"] == modeled.group

    def test_row_parallel_all_reduce_bytes_match(self, probe):
        recs = [r for r in probe["row"] if r["op"] == "all-reduce"]
        assert len(recs) == 1
        modeled = collective_cost("all_reduce", M * N * ITEM, 4,
                                  get_dialect("tpu-v5e"))
        assert int(recs[0]["wire_bytes"]) == modeled.wire_bytes
        assert int(recs[0]["result_bytes"]) == modeled.payload_bytes

    def test_no_stray_collectives(self, probe):
        """Each strategy lowers to exactly its one modeled collective —
        the cost dicts carry one term because the programs do."""
        assert len(probe["col"]) == 1 and len(probe["row"]) == 1


class TestQkvHeadsRule:
    def test_divisible_heads_shard_over_model(self, probe):
        assert probe["qkv_shardable_2way"] is True
        assert probe["qkv_spec_2way"] == "PartitionSpec('data', 'model')"

    def test_non_divisible_heads_replicate(self, probe):
        """4 heads / 2 KV heads on a 4-way axis: a shard boundary would
        cut across the q/k/v seams, so the rule replicates."""
        assert probe["qkv_shardable_4way"] is False
        assert probe["qkv_spec_4way"] == "PartitionSpec('data', None)"

    @pytest.mark.parametrize("t", [2, 4])
    def test_layout_neutral_logits(self, probe, t):
        """Sharded or replicated, the persisted concat's prefill logits
        match the meshless reference."""
        assert probe[f"qkv_maxdiff_{t}way"] < 1e-4
