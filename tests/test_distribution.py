"""Distribution layer: sharding rules, cell builder, HLO analyzer,
roofline math, elastic resharding restore.

These run on the single real CPU device using 1x1 meshes (sharding code
paths execute; splitting is degenerate).  The multi-device SPMD proof is
the dry-run (launch/dryrun.py, 512 forced host devices) — exercised here
via a subprocess smoke on a reduced cell.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_mesh, make_ctx
from repro.models import build_model
from repro.models.config import ModelConfig, ParallelConfig, SHAPES, ShapeConfig
from repro.parallel.sharding import ShardCtx, shard, tree_shardings
from repro.roofline import analysis as roofline
from repro.roofline.hlo_parser import analyze_hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestShardingRules:
    def _ctx(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        return ShardCtx(mesh=mesh)

    def test_spec_resolution(self):
        ctx = self._ctx()
        spec = ctx.spec(("act_batch", "act_seq", "act_embed"))
        assert spec[0] in ("data", ("data",))   # pod absent on this mesh
        assert spec[1] == "model"
        assert spec[2] is None

    def test_duplicate_axis_degrades_to_replicated(self):
        ctx = self._ctx()
        spec = ctx.spec(("q_heads", "mlp"))   # both -> model
        assert spec[0] == "model" and spec[1] is None

    def test_no_mesh_is_identity(self):
        ctx = ShardCtx(mesh=None)
        x = jnp.ones((4, 4))
        assert shard(x, ("act_batch", "act_embed"), ctx) is x

    def test_param_specs_cover_every_leaf(self):
        """Every arch's param tree has a logical spec for every leaf with
        matching rank (+1 for the scanned layer axis)."""
        for arch in configs.ARCHS:
            cfg = configs.get_reduced(arch)
            model = build_model(cfg, ParallelConfig())
            params = jax.eval_shape(
                lambda m=model: m.init_params(jax.random.PRNGKey(0)))
            specs = model.param_specs()
            flat_p = dict(jax.tree_util.tree_flatten_with_path(params)[0])
            flat_s = dict(jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, tuple))[0])
            assert flat_p.keys() == flat_s.keys(), arch
            for k, leaf in flat_p.items():
                assert len(flat_s[k]) == len(leaf.shape), (arch, k)

    def test_cache_specs_cover_every_leaf(self):
        for arch in configs.ARCHS:
            cfg = configs.get_reduced(arch)
            model = build_model(cfg, ParallelConfig())
            cache = jax.eval_shape(lambda m=model: m.init_cache(2, 16))
            specs = model.cache_specs()
            flat_c = dict(jax.tree_util.tree_flatten_with_path(cache)[0])
            flat_s = dict(jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, tuple))[0])
            assert flat_c.keys() == flat_s.keys(), arch
            for k, leaf in flat_c.items():
                assert len(flat_s[k]) == len(leaf.shape), (arch, k)


class TestCellBuilder:
    def test_all_cells_buildable_reduced(self):
        """build_cell assembles fn+specs+shardings for every runnable
        (arch, shape-kind) without lowering."""
        mesh = make_mesh((1, 1), ("data", "model"))
        small = {
            "train_4k": ShapeConfig("train_4k", "train", 32, 4),
            "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32, 2),
            "decode_32k": ShapeConfig("decode_32k", "decode", 32, 4),
        }
        from repro.launch.cells import build_cell
        for arch in configs.ARCHS:
            for shape_name, sc in small.items():
                cell = build_cell(arch, shape_name, mesh, reduced=True,
                                  shape_cfg=sc)
                assert cell.kind in ("train", "prefill", "decode")

    def test_long_500k_rejected_for_full_attention(self):
        from repro.launch.cells import build_cell
        mesh = make_mesh((1, 1), ("data", "model"))
        with pytest.raises(ValueError):
            build_cell("qwen3-32b", "long_500k", mesh, reduced=True)

    def test_reduced_cell_lowers_and_compiles(self):
        """End-to-end lower+compile on the real device (1x1 mesh)."""
        from repro.launch.cells import build_cell
        mesh = make_mesh((1, 1), ("data", "model"))
        sc = ShapeConfig("train_4k", "train", 32, 4)
        cell = build_cell("granite-8b", "train_4k", mesh, reduced=True,
                          shape_cfg=sc)
        with jax.set_mesh(mesh):
            compiled = cell.lower().compile()
        assert compiled.cost_analysis() is not None


class TestHloParser:
    def test_counts_loop_iterations(self):
        def f(x, ws):
            def body(h, w):
                return jnp.dot(h, w, preferred_element_type=jnp.float32), None
            return jax.lax.scan(body, x, ws)[0]
        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        ws = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
        compiled = jax.jit(f).lower(x, ws).compile()
        a = analyze_hlo(compiled.as_text(), 1)
        expect = 12 * 2 * 128 ** 3
        assert abs(a["flops"] - expect) / expect < 0.05

    def test_nested_scan_multiplies(self):
        def f(x, ws):
            def outer(h, w):
                def inner(h2, _):
                    return jnp.dot(h2, w,
                                   preferred_element_type=jnp.float32), None
                h2, _ = jax.lax.scan(inner, h, None, length=3)
                return h2, None
            return jax.lax.scan(outer, x, ws)[0]
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
        compiled = jax.jit(f).lower(x, ws).compile()
        a = analyze_hlo(compiled.as_text(), 1)
        expect = 5 * 3 * 2 * 64 ** 3
        assert abs(a["flops"] - expect) / expect < 0.05

    def test_bytes_nonzero_and_dominated_by_args(self):
        def f(x):
            return x * 2.0 + 1.0
        x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
        compiled = jax.jit(f).lower(x).compile()
        a = analyze_hlo(compiled.as_text(), 1)
        assert a["hbm_bytes"] >= 2 * 1024 * 1024 * 4   # read + write


class TestRooflineMath:
    def test_terms_and_dominance(self):
        t = roofline.roofline_terms(
            flops_per_chip=197e12, bytes_per_chip=819e9,
            wire_bytes_per_chip=50e9, chips=256, mflops=197e12 * 256)
        assert t["t_compute_s"] == pytest.approx(1.0)
        assert t["t_memory_s"] == pytest.approx(1.0)
        assert t["t_collective_s"] == pytest.approx(1.0)
        assert t["roofline_fraction"] == pytest.approx(1.0)

    def test_model_flops_kinds(self):
        cfg = configs.get_config("granite-8b")
        n = cfg.active_param_count()
        tr = roofline.model_flops(cfg, SHAPES["train_4k"])
        pf = roofline.model_flops(cfg, SHAPES["prefill_32k"])
        dc = roofline.model_flops(cfg, SHAPES["decode_32k"])
        assert tr == pytest.approx(6 * n * 4096 * 256)
        assert pf == pytest.approx(2 * n * 32768 * 32)
        assert dc == pytest.approx(2 * n * 128)

    def test_analytic_bytes_decode_dominated_by_cache(self):
        cfg = configs.get_config("mistral-large-123b")
        b = roofline.analytic_hbm_bytes(cfg, SHAPES["decode_32k"], 256)
        assert b["cache"] > 0.3 * b["total"]

    def test_analytic_bytes_train_has_optimizer_traffic(self):
        cfg = configs.get_config("granite-8b")
        b = roofline.analytic_hbm_bytes(cfg, SHAPES["train_4k"], 256)
        assert b["optimizer"] > 0 and b["weights"] > 0 and b["acts"] > 0


class TestElasticRestore:
    def test_restore_onto_different_mesh(self, tmp_path):
        """Save under a 1x1 'data,model' mesh, restore under a 1-axis
        mesh — the elastic-restart path (device_put against new
        shardings)."""
        from repro.checkpoint import CheckpointManager
        cfg = ModelConfig(name="t", family="dense", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=256, dtype="float32")
        par = ParallelConfig()
        mesh1 = make_mesh((1, 1), ("data", "model"))
        ctx1 = make_ctx(mesh1, par)
        model = build_model(cfg, par, ctx1)
        params = model.init_params(jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"params": params})

        mesh2 = make_mesh((1,), ("data",))
        ctx2 = ShardCtx(mesh=mesh2)
        sh2 = tree_shardings(ctx2, model.param_specs())
        got = mgr.restore(1, {"params": params},
                          shardings={"params": sh2})
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(got["params"])[0]),
            np.asarray(jax.tree.leaves(params)[0]))


@pytest.mark.slow
class TestDryRunSubprocess:
    def test_multi_pod_mesh_in_subprocess(self):
        """512 forced devices + production meshes, reduced config, tiny
        shape — proves the dryrun entrypoint works end to end."""
        code = (
            "import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=512'\n"
            "import jax\n"
            "from repro.launch.mesh import make_production_mesh\n"
            "from repro.launch.cells import build_cell\n"
            "from repro.models.config import ShapeConfig\n"
            "for multi in (False, True):\n"
            "    mesh = make_production_mesh(multi_pod=multi)\n"
            "    sc = ShapeConfig('train_4k', 'train', 64, 32)\n"
            "    cell = build_cell('granite-8b', 'train_4k', mesh,\n"
            "                      reduced=True, shape_cfg=sc)\n"
            "    with jax.set_mesh(mesh):\n"
            "        compiled = cell.lower().compile()\n"
            "    assert compiled is not None\n"
            "print('DRYRUN_SMOKE_OK')\n")
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=900)
        assert "DRYRUN_SMOKE_OK" in out.stdout, out.stderr[-2000:]
