"""Multi-device paths that need >1 XLA device: run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
stays at 1 device by design)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_coloring_equals_sim():
    print(run_sub("""
        import numpy as np, jax
        from repro.core import (rmat, partition_graph, compute_order,
                                ColorConfig, color_graph, RecolorConfig,
                                recolor_step, colors_from_views,
                                assert_valid, ordering)
        from jax.sharding import AxisType
        g = rmat.grid2d(32, 32, 9)
        pg = partition_graph(g, 8)
        order = compute_order(pg, ordering.SMALLEST_LAST)
        cfg = ColorConfig(max_colors=64, superstep=64)
        v_sim, s_sim = color_graph(pg, order, cfg)
        mesh = jax.make_mesh((8,), ("workers",), axis_types=(AxisType.Auto,))
        v_sh, s_sh = color_graph(pg, order, cfg, mesh=mesh)
        assert (np.asarray(v_sim) == np.asarray(v_sh)).all(), "views differ"
        rcfg = RecolorConfig(max_colors=64)
        key = jax.random.key(5)
        r_sim, _ = recolor_step(pg, np.asarray(v_sim), "nd", rcfg, key=key)
        r_sh, _ = recolor_step(pg, np.asarray(v_sh), "nd", rcfg, key=key,
                               mesh=mesh)
        assert (np.asarray(r_sim) == np.asarray(r_sh)).all(), "rc differs"
        assert_valid(g, colors_from_views(pg, np.asarray(r_sh)))
        print("sharded == sim OK")
    """))


@pytest.mark.slow
def test_color_many_sharded_equals_sim():
    """Batched multi-graph pipeline on a real workers mesh == sim executor
    (the graph batch axis rides inside each shard via vmap)."""
    print(run_sub("""
        import numpy as np, jax
        from repro.core import (rmat, partition_graph, ColorConfig,
                                RecolorConfig, PipelineConfig, color_many)
        from jax.sharding import AxisType
        graphs = [rmat.rmat_good(6, 8, seed=1), rmat.rmat_bad(6, 8, seed=2),
                  rmat.grid2d(16, 16, 9)]
        pgs = [partition_graph(g, 8) for g in graphs]
        cfg = PipelineConfig(color=ColorConfig(max_colors=64, superstep=64),
                             recolor=RecolorConfig(max_colors=64),
                             n_iters=3, patience=1)
        sim = color_many(pgs, cfg)
        mesh = jax.make_mesh((8,), ("workers",), axis_types=(AxisType.Auto,))
        sh = color_many(pgs, cfg, mesh)
        for a, b in zip(sim, sh):
            assert np.array_equal(a["view"], b["view"]), "views differ"
            assert np.array_equal(a["colors"], b["colors"])
            assert a["history"] == b["history"] and a["color"] == b["color"]
            assert a["n_iters_run"] == b["n_iters_run"]
        print("color_many sharded == sim OK")
    """))


@pytest.mark.slow
def test_elastic_remesh_restore():
    """Save a sharded train state on a (2,) DP mesh, restore on (4,)."""
    print(run_sub("""
        import tempfile, numpy as np, jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.sharding import AxisType
        from repro.train import checkpoint as ckpt
        mesh2 = jax.make_mesh((2,), ("data",), axis_types=(AxisType.Auto,))
        mesh4 = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        x = np.arange(64, dtype=np.float32).reshape(8, 8)
        tree = {"params": {"w": jax.device_put(
            x, NamedSharding(mesh2, P("data")))}}
        with tempfile.TemporaryDirectory() as td:
            ckpt.save(td, 5, tree)
            specs = {"params": {"w": P("data")}}
            step, back = ckpt.restore(td, mesh=mesh4, specs=specs)
            assert step == 5
            w = back["params"]["w"]
            assert len(w.sharding.device_set) == 4, w.sharding
            np.testing.assert_array_equal(np.asarray(w), x)
        print("elastic remesh OK")
    """))


@pytest.mark.slow
def test_compressed_dp_train_step_sharded():
    """int8 EF gradient all-reduce inside shard_map trains a toy model."""
    print(run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.train.compression import make_compressed_train_step

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            l = jnp.mean((pred - batch["y"]) ** 2)
            return l, {}

        def opt_update(params, grads, state):
            params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
            return params, state, {}

        step = make_compressed_train_step(loss_fn, opt_update, axis="data")
        w_true = np.random.default_rng(0).normal(0, 1, (8, 1)).astype(
            np.float32)
        params = {"w": jnp.zeros((8, 1))}
        err = {"w": jnp.zeros((8, 1))}
        state = {}
        smapped = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(), P(), P("data")),
            out_specs=(P(), P(), P(), P()), check_vma=False))
        r = np.random.default_rng(1)
        for i in range(60):
            x = r.normal(0, 1, (64, 8)).astype(np.float32)
            y = x @ w_true
            params, state, err, info = smapped(params, state, err,
                                               {"x": x, "y": y})
        final = float(info["loss"])
        assert final < 1e-2, final
        print("compressed DP step OK, loss", final)
    """))


@pytest.mark.slow
def test_model_train_step_on_2x4_mesh():
    """Smoke arch train_step lowers + runs on a real (2,4) data×model mesh."""
    print(run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.configs import get_arch, smoke_of, plan_for_mesh
        from repro.data.pipeline import DataConfig, host_batch, device_batch
        from repro.launch.steps import make_train_step
        from repro.models import param_defs
        from repro.models.layers import ParamDef
        from repro.train.optimizer import OptConfig, init_opt_state
        from repro.train.trainer import init_params_sharded

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        plan = plan_for_mesh(mesh)
        arch = smoke_of(get_arch("moonshot_v1_16b_a3b"))
        pdefs = param_defs(arch)
        specs = jax.tree.map(lambda d: plan.spec(d.dims, d.shape), pdefs,
                             is_leaf=lambda t: isinstance(t, ParamDef))
        with jax.set_mesh(mesh):
            params = init_params_sharded(pdefs, mesh, specs, 0)
            opt_cfg = OptConfig(peak_lr=1e-3, warmup_steps=2)
            opt = init_opt_state(params, opt_cfg)
            fn = jax.jit(make_train_step(arch, plan, opt_cfg))
            dc = DataConfig(vocab_size=arch.vocab_size, seq_len=32,
                            global_batch=4)
            losses = []
            for s in range(6):
                b = device_batch(host_batch(dc, s, arch), mesh, plan)
                params, opt, m = fn(params, opt, b)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        print("2x4 mesh train OK", [round(l, 3) for l in losses])
    """))
