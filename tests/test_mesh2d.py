"""The 2D ``batch × shard`` mesh layer (DESIGN.md §10).

In-process: the ``MeshSpec`` layouts, the ``shard_axis_of`` axis-name
contract (stub meshes — no devices needed), the ``PlanSignature.axes``
cache-key component, and the lane-target padding arithmetic.

Subprocess (8 host devices, ``@slow``): the ISSUE's bitwise pins —
``color_many`` on a 2D mesh with batch=1 equals the 1-axis result
equals ``run_pipeline``/``color_many``, both exchange schemes, distance 1
and 2, plus a genuinely-sharded batch case on a ``(2, 2)`` mesh.
"""
import dataclasses

import pytest

from repro.core.comm import (AXIS, BATCH_AXIS, batch_axis_of, batch_axis_size,
                             shard_axis_of)
from repro.core.pipeline import _lane_target
from repro.launch.mesh import MeshSpec

from test_sharded_subprocess import run_sub


@dataclasses.dataclass
class _StubMesh:
    """Just enough mesh surface for the axis-name contract functions."""
    axis_names: tuple
    sizes: tuple

    @property
    def shape(self):
        return dict(zip(self.axis_names, self.sizes))


class TestMeshSpec:
    def test_layouts(self):
        assert MeshSpec.worker(8) == MeshSpec((8,), (AXIS,))
        assert MeshSpec.coloring(4, 2) == MeshSpec((2, 4), (BATCH_AXIS, AXIS))
        assert MeshSpec.coloring(4) == MeshSpec((1, 4), (BATCH_AXIS, AXIS))
        assert MeshSpec.production().axes == ("data", "model")
        assert MeshSpec.production(multi_pod=True).shape == (2, 16, 16)
        assert MeshSpec.local().shape == (1, 1)
        assert MeshSpec.coloring(4, 2).n_devices == 8

    def test_shape_axes_must_agree(self):
        with pytest.raises(AssertionError):
            MeshSpec((2, 4), ("workers",))

    def test_local_build_smoke(self):
        # in-process: only 1 device, but the degenerate meshes build
        mesh = MeshSpec.local().build()
        assert shard_axis_of(mesh) == "model"      # all-size-1 fallback
        assert batch_axis_of(mesh) is None
        assert batch_axis_size(mesh) == 1
        mesh1 = MeshSpec.coloring(1, 1).build()
        assert shard_axis_of(mesh1) == AXIS
        assert batch_axis_size(mesh1) == 1


class TestShardAxisContract:
    def test_workers_always_wins(self):
        m = _StubMesh((BATCH_AXIS, AXIS), (2, 4))
        assert shard_axis_of(m) == AXIS
        assert batch_axis_of(m) == BATCH_AXIS
        assert batch_axis_size(m) == 2

    def test_single_non_batch_axis(self):
        assert shard_axis_of(_StubMesh(("shards",), (8,))) == "shards"
        assert shard_axis_of(_StubMesh((BATCH_AXIS, "s"), (2, 8))) == "s"

    def test_single_sized_axis(self):
        assert shard_axis_of(_StubMesh(("data", "model"), (1, 8))) == "model"
        assert shard_axis_of(_StubMesh(("data", "model"), (8, 1))) == "data"

    def test_all_size_one_smoke_mesh(self):
        assert shard_axis_of(_StubMesh(("data", "model"), (1, 1))) == "model"

    def test_ambiguous_mesh_raises(self):
        with pytest.raises(ValueError, match="MeshSpec"):
            shard_axis_of(_StubMesh(("data", "model"), (2, 4)))


class TestSignatureAxes:
    def test_sim_signature_pins_the_vmap_axis(self):
        from repro.core import (ColorConfig, PipelineConfig, RecolorConfig,
                                partition_graph, plan_signature, rmat)
        pg = partition_graph(rmat.grid2d(8, 8, 5), 4)
        cfg = PipelineConfig(
            color=ColorConfig(max_colors=32, scheme="allgather"),
            recolor=RecolorConfig(max_colors=32, scheme="allgather"))
        sig = plan_signature(pg, cfg)
        assert sig.axes == ((AXIS, 4),)
        assert f"axes={AXIS}=4" in sig.describe()

    def test_mesh_signature_pins_the_mesh_geometry(self):
        from repro.core import (ColorConfig, PipelineConfig, RecolorConfig,
                                partition_graph, plan_signature, rmat)
        pg = partition_graph(rmat.grid2d(8, 8, 5), 1)
        cfg = PipelineConfig(
            color=ColorConfig(max_colors=32, scheme="allgather"),
            recolor=RecolorConfig(max_colors=32, scheme="allgather"))
        mesh = MeshSpec.coloring(1, 1).build()
        sig = plan_signature(pg, cfg, mesh=mesh)
        assert sig.axes == ((BATCH_AXIS, 1), (AXIS, 1))
        # a different geometry is a different program identity
        assert sig != plan_signature(pg, cfg)


class TestLaneTarget:
    def test_pow2_padding(self):
        assert _lane_target(3, True) == 4
        assert _lane_target(4, True) == 4
        assert _lane_target(5, True) == 8
        assert _lane_target(3, False) == 3

    def test_batch_axis_divisibility(self):
        assert _lane_target(1, True, 2) == 2
        assert _lane_target(3, True, 4) == 4
        assert _lane_target(3, False, 2) == 4
        assert _lane_target(4, True, 2) == 4


@pytest.mark.slow
def test_mesh2d_batch1_bitwise_equals_1axis_and_sim():
    """The safety pin: 2D mesh (batch=1) == 1-axis == one device, both
    schemes, distance 1 and 2."""
    print(run_sub("""
        import numpy as np
        from repro.core import (rmat, partition_graph, compute_order,
                                ColorConfig, RecolorConfig, PipelineConfig,
                                color_many, run_pipeline)
        from repro.launch.mesh import make_coloring_mesh, make_worker_mesh
        P = 4
        mesh1 = make_worker_mesh(P)
        mesh2 = make_coloring_mesh(P, batch=1)
        assert tuple(mesh2.axis_names) == ("batch", "workers")
        for scheme, distance in (("sparse", 1), ("allgather", 1),
                                 ("sparse", 2)):
            halo = 2 if distance == 2 else 1
            gs = [rmat.rmat_good(6, 8, seed=3), rmat.grid2d(16, 16, 9)]
            pgs = [partition_graph(g, P, halo=halo) for g in gs]
            cfg = PipelineConfig(
                color=ColorConfig(max_colors=64, superstep=64, scheme=scheme,
                                  distance=distance),
                recolor=RecolorConfig(max_colors=64, scheme=scheme,
                                      distance=distance),
                n_iters=2, patience=1)
            sim = color_many(pgs, cfg, pad_batch=True)
            one = color_many(pgs, cfg, mesh1, pad_batch=True)
            two = color_many(pgs, cfg, mesh2, pad_batch=True)
            for a, b, c in zip(sim, one, two):
                assert np.array_equal(a["view"], b["view"])
                assert np.array_equal(a["view"], c["view"])
                assert np.array_equal(a["colors"], c["colors"])
                assert a["history"] == b["history"] == c["history"]
                assert a["color"] == b["color"] == c["color"]
            # solo fused pipeline on the 2D mesh == sim
            order = compute_order(pgs[0], "internal_first")
            v_sim, r_sim = run_pipeline(pgs[0], order, cfg)
            v_2d, r_2d = run_pipeline(pgs[0], order, cfg, mesh2)
            assert np.array_equal(np.asarray(v_sim), np.asarray(v_2d))
            assert r_sim == r_2d
            print("pin OK:", scheme, "D", distance)
        print("mesh2d batch=1 bitwise pins OK")
    """))


@pytest.mark.slow
def test_mesh2d_sharded_batch_on_2x2_mesh():
    """(2, 2) mesh: 2 shards × 2 batch lanes per device group — lanes are
    genuinely sharded over the batch axis and results still match sim."""
    print(run_sub("""
        import numpy as np
        from repro.core import (rmat, partition_graph, ColorConfig,
                                RecolorConfig, PipelineConfig, color_many)
        from repro.launch.mesh import make_coloring_mesh
        P = 2
        mesh = make_coloring_mesh(P, batch=2)
        assert mesh.devices.shape == (2, 2)
        gs = [rmat.rmat_er(6, 8, seed=s) for s in (1, 2, 3)]
        pgs = [partition_graph(g, P) for g in gs]
        cfg = PipelineConfig(
            color=ColorConfig(max_colors=64, superstep=64, scheme="sparse"),
            recolor=RecolorConfig(max_colors=64, scheme="sparse"),
            n_iters=2, patience=1)
        sim = color_many(pgs, cfg, pad_batch=True)
        sh = color_many(pgs, cfg, mesh, pad_batch=True)
        for a, b in zip(sim, sh):
            assert np.array_equal(a["view"], b["view"])
            assert np.array_equal(a["colors"], b["colors"])
            assert a["history"] == b["history"] and a["color"] == b["color"]
            assert a["n_iters_run"] == b["n_iters_run"]
        print("(2,2) mesh sharded-batch OK")
    """))
