"""Compile the Pallas kernels and the fused pipeline for a described v5e chip.

Nothing runs: the TPU compiler, installed alongside JAX, compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip would refuse (constructs Mosaic cannot lower, blocks off the tiling,
more VMEM or HBM than the chip has).  Interpret mode cannot show any of
that.  Each compile must emit the kernels (``tpu_custom_call``) and fit one
chip's 16 GB.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ordering, partition_graph, presets, rmat
from repro.core.pipeline import pipeline_sim_program, program_cache_clear
from repro.kernels import ops

HBM_BYTES = 16 * 10**9       # one v5e chip
# MAXD above the ELL width of rmat_good(20, 8) (603 at seed 0)
MAXD, MAX_COLORS, V = 678, 1024, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """Compile ``fn`` for one described chip from argument shapes; returns
    the compiled executable after the kernel and memory checks."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    # this process's default backend is the CPU: steer the kernels off
    # interpret mode, and keep compiles for an absent chip out of the
    # persistent cache (they cannot be read back without one)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *args):
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                           sharding=one_chip), args)
        compiled = jax.jit(fn).lower(*shapes).compile()
        assert "tpu_custom_call" in compiled.as_text()
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
        assert total < HBM_BYTES, total
        return compiled

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


SELECTIONS = [("first_fit", {}), ("random_x", dict(x=10)),
              ("staggered", dict(offset=64))]


# the narrowest widths of a recolor chunk or a speculative tile
# (``speculative.gather_widths``) and the full ELL width
@pytest.mark.parametrize("width", [8, 16, MAXD])
@pytest.mark.parametrize("tiles", [1, 2], ids=["d1", "d2"])
@pytest.mark.parametrize("selection,kw", SELECTIONS,
                         ids=[s for s, _ in SELECTIONS])
def test_select_kernel_compiles(chip_compile, tiles, selection, kw, width):
    entry = ops.select_colors if tiles == 1 else ops.select_colors_d2

    def fn(*args):
        *nbrs, active, rand = args
        return entry(*nbrs, active, rand, max_colors=MAX_COLORS,
                     selection=selection, backend="pallas", **kw)

    chip_compile(fn, *[_spec((V, width))] * tiles, _spec((V,), jnp.bool_),
                 _spec((V,), jnp.uint32))


@pytest.mark.parametrize("tiles", [1, 2], ids=["d1", "d2"])
def test_conflict_kernel_compiles(chip_compile, tiles):
    entry = ops.detect_conflicts if tiles == 1 else ops.detect_conflicts_d2

    def fn(myc, myp, *args):
        *nbrs, active = args
        return entry(myc, myp, *nbrs, active, backend="pallas")

    chip_compile(fn, _spec((V,)), _spec((V,)), *[_spec((V, MAXD))] * 2 * tiles,
                 _spec((V,), jnp.bool_))


# the speculative phase's detection chunks gather at the same widths
@pytest.mark.parametrize("width", [8, 16])
def test_conflict_kernel_compiles_narrow(chip_compile, width):
    def fn(myc, myp, *args):
        *nbrs, active = args
        return ops.detect_conflicts(myc, myp, *nbrs, active, backend="pallas")

    chip_compile(fn, _spec((V,)), _spec((V,)), *[_spec((V, width))] * 2,
                 _spec((V,), jnp.bool_))


def test_fused_pipeline_compiles(chip_compile):
    """The whole ``pipeline_sim`` program (speculative Random-X coloring +
    K fused ND recoloring iterations, P=4 vmapped shards) on the kernels."""
    g = rmat.rmat_good(14, 8, seed=0)
    pg = partition_graph(g, 4)
    preset = presets.quality(x=10)
    cfg = presets.pipeline_config(preset, n_iters=5)
    cfg = dataclasses.replace(
        cfg, color=dataclasses.replace(cfg.color, backend="pallas"),
        recolor=dataclasses.replace(cfg.recolor, backend="pallas"))
    order = np.asarray(ordering.compute_order(pg, preset.ordering))
    program_cache_clear()
    try:
        fn, args = pipeline_sim_program(pg, order, cfg)
        chip_compile(fn, *args)
    finally:
        program_cache_clear()    # the program was traced for the chip
