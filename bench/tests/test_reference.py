"""The plain reference accepts the program's colorings and refuses a
coloring with a single corrupted entry; the control fails it."""
import numpy as np
import pytest

from bench.harness import graphs, reference


@pytest.fixture(scope="module")
def rmat():
    return graphs.rmat(9, 8, (0.45, 0.15, 0.15, 0.25), seed=3)


@pytest.fixture(scope="module")
def program_colors(rmat):
    from repro.core import (Graph, colors_from_views, compute_order,
                            partition_graph, pipeline_sim, presets)

    g = Graph(n=rmat.n, indptr=rmat.indptr, indices=rmat.indices)
    pg = partition_graph(g, 4, seed=1)
    preset = presets.quality(x=10)
    view, _ = pipeline_sim(pg, compute_order(pg, preset.ordering),
                           presets.pipeline_config(preset, n_iters=2))
    return colors_from_views(pg, view)


def test_accepts_the_programs_coloring(rmat, program_colors):
    r = reference.check(rmat, program_colors)
    assert r["conflicts"] == 0 and r["uncolored"] == 0
    assert r["colors"] == np.unique(program_colors).size


@pytest.mark.parametrize("how", ["neighbour_color", "zero", "negative"])
def test_refuses_one_corrupted_entry(rmat, program_colors, how):
    c = program_colors.copy()
    v = int(np.argmax(np.diff(rmat.indptr)))
    u = int(rmat.indices[rmat.indptr[v]])
    c[v] = {"neighbour_color": c[u], "zero": 0, "negative": -1}[how]
    r = reference.check(rmat, c)
    assert r["conflicts"] + r["uncolored"] >= 1


def test_refuses_a_coloring_of_another_size(rmat, program_colors):
    r = reference.check(rmat, program_colors[:-1])
    assert r["conflicts"] > 0 and r["uncolored"] == rmat.n


def test_greedy_counts():
    clique = graphs.from_edges(5, *np.triu_indices(5, 1))
    path = graphs.from_edges(6, np.arange(5), np.arange(1, 6))
    assert reference.greedy_colors(clique) == 5
    assert reference.greedy_colors(path) == 2
    grid = graphs.grid3d_27pt(4, 4, 4)
    assert reference.greedy_colors(grid) == 8


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_control_fails_the_check(rmat, seed):
    c = reference.control_coloring(rmat, 4, seed)
    r = reference.check(rmat, c)
    assert r["uncolored"] == 0 and r["conflicts"] > 0
    offs = np.linspace(0, rmat.n, 5).astype(np.int64)
    src = np.repeat(np.arange(rmat.n), np.diff(rmat.indptr))
    blk = np.searchsorted(offs, np.arange(rmat.n), side="right") - 1
    inside = blk[src] == blk[rmat.indices]
    assert not (c[src] == c[rmat.indices])[inside].any()


@pytest.mark.parametrize("cell", ["rmat_good.batch", "rmat_good.serve"])
def test_control_in_the_programs_place_is_not_correct(cpu_run, monkeypatch,
                                                      cell):
    from bench.tools.control import control_patch
    from bench.harness.drivers import open_loop

    monkeypatch.setattr(open_loop, "DRAIN_S", 2.0)
    out = cpu_run(cell, patch=control_patch(3), backend="xla")
    assert out["correct"] is False
    assert out["checks"]["conflicts"]["value"] > 0
    assert out["checks"]["uncolored"]["value"] == 0
