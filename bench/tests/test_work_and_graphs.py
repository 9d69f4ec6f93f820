"""The work counter's pinned count, and the benchmark's generators against
the program's own (same algorithm, same seed, same graph)."""
import numpy as np
import pytest

from bench.harness import graphs, work


def test_work_counter_pinned():
    # 7 passes x (8 B x 40 directed edges + 4 B x 10 vertices)
    assert work.coloring_bytes(10, 40, 5) == 2520
    g = graphs.grid3d_27pt(3, 3, 3)
    assert g.directed_edges == 2 * 158
    assert work.coloring_bytes(g.n, g.directed_edges, 0) == 2 * (
        8 * 316 + 4 * 27)


@pytest.mark.parametrize("scale,seed", [(8, 0), (10, 5)])
def test_rmat_matches_the_programs_generator(scale, seed):
    from repro.core import rmat

    ours = graphs.rmat(scale, 8, (0.45, 0.15, 0.15, 0.25), seed)
    theirs = rmat.rmat_good(scale, 8, seed=seed)
    assert ours.n == theirs.n
    np.testing.assert_array_equal(ours.indptr, theirs.indptr)
    np.testing.assert_array_equal(ours.indices, theirs.indices)


def test_grid_matches_the_programs_generator():
    from repro.core import rmat

    ours = graphs.grid3d_27pt(6, 5, 4)
    theirs = rmat.grid3d(6, 5, 4)
    np.testing.assert_array_equal(ours.indptr, theirs.indptr)
    np.testing.assert_array_equal(ours.indices, theirs.indices)
    assert ours.max_degree == 26
