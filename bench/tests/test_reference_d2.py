"""The distance-2 reference on hand-made graphs and against the program's
own validator, and both cells this reference and the sharded executor
serve, run through the harness on the CPU at a tiny size."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import graphs, reference, reference_d2


def _path3():
    return graphs.from_edges(3, np.array([0, 1]), np.array([1, 2]))


def _star(leaves=4):
    return graphs.from_edges(leaves + 1, np.zeros(leaves, np.int64),
                             np.arange(1, leaves + 1))


def test_path_of_three_needs_three_colors():
    g = _path3()
    assert reference_d2.greedy_colors_d2(g) == 3
    ends_alike = reference_d2.check(g, [1, 2, 1])
    assert ends_alike["conflicts"] == 1 and ends_alike["uncolored"] == 0
    assert reference_d2.check(g, [1, 2, 3]) == dict(conflicts=0, uncolored=0,
                                                    colors=3)
    assert reference.check(g, [1, 2, 1])["conflicts"] == 0


def test_star_leaves_all_differ():
    g = _star(4)
    assert reference_d2.greedy_colors_d2(g) == 5
    assert reference_d2.check(g, [1, 2, 3, 4, 5])["conflicts"] == 0
    two_alike = reference_d2.check(g, [1, 2, 3, 2, 5])
    assert two_alike["conflicts"] == 1
    assert reference_d2.check(g, [1, 0, 3, 4, 5])["uncolored"] == 1
    assert reference_d2.check(g, [1, 2, 3])["uncolored"] == g.n


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_distance1_coloring_is_no_distance2_coloring(seed):
    """A proper distance-1 coloring of a 27-point grid (First Fit in a
    seeded order) passes the distance-1 check and fails this one."""
    g = graphs.grid3d_27pt(6, 5, 4)
    c = reference.control_coloring(g, 1, seed)
    assert reference.check(g, c)["conflicts"] == 0
    r = reference_d2.check(g, c)
    assert r["uncolored"] == 0 and r["conflicts"] > 0
    assert reference_d2.greedy_colors_d2(g) == 27     # the 3x3x3 pattern


@pytest.mark.parametrize("gname", ["grid", "rmat"])
def test_agrees_with_the_programs_validator(gname):
    from repro.core import Graph, check_coloring

    g = (graphs.grid3d_27pt(6, 5, 4) if gname == "grid" else
         graphs.rmat(8, 8, (0.45, 0.15, 0.15, 0.25), seed=1))
    pg = Graph(n=g.n, indptr=g.indptr, indices=g.indices)
    adj = [set(g.indices[g.indptr[v]:g.indptr[v + 1]].tolist())
           for v in range(g.n)]
    two_hop = sum(len(set().union(*(adj[w] for w in adj[v])) - adj[v] - {v})
                  for v in range(g.n))
    assert reference_d2.two_hop_pairs(g) == two_hop
    rng = np.random.default_rng(7)
    for k in (g.n, 4 * g.n, 40 * g.n):
        c = rng.integers(1, k + 1, g.n)
        ok = check_coloring(pg, c, distance=2)["valid"]
        assert (reference_d2.check(g, c)["conflicts"] == 0) == ok


def _recording(log):
    """A patch that keeps each job's colors before the check drops them."""
    def patch(st):
        job = st.job

        def recorded(run, i):
            rec = job(run, i)
            log[i] = rec["colors"].copy()
            return rec
        st.job = recorded
    return patch


def test_d2_cell_runs_on_cpu(cpu_run):
    out = cpu_run("hpcg27_jacobian_d2.batch", traced=True, backend="xla")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["two_hop_slots.d2"]["value"] > 0
    assert out["info"]["greedy_colors"] == 27


def test_four_chip_cell_colors_as_one_chip(cpu_run):
    """The sharded cell colors the graphs of ``rmat_good.batch`` with the
    same seeds, color for color."""
    logs = {}
    for cell in ("rmat_good.batch.4chip", "rmat_good.batch"):
        logs[cell] = {}
        out = cpu_run(cell, seconds=2.0, backend="xla",
                      patch=_recording(logs[cell]))
        assert out["correct"] is True, out["checks"]
    a, b = logs.values()
    common = sorted(set(a) & set(b))
    assert common
    for i in common:
        np.testing.assert_array_equal(a[i], b[i])


@pytest.fixture
def no_exchange(monkeypatch):
    """Every boundary exchange returns the view it was given."""
    import repro.core.recolor as recolor
    import repro.core.speculative as speculative

    def make_exchange(*args, **kw):
        return lambda view, round_mask=None, apply_mask=None: (
            view, jnp.int32(0))
    monkeypatch.setattr(speculative, "make_exchange", make_exchange)
    monkeypatch.setattr(recolor, "make_exchange", make_exchange)


def test_d2_exchange_left_out_is_not_correct(cpu_run, no_exchange):
    out = cpu_run("hpcg27_jacobian_d2.batch", backend="xla")
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["conflicts"]["value"] > 0
