"""Distance-2 coloring over the two-hop halo: validity, goldens, equivalence.

The acceptance matrix of the D2 subsystem: for grids + all three RMAT
classes at P in {2, 4, 16}, the distributed D2 coloring must

  - carry zero distance-2 conflicts (``check_coloring(distance=2)``),
  - be bitwise-identical across the sparse / all-gather exchange schemes,
  - be bitwise-identical across the xla / pallas-interpret backends,
  - match the golden (n_colors, sha) pins below.

``tile=16`` bounds intra-tile speculative conflicts: inside one tile every
member of a distance-2 clique (e.g. a hub's neighbourhood) sees the same
forbidden set and picks the same first-fit color, so progress per round per
clique is one vertex *per tile* — small tiles keep skewed RMAT graphs
converging in tens of rounds (DESIGN.md §5).
"""
import hashlib
from functools import lru_cache

import jax
import numpy as np
import pytest

from repro.core import (ColorConfig, PipelineConfig, RecolorConfig,
                        check_coloring, color_graph_sim, colors_from_views,
                        compute_order, ordering, partition_graph,
                        pipeline_sim, recolor_sim, rmat)

GRAPHS = {
    "grid2d": lambda: rmat.grid2d(12, 12, 9),
    "grid3d": lambda: rmat.grid3d(6, 6, 6),
    "rmat_er": lambda: rmat.rmat_er(8, 8, seed=1),
    "rmat_good": lambda: rmat.rmat_good(8, 8, seed=1),
    "rmat_bad": lambda: rmat.rmat_bad(8, 8, seed=1),
}
P_SWEEP = (2, 4, 16)

CFG = dict(max_colors=512, superstep=64, tile=16, max_rounds=256, seed=0,
           distance=2)


def _hash(colors: np.ndarray) -> str:
    return hashlib.sha256(colors.astype(np.int32).tobytes()).hexdigest()[:16]


@lru_cache(maxsize=None)
def _graph(gname):
    return GRAPHS[gname]()


@lru_cache(maxsize=None)
def _pgraph(gname, P):
    return partition_graph(_graph(gname), P, halo=2)


@lru_cache(maxsize=None)
def _color_d2(gname, P, scheme="sparse", backend="xla"):
    pg = _pgraph(gname, P)
    order = compute_order(pg, ordering.NATURAL)
    cfg = ColorConfig(scheme=scheme, backend=backend, **CFG)
    view, stats = color_graph_sim(pg, order, cfg)
    return np.asarray(view), stats


def _assert_views_equal(pg, va, vb):
    """Bitwise equality over local slots + each shard's real ghosts."""
    np.testing.assert_array_equal(va[:, : pg.n_local_max],
                                  vb[:, : pg.n_local_max])
    for p in range(pg.P):
        ng = int(pg.n_ghost[p])
        np.testing.assert_array_equal(
            va[p, pg.n_local_max : pg.n_local_max + ng],
            vb[p, pg.n_local_max : pg.n_local_max + ng])


# (gname, P) -> (n_colors, sha16) of the sparse/xla D2 coloring.
D2_GOLD = {
    ("grid2d", 2): (15, "448c19943ff1f812"),
    ("grid2d", 4): (15, "b6c120b743514b90"),
    ("grid2d", 16): (14, "e867c988e04b521f"),
    ("grid3d", 2): (41, "69b5d0621b1c0650"),
    ("grid3d", 4): (40, "54d91afa1c37e30f"),
    ("grid3d", 16): (39, "25e1d8add1b79810"),
    ("rmat_er", 2): (64, "5f511f8598f9f47c"),
    ("rmat_er", 4): (63, "93a9146971130836"),
    ("rmat_er", 16): (63, "d0bc78a755459e25"),
    ("rmat_good", 2): (67, "71ed9af071a5446c"),
    ("rmat_good", 4): (65, "8fc404023e6013a8"),
    ("rmat_good", 16): (65, "3120609686f71fdd"),
    ("rmat_bad", 2): (82, "ca0b4a9c55621082"),
    ("rmat_bad", 4): (83, "076b557e3613881c"),
    ("rmat_bad", 16): (82, "f82f163cbf4a7166"),
}


@pytest.mark.parametrize("P", P_SWEEP)
@pytest.mark.parametrize("gname", list(GRAPHS))
def test_d2_valid_and_golden(gname, P):
    g = _graph(gname)
    pg = _pgraph(gname, P)
    view, stats = _color_d2(gname, P)
    colors = colors_from_views(pg, view)
    st = check_coloring(g, colors, distance=2)
    assert st["valid"], st
    assert st["n_colors"] == stats["n_colors"]
    want_nc, want_hash = D2_GOLD[(gname, P)]
    assert stats["n_colors"] == want_nc
    assert _hash(colors) == want_hash


@pytest.mark.parametrize("P", P_SWEEP)
@pytest.mark.parametrize("gname", list(GRAPHS))
def test_d2_sparse_equals_allgather(gname, P):
    pg = _pgraph(gname, P)
    va, _ = _color_d2(gname, P, scheme="sparse")
    vb, _ = _color_d2(gname, P, scheme="allgather")
    _assert_views_equal(pg, va, vb)


@pytest.mark.parametrize("P", P_SWEEP)
@pytest.mark.parametrize("gname", list(GRAPHS))
def test_d2_xla_equals_pallas(gname, P):
    pg = _pgraph(gname, P)
    va, sa = _color_d2(gname, P, backend="xla")
    vb, sb = _color_d2(gname, P, backend="pallas")
    _assert_views_equal(pg, va, vb)
    assert sa["n_colors"] == sb["n_colors"]


def test_d2_sequential_mode_valid():
    """The paper-faithful scalar loop honors the two-hop constraint too."""
    g, P = _graph("rmat_good"), 4
    pg = _pgraph("rmat_good", P)
    order = compute_order(pg, ordering.NATURAL)
    cfg = ColorConfig(parallel_chunk=False, **CFG)
    view, _ = color_graph_sim(pg, order, cfg)
    st = check_coloring(g, colors_from_views(pg, np.asarray(view)),
                        distance=2)
    assert st["valid"], st


@pytest.mark.parametrize("distance", [1, 2])
def test_gather2_slots_count_two_hop_rows(distance):
    """``gather2_slots`` is rows × ``maxd2`` for every gather through the
    two-hop ELL, over all shards, and 0 at distance 1.  With the superstep,
    the tile and the recolor chunk at least a shard's rows, a speculative
    round gathers every row once to color and twice (colors, priorities)
    to detect, and a recolor iteration gathers every row once a class."""
    g, P = _graph("grid3d"), 2
    pg = partition_graph(g, P, halo=distance)
    cfg = PipelineConfig(
        color=ColorConfig(max_colors=512, superstep=512, tile=512,
                          distance=distance, scheme="sparse"),
        recolor=RecolorConfig(max_colors=512, chunk=512, distance=distance,
                              scheme="sparse"),
        n_iters=2)
    view, res = pipeline_sim(pg, compute_order(pg, ordering.NATURAL), cfg)
    assert check_coloring(g, colors_from_views(pg, np.asarray(view)),
                          distance=distance)["valid"]
    rows = pg.n_local_max
    width = pg.maxd2 if distance == 2 else 0
    assert res["color"]["gather2_slots"] == (
        res["color"]["n_rounds"] * 3 * rows * width * P)
    assert len(res["history"]) == 2
    for h in res["history"]:
        assert h["gather2_slots"] == h["n_colors_before"] * rows * width * P
    assert (res["color"]["gather2_slots"] > 0) == (distance == 2)


def test_d1_on_halo2_partition_matches_halo1():
    """The wider halo changes comm structure, never D1 colorings."""
    g = _graph("rmat_good")
    pg1 = partition_graph(g, 4, halo=1)
    pg2 = _pgraph("rmat_good", 4)
    order1 = compute_order(pg1, ordering.NATURAL)
    order2 = compute_order(pg2, ordering.NATURAL)
    cfg = ColorConfig(max_colors=512, superstep=64, seed=0)
    v1, _ = color_graph_sim(pg1, order1, cfg)
    v2, _ = color_graph_sim(pg2, order2, cfg)
    np.testing.assert_array_equal(colors_from_views(pg1, np.asarray(v1)),
                                  colors_from_views(pg2, np.asarray(v2)))


class TestD2Recolor:
    @pytest.fixture(scope="class")
    def seeded(self):
        gname, P = "rmat_good", 4
        view, stats = _color_d2(gname, P)
        return _graph(gname), _pgraph(gname, P), view, stats

    @pytest.mark.parametrize("perm", ["rv", "ni", "nd", "rand"])
    def test_permutations_valid_and_no_worse(self, seeded, perm):
        g, pg, view, stats = seeded
        cfg = RecolorConfig(max_colors=512, distance=2)
        v2, st = recolor_sim(pg, view, perm, cfg, key=jax.random.key(11))
        colors = colors_from_views(pg, np.asarray(v2))
        chk = check_coloring(g, colors, distance=2)
        assert chk["valid"], chk
        assert st["n_colors"] <= stats["n_colors"]

    def test_piggyback_equals_per_step(self, seeded):
        """The D2 dep sources (CSR + two-hop ELL) defer no needed round."""
        g, pg, view, _ = seeded
        key = jax.random.key(3)
        v_pig, st_pig = recolor_sim(pg, view, "nd", RecolorConfig(
            max_colors=512, distance=2, piggyback=True), key=key)
        v_all, st_all = recolor_sim(pg, view, "nd", RecolorConfig(
            max_colors=512, distance=2, piggyback=False), key=key)
        _assert_views_equal(pg, np.asarray(v_pig), np.asarray(v_all))
        assert st_pig["n_exchanges"] <= st_all["n_exchanges"]

    def test_scheme_equivalence(self, seeded):
        g, pg, view, _ = seeded
        key = jax.random.key(5)
        va, _ = recolor_sim(pg, view, "nd", RecolorConfig(
            max_colors=512, distance=2, scheme="allgather"), key=key)
        vs, _ = recolor_sim(pg, view, "nd", RecolorConfig(
            max_colors=512, distance=2, scheme="sparse"), key=key)
        _assert_views_equal(pg, np.asarray(va), np.asarray(vs))


class TestPartialD2:
    """Bipartite partial coloring: only a marked subset is constrained."""

    def _marked(self, g, pg):
        marked_g = np.arange(g.n) % 2 == 0          # "column" vertices
        marked = np.zeros((pg.P, pg.n_local_max), bool)
        for p in range(pg.P):
            nl, lo = int(pg.n_local[p]), int(pg.offs[p])
            marked[p, :nl] = marked_g[lo : lo + nl]
        return marked_g, marked

    @pytest.mark.parametrize("gname", ["grid2d", "rmat_good"])
    def test_partial_d2_valid(self, gname):
        g, P = _graph(gname), 4
        pg = _pgraph(gname, P)
        marked_g, marked = self._marked(g, pg)
        order = compute_order(pg, ordering.NATURAL)
        cfg = ColorConfig(partial=True, **CFG)
        view, stats = color_graph_sim(pg, order, cfg, marked=marked)
        colors = colors_from_views(pg, np.asarray(view))
        assert (colors[~marked_g] == 0).all()        # untouched subset
        chk = check_coloring(g, colors, distance=2, marked=marked_g)
        assert chk["valid"], chk
        # partial never needs more colors than the full D2 coloring
        _, full = _color_d2(gname, P)
        assert stats["n_colors"] <= full["n_colors"]

    def test_partial_requires_marked(self):
        pg = _pgraph("grid2d", 2)
        order = compute_order(pg, ordering.NATURAL)
        with pytest.raises(AssertionError):
            color_graph_sim(pg, order, ColorConfig(partial=True, **CFG))

    def test_partial_then_recolor(self):
        """RC on a partial coloring recolors only the marked classes.

        No flag needed: unmarked vertices are class 0, which the step loop
        skips unconditionally.
        """
        g, P = _graph("grid2d"), 4
        pg = _pgraph("grid2d", P)
        marked_g, marked = self._marked(g, pg)
        order = compute_order(pg, ordering.NATURAL)
        view, _ = color_graph_sim(pg, order,
                                  ColorConfig(partial=True, **CFG),
                                  marked=marked)
        cfg = RecolorConfig(max_colors=512, distance=2)
        v2, _ = recolor_sim(pg, view, "nd", cfg, key=jax.random.key(2))
        colors = colors_from_views(pg, np.asarray(v2))
        assert (colors[~marked_g] == 0).all()
        chk = check_coloring(g, colors, distance=2, marked=marked_g)
        assert chk["valid"], chk


def test_distance2_requires_halo2():
    g = _graph("grid2d")
    pg = partition_graph(g, 2, halo=1)
    order = compute_order(pg, ordering.NATURAL)
    with pytest.raises(ValueError, match="halo=2"):
        color_graph_sim(pg, order, ColorConfig(**CFG))


def test_validator_negative_sentinel_no_crash():
    """A leaked -1 sentinel color must report, not raise (np.bincount)."""
    g = _graph("grid2d")
    colors = np.ones(g.n, np.int32)
    colors[5] = -1
    for distance in (1, 2):
        st = check_coloring(g, colors, distance=distance)
        assert not st["valid"]
        assert st["n_uncolored"] == 1
