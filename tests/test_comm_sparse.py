"""Sparse neighbour-to-neighbour exchange vs the all-gather scheme.

The two schemes must be *bitwise interchangeable* (DESIGN.md §2): same
colorings from both drivers for any graph/partition, with the sparse scheme
shipping no more bytes than the broadcast — and exactly zero bytes when the
partition has zero cross edges.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ColorConfig, PipelineConfig, RecolorConfig,
                        assert_valid, bucket_graphs, color_graph,
                        colors_from_views, compute_order, ordering,
                        partition_graph, recolor_step, resolve_pipeline_cfg,
                        resolve_scheme, rmat, stats_to_host)
from repro.core.graph import Graph


def _run_both(pg, order, mk_cfg):
    views, stats = {}, {}
    for scheme in ("allgather", "sparse"):
        views[scheme], stats[scheme] = mk_cfg(scheme)
    return views, stats


def _assert_views_equal(pg, va, vs):
    """Bitwise equality over every *meaningful* slot.

    The two schemes treat ghost-slot padding differently (the all-gather
    refresh writes ``table[0, 0]`` into padded ghosts, the sparse rounds
    never touch them), so only local slots and each shard's real ghosts are
    compared.
    """
    va, vs = np.asarray(va), np.asarray(vs)
    np.testing.assert_array_equal(va[:, : pg.n_local_max],
                                  vs[:, : pg.n_local_max])
    for p in range(pg.P):
        ng = int(pg.n_ghost[p])
        np.testing.assert_array_equal(
            va[p, pg.n_local_max : pg.n_local_max + ng],
            vs[p, pg.n_local_max : pg.n_local_max + ng])


# --------------------------------------------------- scheme equivalence ----

@pytest.mark.parametrize("P", [2, 4, 8])
@pytest.mark.parametrize("seed", [3, 11])
def test_sparse_equals_allgather_speculative(P, seed):
    """Seeded RMAT sweep: identical colorings, no more wire bytes."""
    g = rmat.rmat_good(9, 8, seed=seed)
    pg = partition_graph(g, P)
    order = compute_order(pg, ordering.NATURAL)
    views, stats = _run_both(pg, order, lambda s: color_graph(
        pg, order, ColorConfig(max_colors=512, superstep=64, seed=0,
                               scheme=s)))
    _assert_views_equal(pg, views["allgather"], views["sparse"])
    assert_valid(g, colors_from_views(pg, np.asarray(views["sparse"])))
    assert stats["sparse"]["n_exchanges"] == stats["allgather"]["n_exchanges"]
    if P > 1:
        assert 0 < stats["sparse"]["wire_bytes"] <= \
            stats["allgather"]["wire_bytes"]


@pytest.mark.parametrize("P", [2, 4, 8])
def test_sparse_equals_allgather_recolor(P):
    """Both recoloring drivers agree across schemes (and stay valid)."""
    import jax
    g = rmat.rmat_good(9, 8, seed=5)
    pg = partition_graph(g, P)
    order = compute_order(pg, ordering.NATURAL)
    seed_view, _ = color_graph(
        pg, order, ColorConfig(max_colors=512, superstep=64, seed=0))
    key = jax.random.key(7)
    views, stats = _run_both(pg, order, lambda s: recolor_step(
        pg, seed_view, "nd", RecolorConfig(max_colors=512, scheme=s),
        key=key))
    _assert_views_equal(pg, views["allgather"], views["sparse"])
    assert_valid(g, colors_from_views(pg, np.asarray(views["sparse"])))
    assert stats["sparse"]["n_exchanges"] == stats["allgather"]["n_exchanges"]
    if P > 1:
        assert 0 < stats["sparse"]["wire_bytes"] <= \
            stats["allgather"]["wire_bytes"]


def test_sparse_piggyback_equals_per_step():
    """Per-link round masks still deliver every color just in time."""
    import jax
    g = rmat.rmat_good(9, 8, seed=5)
    pg = partition_graph(g, 4)
    order = compute_order(pg, ordering.NATURAL)
    seed_view, _ = color_graph(
        pg, order, ColorConfig(max_colors=512, superstep=64, seed=0))
    key = jax.random.key(3)
    v_pig, st_pig = recolor_step(pg, seed_view, "nd", RecolorConfig(
        max_colors=512, piggyback=True, scheme="sparse"), key=key)
    v_all, st_all = recolor_step(pg, seed_view, "nd", RecolorConfig(
        max_colors=512, piggyback=False, scheme="sparse"), key=key)
    _assert_views_equal(pg, v_pig, v_all)
    assert st_pig["wire_bytes"] < st_all["wire_bytes"]


# ------------------------------------------------ zero-cross-edge graphs ----

def _disjoint_cliques(k: int, size: int) -> Graph:
    """k cliques of `size` vertices, no edges between them."""
    n = k * size
    rows, cols = [], []
    for c in range(k):
        base = c * size
        for v in range(size):
            for u in range(size):
                if u != v:
                    rows.append(base + v)
                    cols.append(base + u)
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, np.asarray(rows) + 1, 1)
    return Graph(n, np.cumsum(indptr), np.asarray(cols, np.int32))


def test_zero_cross_edges_zero_sparse_bytes():
    """Block partition along component boundaries: no rounds, no bytes."""
    g = _disjoint_cliques(4, 8)
    pg = partition_graph(g, 4)                 # blocks == components
    assert (pg.n_ghost == 0).all() and (pg.n_boundary == 0).all()
    plan = pg.comm_plan
    assert plan.shifts == () and plan.bytes_per_exchange() == 0
    order = compute_order(pg, ordering.NATURAL)
    view, st = color_graph(pg, order, ColorConfig(
        max_colors=64, superstep=8, scheme="sparse"))
    assert_valid(g, colors_from_views(pg, np.asarray(view)))
    assert st["wire_bytes"] == 0
    # ... and no exchange events at all: nothing was ever pending
    assert st["n_exchanges"] == 0
    # the broadcast scheme ships (P-1)*max_b bytes per event regardless
    _, st_ag = color_graph(pg, order, ColorConfig(
        max_colors=64, superstep=8, scheme="allgather"))
    assert st_ag["wire_bytes"] == 0  # elided: no boundary vertex ever colored


def test_zero_cross_edges_zero_recolor_bytes():
    g = _disjoint_cliques(4, 8)
    pg = partition_graph(g, 4)
    order = compute_order(pg, ordering.NATURAL)
    view, _ = color_graph(pg, order, ColorConfig(max_colors=64,
                                                     superstep=8))
    v2, st = recolor_step(pg, view, "nd",
                          RecolorConfig(max_colors=64, scheme="sparse"))
    assert_valid(g, colors_from_views(pg, np.asarray(v2)))
    assert st["wire_bytes"] == 0


# ------------------------------------------------------- plan structure ----

def test_comm_plan_structure():
    g = rmat.rmat_good(9, 8, seed=3)
    pg = partition_graph(g, 4)
    plan = pg.comm_plan
    P = pg.P
    # n_send[p, q] counts exactly q's ghosts owned by p
    for q in range(P):
        ng = int(pg.n_ghost[q])
        owners = pg.ghost_owner[q, :ng]
        for p in range(P):
            assert plan.n_send[p, q] == int((owners == p).sum())
    # exact widths are the per-shift maxima; compiled widths are their
    # pow2 rungs (shape-static quantization); every send row is
    # sentinel-padded out to the rung
    from repro.core.graph import _ceil_pow2
    for r, k in enumerate(plan.shifts):
        counts = [plan.n_send[p, (p + k) % P] for p in range(P)]
        assert plan.exact_widths[r] == max(counts)
        assert plan.widths[r] == _ceil_pow2(max(counts))
        for p in range(P):
            row = plan.send_slot[p, r]
            c = plan.n_send[p, (p + k) % P]
            assert (row[:c] < pg.n_local_max).all()          # local slots
            assert (row[c:] == pg.sentinel).all()
            # the slots p sends to q are exactly q's ghosts owned by p,
            # ascending by global id
            q = (p + k) % P
            ngq = int(pg.n_ghost[q])
            vids = pg.gvid[q, pg.n_local_max : pg.n_local_max + ngq]
            mine = vids[pg.ghost_owner[q, :ngq] == p] - pg.offs[p]
            np.testing.assert_array_equal(row[:c], mine)
    # receive side: ghost g refreshes from position ghost_pos of round
    # shift_to_round[ghost_shift]
    for q in range(P):
        ng = int(pg.n_ghost[q])
        for gi in range(ng):
            p = int(pg.ghost_owner[q, gi])
            k = int(plan.ghost_shift[q, gi])
            assert k == (q - p) % P
            r = int(plan.shift_to_round[q, k])
            assert plan.shifts[r] == k
            slot = plan.send_slot[p, r, int(plan.ghost_pos[q, gi])]
            assert pg.gvid[p, slot] == pg.gvid[q, pg.n_local_max + gi]


def test_stats_to_host_handles_0d_and_stacked():
    out = stats_to_host(dict(a=jnp.int32(3), b=jnp.full((4,), 7, jnp.int32)))
    assert out == dict(a=3, b=7)
    assert all(isinstance(v, int) for v in out.values())


def test_wire16_halves_sparse_bytes():
    g = rmat.rmat_good(9, 8, seed=3)
    pg = partition_graph(g, 4)
    order = compute_order(pg, ordering.NATURAL)
    mk = lambda w: stats_to_host(color_graph(pg, order, ColorConfig(
        max_colors=512, superstep=64, scheme="sparse", wire16=w))[1])
    assert mk(True)["wire_bytes"] * 2 == mk(False)["wire_bytes"]


# ------------------------------------------------- the scheme decision -----

_DECISION_GRAPHS = {"rmat_good": lambda: rmat.rmat_good(9, 8, seed=3),
                    "grid3d": lambda: rmat.grid3d(12, 12, 12)}


@pytest.mark.parametrize("graph,P,solo,bucket", [
    ("rmat_good", 4, "allgather", "sparse"),
    ("rmat_good", 16, "sparse", "sparse"),
    ("grid3d", 4, "sparse", "sparse"),
    ("grid3d", 16, "sparse", "sparse"),
])
def test_auto_scheme_of_partition_and_bucket(graph, P, solo, bucket):
    """``scheme="auto"`` resolves to the same scheme for a partition (the
    solo program) and for its one-graph bucket (``color_many``'s program)
    as the two rules gave before they became one.  The bucket pads
    ``max_boundary`` to a power of two, so RMAT at P=4 ties the
    all-gather's bytes there and goes sparse."""
    pg = partition_graph(_DECISION_GRAPHS[graph](), P, seed=0)
    bk = bucket_graphs([pg])[0]
    assert resolve_scheme("auto", pg) == solo
    assert resolve_scheme("auto", bk) == bucket
    cfg = PipelineConfig(
        color=ColorConfig(max_colors=64, scheme="auto"),
        recolor=RecolorConfig(max_colors=64, scheme="auto"))
    for part, want in ((pg, solo), (bk, bucket)):
        got = resolve_pipeline_cfg(part, cfg)
        assert (got.color.scheme, got.recolor.scheme) == (want, want)
