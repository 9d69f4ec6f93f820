"""Fused device-resident pipeline (ISSUE 4) + quality-metric/RNG bugfixes.

The acceptance matrix of ``pipeline.color_then_recolor``: the fused program
(initial speculative coloring + K recoloring iterations in one
``lax.while_loop``) must be *bitwise identical* — views and every
per-iteration stat — to the host-looped ``color_graph`` +
``recolor_iterations(fused=False)`` reference sequence, across P, exchange
schemes, and distance 1|2; the adaptive stop must fire on a plateaued
schedule.  The satellite regressions pin the corrected distinct-color
quality metric, the masked ``class_sizes`` scatter, and the per-call /
split RNG keys.
"""
import jax
import numpy as np
import pytest

from repro.core import (ColorConfig, Graph, PipelineConfig, RecolorConfig,
                        arc_sim, check_coloring, color_graph,
                        colors_from_views, compute_order, ordering,
                        partition_graph, run_pipeline, recolor_iterations,
                        recolor_step, rmat)
from repro.core.comm import AxisComm, run_spmd
from repro.core.recolor import class_sizes

MC = 512
CCFG = dict(max_colors=MC, superstep=64, seed=0)


def _graph():
    return rmat.rmat_good(8, 8, seed=3)


def _host_reference(pg, order, ccfg, rcfg, n_iters, **sched):
    view, cstats = color_graph(pg, order, ccfg)
    view, hist = recolor_iterations(pg, np.asarray(view), n_iters, rcfg,
                                    fused=False, **sched)
    return np.asarray(view), cstats, hist


def _assert_pipeline_equals_host(pg, order, ccfg, rcfg, n_iters, **sched):
    v_host, _, hist_host = _host_reference(pg, order, ccfg, rcfg, n_iters,
                                           **sched)
    pcfg = PipelineConfig(color=ccfg, recolor=rcfg, n_iters=n_iters, **sched)
    v_fused, res = run_pipeline(pg, order, pcfg)
    np.testing.assert_array_equal(np.asarray(v_fused), v_host)
    assert res["n_iters_run"] == n_iters
    assert res["history"] == hist_host        # every stat, every iteration
    return res


@pytest.mark.parametrize("P", [2, 4, 16])
def test_fused_equals_host_loop(P):
    """Fused == host loop bitwise (view + per-iteration stats), P sweep."""
    pg = partition_graph(_graph(), P)
    order = compute_order(pg, ordering.NATURAL)
    _assert_pipeline_equals_host(pg, order, ColorConfig(**CCFG),
                                 RecolorConfig(max_colors=MC), 5,
                                 base_perm="nd", rand_every=2, seed=0)


@pytest.mark.parametrize("scheme", ["sparse", "allgather"])
def test_fused_equals_host_loop_schemes(scheme):
    """Both boundary-exchange schemes, explicitly (beyond the CI matrix)."""
    pg = partition_graph(_graph(), 4)
    order = compute_order(pg, ordering.NATURAL)
    _assert_pipeline_equals_host(
        pg, order, ColorConfig(scheme=scheme, **CCFG),
        RecolorConfig(max_colors=MC, scheme=scheme), 4,
        base_perm="nd", rand_pow2=True, seed=1)


def test_fused_equals_host_loop_d2():
    """Distance-2 pipeline over the two-hop halo matches the host loop."""
    pg = partition_graph(_graph(), 4, halo=2)
    order = compute_order(pg, ordering.NATURAL)
    ccfg = ColorConfig(max_colors=MC, superstep=64, tile=16, max_rounds=256,
                       distance=2, seed=0)
    _assert_pipeline_equals_host(pg, order, ccfg,
                                 RecolorConfig(max_colors=MC, distance=2), 3,
                                 base_perm="nd", seed=0)


def test_recolor_iterations_fused_wrapper_bitwise():
    """The default (fused) recolor_iterations == its own host loop."""
    pg = partition_graph(_graph(), 4)
    order = compute_order(pg, ordering.NATURAL)
    view, _ = color_graph(pg, order, ColorConfig(**CCFG))
    rcfg = RecolorConfig(max_colors=MC)
    kw = dict(base_perm="nd", rand_every=3, seed=5)
    v_host, h_host = recolor_iterations(pg, np.asarray(view), 6, rcfg,
                                        fused=False, **kw)
    v_fused, h_fused = recolor_iterations(pg, np.asarray(view), 6, rcfg, **kw)
    np.testing.assert_array_equal(np.asarray(v_fused), np.asarray(v_host))
    assert h_fused == h_host


def test_adaptive_stop_fires_on_plateau():
    """patience=k quits after k non-improving iterations (paper's knob)."""
    pg = partition_graph(_graph(), 4)
    order = compute_order(pg, ordering.NATURAL)
    pcfg = PipelineConfig(color=ColorConfig(**CCFG),
                          recolor=RecolorConfig(max_colors=MC),
                          n_iters=16, base_perm="nd", patience=2)
    view, res = run_pipeline(pg, order, pcfg)
    assert res["n_iters_run"] < 16
    assert len(res["history"]) == res["n_iters_run"]
    cs = [h["n_colors_distinct"] for h in res["history"]]
    assert cs[-1] == cs[-2] == cs[-3]          # the plateau that tripped it
    # the stopped run is a bitwise prefix of the full run (patience only
    # truncates — the quality it trades away is exactly the paper's knob)
    pcfg_full = PipelineConfig(color=ColorConfig(**CCFG),
                               recolor=RecolorConfig(max_colors=MC),
                               n_iters=16, base_perm="nd")
    _, res_full = run_pipeline(pg, order, pcfg_full)
    assert res["history"] == res_full["history"][: res["n_iters_run"]]


def test_pipeline_smoke_rmat_adaptive():
    """Tier-1 smoke: small RMAT, K=4, adaptive stop, valid end-to-end."""
    g = rmat.rmat_bad(8, 8, seed=1)
    pg = partition_graph(g, 4)
    order = compute_order(pg, ordering.INTERNAL_FIRST)
    pcfg = PipelineConfig(color=ColorConfig(max_colors=1024, superstep=64),
                          recolor=RecolorConfig(max_colors=1024),
                          n_iters=4, patience=2)
    view, res = run_pipeline(pg, order, pcfg)
    colors = colors_from_views(pg, np.asarray(view))
    st = check_coloring(g, colors)
    assert st["valid"], st
    assert 1 <= res["n_iters_run"] <= 4
    last = res["history"][-1]
    assert st["n_colors"] == last["n_colors_distinct"]
    assert last["n_colors_distinct"] <= res["color"]["n_colors"]


def test_pipeline_partial_marked():
    """partial=True + marked flows through the fused pipeline unchanged."""
    g = rmat.grid2d(12, 12, 9)
    pg = partition_graph(g, 2, halo=2)
    marked_g = np.arange(g.n) % 2 == 0
    marked = np.zeros((pg.P, pg.n_local_max), bool)
    for p in range(pg.P):
        nl, lo = int(pg.n_local[p]), int(pg.offs[p])
        marked[p, :nl] = marked_g[lo: lo + nl]
    order = compute_order(pg, ordering.NATURAL)
    pcfg = PipelineConfig(
        color=ColorConfig(max_colors=MC, superstep=64, tile=16,
                          max_rounds=256, distance=2, partial=True),
        recolor=RecolorConfig(max_colors=MC, distance=2), n_iters=2)
    view, res = run_pipeline(pg, order, pcfg, marked=marked)
    colors = colors_from_views(pg, np.asarray(view))
    assert (colors[~marked_g] == 0).all()
    chk = check_coloring(g, colors, distance=2, marked=marked_g)
    assert chk["valid"], chk


# ------------------------------------------------- satellite regressions --

def test_check_coloring_counts_distinct_colors():
    """A gappy coloring must report distinct colors, not the max id."""
    # path graph 0-1-2-3
    indptr = np.array([0, 1, 3, 5, 6], np.int64)
    indices = np.array([1, 0, 2, 1, 3, 2], np.int32)
    g = Graph(4, indptr, indices)
    colors = np.array([1, 9, 1, 9], np.int32)      # classes 2..8 are empty
    st = check_coloring(g, colors)
    assert st["valid"]
    assert st["n_colors"] == 2                     # was 9 before the fix
    assert st["max_color_id"] == 9
    assert g.num_colors(colors) == 2
    assert len(st["class_sizes"]) == 9             # still indexed by id
    assert st["class_sizes"][0] == 2 and st["class_sizes"][8] == 2


def test_check_coloring_gapfree_unchanged():
    """On gap-free colorings the corrected metric equals the old one."""
    g = _graph()
    pg = partition_graph(g, 4)
    order = compute_order(pg, ordering.NATURAL)
    view, stats = color_graph(pg, order, ColorConfig(**CCFG))
    st = check_coloring(g, colors_from_views(pg, np.asarray(view)))
    assert st["n_colors"] == st["max_color_id"] == stats["n_colors"]
    assert stats["n_colors_distinct"] == st["n_colors"]


def test_color_stats_distinct_on_staggered_gaps():
    """Staggered FF leaves id gaps: device + host metrics must agree that
    the distinct count, not the max id, is the quality number."""
    g = _graph()
    pg = partition_graph(g, 4)
    order = compute_order(pg, ordering.NATURAL)
    view, stats = color_graph(
        pg, order, ColorConfig(max_colors=MC, superstep=64,
                               selection="staggered", seed=0))
    st = check_coloring(g, colors_from_views(pg, np.asarray(view)))
    assert stats["n_colors_distinct"] == st["n_colors"]
    assert stats["n_colors"] == st["max_color_id"]
    assert stats["n_colors_distinct"] < stats["n_colors"]   # real gaps


def test_class_sizes_masks_out_of_range():
    """A poisoned view must not inflate the last class (clip-mode scatter)."""
    mc, n_local, n_local_max = 32, 6, 8
    view = np.array([1, 1, mc + 7, -3, 2, mc - 1, 0, 0, 0], np.int32)
    fn = lambda v, axis, lane_axes: class_sizes(
        v, np.int32(n_local), n_local_max, mc, AxisComm(axis, lane_axes))
    sizes, n_oor = run_spmd(fn, None, 1, (view[None],))
    sizes = np.asarray(sizes)[0]
    assert int(n_oor[0]) == 2                      # mc+7 and -3
    assert sizes[mc - 1] == 1                      # NOT silently 3
    assert sizes[1] == 2 and sizes[2] == 1
    assert sizes.sum() == 4                        # class 0 + poison excluded


def test_recolor_out_of_range_stat_surfaces():
    pg = partition_graph(_graph(), 2)
    order = compute_order(pg, ordering.NATURAL)
    view, _ = color_graph(pg, order, ColorConfig(**CCFG))
    poisoned = np.asarray(view).copy()
    poisoned[0, 0] = MC + 5
    _, st = recolor_step(pg, poisoned, "nd", RecolorConfig(max_colors=MC),
                         key=jax.random.key(0))
    assert st["n_out_of_range"] == 1
    _, st_ok = recolor_step(pg, np.asarray(view), "nd",
                            RecolorConfig(max_colors=MC),
                            key=jax.random.key(0))
    assert st_ok["n_out_of_range"] == 0


def test_back_to_back_rand_iterations_differ():
    """Two manual RAND calls without keys must not replay one permutation."""
    pg = partition_graph(_graph(), 4)
    order = compute_order(pg, ordering.NATURAL)
    view, _ = color_graph(pg, order, ColorConfig(**CCFG))
    cfg = RecolorConfig(max_colors=MC)
    v1, _ = recolor_step(pg, np.asarray(view), "rand", cfg)
    v2, _ = recolor_step(pg, np.asarray(view), "rand", cfg)
    assert (np.asarray(v1) != np.asarray(v2)).any()
    # explicit keys stay fully reproducible
    v3, _ = recolor_step(pg, np.asarray(view), "rand", cfg,
                         key=jax.random.key(3))
    v4, _ = recolor_step(pg, np.asarray(view), "rand", cfg,
                         key=jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(v3), np.asarray(v4))


def test_arc_back_to_back_differs_and_explicit_key_reproduces():
    """aRC default keys advance per call, and the rank/repair streams are
    split — Random-X makes the repair stream observable in the output."""
    pg = partition_graph(_graph(), 4)
    order = compute_order(pg, ordering.NATURAL)
    view, _ = color_graph(pg, order, ColorConfig(**CCFG))
    rcfg = RecolorConfig(max_colors=MC)
    scfg = ColorConfig(max_colors=MC, superstep=64, selection="random_x",
                       random_x=10)
    v1, _ = arc_sim(pg, np.asarray(view), "rand", rcfg, scfg)
    v2, _ = arc_sim(pg, np.asarray(view), "rand", rcfg, scfg)
    assert (np.asarray(v1) != np.asarray(v2)).any()
    key = jax.random.key(9)
    v3, _ = arc_sim(pg, np.asarray(view), "rand", rcfg, scfg, key=key)
    v4, _ = arc_sim(pg, np.asarray(view), "rand", rcfg, scfg, key=key)
    np.testing.assert_array_equal(np.asarray(v3), np.asarray(v4))
