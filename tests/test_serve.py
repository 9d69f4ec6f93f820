"""Batched multi-graph pipeline (ISSUE 5): ``color_many`` == solo fused runs.

The acceptance property: each graph of a batch — padded into its shape
bucket and run on the bucket's shared (union) sparse round schedule — must
be *bitwise identical*, view and every per-iteration stat including
measured ``wire_bytes``, to a solo ``run_pipeline`` run of the same padded
member under its own comm plan with the same per-graph keys.  Swept across
bucket boundaries, both exchange schemes, distance 1 and 2, randomized
selection, and the per-graph adaptive stop (lanes stopping at different
iterations inside one vmapped ``lax.while_loop``).
"""
import jax
import numpy as np
import pytest

from repro.core import (ColorConfig, PipelineConfig, RecolorConfig,
                        bucket_graphs, check_coloring, color_many,
                        compute_order, ordering, pad_partition,
                        partition_graph, run_pipeline, rmat)
from repro.launch.serve_coloring import (ColoringService, FakeClock,
                                         ServeConfig, default_config)

MC = 512


def _mix():
    """Four small graphs that land in >= 2 shape buckets."""
    return [rmat.rmat_good(6, 8, seed=1), rmat.rmat_bad(6, 8, seed=2),
            rmat.rmat_good(8, 8, seed=3), rmat.grid2d(16, 16, 9)]


def _solo_keys(cfg, gi):
    """The folded per-graph default streams of ``color_many``."""
    return (jax.random.fold_in(jax.random.key(cfg.color.seed), gi),
            jax.random.fold_in(jax.random.key(cfg.seed), gi))


def _assert_matches_solo(pgs, cfg, res, order_kind):
    """Every batch lane == run_pipeline on its padded member (own plan)."""
    for bucket in bucket_graphs(pgs):
        for j, gi in enumerate(bucket.indices):
            m = bucket.members[j]
            ck, rk = _solo_keys(cfg, gi)
            v, solo = run_pipeline(m, compute_order(m, order_kind), cfg,
                                   color_key=ck, recolor_key=rk)
            np.testing.assert_array_equal(res[gi]["view"], np.asarray(v))
            assert res[gi]["history"] == solo["history"]
            assert res[gi]["color"] == solo["color"]
            assert res[gi]["n_iters_run"] == solo["n_iters_run"]


@pytest.mark.parametrize("P,scheme", [(4, "sparse"), (2, "allgather")])
def test_color_many_bitwise_matches_solo(P, scheme):
    """Across bucket boundaries + the union round schedule, both schemes."""
    graphs = _mix()
    pgs = [partition_graph(g, P) for g in graphs]
    assert len(bucket_graphs(pgs)) >= 2          # really spans buckets
    cfg = PipelineConfig(
        color=ColorConfig(max_colors=MC, superstep=64, scheme=scheme,
                          selection="random_x", random_x=10),
        recolor=RecolorConfig(max_colors=MC, scheme=scheme),
        n_iters=3, base_perm="nd", rand_every=2)
    res = color_many(pgs, cfg, orders=ordering.NATURAL)
    for g, r in zip(graphs, res):
        st = check_coloring(g, r["colors"])
        assert st["valid"], st
        assert st["n_colors"] == r["history"][-1]["n_colors_distinct"]
    _assert_matches_solo(pgs, cfg, res, ordering.NATURAL)


def test_color_many_d2_two_hop_halo():
    """Distance-2 batch over halo=2 partitions matches the solo pipeline."""
    graphs = [rmat.grid2d(12, 12, 9), rmat.grid2d(16, 12, 9)]
    pgs = [partition_graph(g, 2, halo=2) for g in graphs]
    cfg = PipelineConfig(
        color=ColorConfig(max_colors=MC, superstep=64, tile=16,
                          max_rounds=256, distance=2),
        recolor=RecolorConfig(max_colors=MC, distance=2), n_iters=2)
    res = color_many(pgs, cfg)
    for g, r in zip(graphs, res):
        assert check_coloring(g, r["colors"], distance=2)["valid"]
    _assert_matches_solo(pgs, cfg, res, ordering.INTERNAL_FIRST)


def test_color_many_per_graph_adaptive_stop():
    """Lanes stop at different iterations; each stays a bitwise solo run
    (vmap's while_loop select-masks the body on finished lanes)."""
    pgs = [partition_graph(rmat.rmat_good(7, 8, seed=s), 4)
           for s in (1, 2, 3, 4)]
    cfg = PipelineConfig(
        color=ColorConfig(max_colors=MC, superstep=64),
        recolor=RecolorConfig(max_colors=MC),
        n_iters=12, base_perm="nd", rand_every=2, patience=1)
    res = color_many(pgs, cfg)
    iters = [r["n_iters_run"] for r in res]
    assert len(set(iters)) > 1                   # genuinely divergent stops
    assert all(it < 12 for it in iters)
    assert all(len(r["history"]) == it for r, it in zip(res, iters))
    _assert_matches_solo(pgs, cfg, res, ordering.INTERNAL_FIRST)


def test_color_many_pad_batch_lanes_dropped():
    """pow2 batch-lane padding (serving shape-stability) changes nothing."""
    pgs = [partition_graph(rmat.rmat_good(6, 8, seed=s), 2) for s in (1, 2, 3)]
    cfg = PipelineConfig(color=ColorConfig(max_colors=MC, superstep=64),
                         recolor=RecolorConfig(max_colors=MC), n_iters=2)
    a = color_many(pgs, cfg)
    b = color_many(pgs, cfg, pad_batch=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["view"], y["view"])
        np.testing.assert_array_equal(x["colors"], y["colors"])
        assert x["history"] == y["history"] and x["color"] == y["color"]


def test_pad_partition_preserves_coloring():
    """Padding every dim is inert: same colors, same stats (sparse plan
    widths are invariant to padding; First Fit is shape-independent)."""
    g = rmat.rmat_good(7, 8, seed=5)
    pg = partition_graph(g, 4)
    padded = pad_partition(
        pg, n_local_max=pg.n_local_max + 7, max_ghost=pg.max_ghost + 3,
        max_boundary=pg.max_boundary + 2, m_local_max=pg.m_local_max + 11,
        maxd=pg.maxd + 5)
    cfg = PipelineConfig(
        color=ColorConfig(max_colors=MC, superstep=64, scheme="sparse"),
        recolor=RecolorConfig(max_colors=MC, scheme="sparse"), n_iters=2)
    outs = []
    for q in (pg, padded):
        v, r = run_pipeline(q, compute_order(q, ordering.NATURAL), cfg)
        colors = q.gather_global_colors(np.asarray(v)[:, :q.n_local_max])
        outs.append((colors, r))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    # gather_slots counts the slots of the padded tiles (more rows a
    # chunk, a wider top width), so it is compared apart from the rest
    slots = [[h.pop("gather_slots") for h in r["history"] + [r["color"]]]
             for _, r in outs]
    assert all(a <= b for a, b in zip(*slots))
    assert outs[0][1] == outs[1][1]              # histories + color stats
    assert pad_partition(pg) is pg               # no-op fast path


def test_bucket_graphs_partitions_input():
    pgs = [partition_graph(g, 2) for g in _mix()]
    buckets = bucket_graphs(pgs)
    covered = sorted(i for b in buckets for i in b.indices)
    assert covered == list(range(len(pgs)))
    for b in buckets:
        dims = {(m.n_local_max, m.maxd, m.max_ghost, m.max_boundary,
                 m.m_local_max) for m in b.members}
        assert len(dims) == 1                    # stackable shapes
    # exact-match mode groups only identical dims
    exact = bucket_graphs(pgs, round_pow2=False)
    assert len(exact) >= len(buckets)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_batched_leading_dim_matches_loop(rng, backend):
    """(B, V, MAXD) kernel inputs == per-graph loop, both backends (the
    batched pipeline's multi-graph tiles flatten onto the row/grid axis)."""
    from repro.kernels import ops
    b, v, d, mc = 3, 37, 9, 64
    nbr = rng.integers(-2, mc + 8, (b, v, d)).astype(np.int32)
    nbr2 = rng.integers(-2, mc + 8, (b, v, 5)).astype(np.int32)
    active = rng.random((b, v)) < 0.85
    rand = rng.integers(0, 2**32, (b, v), dtype=np.uint32)
    myc = rng.integers(0, mc, (b, v)).astype(np.int32)
    myp = rng.integers(0, 10_000, (b, v)).astype(np.int32)
    nbrp = rng.integers(0, 10_000, (b, v, d)).astype(np.int32)
    nbr2p = rng.integers(0, 10_000, (b, v, 5)).astype(np.int32)
    kw = dict(backend=backend, interpret=None if backend == "xla" else True)

    got = ops.select_colors(nbr, active, rand, max_colors=mc,
                            selection=ops.RANDOM_X, x=5, **kw)
    got2 = ops.select_colors_d2(nbr, nbr2, active, max_colors=mc, **kw)
    conf = ops.detect_conflicts(myc, myp, nbr, nbrp, active, **kw)
    conf2 = ops.detect_conflicts_d2(myc, myp, nbr, nbrp, nbr2, nbr2p,
                                    active, **kw)
    assert got.shape == (b, v) and conf.shape == (b, v)
    for i in range(b):
        np.testing.assert_array_equal(
            np.asarray(got[i]),
            np.asarray(ops.select_colors(nbr[i], active[i], rand[i],
                                         max_colors=mc,
                                         selection=ops.RANDOM_X, x=5, **kw)))
        np.testing.assert_array_equal(
            np.asarray(got2[i]),
            np.asarray(ops.select_colors_d2(nbr[i], nbr2[i], active[i],
                                            max_colors=mc, **kw)))
        np.testing.assert_array_equal(
            np.asarray(conf[i]),
            np.asarray(ops.detect_conflicts(myc[i], myp[i], nbr[i], nbrp[i],
                                            active[i], **kw)))
        np.testing.assert_array_equal(
            np.asarray(conf2[i]),
            np.asarray(ops.detect_conflicts_d2(myc[i], myp[i], nbr[i],
                                               nbrp[i], nbr2[i], nbr2p[i],
                                               active[i], **kw)))


def test_coloring_service_round_trip():
    """Submit/flush returns valid colorings keyed by request id."""
    svc = ColoringService(
        P=2, validate=True,
        cfg=default_config(max_colors=MC, n_iters=2, patience=0))
    graphs = _mix()
    ids = [svc.submit(g) for g in graphs]
    assert svc.pending == len(graphs)
    res = svc.flush()
    assert svc.pending == 0 and sorted(res) == sorted(ids)
    for g, i in zip(graphs, ids):
        assert res[i]["check"]["valid"]
        assert res[i]["n_colors"] == res[i]["check"]["n_colors"]


@pytest.mark.parametrize("mode", ["flush", "continuous"])
def test_service_stats_counters_consistent(mode):
    """Regression (ISSUE 10 satellite): ``stats()`` always reports the
    shed/deferral counters, ``pending`` == queued + running in every
    state, and completions-by-route sum to the results returned."""
    svc = ColoringService(
        P=2, validate=True, clock=FakeClock(),
        cfg=default_config(max_colors=MC, n_iters=2, patience=0),
        serve=ServeConfig(mode=mode, lanes=2, max_queue=3))
    st = svc.stats()
    for key in ("n_shed", "n_deferred", "n_failed", "solo", "batch",
                "lane", "queued", "running", "engines"):
        assert key in st, key
    assert st["queued"] == st["running"] == svc.pending == 0
    graphs = _mix()
    ids = [svc.submit(g) for g in graphs]
    st = svc.stats()
    assert st["queued"] + st["running"] == svc.pending
    # continuous mode sheds the submit past max_queue; flush never sheds
    n_shed = st["n_shed"]
    assert n_shed == (len(graphs) - 3 if mode == "continuous" else 0)
    assert svc.pending == len(graphs) - n_shed
    res = svc.flush()
    st = svc.stats()
    assert svc.pending == st["queued"] == st["running"] == 0
    assert len(res) == len(graphs) - n_shed
    assert st["solo"] + st["batch"] + st["lane"] == len(res)
    assert st["n_shed"] == n_shed and st["n_failed"] == 0
    for i in ids[:len(graphs) - n_shed]:
        assert res[i]["check"]["valid"]
