"""The compiled-program cache: same-signature work compiles exactly once.

Trace-counter guards (DESIGN.md §2): ``program_cache_stats()["traces"]``
increments only when XLA actually retraces a cached driver program, so
these tests pin the tentpole property — N same-signature graphs through
``color_many`` and through the serving driver cost exactly one compile —
plus the fast 2-bucket serve smoke the CI tier-1 lane runs.
"""
import numpy as np
import pytest

from repro.core import (ColorConfig, PipelineConfig, RecolorConfig,
                        bucket_signature, bucket_graphs, color_many,
                        compute_order, partition_graph, plan_signature,
                        program_cache_clear, program_cache_contains,
                        program_cache_stats, rmat)
from repro.core.pipeline import run_pipeline
from repro.launch.serve_coloring import ColoringService, ServeConfig

P = 4


def _cfg(**kw):
    kw.setdefault("n_iters", 2)
    kw.setdefault("patience", 0)
    return PipelineConfig(color=ColorConfig(max_colors=64),
                          recolor=RecolorConfig(max_colors=64), **kw)


def _same_signature_pgs(seeds, scale=7):
    """Same topology, different tie-break priorities: identical dims and
    plan rungs (the plan depends on ghost structure only) but different
    colorings — genuinely distinct same-signature work items."""
    g = rmat.rmat_good(scale, 8, seed=3)
    return [partition_graph(g, P, seed=s) for s in seeds]


def test_color_many_same_signature_compiles_once():
    cfg = _cfg()
    pgs = _same_signature_pgs((0, 1, 2))
    sigs = {bucket_signature(b, cfg) for b in
            (bucket_graphs([pg])[0] for pg in pgs)}
    assert len(sigs) == 1                      # truly one signature
    program_cache_clear()
    out = color_many(pgs, cfg, pad_batch=True)
    st = program_cache_stats()
    assert (st["misses"], st["traces"]) == (1, 1)
    # a second wave of NEW same-signature graphs reuses the program
    out2 = color_many(_same_signature_pgs((3, 4, 5)), cfg, pad_batch=True)
    st = program_cache_stats()
    assert st["traces"] == 1                   # zero new compiles
    assert st["hits"] == 1
    assert len(out) == len(out2) == 3
    for r in out + out2:
        assert r["colors"].min() >= 1


def test_pipeline_sim_repeat_is_cache_hit():
    pg = _same_signature_pgs((0,))[0]
    cfg = _cfg()
    order = compute_order(pg, "internal_first")
    program_cache_clear()
    v1, _ = run_pipeline(pg, order, cfg)
    v2, _ = run_pipeline(pg, order, cfg)
    st = program_cache_stats()
    assert (st["misses"], st["hits"], st["traces"]) == (1, 1, 1)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_bucket_signature_probe_is_exact():
    """``bucket_signature`` predicts the program ``color_many`` compiles —
    the serving cost model's hit/miss probe never lies."""
    cfg = _cfg()
    pg_a, pg_b = _same_signature_pgs((0, 1))
    program_cache_clear()
    sig = bucket_signature(bucket_graphs([pg_a])[0], cfg)
    assert not program_cache_contains(sig)
    color_many([pg_a], cfg, pad_batch=True)
    sig_b = bucket_signature(bucket_graphs([pg_b])[0], cfg)
    assert sig_b == sig and program_cache_contains(sig_b)
    # dispatching B is then trace-free
    before = program_cache_stats()["traces"]
    color_many([pg_b], cfg, pad_batch=True)
    assert program_cache_stats()["traces"] == before


def test_serve_two_bucket_mix_cache_smoke():
    """CI tier-1 smoke: a 2-bucket traffic mix through the serve driver —
    N same-signature requests compile once, and the warm resubmission
    takes the solo path with a positive program-cache hit rate."""
    cfg = _cfg()
    graphs = [rmat.rmat_good(6, 8, seed=s) for s in (1, 2)] + \
             [rmat.rmat_good(7, 8, seed=s) for s in (1, 2)]
    program_cache_clear()
    # this test pins the *flush* scheduler's batch/solo routing; the
    # continuous engine's trace pins live in test_serve_continuous.py
    svc = ColoringService(P=P, cfg=cfg, validate=True,
                          serve=ServeConfig(mode="flush"))
    ids = [svc.submit(g) for g in graphs]
    cold = svc.flush()
    assert all(cold[i]["route"] == "batch" for i in ids)
    traces_cold = program_cache_stats()["traces"]
    # every signature compiled exactly once in the cold wave
    assert traces_cold == svc.stats()["signatures"]
    # prewarm compiles the one-lane programs (the cold wave compiled the
    # B=2 batch lanes); steady-state traffic then takes the solo hit path
    svc.prewarm(graphs)
    traces_warm = program_cache_stats()["traces"]
    ids2 = [svc.submit(g) for g in graphs]        # warm resubmission
    warm = svc.flush()
    assert all(warm[i]["route"] == "solo" for i in ids2)
    st = svc.stats()
    assert st["hits"] > 0
    hit_rate = st["hits"] / (st["hits"] + st["misses"])
    assert hit_rate > 0
    assert program_cache_stats()["traces"] == traces_warm  # no new compiles
    # request keys fold the request id, so the route never changes colors
    for i, i2 in zip(ids, ids2):
        assert cold[i]["check"]["valid"] and warm[i2]["check"]["valid"]
