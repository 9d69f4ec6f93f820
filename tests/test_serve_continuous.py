"""Continuous-batching scheduler tests on the deterministic fake clock.

The core invariant (the PR 5 lane-equality pin extended to the serving
engine): under *arbitrary* admission interleavings — scripted or random
arrivals, any lanes/chunk/SLO settings — every accepted job's engine
result is bitwise-equal to a solo ``run_pipeline`` run of the same
engine-padded member with the same request-id-folded keys.  Plus: future
semantics, SLO shed / queue-bound determinism, per-lane fault isolation,
stats-counter consistency, and retrace stability across scripts.

``SERVE_STRESS_SCRIPTS`` scales the seeded stress sweep (default 25
locally; the CI ``serve-stress`` job runs 200+).  The hypothesis variant
of the same property runs when hypothesis is installed (CI test extras).
"""
import os

import jax
import numpy as np
import pytest

from repro.core import (ColorConfig, Graph, PipelineConfig, RecolorConfig,
                        compute_order, run_pipeline, program_cache_stats,
                        rmat)
from repro.launch.serve_coloring import (ColoringService, FakeClock,
                                         JobError, ServeConfig, ShedError)
from serve_harness import Arrival, random_script, run_script

P = 2


def _cfg(scheme: str = "sparse", n_iters: int = 3,
         patience: int = 1) -> PipelineConfig:
    return PipelineConfig(
        color=ColorConfig(max_colors=64, superstep=32, selection="random_x",
                          random_x=10, scheme=scheme),
        recolor=RecolorConfig(max_colors=64, scheme=scheme),
        n_iters=n_iters, patience=patience)


def _pool():
    """A small mixed pool: ≥2 shape buckets at P=2."""
    return [rmat.rmat_good(4, 8, seed=1), rmat.rmat_bad(4, 8, seed=2),
            rmat.rmat_er(5, 8, seed=3), rmat.grid2d(8, 8, 5)]


def _clique(n: int) -> Graph:
    ind, indptr = [], [0]
    for u in range(n):
        ind += [v for v in range(n) if v != u]
        indptr.append(len(ind))
    return Graph(n=n, indptr=np.array(indptr), indices=np.array(ind))


def _svc(cfg=None, *, validate=True, **serve_kw) -> ColoringService:
    return ColoringService(P=P, cfg=cfg or _cfg(), validate=validate,
                           clock=FakeClock(), serve=ServeConfig(**serve_kw))


def _assert_bitwise(svc: ColoringService, results: dict) -> int:
    """Every engine-route result == solo run_pipeline of its padded member
    (same folded keys, same resolved config) — views, colors, history and
    iteration counts all bitwise."""
    n = 0
    for jid, r in results.items():
        if r["route"] != "engine" or "error" in r:
            continue
        m, rcfg = r["member"], r["cfg"]
        ck = jax.random.fold_in(jax.random.key(rcfg.color.seed), jid)
        rk = jax.random.fold_in(jax.random.key(rcfg.seed), jid)
        view, solo = run_pipeline(m, compute_order(m, svc.order_kind), rcfg,
                                  color_key=ck, recolor_key=rk)
        colors = m.gather_global_colors(
            np.asarray(view)[:, :m.n_local_max])
        np.testing.assert_array_equal(colors, r["colors"], err_msg=str(jid))
        assert solo["history"] == r["history"], jid
        assert solo["n_iters_run"] == r["n_iters_run"], jid
        n += 1
    return n


def test_continuous_round_trip():
    """Submit a mixed queue, flush: every job valid, engine-routed, and
    bitwise its solo run; pending/stats transitions are consistent."""
    svc = _svc(lanes=2, chunk_iters=1, solo_warm=False)
    graphs = _pool()
    ids = [svc.submit(g) for g in graphs + graphs[::-1]]
    assert svc.pending == len(ids)
    res = svc.flush()
    assert sorted(res) == ids
    assert svc.pending == 0
    for i in ids:
        assert res[i]["check"]["valid"], (i, res[i]["check"])
        assert res[i]["route"] == "engine"
        assert res[i]["latency_s"] >= 0
    assert _assert_bitwise(svc, res) == len(ids)
    st = svc.stats()
    assert st["lane"] == len(ids) and st["n_shed"] == 0
    assert st["queued"] == st["running"] == 0


def test_futures_resolve_without_flush():
    """submit_async futures resolve by driving poll() — no flush call."""
    svc = _svc(lanes=2)
    futs = [svc.submit_async(g) for g in _pool()]
    outs = [f.result() for f in futs]
    for f, out in zip(futs, outs):
        assert f.done() and f.exception() is None
        assert out["check"]["valid"]
    assert svc.pending == 0


def test_mid_flight_admission_bitwise():
    """Arrivals staggered to land while earlier lanes are mid-run: the
    admission swap must not perturb any neighbor lane (bitwise pin)."""
    graphs = _pool()
    svc = _svc(lanes=2, chunk_iters=1, solo_warm=False)
    script = [Arrival(float(t), graphs[t % len(graphs)]) for t in range(8)]
    out = run_script(svc, script)
    assert not out.shed and not out.failed
    # with 2 lanes, 1-iteration chunks and one arrival per poll tick, later
    # jobs were necessarily admitted while earlier lanes were still running
    assert out.polls > 4
    assert _assert_bitwise(svc, out.results) == len(script)


def test_engine_reuse_no_retrace():
    """A second service running the same script reuses every compiled
    engine program — zero new XLA traces (the continuous analog of the
    PR 6 program-cache pin)."""
    graphs = _pool()
    script = [Arrival(float(t), graphs[t % len(graphs)]) for t in range(6)]
    run_script(_svc(lanes=2, solo_warm=False, validate=False), script)
    before = program_cache_stats()["traces"]
    out = run_script(_svc(lanes=2, solo_warm=False, validate=False), script)
    assert len(out.results) == len(script)
    assert program_cache_stats()["traces"] == before


def test_slo_shed_deterministic():
    """One lane, three simultaneous arrivals, SLO of 1.5 virtual seconds:
    the lane takes 3 ticks, so exactly the two waiting jobs age past the
    SLO and shed — the same two on every run."""
    g = _pool()[0]
    svc = _svc(_cfg(n_iters=3, patience=0), lanes=1, chunk_iters=1,
               slo_s=1.5, solo_warm=False)
    out = run_script(svc, [Arrival(0.0, g)] * 3)
    ids = sorted(out.futures)
    assert out.shed == ids[1:]
    assert sorted(out.results) == ids[:1]
    for jid in out.shed:
        with pytest.raises(ShedError):
            out.futures[jid].result()
    st = svc.stats()
    assert st["n_shed"] == 2
    assert st["n_deferred"] == 2      # both waited at least one poll first
    assert _assert_bitwise(svc, out.results) == 1


def test_queue_bound_sheds_at_submit():
    """Submits past max_queue shed immediately with a ShedError future."""
    svc = _svc(lanes=1, max_queue=2, solo_warm=False)
    g = _pool()[0]
    ids = [svc.submit(g) for _ in range(4)]
    st = svc.stats()
    assert st["n_shed"] == 2 and st["queued"] == 2
    assert svc.pending == 2
    for jid in ids[2:]:
        assert isinstance(svc.future(jid).exception(), ShedError)
    res = svc.flush()
    assert sorted(res) == ids[:2]


def test_fault_isolation_saturated_lane():
    """A lane whose graph saturates ``find_first_zero`` (clique wider than
    max_colors leaks uncolored sentinels) fails only its own job; the
    engine drains every neighboring lane to a valid result."""
    svc = _svc(_cfg(n_iters=2, patience=0), validate=False, lanes=2,
               solo_warm=False)
    assert svc.cfg.color.max_colors == 64
    graphs = [_clique(80)] + _pool()[:3]   # K80 needs 80 > 64: saturates
    futs = [svc.submit_async(g) for g in graphs]
    res = svc.flush()
    bad_id = futs[0].id
    with pytest.raises(JobError):
        futs[0].result()
    assert "error" in res[bad_id]
    assert res[bad_id]["check"]["valid"] is False
    for f in futs[1:]:
        out = f.result()                   # engine kept draining
        assert "error" not in out
    st = svc.stats()
    assert st["n_failed"] == 1 and st["lane"] == len(graphs) - 1
    assert _assert_bitwise(svc, res) == len(graphs) - 1


def test_n_iters_zero_lane():
    """K=0 (color-only) engine lanes complete on their first step with an
    empty history — and still match the solo run."""
    svc = _svc(_cfg(n_iters=0), lanes=2, solo_warm=False)
    for g in _pool()[:2]:
        svc.submit(g)
    res = svc.flush()
    for r in res.values():
        assert r["history"] == [] and r["n_iters_run"] == 0
        assert r["check"]["valid"]
    assert _assert_bitwise(svc, res) == 2


def _run_random_script(k: int, graphs, *, verify: bool = True):
    """One seeded random scenario: arrivals, lanes, chunking, SLO all
    drawn from a per-script rng; returns (svc, ScriptResult)."""
    rng = np.random.default_rng(10_000 + k)
    svc = _svc(lanes=int(rng.choice([1, 2, 4])),
               chunk_iters=int(rng.choice([1, 2])),
               slo_s=(None if rng.random() < 0.5
                      else float(rng.uniform(4.0, 12.0))),
               solo_warm=bool(rng.random() < 0.3),
               validate=False)
    script = random_script(rng, graphs, n=int(rng.integers(5, 12)),
                           mean_gap=float(rng.uniform(0.3, 3.0)))
    out = run_script(svc, script)
    # conservation: every submitted job resolved exactly one way
    assert len(out.results) + len(out.shed) == len(script)
    assert not out.failed
    assert svc.pending == 0
    st = svc.stats()
    assert st["n_shed"] == len(out.shed)
    assert st["lane"] + st["solo"] == len(out.results)
    if verify:
        _assert_bitwise(svc, out.results)
    return svc, out


def test_stress_random_scripts():
    """The acceptance property: across N generated arrival scripts (N =
    ``$SERVE_STRESS_SCRIPTS``, 200+ in CI), every accepted job is bitwise
    its solo run and the scheduler's accounting balances."""
    n_scripts = int(os.environ.get("SERVE_STRESS_SCRIPTS", "25"))
    graphs = _pool()
    n_bitwise = 0
    for k in range(n_scripts):
        _, out = _run_random_script(k, graphs)
        n_bitwise += len(out.results)
    assert n_bitwise > 0


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st_h
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_property_hypothesis_scripts():
    """Same property, hypothesis-driven: random arrival scripts / graph
    mixes / SLO settings never perturb a lane (shrinks on failure)."""

    graphs = _pool()

    @settings(max_examples=15, deadline=None)
    @given(seed=st_h.integers(min_value=0, max_value=2**20),
           lanes=st_h.sampled_from([1, 2, 4]),
           chunk=st_h.sampled_from([1, 2]),
           slo=st_h.sampled_from([None, 5.0, 10.0]))
    def prop(seed, lanes, chunk, slo):
        rng = np.random.default_rng(seed)
        svc = _svc(lanes=lanes, chunk_iters=chunk, slo_s=slo,
                   solo_warm=False, validate=False)
        out = run_script(svc, random_script(rng, graphs,
                                            n=int(rng.integers(4, 10)),
                                            mean_gap=1.0))
        assert len(out.results) + len(out.shed) == len(out.futures)
        _assert_bitwise(svc, out.results)

    prop()
