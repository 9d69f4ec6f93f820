"""Open-loop served coloring: independent users send graphs on a schedule.

A ``ColoringService`` in continuous mode runs on the wall clock.  The
requests are fresh R-MAT graphs, one per request, made in set-up from a
pool seed fixed in the traffic file: every run sends the same multiset of
graphs and the same multiset of gaps between arrivals, in an order drawn
from ``--seed``, so a seed changes the order and not the work.  Gaps are
the quantiles of an exponential distribution (Poisson arrivals), scaled
so that the last request is due inside the window.  Each request's
latency runs from when it was due to when ``poll`` returned its result;
after the window the harness keeps polling, up to ``DRAIN_S``, so a late
answer counts as late and only a missing one as missing.

Set-up warms every engine shape the pool falls into (a throwaway service
per shape, so the window's service starts with an empty partition memo).

Traffic parameters: ``rate_per_s``, ``scales`` and ``weights`` (request
sizes, as R-MAT scales, and their shares), ``pool_seed``, ``lanes`` and
``chunk_iters`` (the service's engine lanes and iterations per step).
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import graphs, program, reference

DRAIN_S = 60.0


def _counts(weights, n: int) -> list[int]:
    """``n`` split by ``weights`` with the largest remainders rounded up."""
    w = np.asarray(weights, dtype=float) / sum(weights)
    base = np.floor(w * n).astype(int)
    rest = np.argsort(-(w * n - base), kind="stable")[: n - base.sum()]
    base[rest] += 1
    return base.tolist()


def _serve_config(t: dict):
    from repro.launch.serve_coloring import ServeConfig

    return ServeConfig(mode="continuous", lanes=t["lanes"],
                       chunk_iters=t["chunk_iters"], slo_s=None)


def _engine_key(g, config: dict, cfg):
    """The engine shape a request of graph ``g`` is admitted under:
    its padded dims, its resolved configuration and its exchange plan."""
    from repro.core import bucket_graphs, partition_graph, \
        resolve_pipeline_cfg

    pg = partition_graph(g, config["P"], seed=0, halo=config["distance"])
    m = bucket_graphs([pg])[0].members[0]
    rcfg = resolve_pipeline_cfg(m, cfg)
    static = m.comm_plan.static if rcfg.needs_sparse_plan else None
    return (m.n_local_max, m.max_ghost, m.max_boundary, m.m_local_max,
            m.maxd, m.maxd2, rcfg, static)


class State:
    def __init__(self, run):
        c, t = run.cell.config, run.cell.traffic
        self.config, self.traffic = c, t
        n = max(1, int(round(t["rate_per_s"] * run.seconds)))
        spec = dict(c["graph"])
        self.pool = []
        for scale, k in zip(t["scales"], _counts(t["weights"], n)):
            spec["scale"] = scale
            self.pool += [graphs.make(spec, program.job_seed(
                t["pool_seed"], scale, j)) for j in range(k)]
        rng = np.random.default_rng(abs(int(run.seed)))
        self.order = rng.permutation(n)
        q = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = rng.permutation(q) * (run.seconds / q.sum())
        self.due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        self.cfg = program.pipeline_config(c, program.job_seed(run.seed))
        self.graphs = [program.as_graph(x) for x in self.pool]

    def service(self):
        from repro.launch.serve_coloring import ColoringService

        return ColoringService(P=self.config["P"], cfg=self.cfg,
                               serve=_serve_config(self.traffic))


def setup(run) -> State:
    st = State(run)
    reps = {}
    for g in st.graphs:
        reps.setdefault(_engine_key(g, st.config, st.cfg), g)
    for g in reps.values():      # two admissions: init, step and lane swap
        warm = st.service()
        warm.submit(g)
        warm.submit(g)
        warm.flush()
    run.info.update(requests=len(st.graphs), engine_shapes=len(reps))
    st.svc = st.service()
    return st


def window(run, st: State) -> None:
    svc, rec = st.svc, run.rec
    n = len(st.graphs)
    t0 = time.perf_counter()
    due = t0 + st.due
    end, hard_end = t0 + run.seconds, t0 + run.seconds + DRAIN_S
    arrivals = [dict(k=int(st.order[a]), due=float(due[a]))
                for a in range(n)]
    by_id, i, lag = {}, 0, 0.0

    def step(now):
        nonlocal i, lag
        while i < n and due[i] <= now:
            with rec.span("bench.submit"):
                jid = svc.submit(st.graphs[st.order[i]])
            by_id[jid] = arrivals[i]
            lag = max(lag, time.perf_counter() - due[i])
            i += 1
        if svc.pending:
            with rec.span("bench.poll"):
                res = svc.poll()
            t = time.perf_counter()
            for jid, out in res.items():
                by_id[jid].update(t1=t, colors=out.get("colors"),
                                 reported_colors=out.get("n_colors"),
                                 error=out.get("error"))
        elif i < n:
            time.sleep(max(0.0, min(due[i] - time.perf_counter(), 0.05)))

    with rec.span("bench.window"):
        while time.perf_counter() < end:
            step(time.perf_counter())
    while (i < n or svc.pending) and time.perf_counter() < hard_end:
        step(time.perf_counter())
    run.window = (t0, end)
    run.jobs = arrivals
    run.counters["hard_end"] = hard_end
    run.info.update(gen_lag_s=lag, unsent=n - i, **{
        f"service_{k}": v for k, v in svc.stats().items()})


def verify(run, st: State) -> None:
    """Every request due in the window, answered or not, against the
    plain reference on the graph it sent."""
    rows = []
    for j in run.jobs:
        if j.get("colors") is None or j.get("error"):
            continue
        r = reference.check(st.pool[j["k"]], j["colors"])
        j["valid"] = r["conflicts"] == 0 and r["uncolored"] == 0
        j["colors_ref"] = r["colors"]
        rows.append((r, j))
        del j["colors"]
    run.checks += [
        ("unanswered", len(run.jobs) - len(rows), 0),
        ("conflicts", max((r["conflicts"] for r, _ in rows), default=0), 0),
        ("uncolored", max((r["uncolored"] for r, _ in rows), default=0), 0),
        ("colors_mismatch", max((abs(r["colors"] - j["reported_colors"])
                                 for r, j in rows), default=0), 0),
    ]


def latencies(run) -> np.ndarray:
    """Seconds from due to answer of every request; a missing or wrong
    answer counts as waiting until the drain gave up."""
    hard_end = run.counters["hard_end"]
    return np.array([(j["t1"] if j.get("valid") else hard_end) - j["due"]
                     for j in run.jobs])


def end_to_end(run, st: State) -> dict:
    t0, end = run.window
    lat = latencies(run)
    ok = [j for j in run.jobs if j.get("valid")]
    return dict(
        latency_p95_ms=float(np.percentile(lat, 95)) * 1e3,
        graphs_per_s=sum(j["t1"] <= end for j in ok) / (end - t0),
        colors=float(np.mean([j["colors_ref"] for j in ok])) if ok else 0.0)
