"""Closed-loop batch coloring: one job at a time on a resident graph.

Each job is what a user of a batch coloring waits for: partition the
graph and order its vertices on the host, run the fused coloring program
(``pipeline_sim`` on one chip with P simulated shards, or
``pipeline_sharded`` with one shard per chip), and map the colors back to
global vertex ids.  Job ``i`` of run ``seed`` takes its partition
priorities and both RNG keys from ``job_seed(seed, i)``; nothing is
cached from one job to the next.  The graph is the configuration's, fixed
by its ``graph_seed``, so every run does the same amount of work.

Traffic parameters: ``executor`` (``sim`` | ``sharded``).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench.harness import graphs, program, reference, work

WARMUP_JOB = 2**32 - 1     # an index no counted job reaches


def _executor(run, config: dict):
    from repro.core import pipeline_sharded, pipeline_sim
    from repro.launch.mesh import make_worker_mesh

    kind = run.cell.traffic["executor"]
    if kind == "sim":
        return pipeline_sim
    if kind == "sharded":
        P = config["P"]
        if len(run.devices) < P:
            raise RuntimeError(f"sharded executor needs {P} devices, "
                               f"JAX sees {len(run.devices)}")
        mesh = make_worker_mesh(P)
        return lambda pg, order, cfg, **kw: pipeline_sharded(
            pg, order, cfg, mesh, **kw)
    raise ValueError(f"unknown executor {kind!r}")


class State:
    def __init__(self, run):
        c = run.cell.config
        self.config = c
        self.csr = graphs.make(c["graph"], c["graph"].get("graph_seed", 0))
        self.graph = program.as_graph(self.csr)
        self.cfg = program.pipeline_config(c)
        self.execute = _executor(run, c)

    def job(self, run, i: int) -> dict:
        """One whole coloring; returns its record (colors in global ids)."""
        js = program.job_seed(run.seed, i)
        t0 = time.perf_counter()
        with run.rec.span("bench.host_prep"):
            pg, order = program.prepare(self.graph, self.config, js)
        t1 = time.perf_counter()
        with run.rec.span("bench.dispatch"):
            ck = jax.random.key(js)
            rk = jax.random.fold_in(ck, 1)
            view, res = self.execute(pg, order, self.cfg, color_key=ck,
                                     recolor_key=rk)
        with run.rec.span("bench.fetch"):
            colors = pg.gather_global_colors(
                np.asarray(view)[:, :pg.n_local_max])
        t2 = time.perf_counter()
        hist = res["history"]
        return dict(i=i, t0=t0, t1=t2, prep_s=t1 - t0, colors=colors,
                    reported_colors=(hist[-1]["n_colors_distinct"] if hist
                                     else res["color"]["n_colors_distinct"]),
                    spec_rounds=res["color"]["n_rounds"],
                    iters=res["n_iters_run"])


def setup(run) -> State:
    st = State(run)
    st.job(run, WARMUP_JOB)          # compiles, or loads from the cache
    run.info.update(n=st.csr.n, directed_edges=st.csr.directed_edges,
                    max_degree=st.csr.max_degree)
    return st


def window(run, st: State) -> None:
    """Jobs back to back until ``seconds`` have passed; a job that ends
    after that is not counted, and the window closes at the last one that
    ended inside it."""
    t_start = time.perf_counter()
    end = t_start + run.seconds
    i, done = 0, []
    with run.rec.span("bench.window"):
        while time.perf_counter() < end:
            rec = st.job(run, i)
            i += 1
            if rec["t1"] <= end:
                done.append(rec)
            else:
                run.info["cut_jobs"] = 1
    run.jobs = done
    run.counters["jobs_traced"] = i
    if done:    # where a coloring's time went, for a run that reads slow
        run.info["prep_ms"] = 1e3 * float(np.mean([j["prep_s"]
                                                   for j in done]))
        run.info["program_ms"] = 1e3 * float(np.mean(
            [j["t1"] - j["t0"] - j["prep_s"] for j in done]))
    run.window = (t_start, done[-1]["t1"] if done else end)


def verify(run, st: State) -> None:
    """Every counted coloring against the plain reference."""
    rows = [(reference.check(st.csr, j["colors"]), j) for j in run.jobs]
    run.checks += [
        ("colorings_short", 1 - min(len(rows), 1), 0),
        ("conflicts", max((r["conflicts"] for r, _ in rows), default=0), 0),
        ("uncolored", max((r["uncolored"] for r, _ in rows), default=0), 0),
        ("colors_mismatch", max((abs(r["colors"] - j["reported_colors"])
                                 for r, j in rows), default=0), 0),
    ]
    for r, j in rows:
        j["valid"] = r["conflicts"] == 0 and r["uncolored"] == 0
        j["colors_ref"] = r["colors"]
        del j["colors"]
    run.info["greedy_colors"] = reference.greedy_colors(st.csr)


def end_to_end(run, st: State) -> dict:
    jobs = run.jobs
    if not jobs:
        return {}
    t0, t1 = run.window
    return dict(coloring_s=(t1 - t0) / len(jobs),
                colors=float(np.mean([j["colors_ref"] for j in jobs])))


def work_bytes(run, st: State) -> int:
    """Bytes one coloring needs (``work.coloring_bytes``)."""
    return work.coloring_bytes(st.csr.n, st.csr.directed_edges,
                               st.config["preset"]["recolor_iters"])
