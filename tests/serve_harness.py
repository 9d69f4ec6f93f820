"""Deterministic fake-clock harness for the continuous-batching scheduler.

Drives a ``ColoringService`` (with an injected ``FakeClock``) through a
scripted arrival sequence: time is virtual (one tick per poll by default),
arrivals are submitted exactly when the scripted clock reaches them, and
the event loop interleaves submits with scheduler polls — so mid-flight
lane admission, SLO sheds and deferrals replay *identically* on every
run.  Zero sleeps, zero wall-clock reads, zero flakes.

Usage (tests/test_serve_continuous.py, the CI ``serve-stress`` job):

    clock = FakeClock()
    svc = ColoringService(..., clock=clock, serve=ServeConfig(...))
    script = random_script(rng, graphs, n=20, mean_gap=1.5)
    res = run_script(svc, script)
    # res.results / res.shed / res.failed / res.futures
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.launch.serve_coloring import FakeClock, JobError, ShedError


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One scripted request: submit ``graph`` when the clock reaches ``t``."""
    t: float
    graph: object
    marked: object = None


@dataclasses.dataclass
class ScriptResult:
    """What a scripted run produced.

    ``results`` — every completed result (failures included, keyed by
    request id); ``futures`` — every request's ``JobFuture``; ``shed`` /
    ``failed`` — ids rejected by admission control / failed in their
    lane; ``submit_t`` — scripted submit time per id; ``polls`` — total
    scheduler polls the script took to drain.
    """
    results: dict
    futures: dict
    shed: list
    failed: list
    submit_t: dict
    polls: int


def run_script(svc, arrivals, *, poll_cost: float = 1.0,
               max_polls: int = 20000) -> ScriptResult:
    """Drive ``svc`` through ``arrivals`` on its injected ``FakeClock``.

    Event loop: submit every arrival whose time has come, run one
    ``svc.poll()``, advance the clock by ``poll_cost`` (the scripted cost
    of a scheduler step, in virtual seconds per poll), repeat; when the
    service is idle, jump the clock straight to the next arrival.  With
    the default ``poll_cost=1`` arrival times are effectively in poll
    ticks, so scripts express exact interleavings ("request 3 lands two
    chunks into request 1's run").
    """
    clock = svc._clock
    assert isinstance(clock, FakeClock), "inject a FakeClock into the service"
    pend = sorted(arrivals, key=lambda a: a.t)
    results: dict[int, dict] = {}
    futures: dict[int, object] = {}
    submit_t: dict[int, float] = {}
    i = polls = 0
    while i < len(pend) or svc.pending:
        if not svc.pending and i < len(pend) and pend[i].t > clock.now():
            clock.advance(pend[i].t - clock.now())
        while i < len(pend) and pend[i].t <= clock.now():
            a = pend[i]
            jid = svc.submit(a.graph, marked=a.marked)
            futures[jid] = svc.future(jid)
            submit_t[jid] = clock.now()
            i += 1
        results.update(svc.poll())
        clock.advance(poll_cost)
        polls += 1
        if polls > max_polls:
            raise RuntimeError(f"script did not drain in {max_polls} polls "
                               f"({svc.pending} pending)")
    shed = [jid for jid, f in futures.items()
            if isinstance(f.exception(), ShedError)]
    failed = [jid for jid, f in futures.items()
              if f.exception() is not None
              and not isinstance(f.exception(), ShedError)]
    for jid, f in futures.items():
        assert f.done(), f"request {jid} unresolved after drain"
        if f.exception() is None:
            assert jid in results, jid
        elif isinstance(f.exception(), JobError):
            pass
    return ScriptResult(results=results, futures=futures, shed=shed,
                        failed=failed, submit_t=submit_t, polls=polls)


def random_script(rng: np.random.Generator, graphs, *, n: int,
                  mean_gap: float) -> list[Arrival]:
    """A seeded random arrival script: exponential gaps (Poisson process,
    mean ``mean_gap`` virtual seconds) over a uniform mix of ``graphs``."""
    ts = np.cumsum(rng.exponential(mean_gap, size=n))
    idx = rng.integers(0, len(graphs), size=n)
    return [Arrival(float(t), graphs[int(j)]) for t, j in zip(ts, idx)]
