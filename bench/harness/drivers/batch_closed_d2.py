"""Closed-loop batch distance-2 coloring: ``batch_closed`` held to the
distance-2 guarantee.

Set-up, the measured window and the end-to-end metrics are
``batch_closed``'s; each job partitions with the two-hop halo because the
configuration states ``distance: 2``.  What differs: every counted
coloring is checked by ``reference_d2`` (no two vertices within two hops
share a color), the bytes a coloring needs count the two-hop pairs too,
and each job keeps the program's ``gather2_slots`` (slots read through the
two-hop ELL, speculative phase and every recolor iteration), where the
program reports it.

Traffic parameters: ``executor`` (``sim`` | ``sharded``).
"""
from __future__ import annotations

import numpy as np

from bench.harness import reference_d2, work
from bench.harness.drivers import batch_closed
from bench.harness.drivers.batch_closed import end_to_end, window  # noqa: F401


def _gather2_slots(res) -> int | None:
    stats = [res["color"], *res["history"]]
    if any("gather2_slots" not in s for s in stats):
        return None
    return sum(s["gather2_slots"] for s in stats)


def setup(run):
    st = batch_closed.setup(run)
    execute, job = st.execute, st.job
    last = {}

    def counted(pg, order, cfg, **kw):
        view, res = execute(pg, order, cfg, **kw)
        last["gather2_slots"] = _gather2_slots(res)
        return view, res

    def job_d2(run, i):
        rec = job(run, i)
        rec["gather2_slots"] = last.pop("gather2_slots", None)
        return rec

    st.execute, st.job = counted, job_d2
    return st


def verify(run, st) -> None:
    """Every counted coloring against the plain distance-2 reference."""
    rows = [(reference_d2.check(st.csr, j["colors"]), j) for j in run.jobs]
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
    run.info["greedy_colors"] = reference_d2.greedy_colors_d2(st.csr)
    slots = [j["gather2_slots"] for j in run.jobs]
    if slots and None not in slots:
        run.info["gather2_slots"] = float(np.mean(slots))


def work_bytes(run, st) -> int | None:
    """Bytes one coloring needs (``work.coloring_bytes``), each two-hop
    pair read like an edge; only a traced run's roofline reads it."""
    if not run.traced:
        return None
    return work.coloring_bytes(
        st.csr.n, st.csr.directed_edges + reference_d2.two_hop_pairs(st.csr),
        st.config["preset"]["recolor_iters"])
