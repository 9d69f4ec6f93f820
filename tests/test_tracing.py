"""The program's names in a profiler trace (``repro.tracing``).

Device scopes ride the compiled program's ``op_name`` metadata, the JAX
path of every operation, where a transform may wrap a scope
(``vmap(spec_color)``); host spans land on the profiler's host plane,
nested as the program opens them.  A scope is metadata only: the fused
program's colors are pinned bitwise below to a run recorded before the
scopes existed.
"""
import gc
import hashlib
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import (ColorConfig, PipelineConfig, RecolorConfig,
                        compute_order, ordering, partition_graph,
                        pipeline_sim, rmat)
from repro.core.pipeline import pipeline_sim_program


def _cfg(scheme, distance=1, backend="xla"):
    kw = dict(max_colors=256, scheme=scheme, distance=distance,
              backend=backend)
    return PipelineConfig(
        color=ColorConfig(superstep=64, tile=32, selection="random_x",
                          random_x=4, seed=5, **kw),
        recolor=RecolorConfig(**kw), n_iters=3, base_perm="nd",
        rand_every=2, seed=9)


def _prepare(distance=1):
    pg = partition_graph(rmat.rmat_good(8, 8, seed=3), 4, seed=11,
                         halo=distance)
    return pg, compute_order(pg, ordering.INTERNAL_FIRST)


def _components(op_name: str) -> set:
    """The scopes on a JAX path, each unwrapped from its transforms."""
    return {re.fullmatch(r"(?:[\w.-]*\()*(.*?)\)*", c).group(1)
            for c in op_name.split("/")}


@pytest.mark.parametrize("scheme", ["sparse", "allgather"])
def test_lowered_program_carries_scopes(scheme):
    pg, order = _prepare()
    fn, args = pipeline_sim_program(pg, order, _cfg(scheme))
    lowered = fn.lower(*args)
    hlo = lowered.as_text(dialect="hlo", debug_info=True)
    found = set().union(*map(_components,
                             re.findall(r'op_name="([^"]*)"', hlo)))
    assert set(tracing.SCOPES) <= found
    # once XLA has inlined the called computations, every neighbour gather
    # and every exchange sits inside a coloring phase
    paths = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    for p in paths:
        c = _components(p)
        if {tracing.NBR_GATHER, tracing.EXCHANGE} & c:
            assert {tracing.SPEC_COLOR, tracing.RECOLOR} & c, p


def test_two_hop_tiles_gather_in_nbr_gather():
    """Every gather of a two-hop tile (rows x ``maxd2``), in both coloring
    phases, lies inside ``nbr_gather``.  The piggyback schedule's scan of
    the flattened two-hop ELL reads class steps, not colors for a kernel,
    and stays in ``recolor``'s own time, as the CSR scan does at distance 1.
    """
    pg, order = _prepare(2)
    fn, args = pipeline_sim_program(pg, order, _cfg("sparse", 2))
    hlo = fn.lower(*args).as_text(dialect="hlo", debug_info=True)
    tiles = [op for dims, op in re.findall(
        r'= \w+\[([\d,]+)\][^ ]* gather\(.*op_name="([^"]*)"', hlo)
        if dims.split(",")[-1] == str(pg.maxd2)]
    assert pg.maxd2 not in (pg.maxd, pg.n_local_max)
    for phase in (tracing.SPEC_COLOR, tracing.RECOLOR):
        assert any(phase in _components(op) for op in tiles), phase
    for op in tiles:
        assert tracing.NBR_GATHER in _components(op), op


def test_scope_names_are_no_primitive_names():
    from jax.extend.core import Primitive

    prims = {o.name for o in gc.get_objects() if isinstance(o, Primitive)}
    assert {"gather", "while", "all_gather", "ppermute"} <= prims
    assert not set(tracing.SCOPES) & prims
    assert len(set(tracing.SCOPES)) == len(tracing.SCOPES)


def _host_spans(log_dir: Path) -> list:
    """``(line, start_ns, end_ns, name)`` of every ``repro.*`` host span."""
    from jax.profiler import ProfileData

    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            out += [((plane.name, k), ev.start_ns, ev.end_ns, ev.name)
                    for ev in line.events if ev.name.startswith("repro.")]
    return out


def _parents(spans, child) -> set:
    """Names of the spans around each ``child`` span on its own thread."""
    return {n for ln, s, e, n in spans for cl, cs, ce, cn in spans
            if cn == child and ln == cl and s <= cs and ce <= e and n != cn}


def test_host_spans_nest(tmp_path):
    g = rmat.rmat_good(8, 8, seed=3)
    cfg = _cfg("sparse")
    pipeline_sim(*_prepare(), cfg)                  # compiles outside
    with jax.profiler.trace(str(tmp_path)):
        pg = partition_graph(g, 4, seed=12)
        order = compute_order(pg, ordering.INTERNAL_FIRST)
        view, _ = pipeline_sim(pg, order, cfg)
        pg.gather_global_colors(np.asarray(view)[:, :pg.n_local_max])
    spans = _host_spans(tmp_path)
    names = [n for *_, n in spans]
    # every span but the two-hop build, which runs at halo=2 only
    assert set(names) == set(tracing.SPANS) - {tracing.PARTITION_TWO_HOP}
    for name in (tracing.PARTITION, tracing.PARTITION_HALO,
                 tracing.PARTITION_ELL, tracing.ORDER, tracing.UPLOAD,
                 tracing.PROGRAM, tracing.WAIT, tracing.TO_GLOBAL):
        assert names.count(name) == 1, name
    assert _parents(spans, tracing.PARTITION_HALO) == {tracing.PARTITION}
    assert _parents(spans, tracing.PARTITION_ELL) == {tracing.PARTITION}
    # the comm plan is built lazily, on the first use of the sparse plan
    assert tracing.PARTITION not in _parents(spans, tracing.COMM_PLAN)
    for name in (tracing.PARTITION, tracing.ORDER, tracing.UPLOAD,
                 tracing.PROGRAM, tracing.WAIT, tracing.TO_GLOBAL):
        assert _parents(spans, name) == set(), name
    start = {n: s for _, s, _, n in spans}
    assert (start[tracing.PARTITION] < start[tracing.ORDER]
            < start[tracing.UPLOAD] < start[tracing.PROGRAM]
            < start[tracing.WAIT] < start[tracing.TO_GLOBAL])


def test_two_hop_span_nests(tmp_path):
    """At halo=2 the two-hop build is a child of the partition, before the
    halo sets that read its pairs."""
    g = rmat.rmat_good(8, 8, seed=3)
    with jax.profiler.trace(str(tmp_path)):
        partition_graph(g, 4, seed=12, halo=2)
    spans = _host_spans(tmp_path)
    names = [n for *_, n in spans]
    assert sorted(names) == sorted([tracing.PARTITION,
                                    tracing.PARTITION_TWO_HOP,
                                    tracing.PARTITION_HALO,
                                    tracing.PARTITION_ELL])
    for child in (tracing.PARTITION_TWO_HOP, tracing.PARTITION_HALO,
                  tracing.PARTITION_ELL):
        assert _parents(spans, child) == {tracing.PARTITION}, child
    span = {n: (s, e) for _, s, e, n in spans}
    assert (span[tracing.PARTITION_TWO_HOP][1]
            <= span[tracing.PARTITION_HALO][0])


# (distance, scheme, backend) -> sha256[:16] of the global colors, recorded
# from the fused program before it carried any scope
COLORS_PIN = {
    (1, "sparse", "xla"): "50e1213821e55e02",
    (1, "allgather", "xla"): "50e1213821e55e02",
    (2, "sparse", "xla"): "42bd9473faf38263",
    (2, "allgather", "xla"): "42bd9473faf38263",
    (1, "sparse", "pallas"): "50e1213821e55e02",
}


@pytest.mark.parametrize("distance,scheme,backend", list(COLORS_PIN))
def test_colors_bitwise_as_recorded(distance, scheme, backend):
    pg, order = _prepare(distance)
    view, _ = pipeline_sim(pg, order, _cfg(scheme, distance, backend))
    colors = pg.gather_global_colors(np.asarray(view)[:, :pg.n_local_max])
    digest = hashlib.sha256(colors.astype(np.int32).tobytes()).hexdigest()
    assert digest[:16] == COLORS_PIN[(distance, scheme, backend)]
