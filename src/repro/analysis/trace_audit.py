"""Layer 2 of repro-lint: the jaxpr collective audit (DESIGN.md §9).

Where the AST rules reason about *source*, this layer abstract-evals the
shipped entry points at P=2 and asserts on the traced program itself:

1. **Shard-uniform collective sequence** — the ordered list of collective
   primitives (psum/pmax/ppermute/all_gather/...) in the per-shard
   program must be exactly the list in the vmapped (``run_sim``) and
   graph-batched (``color_many``) programs, for every exchange scheme,
   less the graph-batched program's reductions over its named lane axis
   (the lane-uniform control values).  A shard- or lane-dependent
   collective would show up as a sequence mismatch — the static moral
   equivalent of a deadlock.
2. **Scheme resolution** — ``scheme="auto"`` must trace to bitwise the
   program of whichever concrete scheme ``resolve_scheme`` picks: same
   collective sequence, nothing else.
3. **No host callbacks** — the fused pipeline jaxprs (including every
   ``while``/``cond``/``scan`` sub-jaxpr) contain zero callback
   primitives; the device loop never bounces through the host.
4. **One compile per PlanSignature** — dispatching a ≥3-signature graph
   family through ``pipeline_sim`` twice traces exactly once per
   distinct signature (the program-cache contract of DESIGN.md §2).

``run_trace_audit`` returns a :class:`TraceAudit`; the
``tools.repro_lint --trace-audit`` CLI and ``tests/test_trace_audit.py``
both consume it.
"""
from __future__ import annotations

import dataclasses

#: collective primitive names we pin sequences of (jaxpr ``eqn.primitive``).
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "ppermute", "pshuffle",
    "all_to_all", "axis_index",
})

#: host-callback primitives that must never appear in a fused program.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call",
})


@dataclasses.dataclass
class TraceAudit:
    """Outcome of one audit run: passed checks + human-readable failures."""

    checks: list = dataclasses.field(default_factory=list)    # (name, detail)
    failures: list = dataclasses.field(default_factory=list)  # str

    @property
    def ok(self) -> bool:
        return not self.failures

    def record(self, name: str, ok: bool, detail: str) -> None:
        if ok:
            self.checks.append((name, detail))
        else:
            self.failures.append(f"{name}: {detail}")

    def summary_lines(self) -> list:
        lines = [f"trace-audit: {len(self.checks)} check(s) passed, "
                 f"{len(self.failures)} failure(s)"]
        lines += [f"  ok   {name}: {detail}" for name, detail in self.checks]
        lines += [f"  FAIL {msg}" for msg in self.failures]
        return lines


# ------------------------------------------------------- jaxpr traversal --

def _param_jaxprs(params):
    """Sub-jaxprs referenced by one equation, in params order.

    Covers ``cond`` (branches), ``while`` (cond/body), ``scan``/``pjit``/
    ``remat``/``custom_*`` (jaxpr) without enumerating primitive names:
    anything shaped like a (Closed)Jaxpr in the params is walked.
    """
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _walk_prims(jaxpr, out: list) -> None:
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for sub in _param_jaxprs(eqn.params):
            _walk_prims(sub, out)


def prim_sequence(closed_jaxpr) -> tuple:
    """Every primitive in program order, sub-jaxprs inlined at their eqn."""
    out: list = []
    _walk_prims(getattr(closed_jaxpr, "jaxpr", closed_jaxpr), out)
    return tuple(out)


def _eqn_axes(eqn) -> tuple:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    return tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)


def _walk_collectives(jaxpr, skip: frozenset, out: list) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            axes = set(_eqn_axes(eqn))
            if not (axes and axes <= skip):
                out.append(eqn.primitive.name)
        for sub in _param_jaxprs(eqn.params):
            _walk_collectives(sub, skip, out)


def collective_sequence(closed_jaxpr, skip_axes: tuple = ()) -> tuple:
    """Collective primitives in program order; those over ``skip_axes``
    alone (by name) are left out."""
    out: list = []
    _walk_collectives(getattr(closed_jaxpr, "jaxpr", closed_jaxpr),
                      frozenset(skip_axes), out)
    return tuple(out)


def callback_prims(closed_jaxpr) -> tuple:
    return tuple(p for p in prim_sequence(closed_jaxpr)
                 if p in CALLBACK_PRIMS)


# ------------------------------------------------------------- the audit --

def _shard_aval(v, jax):
    """Per-shard ShapeDtypeStruct: drop the leading P axis of a stacked
    partition array."""
    import numpy as np
    v = np.asarray(v)
    return jax.ShapeDtypeStruct(v.shape[1:], v.dtype)


def _entry_jaxprs(pg, cfg, P, jax):
    """(name -> abstract jaxpr) for one resolved config.

    ``pipe`` / ``loop`` are the per-shard SPMD programs behind
    ``pipeline_sim`` / ``recolor_loop_sim``; ``pipe_vmap`` is the
    ``run_sim`` lane-stacked program, ``lanes_vmap`` the same with the
    graph-lane axis named (traced as one lane), and ``many`` the
    graph-batched ``color_many`` program, which vmaps ``lanes_vmap`` over
    that axis — the sequence equalities between them are check (1).
    """
    import numpy as np

    from ..core.comm import AXIS, LANE_AXIS, run_sim
    from ..core.pipeline import (_plan_static, color_then_recolor,
                                 recolor_loop_spmd)

    arrs = pg.arrays(sparse=cfg.needs_sparse_plan)
    ps = _plan_static(pg, cfg)
    shard_arrs = {k: _shard_aval(v, jax) for k, v in arrs.items()}
    n_local_max = shard_arrs["indptr"].shape[0] - 1
    n_slots = shard_arrs["prio"].shape[0]   # n_local_max + max_ghost + 1
    order = jax.ShapeDtypeStruct((n_local_max,), np.int32)
    view = jax.ShapeDtypeStruct((n_slots,), np.int32)
    key = jax.random.key(0)
    axis_env = [(AXIS, P)]

    pipe = lambda a, o, ck, rk: color_then_recolor(
        a, o, ck, rk, cfg=cfg, P_size=P, plan_static=ps)
    loop = lambda a, v, rk: recolor_loop_spmd(
        a, v, rk, cfg=cfg, P_size=P, plan_static=ps)

    stack = lambda s: jax.ShapeDtypeStruct((P,) + tuple(s.shape), s.dtype)
    full_arrs = {k: stack(v) for k, v in shard_arrs.items()}
    pipe_vmap = lambda a, o, ck, rk: run_sim(pipe, P, (a, o), (ck, rk))
    pipe_lanes = lambda a, o, ck, rk: color_then_recolor(
        a, o, ck, rk, cfg=cfg, P_size=P, plan_static=ps,
        lane_axes=(LANE_AXIS,))
    lanes_vmap = lambda a, o, ck, rk: run_sim(pipe_lanes, P, (a, o),
                                              (ck, rk))
    many = jax.vmap(lanes_vmap, in_axes=(0, 0, 0, 0), axis_name=LANE_AXIS)
    batch = lambda s: jax.ShapeDtypeStruct((2,) + tuple(s.shape), s.dtype)
    keys2 = jax.numpy.stack([key, jax.random.key(1)])

    return {
        "pipe": jax.make_jaxpr(pipe, axis_env=axis_env)(
            shard_arrs, order, key, key),
        "loop": jax.make_jaxpr(loop, axis_env=axis_env)(
            shard_arrs, view, key),
        "pipe_vmap": jax.make_jaxpr(pipe_vmap)(
            full_arrs, stack(order), key, key),
        "lanes_vmap": jax.make_jaxpr(lanes_vmap,
                                     axis_env=[(LANE_AXIS, 2)])(
            full_arrs, stack(order), key, key),
        "many": jax.make_jaxpr(many)(
            {k: batch(v) for k, v in full_arrs.items()},
            batch(stack(order)), keys2, keys2),
    }


def _audit_collectives(audit: TraceAudit, pg, base_cfg, P, jax) -> None:
    import dataclasses as dc

    from ..core.comm import (ALLGATHER, AUTO, LANE_AXIS, SPARSE,
                             resolve_scheme)
    from ..core.pipeline import resolve_pipeline_cfg

    def with_scheme(scheme):
        cfg = dc.replace(
            base_cfg, color=dc.replace(base_cfg.color, scheme=scheme),
            recolor=dc.replace(base_cfg.recolor, scheme=scheme))
        return resolve_pipeline_cfg(pg, cfg)

    seqs = {}     # scheme -> entry name -> collective sequence
    for scheme in (SPARSE, ALLGATHER, AUTO):
        jaxprs = _entry_jaxprs(pg, with_scheme(scheme), P, jax)
        seqs[scheme] = {n: collective_sequence(j) for n, j in jaxprs.items()}
        # the lane program less its lane-uniform reductions
        seqs[scheme]["lanes_vmap/shard"] = collective_sequence(
            jaxprs["lanes_vmap"], skip_axes=(LANE_AXIS,))
        for name, j in jaxprs.items():
            cbs = callback_prims(j)
            detail = (f"{name}/{scheme}: callback-free fused program"
                      if not cbs else f"{name}/{scheme}: {list(cbs)}")
            audit.record("no-host-callbacks", not cbs, detail)

    # Under run_sim's lane-vmap, shuffles (ppermute/all_gather/axis_index)
    # lower into lane gathers; cross-shard *reductions* keep their
    # primitive.  So the shard-uniformity pin is: the ordered reduction
    # subsequence survives batching bit-for-bit; naming the graph-lane
    # axis adds only reductions over that axis (the lane-uniform control
    # values), and batching over it (color_many) adds nothing more.
    reductions = {"psum", "pmax", "pmin", "pmean"}
    red = lambda seq: tuple(p for p in seq if p in reductions)
    for scheme in (SPARSE, ALLGATHER):
        per_shard = seqs[scheme]["pipe"]
        audit.record(
            "collectives-present", len(per_shard) > 0,
            f"pipe/{scheme}: {len(per_shard)} collective(s) in the "
            f"per-shard program")
        same = red(seqs[scheme]["pipe_vmap"]) == red(per_shard)
        audit.record(
            "shard-uniform-sequence", same,
            f"pipe_vmap/{scheme} reduction sequence "
            + (f"matches per-shard program ({len(red(per_shard))} "
               f"reduction(s))" if same else
               f"diverges: {red(seqs[scheme]['pipe_vmap'])[:8]} vs "
               f"{red(per_shard)[:8]}"))
        for name, want in (("lanes_vmap/shard", "pipe_vmap"),
                           ("many", "lanes_vmap")):
            same = seqs[scheme][name] == seqs[scheme][want]
            audit.record(
                "batch-invariant-sequence", same,
                f"{name}/{scheme} collective sequence "
                + (f"identical to {want}'s" if same else
                   f"diverges: {seqs[scheme][name][:8]} vs "
                   f"{seqs[scheme][want][:8]}"))

    resolved = resolve_scheme(AUTO, pg)
    for name in ("pipe", "loop", "pipe_vmap", "many"):
        same = seqs[AUTO][name] == seqs[resolved][name]
        audit.record(
            "auto-resolves-identically", same,
            f"{name}: auto == {resolved}"
            + ("" if same else
               f" FAILED ({seqs[AUTO][name][:8]} vs "
               f"{seqs[resolved][name][:8]})"))

    # recolor-only loop is a strict suffix family of the full pipeline's
    # collectives: the loop must not invent exchanges the pipeline lacks.
    for scheme in (SPARSE, ALLGATHER):
        loop_set = set(seqs[scheme]["loop"])
        pipe_set = set(seqs[scheme]["pipe"])
        audit.record(
            "loop-within-pipe", loop_set <= pipe_set,
            f"loop/{scheme} collective kinds {sorted(loop_set)} within "
            f"pipe's {sorted(pipe_set)}")


def _audit_compile_cache(audit: TraceAudit, graphs, cfg, P, jax) -> None:
    """One XLA trace per distinct PlanSignature across a graph family."""
    from ..core.graph import partition_graph
    from ..core.ordering import NATURAL, compute_order
    from ..core.pipeline import (pipeline_sim, plan_signature,
                                 program_cache_clear, program_cache_stats)

    program_cache_clear()
    sigs = set()
    dispatches = 0
    for g in graphs:
        pg = partition_graph(g, P)
        sigs.add(plan_signature(pg, cfg))
        order = compute_order(pg, NATURAL)
        for seed in (0, 1):
            key = jax.random.key(seed)
            pipeline_sim(pg, order, cfg, recolor_key=key)
            dispatches += 1
    stats = program_cache_stats()
    audit.record(
        "distinct-signatures", len(sigs) >= 3,
        f"{len(sigs)} distinct PlanSignature(s) in the swept family")
    audit.record(
        "one-compile-per-signature", stats["traces"] == len(sigs),
        f"{dispatches} dispatches -> {stats['traces']} trace(s) for "
        f"{len(sigs)} signature(s) (hits={stats['hits']}, "
        f"misses={stats['misses']})")


def run_trace_audit(P: int = 2) -> TraceAudit:
    """Run the full audit on tiny P=2 graphs (a few seconds of compiles)."""
    import jax

    from ..core.graph import partition_graph
    from ..core.pipeline import PipelineConfig
    from ..core.recolor import RecolorConfig
    from ..core.rmat import grid2d, rmat_good
    from ..core.speculative import ColorConfig

    audit = TraceAudit()
    base_cfg = PipelineConfig(
        color=ColorConfig(max_colors=64, superstep=16, max_rounds=8),
        recolor=RecolorConfig(max_colors=64, chunk=32),
        n_iters=2, patience=0)

    g = grid2d(8, 8, 9)
    pg = partition_graph(g, P)
    _audit_collectives(audit, pg, base_cfg, P, jax)

    # ≥3 signatures: two grid sizes (different n_local_max) + an rmat
    # (different degree structure); each dispatched twice.
    family = [grid2d(8, 8, 9), grid2d(16, 16, 9), rmat_good(6, 4, seed=1)]
    _audit_compile_cache(audit, family, base_cfg, P, jax)
    return audit
