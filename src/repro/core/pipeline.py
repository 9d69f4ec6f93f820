"""Fused device-resident coloring → iterative-recoloring pipeline.

The paper's central result is a *loop*: a cheap speculative initial coloring
followed by multiple recoloring iterations dominates the time-quality Pareto
front.  The host-looped form (``recolor_iterations`` dispatching one
``recolor_step`` per iteration) pays a host-device round-trip per iteration —
the color view and every stat sync through the host, and each permutation
kind traces its own program.  ``color_then_recolor`` keeps the whole
experiment resident on device, the "communicate only what changed"
discipline of the distributed-GPU coloring literature (Bogle & Slota 2021;
Rokos et al. 2015) applied to the iteration loop itself:

- the initial speculative coloring (any selection/ordering, distance 1|2,
  ``partial``/``marked``) and K recoloring iterations run inside **one
  jitted program** — the comm plan, ELL arrays and exchange closures are
  bound once;
- the per-iteration permutation schedule (ND-RAND%x / ND-RAND%2^i, see
  ``schedule_for_iteration``) is resolved as **traced branches**
  (``permutation_rank_traced``): the kind id array is static per config, so
  no re-tracing per kind and the loop is a single ``lax.while_loop``;
- the RNG key is **folded per iteration** (``fold_in(key, it)``) — bitwise
  the same stream as the host loop, and two iterations never share a RAND
  permutation;
- **adaptive stopping**: the loop quits early once the global *distinct*
  color count has failed to improve for ``patience`` consecutive iterations
  (the paper's time-quality knob; ``patience=0`` always runs all K);
- per-iteration stats land in a device-resident ``(K, len(HISTORY_STATS))``
  int32 history (colors, distinct colors, exchanges, gathered ELL slots,
  supersteps, wire bytes, out-of-range count, permutation id, ran flag),
  unpacked **once** at the end — the only host sync of the whole run.

``recolor_iterations`` is a thin wrapper over the recolor-only loop
(``recolor_loop_spmd``); the host loop survives behind ``fused=False`` as the
bitwise reference (tests/test_pipeline.py pins fused == host at P ∈
{2, 4, 16}, both exchange schemes, distance 1 and 2).

Every driver here takes ``mesh``: ``None`` simulates the P shards on one
device, a mesh runs them on its shard axis (``comm.run_spmd``), and both
take their compiled programs from the one cache of ``programs.py``.

**Batched multi-graph pipeline** (``color_many``, DESIGN.md §8):
production coloring traffic arrives as *many* small-to-medium graphs
(per-batch conflict graphs, per-tile sparsity patterns), not one giant
one.  ``bucket_graphs`` pads the partitions into shape buckets; within a
bucket the fused program is lifted over a leading graph axis with
``vmap`` — per-graph RNG keys, per-graph ``(K, n_stats)``
histories, and a per-graph adaptive stop.  The graph axis is a named vmap
axis (``comm.LANE_AXIS``) in the program's ``lane_axes``, so its control
values are lane-uniform as across a 2D mesh (DESIGN.md §10): the loops run
while *any* graph's predicate holds, a finished lane freezes its carry or
runs bitwise no-op bodies, and each lane's result is bitwise the solo
run's (tests/test_serve.py pins this per graph, across bucket boundaries,
both exchange schemes, distance 1 and 2).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing

from . import ordering
from .comm import (AUTO, AXIS, SPARSE, AxisComm, batch_axis_size,
                   resolve_cfg)
from .graph import PartitionedGraph, _ceil_pow2, bucket_graphs
from .ordering import compute_order
from .programs import (PlanSignature, cached_jit, device_arrays, dispatch,
                       signature, spmd_program)
from .recolor import (ALL_PERMS, ND, PERM_IDS, RecolorConfig, class_sizes,
                      permutation_rank, permutation_rank_traced,
                      recolor_pass_spmd, recolor_step,
                      schedule_for_iteration)
from .speculative import ColorConfig, _apply_partial, color_spmd

# Column layout of the device-resident per-iteration history.  ``ran`` marks
# rows the adaptive stop never reached (they stay zero).
HISTORY_STATS = ("n_colors", "n_colors_distinct", "n_colors_before",
                 "n_exchanges", "gather_slots", "gather2_slots", "n_steps",
                 "wire_bytes", "n_out_of_range", "perm_id", "ran")


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Static configuration of the fused color→recolor pipeline.

    ``n_iters`` (K) caps the recoloring iterations; ``patience`` (in
    iterations, 0 = off) is the adaptive stop on the global distinct-color
    count; the color/recolor stages keep their own configs (``color=None``
    = recolor-only).  Drivers: ``run_pipeline`` for one graph,
    ``color_many`` for a bucketed batch — on one device or a mesh, all
    bitwise-identical per graph for the same keys.
    """

    color: ColorConfig | None = None  # None = recolor-only (seed view given)
    recolor: RecolorConfig = RecolorConfig()
    n_iters: int = 8               # K — max recoloring iterations
    base_perm: str = ND            # schedule base (paper's best: ND)
    rand_every: int = 0            # ND-RAND%x: RAND every x-th iteration
    rand_pow2: bool = False        # ND-RAND%2^i: RAND at power-of-two its
    patience: int = 0              # adaptive stop: quit after this many
                                   # non-improving iterations (0 = run all K)
    seed: int = 0                  # recoloring key seed (folded per it)

    def __post_init__(self):
        assert self.n_iters >= 0
        assert self.patience >= 0
        assert self.base_perm in ALL_PERMS, f"bad perm {self.base_perm!r}"
        if self.color is not None:
            assert self.color.distance == self.recolor.distance, (
                "one device layout serves both stages: color and recolor "
                "must agree on distance")

    @property
    def kind_ids(self) -> tuple:
        """Static per-iteration permutation ids (the ND-RAND%x schedule)."""
        return tuple(
            PERM_IDS[schedule_for_iteration(it, self.base_perm,
                                            self.rand_every, self.rand_pow2)]
            for it in range(1, self.n_iters + 1))

    @property
    def has_auto(self) -> bool:
        """True while any stage's scheme is still the unresolved "auto"."""
        return (self.recolor.scheme == AUTO
                or (self.color is not None and self.color.scheme == AUTO))

    @property
    def needs_sparse_plan(self) -> bool:
        assert not self.has_auto, (
            "scheme='auto' must be resolved against a partition first "
            "(resolve_pipeline_cfg)")
        return (self.recolor.scheme == SPARSE
                or (self.color is not None and self.color.scheme == SPARSE))


def _recolor_loop_fns(arrs, key, cfg: PipelineConfig,
                      P_size: int | None = None, plan_static=None,
                      axis: str = AXIS, lane_axes: tuple = ()):
    """The fused recolor loop's traced pieces: ``(body, cond, lane_on)``.

    ``body``/``cond`` close over ``arrs``/``key`` and operate on the carry
    built by ``recolor_carry_init``.  ``lane_on(state)`` is this lane's own
    adaptive-stop predicate (``cond`` is its mesh-uniform reduction).
    Factored out so the uninterrupted ``recolor_loop_spmd`` and the
    chunked ``pipeline_step_spmd`` run the *same* body — the body freezes
    a finished lane's carry via select-mask, so applying it past the stop
    is a bitwise no-op, which is what makes chunked stepping equal to the
    one-shot ``lax.while_loop``.
    """
    rcfg = cfg.recolor
    comm = AxisComm(axis, lane_axes)
    n_local_max = arrs["indptr"].shape[0] - 1
    mc = rcfg.max_colors
    K = cfg.n_iters
    assert K >= 1
    kind_ids = jnp.asarray(np.asarray(cfg.kind_ids, np.int32))
    patience = cfg.patience if cfg.patience else K + 1  # K+1 never trips

    # Narrow the traced permutation switch to the kinds the static schedule
    # actually uses: vmap lowers ``lax.switch`` to run-every-branch + select,
    # so a batched (color_many) run would otherwise pay all four rank sorts
    # per iteration per graph.  A single-kind schedule (e.g. pure ND) skips
    # the switch entirely; ND-RAND%x narrows it to two branches.  Each
    # branch is the same static function, so this is bitwise-neutral.
    present = tuple(sorted(set(cfg.kind_ids)))
    if len(present) == 1:
        kind0 = ALL_PERMS[present[0]]
        rank_of = lambda sizes, kid, ikey: permutation_rank(sizes, kind0,
                                                            ikey)
    elif len(present) < len(ALL_PERMS):
        present_arr = jnp.asarray(np.asarray(present, np.int32))
        branches = [lambda s, ky, k=ALL_PERMS[p]: permutation_rank(s, k, ky)
                    for p in present]
        rank_of = lambda sizes, kid, ikey: jax.lax.switch(
            jnp.searchsorted(present_arr, kid).astype(jnp.int32), branches,
            sizes, ikey)
    else:
        rank_of = permutation_rank_traced

    def lane_on(state):
        _, it, _, stall, _, _, _ = state
        return (it <= K) & (stall < patience)

    def body(state):
        view, it, best, stall, hist, sizes, n_oor = state
        # this lane's own adaptive stop: when it has tripped but a batch
        # lane elsewhere on the mesh keeps the loop alive, the body still
        # executes (uniform collectives) and the carry freezes below
        on = lane_on(state)
        ikey = jax.random.fold_in(key, it)           # host loop's per-it key
        kid = kind_ids[it - 1]
        n_classes = jnp.sum(sizes > 0).astype(jnp.int32)
        rank = rank_of(sizes, kid, ikey)
        view, st = recolor_pass_spmd(arrs, view, rank, n_classes, rcfg,
                                     P_size=P_size, plan_static=plan_static,
                                     axis=axis, lane_axes=lane_axes)
        # post-iteration sizes double as the next iteration's schedule input
        # (local slots are final once the iteration ends, so this is bitwise
        # the class_sizes the host loop recomputes at its next call)
        sizes, oor_next = class_sizes(view, arrs["n_local"], n_local_max, mc,
                                      comm)
        nd_after = jnp.sum(sizes > 0).astype(jnp.int32)
        row = jnp.stack([st["n_colors"], nd_after, n_classes,
                         st["n_exchanges"], st["gather_slots"],
                         st["gather2_slots"], st["n_steps"],
                         st["wire_bytes"], n_oor, kid,
                         jnp.int32(1)]).astype(jnp.int32)
        hist = jax.lax.dynamic_update_slice(hist, row[None],
                                            (it - 1, jnp.int32(0)))
        improved = nd_after < best
        new_state = (view, it + 1, jnp.minimum(best, nd_after),
                     jnp.where(improved, jnp.int32(0), stall + 1), hist,
                     sizes, oor_next)
        return jax.tree.map(lambda n, o: jnp.where(on, n, o),
                            new_state, state)

    def cond(state):
        return comm.lane_uniform(lane_on(state))

    return body, cond, lane_on


def recolor_carry_init(arrs, view, cfg: PipelineConfig,
                       axis: str = AXIS, lane_axes: tuple = ()):
    """The recolor loop's initial carry from a colored view.

    Carry layout: ``(view, it, best, stall, hist, sizes, n_out_of_range)``
    — ``it`` is 1-based (``it - 1`` iterations have run), ``hist`` the
    device-resident ``(max(K,1), n_stats)`` history.  Feeding this carry
    to ``pipeline_step_spmd`` in chunks replays ``recolor_loop_spmd``
    bitwise; the serving engine holds one such carry per lane.
    """
    comm = AxisComm(axis, lane_axes)
    n_local_max = arrs["indptr"].shape[0] - 1
    K = cfg.n_iters
    hist0 = jnp.zeros((max(K, 1), len(HISTORY_STATS)), jnp.int32)
    sizes0, oor0 = class_sizes(view, arrs["n_local"], n_local_max,
                               cfg.recolor.max_colors, comm)
    return (view, jnp.int32(1), jnp.int32(jnp.iinfo(jnp.int32).max),
            jnp.int32(0), hist0, sizes0, oor0)


@tracing.scope(tracing.RECOLOR)
def recolor_loop_spmd(arrs, view, key, cfg: PipelineConfig,
                      P_size: int | None = None, plan_static=None,
                      axis: str = AXIS, lane_axes: tuple = ()):
    """K fused recoloring iterations in one ``lax.while_loop`` (per-shard).

    Each iteration folds ``it`` into ``key``, reads its permutation kind
    from the static schedule, and runs ``recolor_pass_spmd`` — bitwise the
    host loop's iteration, minus the host round-trip.  Returns
    ``(view, history (K, n_stats) int32, n_iters_run)``.

    On a 2D ``batch × shard`` mesh (``lane_axes``, DESIGN.md §10) the loop
    runs while *any* batch lane's adaptive stop holds — a recoloring
    iteration is not idempotent, so a lane whose own stop tripped freezes
    its entire carry (view, history, counters) while its body keeps
    executing the mesh-uniform collective sequence for its peers.  This is
    the shard_map form of what ``vmap`` of ``lax.while_loop`` already does
    for same-device lanes (run-to-global-stop + select-mask), so lane
    results stay bitwise the solo run's.
    """
    if cfg.n_iters == 0:
        hist0 = jnp.zeros((1, len(HISTORY_STATS)), jnp.int32)
        return view, hist0, jnp.int32(0)
    body, cond, _ = _recolor_loop_fns(arrs, key, cfg, P_size=P_size,
                                      plan_static=plan_static, axis=axis,
                                      lane_axes=lane_axes)
    state0 = recolor_carry_init(arrs, view, cfg, axis=axis,
                                lane_axes=lane_axes)
    view, it, _, _, hist, _, _ = jax.lax.while_loop(cond, body, state0)
    return view, hist, it - 1


def pipeline_carry_spmd(arrs, order, color_key, cfg: PipelineConfig,
                        P_size: int | None = None, plan_static=None,
                        axis: str = AXIS, lane_axes: tuple = ()):
    """Initial coloring + recolor carry for *stepped* execution (per-shard).

    The front half of ``color_then_recolor``: runs ``color_spmd`` and
    packs the result into a ``recolor_carry_init`` carry instead of
    entering the while loop.  Returns ``(carry, color_stats)`` — advance
    the carry with ``pipeline_step_spmd``.  This is the serving engine's
    lane-admission program (DESIGN.md §11).
    """
    assert cfg.color is not None, "pipeline_carry_spmd needs cfg.color"
    view, cstats = color_spmd(arrs, order, color_key, cfg.color,
                              P_size=P_size, plan_static=plan_static,
                              axis=axis, lane_axes=lane_axes)
    carry = recolor_carry_init(arrs, view, cfg, axis=axis,
                               lane_axes=lane_axes)
    return carry, cstats


@tracing.scope(tracing.RECOLOR)
def pipeline_step_spmd(arrs, carry, key, cfg: PipelineConfig, chunk: int,
                       P_size: int | None = None, plan_static=None,
                       axis: str = AXIS, lane_axes: tuple = ()):
    """Advance a recolor carry by ``chunk`` fused iterations (per-shard).

    Applies the while loop's *body* a fixed ``chunk`` times
    (``lax.fori_loop`` with static bounds — uniform control flow by
    construction) and returns ``(carry, done)``.  Because the body
    select-freezes a lane whose adaptive stop has tripped, applications
    past the stop are bitwise no-ops: running ``pipeline_step_spmd`` until
    ``done`` yields exactly the carry ``recolor_loop_spmd`` would have
    produced uninterrupted, for any chunk size.  The serving engine
    interleaves lane admission between chunks on this guarantee.
    """
    assert chunk >= 1
    if cfg.n_iters == 0:
        return carry, jnp.bool_(True)
    body, _, lane_on = _recolor_loop_fns(arrs, key, cfg, P_size=P_size,
                                         plan_static=plan_static, axis=axis,
                                         lane_axes=lane_axes)
    carry = jax.lax.fori_loop(0, chunk, lambda _, s: body(s), carry)
    return carry, ~lane_on(carry)


def color_then_recolor(arrs, order, color_key, recolor_key,
                       cfg: PipelineConfig, P_size: int | None = None,
                       plan_static=None, axis: str = AXIS,
                       lane_axes: tuple = ()):
    """The fused pipeline program (per-shard SPMD, jit/shard_map ready).

    Initial speculative coloring + K recoloring iterations, all device
    resident.  ``axis`` names the shard mesh axis of every collective;
    ``lane_axes`` the batch axes of a 2D mesh whose lanes this program's
    control flow must stay uniform over (DESIGN.md §10).
    Returns ``(view, color_stats, history, n_iters_run)``.
    """
    assert cfg.color is not None, "color_then_recolor needs cfg.color"
    view, cstats = color_spmd(arrs, order, color_key, cfg.color,
                              P_size=P_size, plan_static=plan_static,
                              axis=axis, lane_axes=lane_axes)
    view, hist, n_run = recolor_loop_spmd(arrs, view, recolor_key, cfg,
                                          P_size=P_size,
                                          plan_static=plan_static, axis=axis,
                                          lane_axes=lane_axes)
    return view, cstats, hist, n_run


# ----------------------------------------------------------------- drivers --

def _history_to_host(hist) -> list[dict]:
    """(K, n_stats) (or (P, K, n_stats) stacked) device history -> dicts.

    One unpacking at the end of the run — the host loop's per-iteration
    ``stats_to_host`` sync collapsed into a single transfer.  Rows the
    adaptive stop never reached (``ran == 0``) are dropped.
    """
    hist = np.asarray(hist)
    if hist.ndim == 3:                       # (P, K, n_stats) shard stack
        hist = hist.max(axis=0)
    out = []
    for i in range(hist.shape[0]):
        row = {k: int(v) for k, v in zip(HISTORY_STATS, hist[i])}
        if not row.pop("ran"):
            break
        row["perm"] = ALL_PERMS[row.pop("perm_id")]
        row["iteration"] = i + 1
        out.append(row)
    return out


def _plan_static(part, cfg: PipelineConfig):
    """The plan statics a program of ``cfg`` over ``part`` (a partition or
    a bucket) is traced for: ``None`` where no stage runs sparse."""
    return part.plan_static if cfg.needs_sparse_plan else None


def _signature(kind: str, P: int, cfg: PipelineConfig, plan_static, arrs,
               batch: int = 0, mesh=None) -> PlanSignature:
    """A fused program's signature: it names the seed stage's color bound
    and scheme (the recolor stage's, recolor-only) and the distance."""
    stage = cfg.recolor if cfg.color is None else cfg.color
    return signature(kind, P, cfg, plan_static, arrs, stage=stage,
                     batch=batch, mesh=mesh)


def resolve_pipeline_cfg(part, cfg: PipelineConfig) -> PipelineConfig:
    """Concretize any ``scheme="auto"`` stage against ``part``'s comm plan.

    ``part`` is a ``PartitionedGraph``, or a ``GraphBucket`` whose members
    share one compiled program (the decision is then made once, from the
    union plan).  The decision (``comm.resolve_scheme``) compares the
    *padded* sparse plan bytes — what the compiled program physically
    ships — against the broadcast's; an explicit scheme passes through
    untouched.
    """
    if not cfg.has_auto:
        return cfg
    return dataclasses.replace(
        cfg, color=None if cfg.color is None else resolve_cfg(part, cfg.color),
        recolor=resolve_cfg(part, cfg.recolor))


def plan_signature(pg: PartitionedGraph, cfg: PipelineConfig, *,
                   kind: str = "pipe", batch: int = 0,
                   mesh=None) -> PlanSignature:
    """The signature a ``run_pipeline`` dispatch of ``pg`` on ``mesh``
    would use.

    Public inspection hook (``launch/dryrun.py``, the serving cost model,
    tests): resolves "auto", builds the device dict host-side and derives
    the exact cache key without compiling anything.
    """
    cfg = resolve_pipeline_cfg(pg, cfg)
    arrs = pg.arrays(sparse=cfg.needs_sparse_plan)
    return _signature(kind, pg.P, cfg, _plan_static(pg, cfg), arrs,
                      batch=batch, mesh=mesh)


def bucket_signature(bucket, cfg: PipelineConfig, *, pad_batch: bool = True,
                     mesh=None) -> PlanSignature:
    """The signature a ``color_many`` dispatch of ``bucket`` on ``mesh``
    would use.

    The serving driver's cost model probes the program cache with this
    before deciding solo-vs-batch routing; nothing is stacked or compiled —
    batch padding and the sharded layout's axis swap are applied to shapes
    only.
    """
    bcfg = resolve_pipeline_cfg(bucket, cfg)
    ma = bucket.member_arrays(0, sparse=bcfg.needs_sparse_plan)
    lane_multiple = batch_axis_size(mesh) if mesh is not None else 1
    B = _lane_target(bucket.B, pad_batch, lane_multiple)

    def dim(v):
        s = (B,) + tuple(v.shape)
        return (s[1], s[0]) + s[2:] if mesh is not None else s

    shapes = {k: jax.ShapeDtypeStruct(dim(v), np.asarray(v).dtype)
              for k, v in ma.items()}
    return _signature("many", bucket.P, bcfg, _plan_static(bucket, bcfg),
                      shapes, batch=B, mesh=mesh)


def _recolor_loop(pg: PartitionedGraph, view, cfg: PipelineConfig):
    """Fused recolor-only loop on one device: ``recolor_iterations``' core.

    Returns ``(view, history list-of-dicts)``.
    """
    cfg = resolve_pipeline_cfg(pg, cfg)
    arrs = device_arrays(pg, cfg.needs_sparse_plan)
    ps = _plan_static(pg, cfg)
    sig = _signature("loop", pg.P, cfg, ps, arrs)
    fn = partial(recolor_loop_spmd, cfg=cfg, P_size=pg.P, plan_static=ps)
    view, hist, _ = dispatch(sig, fn, pg.P, None, (arrs, jnp.asarray(view)),
                             (jax.random.key(cfg.seed),))
    with jax.profiler.TraceAnnotation(tracing.WAIT):
        hist = jax.device_get(hist)                  # one host transfer
    return view, _history_to_host(hist)


def recolor_iterations(pg: PartitionedGraph, view, n_iters: int,
                       cfg: RecolorConfig, *, base_perm: str = ND,
                       rand_every: int = 0, rand_pow2: bool = False,
                       seed: int = 0, collect=None, fused: bool = True):
    """Run `n_iters` RC iterations with an ND-RAND%x style schedule (sim).

    By default the loop runs *device-resident* through the fused recolor
    loop (``recolor_loop_spmd``): one jitted program, no per-iteration
    host round-trip, bitwise-identical views and history to the host loop.
    ``fused=False`` forces the host loop (one ``recolor_step`` dispatch per
    iteration) — kept as the reference the fused path is tested against;
    ``collect=`` implies it, since per-iteration views must reach the host.
    """
    if fused and collect is None and n_iters > 0:
        pcfg = PipelineConfig(
            color=None, recolor=cfg, n_iters=n_iters, base_perm=base_perm,
            rand_every=rand_every, rand_pow2=rand_pow2, seed=seed)
        return _recolor_loop(pg, view, pcfg)
    history = []
    for it in range(1, n_iters + 1):
        kind = schedule_for_iteration(it, base_perm, rand_every, rand_pow2)
        key = jax.random.fold_in(jax.random.key(seed), it)
        view, stats = recolor_step(pg, view, kind, cfg, key)
        stats["iteration"], stats["perm"] = it, kind
        history.append(stats)
        if collect is not None:
            collect(view, stats)
    return view, history


def _keys(cfg: PipelineConfig, color_key, recolor_key):
    if color_key is None:
        color_key = jax.random.key(cfg.color.seed)
    if recolor_key is None:
        recolor_key = jax.random.key(cfg.seed)
    return color_key, recolor_key


def _pipeline_result(view, cstats, hist, n_run):
    # shard-max the stats on device, then cross to the host once: stats,
    # history and iteration count ride a single device_get
    cmax = {k: jnp.max(v) for k, v in cstats.items()}
    with jax.profiler.TraceAnnotation(tracing.WAIT):
        cmax, hist, n_run = jax.device_get((cmax, hist, n_run))
    return view, dict(color={k: int(v) for k, v in cmax.items()},
                      history=_history_to_host(hist),
                      n_iters_run=int(np.max(n_run)))


def _pipeline_parts(pg, order, cfg, mesh, marked, color_key, recolor_key):
    """``(signature, per-shard fn, sharded args, other args)`` of the fused
    program ``run_pipeline`` dispatches."""
    assert cfg.color is not None, "run_pipeline needs cfg.color"
    cfg = resolve_pipeline_cfg(pg, cfg)
    arrs = device_arrays(pg, cfg.needs_sparse_plan)
    order = jnp.asarray(_apply_partial(order, cfg.color, marked))
    ps = _plan_static(pg, cfg)
    sig = _signature("pipe", pg.P, cfg, ps, arrs, mesh=mesh)
    fn = partial(color_then_recolor, cfg=cfg, P_size=pg.P, plan_static=ps)
    return sig, fn, (arrs, order), _keys(cfg, color_key, recolor_key)


def run_pipeline(pg: PartitionedGraph, order, cfg: PipelineConfig,
                 mesh=None, *, marked=None, color_key=None,
                 recolor_key=None):
    """Run the fused pipeline: speculative coloring + K recoloring
    iterations in one device-resident program.

    ``mesh=None`` simulates the P shards on one device (P vmap lanes); a
    mesh runs them on its shard axis (``shard_axis_of(mesh)``) via
    shard_map — on a 2D ``batch × shard`` mesh the solo graph is
    replicated over the batch axis — bitwise the same results.
    ``order``/``marked`` as ``speculative.color_graph``; ``color_key`` /
    ``recolor_key`` default to ``key(cfg.color.seed)`` / ``key(cfg.seed)``.
    Returns ``(view, result)``: ``view`` is the final ``(P, n_slots)``
    device view and ``result`` holds the initial-coloring stats
    (``"color"``, keys as ``color_graph``), the per-iteration
    ``"history"`` (one dict per executed iteration, keys as
    ``recolor_step`` plus ``perm``/``iteration``) and ``"n_iters_run"``
    (adaptive stop included).
    """
    sig, fn, sharded, keys = _pipeline_parts(pg, order, cfg, mesh, marked,
                                             color_key, recolor_key)
    return _pipeline_result(*dispatch(sig, fn, pg.P, mesh, sharded, keys))


# The benchmark's executors (``bench/harness/drivers/batch_closed.py``,
# ``bench/tests/test_reference.py``) import these two names; ``mesh`` is
# the fourth positional parameter, so both of their call shapes work.
pipeline_sim = pipeline_sharded = run_pipeline


def pipeline_program(pg: PartitionedGraph, order, cfg: PipelineConfig,
                     mesh=None, *, marked=None, color_key=None,
                     recolor_key=None):
    """The cached jitted program ``run_pipeline`` dispatches, with its
    arguments: ``(fn, args)``.  ``fn.lower(*args).compile()`` compiles
    exactly what ``run_pipeline`` runs (compile timing, HLO inspection,
    compiling for a described device from the arguments' shapes), and
    ``fn(*args)`` dispatches it without waiting for the result."""
    sig, fn, sharded, keys = _pipeline_parts(pg, order, cfg, mesh, marked,
                                             color_key, recolor_key)
    return spmd_program(sig, fn, pg.P, len(sharded), mesh), sharded + keys


# ----------------------------------------------- continuous-engine programs --

def engine_init_program(P: int, cfg: PipelineConfig, plan_static, arrs,
                        mesh=None):
    """Cached single-lane admission program for the serving engine.

    ``(arrs, order, color_key) -> (carry, color_stats)`` — initial coloring
    packed into a recolor carry (``pipeline_carry_spmd``).  ``arrs`` is the
    lane's host- or device-side input dict, used for the cache signature;
    the engine runs this once per admitted request and scatters the result
    into its lane buffers, so admission never recompiles (DESIGN.md §11).
    """
    assert not cfg.has_auto
    sig = _signature("engine_init", P, cfg, plan_static, arrs, mesh=mesh)
    fn = partial(pipeline_carry_spmd, cfg=cfg, P_size=P,
                 plan_static=plan_static)
    return spmd_program(sig, fn, P, 2, mesh)


def engine_step_program(P: int, cfg: PipelineConfig, plan_static, arrs,
                        B: int, chunk: int, mesh=None):
    """Cached all-lanes step program for the serving engine.

    ``(arrs, carry, keys) -> (carry, done)`` — every lane advances by
    ``chunk`` fused recoloring iterations (``pipeline_step_spmd`` vmapped
    over the B lane axis), with the carry input buffers **donated**: the
    engine owns exactly one generation of lane state at a time.  Sim
    layout stacks lanes on axis 0 (``(B, P, ...)``, ``done (B, P)``); on a
    mesh the lanes ride ``comm.run_spmd``'s ``(P, B, ...)`` layout
    (``done (P, B)``) and are sharded over the batch mesh axis.  Lanes
    whose stop has tripped (or that are empty) are frozen by the body's
    select-mask, so a partially idle engine steps bitwise-inertly.
    """
    assert not cfg.has_auto
    sig = _signature(f"engine_step{chunk}", P, cfg, plan_static, arrs,
                     batch=B, mesh=mesh)
    fn = partial(pipeline_step_spmd, cfg=cfg, chunk=chunk, P_size=P,
                 plan_static=plan_static)
    return spmd_program(sig, fn, P, 2, mesh, lanes=True, donate_argnums=(1,))


def engine_put_program(P: int, cfg: PipelineConfig, plan_static, arrs,
                       B: int, mesh=None):
    """Cached lane-scatter program for the serving engine.

    ``(bufs, vals, b) -> bufs`` — write one admitted lane's arrays/carry/
    stats (``vals``, unstacked) into lane ``b`` of the engine's stacked
    buffers in ONE donated dispatch.  Eagerly scattering the ~30 buffers
    one ``.at[b].set`` at a time costs a device round-trip per buffer and
    dominates admission latency; this program is the whole swap.  ``b``
    is a traced operand, so every lane shares the one compiled program.
    """
    assert not cfg.has_auto
    sig = _signature("engine_put", P, cfg, plan_static, arrs, batch=B,
                     mesh=mesh)
    lane_axis = 0 if mesh is None else 1

    def put(bufs, vals, b):
        return jax.tree.map(
            lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                buf, v, b, axis=lane_axis), bufs, vals)

    return cached_jit(sig, put, donate_argnums=(0,))


# ------------------------------------------- batched multi-graph pipeline --
#
# The graph lanes one device holds are a vmap named ``LANE_AXIS``, and the
# lane programs carry it in ``lane_axes`` (with a 2D mesh's batch axis):
# their control values (trip counts, exchange gates, the recolor chunk
# width) are then lane-uniform, so each ``cond``/``switch`` stays one branch
# taken instead of vmap's run-every-branch + select.

def _keys_many(cfg: PipelineConfig, n, color_keys, recolor_keys):
    """Per-graph key lists: defaults fold the graph's input position into
    the config seeds, so every graph gets an independent stream and a solo
    rerun with the same folded key reproduces its lane bitwise."""
    if color_keys is None:
        base = jax.random.key(cfg.color.seed)
        color_keys = [jax.random.fold_in(base, i) for i in range(n)]
    if recolor_keys is None:
        base = jax.random.key(cfg.seed)
        recolor_keys = [jax.random.fold_in(base, i) for i in range(n)]
    assert len(color_keys) == n and len(recolor_keys) == n
    return list(color_keys), list(recolor_keys)


def _bucket_order(bucket, cfg: PipelineConfig, orders, marked):
    """(B, P, n_local_max) visit order for one bucket's members.

    ``orders`` is an ordering-kind string (computed per padded member —
    identical to padding the original's order, local slots are unchanged)
    or a per-graph sequence of ``(P, n_local_max)`` arrays padded here with
    -1 to the bucket width.  ``marked`` masks are padded with False.

    Kind-string orders with no ``marked`` masks are cached on the bucket:
    a memoized serving bucket must not recompute orders per warm request.
    """
    cache = key = None
    if marked is None and (orders is None or isinstance(orders, str)):
        key = (orders, cfg.color)
        cache = bucket.__dict__.setdefault("_order_cache", {})
        if key in cache:
            return cache[key]
    rows = []
    for j, gi in enumerate(bucket.indices):
        m = bucket.members[j]
        if orders is None or isinstance(orders, str):
            o = compute_order(m, orders or ordering.INTERNAL_FIRST)
        else:
            o = np.asarray(orders[gi])
            o = np.pad(o, ((0, 0), (0, m.n_local_max - o.shape[1])),
                       constant_values=-1)
        mk = None if marked is None else marked[gi]
        if mk is not None:
            mk = np.asarray(mk, dtype=bool)
            mk = np.pad(mk, ((0, 0), (0, m.n_local_max - mk.shape[1])))
        rows.append(_apply_partial(o, cfg.color, mk))
    out = np.stack(rows)
    if cache is not None:
        cache[key] = out
    return out


def _lane_target(B: int, pad_batch: bool, lane_multiple: int = 1) -> int:
    """Padded lane count: pow2 under ``pad_batch``, and always a multiple
    of ``lane_multiple`` (the batch mesh axis size — a 2D mesh shards the
    lane axis, so it must divide evenly)."""
    t = _ceil_pow2(B) if pad_batch else B
    return -(-t // lane_multiple) * lane_multiple


def _pad_batch_lanes(st, order_b, cks_b, rks_b, B, target):
    """Pad the batch axis up to ``target`` lanes with dummy lanes.

    The extra lanes replicate member 0 (lanes are independent, results are
    dropped on unpacking), so a service's batch programs see pow2 batch
    shapes only and keep hitting the jit cache as queue depth fluctuates.
    """
    ext = target - B
    if ext:
        st = {k: np.concatenate([v, np.repeat(v[:1], ext, axis=0)])
              for k, v in st.items()}
        order_b = np.concatenate(
            [order_b, np.repeat(order_b[:1], ext, axis=0)])
        cks_b = cks_b + [cks_b[0]] * ext
        rks_b = rks_b + [rks_b[0]] * ext
    return st, order_b, cks_b, rks_b


def _bucket_inputs(bucket, cfg, orders, marked, cks, rks, pad_batch,
                   lane_multiple: int = 1):
    """Per-bucket dispatch inputs of ``color_many``."""
    st = bucket.stacked_arrays(sparse=cfg.needs_sparse_plan)
    order_b = _bucket_order(bucket, cfg, orders, marked)
    cks_b = [cks[i] for i in bucket.indices]
    rks_b = [rks[i] for i in bucket.indices]
    st, order_b, cks_b, rks_b = _pad_batch_lanes(
        st, order_b, cks_b, rks_b, bucket.B,
        _lane_target(bucket.B, pad_batch, lane_multiple))
    ps = bucket.plan_static if cfg.needs_sparse_plan else None
    return st, order_b, cks_b, rks_b, ps


def _unpack_bucket(out, bucket, bi, pgs, results):
    """(B, P, ...) batch outputs -> per-graph result dicts (input order)."""
    # every per-graph output crosses to the host in one device_get
    view, cstats, hist, n_run = jax.device_get(out)
    for j, gi in enumerate(bucket.indices):
        v = view[j]
        results[gi] = dict(
            view=v,
            colors=pgs[gi].gather_global_colors(
                v[:, :bucket.members[j].n_local_max]),
            color={k: int(a[j].max()) for k, a in cstats.items()},
            history=_history_to_host(hist[j]),
            n_iters_run=int(n_run[j].max()),
            bucket=bi)
    return results


def color_many(pgs, cfg: PipelineConfig, mesh=None, *, orders=None,
               marked=None, color_keys=None, recolor_keys=None, buckets=None,
               pad_batch: bool = False):
    """Color a batch of partitioned graphs through one fused program each
    bucket — the batched service's dispatch core.

    ``pgs`` — same-``P`` ``PartitionedGraph`` list (``halo`` per
    ``cfg``'s distance).  ``mesh=None`` runs on one device: a vmap over
    graphs of a vmap over shards.  On a 1D mesh the graph batch axis rides
    *inside* each shard (vmap under shard_map); on a 2D ``batch × shard``
    mesh (``make_coloring_mesh(P, batch=Bm)``) the lanes are additionally
    sharded over the batch axis — each device vmaps B/Bm lanes, and lane
    counts are padded to a multiple of Bm.  ``orders`` — an ``ordering``
    kind string (default ``internal_first``) or per-graph ``(P,
    n_local_max)`` arrays.  ``marked`` — per-graph partial-coloring masks
    (``cfg.color.partial``).  ``color_keys``/``recolor_keys`` — per-graph
    JAX keys; the default folds each graph's input position into the
    config seeds.  ``buckets`` — a precomputed ``bucket_graphs(pgs)``
    result (a server that already bucketed its queue passes it to skip the
    host-side re-pad).  ``pad_batch=True`` rounds every bucket's batch axis
    up to a power of two with dropped dummy lanes, so batch-program shapes
    stay stable as queue depth fluctuates (jit-cache friendly serving).

    Returns one dict per input graph (input order): ``view`` ``(P,
    n_slots)`` padded device view, ``colors`` ``(n_global,)`` 1-based,
    ``color`` initial-coloring stats, ``history``/``n_iters_run`` as
    ``run_pipeline``, and the ``bucket`` index.  Each graph's view and
    history are bitwise a solo ``run_pipeline`` run on its padded member
    (``bucket.members[j]``) with the same keys, on any mesh.
    """
    assert cfg.color is not None, "color_many needs cfg.color"
    pgs = list(pgs)
    if buckets is None:
        buckets = bucket_graphs(pgs)
    cks, rks = _keys_many(cfg, len(pgs), color_keys, recolor_keys)
    lane_multiple = batch_axis_size(mesh) if mesh is not None else 1
    results = [None] * len(pgs)
    for bi, bucket in enumerate(buckets):
        bcfg = resolve_pipeline_cfg(bucket, cfg)
        st, order_b, cks_b, rks_b, ps = _bucket_inputs(
            bucket, bcfg, orders, marked, cks, rks, pad_batch, lane_multiple)
        arrs = {k: jnp.asarray(v) for k, v in st.items()}
        order_b = jnp.asarray(order_b)
        if mesh is not None:
            # leading axis P for shard_map; per-shard arrays carry (B, ...)
            arrs = {k: jnp.moveaxis(v, 0, 1) for k, v in arrs.items()}
            order_b = jnp.moveaxis(order_b, 0, 1)
        sig = _signature("many", bucket.P, bcfg, ps, arrs, batch=len(cks_b),
                         mesh=mesh)
        fn = partial(color_then_recolor, cfg=bcfg, P_size=bucket.P,
                     plan_static=ps)
        out = dispatch(sig, fn, bucket.P, mesh, (arrs, order_b),
                       (jnp.stack(cks_b), jnp.stack(rks_b)), lanes=True)
        if mesh is not None:
            # outputs carry (P, B, ...): put the graph axis back in front
            out = jax.tree.map(lambda x: np.moveaxis(np.asarray(x), 0, 1),
                               out)
        _unpack_bucket(out, bucket, bi, pgs, results)
    return results
