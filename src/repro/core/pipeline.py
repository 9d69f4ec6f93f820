"""Fused device-resident coloring → iterative-recoloring pipeline.

The paper's central result is a *loop*: a cheap speculative initial coloring
followed by multiple recoloring iterations dominates the time-quality Pareto
front.  The host-looped form (``recolor_iterations`` dispatching one
``recolor_sim`` per iteration) pays a host-device round-trip per iteration —
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
  int32 history (colors, distinct colors, exchanges, supersteps, wire bytes,
  out-of-range count, permutation id, ran flag), unpacked **once** at the
  end — the only host sync of the whole run.

``recolor_iterations`` is a thin wrapper over the recolor-only loop
(``recolor_loop_sim``); the host loop survives behind ``fused=False`` as the
bitwise reference (tests/test_pipeline.py pins fused == host at P ∈
{2, 4, 16}, both exchange schemes, distance 1 and 2).

**Batched multi-graph pipeline** (``color_many`` / ``color_many_sharded``,
DESIGN.md §8): production coloring traffic arrives as *many*
small-to-medium graphs (per-batch conflict graphs, per-tile sparsity
patterns), not one giant one.  ``bucket_graphs`` pads the partitions into
shape buckets; within a bucket the fused program is lifted over a leading
graph axis with ``vmap`` — per-graph RNG keys, per-graph ``(K, n_stats)``
histories, and a per-graph adaptive stop: ``vmap`` of ``lax.while_loop``
runs while *any* graph's predicate holds and select-masks the body on
finished lanes, so each lane's result is bitwise the solo run's
(tests/test_serve.py pins this per graph, across bucket boundaries, both
exchange schemes, distance 1 and 2).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing

from . import ordering
from .comm import (ALLGATHER, AUTO, AXIS, SPARSE, AxisComm,
                   allgather_bytes_per_exchange, batch_axis_of,
                   batch_axis_size, mesh_axes, run_sharded, run_sharded_many,
                   run_sim, shard_axis_of, stats_to_host)
from .graph import PartitionedGraph, _ceil_pow2, bucket_graphs
from .ordering import compute_order
from .recolor import (ALL_PERMS, ND, PERM_IDS, RecolorConfig, class_sizes,
                      permutation_rank, permutation_rank_traced,
                      recolor_pass_spmd, schedule_for_iteration)
from .speculative import ColorConfig, _apply_partial, color_spmd, resolve_cfg

# Column layout of the device-resident per-iteration history.  ``ran`` marks
# rows the adaptive stop never reached (they stay zero).
HISTORY_STATS = ("n_colors", "n_colors_distinct", "n_colors_before",
                 "n_exchanges", "n_steps", "wire_bytes", "n_out_of_range",
                 "perm_id", "ran")


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Static configuration of the fused color→recolor pipeline.

    ``n_iters`` (K) caps the recoloring iterations; ``patience`` (in
    iterations, 0 = off) is the adaptive stop on the global distinct-color
    count; the color/recolor stages keep their own configs (``color=None``
    = recolor-only).  Drivers: ``pipeline_sim`` / ``pipeline_sharded`` for
    one graph, ``color_many`` / ``color_many_sharded`` for a bucketed
    batch — all four bitwise-identical per graph for the same keys.
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
                         st["n_exchanges"], st["n_steps"], st["wire_bytes"],
                         n_oor, kid, jnp.int32(1)]).astype(jnp.int32)
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


def _plan_static(pg: PartitionedGraph, cfg: PipelineConfig):
    return pg.comm_plan.static if cfg.needs_sparse_plan else None


def _pipeline_arrays(pg: PartitionedGraph, cfg: PipelineConfig) -> dict:
    """Device-resident input dict, cached on the partition instance.

    JAX arrays are immutable, so the same device buffers serve every
    dispatch of this partition — a memoized serving entry pays the
    host->device transfer once, not per warm request.
    """
    cache = pg.__dict__.setdefault("_device_arrays", {})
    sparse = cfg.needs_sparse_plan
    if sparse not in cache:
        with jax.profiler.TraceAnnotation(tracing.UPLOAD):
            cache[sparse] = {k: jnp.asarray(v)
                             for k, v in pg.arrays(sparse=sparse).items()}
    return cache[sparse]


# ------------------------------------------------- compiled-program cache --

@dataclasses.dataclass(frozen=True)
class PlanSignature:
    """Hashable identity of one compiled pipeline program (DESIGN.md §2).

    Two dispatches with equal signatures share one lowered program.  The
    named fields are the readable core (``launch/dryrun.py`` prints them):
    ``rungs`` is the comm plan's static ``(shifts, pow2 widths)`` — the
    part width quantization exists to stabilize — and ``scheme`` is the
    *resolved* exchange scheme (never "auto").  ``dims`` pins every input
    array's ``(name, shape, dtype)`` so signature equality is exactly as
    strict as the jit trace, ``axes`` pins the mesh layout as ``((axis
    name, axis size), ...)`` — two meshes with different axis names or
    shapes lower different collectives, so they must not share a program —
    and ``cfg`` carries the full static config; ``extra`` holds non-array
    trace context (the mesh object, for sharded programs).
    """

    kind: str          # program family: pipe_sim | loop_sim | pipe_sharded
                       # | many_sim | many_sharded
    P: int
    n_local_max: int
    maxd: int
    max_colors: int
    distance: int
    scheme: str        # resolved: "sparse" | "allgather"
    rungs: tuple       # plan static (shifts, pow2 widths); () for allgather
    batch: int         # vmapped graph lanes (0 = solo program)
    cfg: object        # resolved PipelineConfig (trace-static)
    dims: tuple        # ((name, shape, dtype), ...) of every input array
    axes: tuple = ()   # mesh layout ((axis name, axis size), ...)
    extra: object = None

    def describe(self) -> str:
        """The human-readable core (what ``dryrun --coloring`` reports)."""
        axes = "×".join(f"{n}={s}" for n, s in self.axes) or "-"
        return (f"kind={self.kind} P={self.P} "
                f"n_local_max={self.n_local_max} maxd={self.maxd} "
                f"max_colors={self.max_colors} distance={self.distance} "
                f"scheme={self.scheme} batch={self.batch} axes={axes} "
                f"rungs={self.rungs[1] if self.rungs else ()}")


class _ProgramCache:
    """Process-wide LRU of jitted pipeline programs keyed on PlanSignature.

    ``hits``/``misses`` count signature lookups; ``traces`` counts actual
    XLA traces (a Python side effect inside each jitted wrapper, executed
    once per trace) — the regression tests pin ``traces`` so a silently
    widened cache key can't reintroduce retrace-per-graph dispatch.
    """

    def __init__(self, maxsize: int = 128):
        self._fns: OrderedDict = OrderedDict()
        self.maxsize = maxsize
        self.hits = self.misses = self.traces = 0

    def get(self, sig: PlanSignature, build):
        fn = self._fns.get(sig)
        if fn is not None:
            self._fns.move_to_end(sig)
            self.hits += 1
            return fn
        self.misses += 1
        fn = build()
        self._fns[sig] = fn
        while len(self._fns) > self.maxsize:
            self._fns.popitem(last=False)
        return fn

    def clear(self):
        self._fns.clear()
        self.hits = self.misses = self.traces = 0


_PROGRAMS = _ProgramCache()


def program_cache_stats() -> dict:
    """Snapshot of the process-wide program cache counters."""
    return dict(hits=_PROGRAMS.hits, misses=_PROGRAMS.misses,
                traces=_PROGRAMS.traces, size=len(_PROGRAMS._fns))


def program_cache_clear() -> None:
    """Drop every cached program and zero the counters (tests/benchmarks)."""
    _PROGRAMS.clear()


def _dispatch(sig: PlanSignature, build, *args):
    """Look the program up (building it on a miss) and dispatch it; its
    first call compiles, inside the span."""
    with jax.profiler.TraceAnnotation(tracing.PROGRAM):
        return _PROGRAMS.get(sig, build)(*args)


def _count_traces(fn):
    """Increment the trace counter when (and only when) XLA traces ``fn``."""
    def wrapped(*args):
        _PROGRAMS.traces += 1
        return fn(*args)
    return wrapped


def _dims_of(arrs) -> tuple:
    return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                        for k, v in arrs.items()))


def _mesh_axes_or_sim(mesh, P: int) -> tuple:
    """Signature ``axes``: the mesh layout, or the sim executor's implied
    single vmap axis (``run_sim`` binds ``AXIS`` at size P)."""
    return ((AXIS, P),) if mesh is None else mesh_axes(mesh)


def _signature(kind: str, P: int, cfg: PipelineConfig, plan_static, arrs,
               batch: int = 0, extra=None) -> PlanSignature:
    mc = (cfg.color.max_colors if cfg.color is not None
          else cfg.recolor.max_colors)
    return PlanSignature(
        kind=kind, P=P, n_local_max=int(arrs["indptr"].shape[-1]) - 1,
        maxd=int(arrs["nbr"].shape[-1]), max_colors=mc,
        distance=cfg.recolor.distance, scheme=cfg.recolor.scheme,
        rungs=plan_static if plan_static is not None else (),
        batch=batch, cfg=cfg, dims=_dims_of(arrs),
        axes=_mesh_axes_or_sim(extra, P), extra=extra)


def resolve_pipeline_cfg(pg: PartitionedGraph,
                         cfg: PipelineConfig) -> PipelineConfig:
    """Concretize any ``scheme="auto"`` stage against ``pg``'s comm plan.

    The decision (``comm.resolve_scheme``) compares the *padded* sparse
    plan bytes — what the compiled program physically ships — against the
    broadcast's; an explicit scheme passes through untouched.
    """
    if not cfg.has_auto:
        return cfg
    return dataclasses.replace(
        cfg, color=None if cfg.color is None else resolve_cfg(pg, cfg.color),
        recolor=resolve_cfg(pg, cfg.recolor))


def plan_signature(pg: PartitionedGraph, cfg: PipelineConfig, *,
                   kind: str | None = None, batch: int = 0,
                   mesh=None) -> PlanSignature:
    """The signature a ``pipeline_sim``-family dispatch of ``pg`` would use.

    Public inspection hook (``launch/dryrun.py``, the serving cost model,
    tests): resolves "auto", builds the device dict host-side and derives
    the exact cache key without compiling anything.  ``mesh`` selects the
    ``pipeline_sharded`` program (``kind`` defaults accordingly).
    """
    if kind is None:
        kind = "pipe_sim" if mesh is None else "pipe_sharded"
    cfg = resolve_pipeline_cfg(pg, cfg)
    arrs = pg.arrays(sparse=cfg.needs_sparse_plan)
    return _signature(kind, pg.P, cfg, _plan_static(pg, cfg), arrs,
                      batch=batch, extra=mesh)


def program_cache_contains(sig: PlanSignature) -> bool:
    """Cache-probe for the serving cost model — no counter side effects."""
    return sig in _PROGRAMS._fns


def bucket_signature(bucket, cfg: PipelineConfig, *, pad_batch: bool = True,
                     mesh=None) -> PlanSignature:
    """The signature a ``color_many``(`_sharded``) dispatch of ``bucket``
    would use.

    The serving driver's cost model probes the program cache with this
    before deciding solo-vs-batch routing; nothing is stacked or compiled —
    batch padding and the sharded layout's axis swap are applied to shapes
    only.
    """
    bcfg = _resolve_bucket_cfg(bucket, cfg)
    ma = bucket.member_arrays(0, sparse=bcfg.needs_sparse_plan)
    lane_multiple = batch_axis_size(mesh) if mesh is not None else 1
    B = _lane_target(bucket.B, pad_batch, lane_multiple)

    def dim(v):
        s = (B,) + tuple(v.shape)
        return (s[1], s[0]) + s[2:] if mesh is not None else s

    dims = tuple(sorted((k, dim(v), str(np.asarray(v).dtype))
                        for k, v in ma.items()))
    ps = bucket.plan_static if bcfg.needs_sparse_plan else None
    mc = (bcfg.color.max_colors if bcfg.color is not None
          else bcfg.recolor.max_colors)
    return PlanSignature(
        kind="many_sim" if mesh is None else "many_sharded", P=bucket.P,
        n_local_max=bucket.members[0].n_local_max,
        maxd=bucket.members[0].maxd, max_colors=mc,
        distance=bcfg.recolor.distance, scheme=bcfg.recolor.scheme,
        rungs=ps if ps is not None else (), batch=B, cfg=bcfg, dims=dims,
        axes=_mesh_axes_or_sim(mesh, bucket.P), extra=mesh)


def _bucket_scheme(bucket) -> str:
    """Trace-time sparse-vs-allgather pick for one bucket (union plan)."""
    sparse_b = sum(bucket.plan_static[1]) * 4
    ag_b = allgather_bytes_per_exchange(bucket.P,
                                        bucket.members[0].max_boundary)
    return SPARSE if sparse_b <= ag_b else ALLGATHER


def _resolve_bucket_cfg(bucket, cfg: PipelineConfig) -> PipelineConfig:
    """Per-bucket "auto" resolution: members share one compiled program, so
    the decision is made once from the union plan's padded bytes."""
    if not cfg.has_auto:
        return cfg
    scheme = _bucket_scheme(bucket)
    fix = lambda c: (None if c is None else
                     dataclasses.replace(c, scheme=scheme)
                     if c.scheme == AUTO else c)
    return dataclasses.replace(cfg, color=fix(cfg.color),
                               recolor=fix(cfg.recolor))


def recolor_loop_sim(pg: PartitionedGraph, view, cfg: PipelineConfig,
                     key=None):
    """Fused recolor-only loop (sim executor): ``recolor_iterations``' core.

    Returns ``(view, history list-of-dicts, n_iters_run)``.
    """
    cfg = resolve_pipeline_cfg(pg, cfg)
    arrs = _pipeline_arrays(pg, cfg)
    if key is None:
        key = jax.random.key(cfg.seed)
    ps = _plan_static(pg, cfg)
    sig = _signature("loop_sim", pg.P, cfg, ps, arrs)

    def build(P=pg.P):
        fn = partial(recolor_loop_spmd, cfg=cfg, P_size=P, plan_static=ps)
        return jax.jit(_count_traces(
            lambda arrs, view, key: run_sim(fn, P, (arrs, view), (key,))))

    view, hist, n_run = _dispatch(sig, build, arrs, jnp.asarray(view), key)
    with jax.profiler.TraceAnnotation(tracing.WAIT):
        hist, n_run = jax.device_get((hist, n_run))  # one host transfer
    return view, _history_to_host(hist), int(np.max(n_run))


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


def pipeline_sim(pg: PartitionedGraph, order, cfg: PipelineConfig, *,
                 marked=None, color_key=None, recolor_key=None):
    """Run the fused pipeline *simulated* on one device (P vmap lanes).

    ``order``/``marked`` as ``color_graph_sim``; ``color_key`` /
    ``recolor_key`` default to ``key(cfg.color.seed)`` / ``key(cfg.seed)``.
    Returns ``(view, result)``: ``view`` is the final ``(P, n_slots)``
    device view and ``result`` holds the initial-coloring stats
    (``"color"``, keys as ``color_graph_sim``), the per-iteration
    ``"history"`` (one dict per executed iteration, keys as
    ``recolor_sim`` plus ``perm``/``iteration``) and ``"n_iters_run"``
    (adaptive stop included).  ``pipeline_sharded`` is the
    bitwise-identical ``workers``-mesh variant.
    """
    sig, build, args = _pipeline_sim_parts(pg, order, cfg, marked,
                                           color_key, recolor_key)
    return _pipeline_result(*_dispatch(sig, build, *args))


def pipeline_sim_program(pg: PartitionedGraph, order, cfg: PipelineConfig, *,
                         marked=None, color_key=None, recolor_key=None):
    """The cached jitted program ``pipeline_sim`` dispatches, with its
    arguments: ``(fn, args)``.  ``fn.lower(*args).compile()`` compiles
    exactly what ``pipeline_sim`` runs (compile timing, HLO inspection,
    compiling for a described device from the arguments' shapes)."""
    sig, build, args = _pipeline_sim_parts(pg, order, cfg, marked,
                                           color_key, recolor_key)
    return _PROGRAMS.get(sig, build), args


def _pipeline_sim_parts(pg, order, cfg, marked, color_key, recolor_key):
    """``(signature, build, args)`` of ``pipeline_sim``'s program."""
    assert cfg.color is not None, "pipeline_sim needs cfg.color"
    cfg = resolve_pipeline_cfg(pg, cfg)
    arrs = _pipeline_arrays(pg, cfg)
    order = _apply_partial(order, cfg.color, marked)
    ck, rk = _keys(cfg, color_key, recolor_key)
    ps = _plan_static(pg, cfg)
    sig = _signature("pipe_sim", pg.P, cfg, ps, arrs)

    def build(P=pg.P):
        fn = partial(color_then_recolor, cfg=cfg, P_size=P, plan_static=ps)
        return jax.jit(_count_traces(
            lambda arrs, order, ck, rk: run_sim(fn, P, (arrs, order),
                                                (ck, rk))))

    return sig, build, (arrs, jnp.asarray(order), ck, rk)


def pipeline_sharded(pg: PartitionedGraph, order, cfg: PipelineConfig, mesh,
                     *, marked=None, color_key=None, recolor_key=None):
    """Run the fused pipeline on a real mesh shard axis
    (``shard_axis_of(mesh)``) via shard_map; on a 2D ``batch × shard``
    mesh the solo graph is replicated over the batch axis."""
    assert cfg.color is not None, "pipeline_sharded needs cfg.color"
    cfg = resolve_pipeline_cfg(pg, cfg)
    arrs = _pipeline_arrays(pg, cfg)
    order = _apply_partial(order, cfg.color, marked)
    ck, rk = _keys(cfg, color_key, recolor_key)
    ps = _plan_static(pg, cfg)
    sig = _signature("pipe_sharded", pg.P, cfg, ps, arrs, extra=mesh)

    def build(P=pg.P):
        axis = shard_axis_of(mesh)
        fn = partial(color_then_recolor, cfg=cfg, P_size=P, plan_static=ps,
                     axis=axis)
        return jax.jit(_count_traces(
            lambda a, o, k1, k2: run_sharded(fn, mesh, (a, o), (k1, k2),
                                             axis=axis)))

    out = _dispatch(sig, build, arrs, jnp.asarray(order), ck, rk)
    return _pipeline_result(*out)


# ------------------------------------------- batched multi-graph pipeline --

def _many_sim_program(sig, P, cfg, plan_static):
    """One jitted program per signature: vmap over graphs of vmap over
    shards — reused across batches (and graphs) through ``_PROGRAMS``."""
    def build():
        fn = partial(color_then_recolor, cfg=cfg, P_size=P,
                     plan_static=plan_static)
        inner = lambda arrs, order, ck, rk: run_sim(fn, P, (arrs, order),
                                                    (ck, rk))
        return jax.jit(_count_traces(jax.vmap(inner)))
    return _PROGRAMS.get(sig, build)


def _many_sharded_program(sig, P, cfg, plan_static, mesh):
    """Cached mesh dispatch — without it every flush would rebuild the
    vmap/jit wrappers and recompile, defeating the pow2 shape bucketing
    the serving path relies on.

    On a 2D ``batch × shard`` mesh the graph lanes are *sharded* over the
    batch axis (``run_sharded_many``): each device vmaps only its B/Bm
    lanes, and the per-graph RNG keys ride as batch-sharded lane args.  On
    a 1D mesh this degenerates to the classic vmap-inside-shard_map."""
    def build():
        axis = shard_axis_of(mesh)
        baxis = batch_axis_of(mesh)
        lane_axes = (baxis,) if baxis is not None else ()
        fn = jax.vmap(partial(color_then_recolor, cfg=cfg, P_size=P,
                              plan_static=plan_static, axis=axis,
                              lane_axes=lane_axes))
        return jax.jit(_count_traces(
            lambda a, o, k1, k2: run_sharded_many(fn, mesh, (a, o),
                                                  (k1, k2), axis=axis)))
    return _PROGRAMS.get(sig, build)


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
    sig = _signature("engine_init", P, cfg, plan_static, arrs, extra=mesh)

    def build():
        if mesh is None:
            fn = partial(pipeline_carry_spmd, cfg=cfg, P_size=P,
                         plan_static=plan_static)
            return jax.jit(_count_traces(
                lambda a, o, ck: run_sim(fn, P, (a, o), (ck,))))
        axis = shard_axis_of(mesh)
        fn = partial(pipeline_carry_spmd, cfg=cfg, P_size=P,
                     plan_static=plan_static, axis=axis)
        return jax.jit(_count_traces(
            lambda a, o, ck: run_sharded(fn, mesh, (a, o), (ck,),
                                         axis=axis)))

    return _PROGRAMS.get(sig, build)


def engine_step_program(P: int, cfg: PipelineConfig, plan_static, arrs,
                        B: int, chunk: int, mesh=None):
    """Cached all-lanes step program for the serving engine.

    ``(arrs, carry, keys) -> (carry, done)`` — every lane advances by
    ``chunk`` fused recoloring iterations (``pipeline_step_spmd`` vmapped
    over the B lane axis), with the carry input buffers **donated**: the
    engine owns exactly one generation of lane state at a time.  Sim
    layout stacks lanes on axis 0 (``(B, P, ...)``, ``done (B, P)``); on a
    mesh the lanes ride ``run_sharded_many``'s ``(P, B, ...)`` layout
    (``done (P, B)``) and are sharded over the batch mesh axis.  Lanes
    whose stop has tripped (or that are empty) are frozen by the body's
    select-mask, so a partially idle engine steps bitwise-inertly.
    """
    assert not cfg.has_auto
    sig = _signature(f"engine_step{chunk}", P, cfg, plan_static, arrs,
                     batch=B, extra=mesh)

    def build():
        if mesh is None:
            fn = partial(pipeline_step_spmd, cfg=cfg, chunk=chunk, P_size=P,
                         plan_static=plan_static)
            inner = lambda a, c, k: run_sim(fn, P, (a, c), (k,))
            return jax.jit(_count_traces(jax.vmap(inner)),
                           donate_argnums=(1,))
        axis = shard_axis_of(mesh)
        baxis = batch_axis_of(mesh)
        lane_axes = (baxis,) if baxis is not None else ()
        fn = jax.vmap(partial(pipeline_step_spmd, cfg=cfg, chunk=chunk,
                              P_size=P, plan_static=plan_static, axis=axis,
                              lane_axes=lane_axes))
        return jax.jit(_count_traces(
            lambda a, c, k: run_sharded_many(fn, mesh, (a, c), (k,),
                                             axis=axis)),
            donate_argnums=(1,))

    return _PROGRAMS.get(sig, build)


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
                     extra=mesh)
    lane_axis = 0 if mesh is None else 1

    def build():
        def put(bufs, vals, b):
            return jax.tree.map(
                lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                    buf, v, b, axis=lane_axis), bufs, vals)
        return jax.jit(_count_traces(put), donate_argnums=(0,))

    return _PROGRAMS.get(sig, build)


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
    """Per-bucket dispatch inputs, shared by the sim and sharded drivers."""
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


def color_many(pgs, cfg: PipelineConfig, *, orders=None, marked=None,
               color_keys=None, recolor_keys=None, buckets=None,
               pad_batch: bool = False):
    """Color a batch of partitioned graphs through one fused program each
    bucket (sim executor) — the batched service's dispatch core.

    ``pgs`` — same-``P`` ``PartitionedGraph`` list (``halo`` per
    ``cfg``'s distance).  ``orders`` — an ``ordering`` kind string (default
    ``internal_first``) or per-graph ``(P, n_local_max)`` arrays.
    ``marked`` — per-graph partial-coloring masks (``cfg.color.partial``).
    ``color_keys``/``recolor_keys`` — per-graph JAX keys; the default folds
    each graph's input position into the config seeds.  ``buckets`` — a
    precomputed ``bucket_graphs(pgs)`` result (a server that already
    bucketed its queue passes it to skip the host-side re-pad).
    ``pad_batch=True`` rounds every bucket's batch axis up to a power of
    two with dropped dummy lanes, so batch-program shapes stay stable as
    queue depth fluctuates (jit-cache friendly serving).

    Returns one dict per input graph (input order): ``view`` ``(P,
    n_slots)`` padded device view, ``colors`` ``(n_global,)`` 1-based,
    ``color`` initial-coloring stats, ``history``/``n_iters_run`` as
    ``pipeline_sim``, and the ``bucket`` index.  Each graph's view and
    history are bitwise a solo ``pipeline_sim`` run on its padded member
    (``bucket.members[j]``) with the same keys.
    """
    assert cfg.color is not None, "color_many needs cfg.color"
    pgs = list(pgs)
    if buckets is None:
        buckets = bucket_graphs(pgs)
    cks, rks = _keys_many(cfg, len(pgs), color_keys, recolor_keys)
    results = [None] * len(pgs)
    for bi, bucket in enumerate(buckets):
        bcfg = _resolve_bucket_cfg(bucket, cfg)
        st, order_b, cks_b, rks_b, ps = _bucket_inputs(
            bucket, bcfg, orders, marked, cks, rks, pad_batch)
        sig = _signature("many_sim", bucket.P, bcfg, ps, st,
                         batch=len(cks_b))
        out = _many_sim_program(sig, bucket.P, bcfg, ps)(
            {k: jnp.asarray(v) for k, v in st.items()},
            jnp.asarray(order_b), jnp.stack(cks_b), jnp.stack(rks_b))
        _unpack_bucket(out, bucket, bi, pgs, results)
    return results


def color_many_sharded(pgs, cfg: PipelineConfig, mesh, *, orders=None,
                       marked=None, color_keys=None, recolor_keys=None,
                       buckets=None, pad_batch: bool = False):
    """``color_many`` on a real mesh: collectives run over the mesh's
    shard axis (``shard_axis_of``).  On a 1D mesh the graph batch axis
    rides *inside* each shard (vmap under shard_map); on a 2D ``batch ×
    shard`` mesh (``make_coloring_mesh(P, batch=Bm)``) the lanes are
    additionally sharded over the batch axis — each device vmaps B/Bm
    lanes, and lane counts are padded to a multiple of Bm.  Either way
    every per-graph result is bitwise the sim executor's."""
    assert cfg.color is not None, "color_many_sharded needs cfg.color"
    pgs = list(pgs)
    if buckets is None:
        buckets = bucket_graphs(pgs)
    cks, rks = _keys_many(cfg, len(pgs), color_keys, recolor_keys)
    results = [None] * len(pgs)
    for bi, bucket in enumerate(buckets):
        bcfg = _resolve_bucket_cfg(bucket, cfg)
        st, order_b, cks_b, rks_b, ps = _bucket_inputs(
            bucket, bcfg, orders, marked, cks, rks, pad_batch,
            lane_multiple=batch_axis_size(mesh))
        # leading axis P for shard_map; per-shard arrays carry (B, ...)
        arrs = {k: jnp.moveaxis(jnp.asarray(v), 0, 1) for k, v in st.items()}
        order_b = jnp.moveaxis(jnp.asarray(order_b), 0, 1)
        sig = _signature("many_sharded", bucket.P, bcfg, ps, arrs,
                         batch=len(cks_b), extra=mesh)
        out = _many_sharded_program(sig, bucket.P, bcfg, ps, mesh)(
            arrs, order_b, jnp.stack(cks_b), jnp.stack(rks_b))
        # outputs carry (P, B, ...): put the graph axis back in front
        out = jax.tree.map(lambda x: np.moveaxis(np.asarray(x), 0, 1), out)
        _unpack_bucket(out, bucket, bi, pgs, results)
    return results
