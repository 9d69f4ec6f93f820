"""Distributed iterative recoloring (paper §3) — the core contribution.

Synchronous recoloring (RC): given a valid K-coloring, recolor in K steps.
Step ``t`` first-fit-colors the whole color class ``perm(t)`` — an independent
set, so the step is *fully data-parallel* (vectorized over the class on TPU,
no intra-step ordering) and the procedure is conflict-free by construction;
distributed RC equals sequential RC for the same seed coloring (§3, tested).

The step loop is *work-efficient* (DESIGN.md §4): vertices are sorted by
class step once, and each step processes only its own class as fixed-size
chunks of the sorted order — an ELL-row gather of neighbour colors followed
by bitset first-fit through ``kernels.ops.select_colors`` (Pallas on TPU,
the same math vectorized under XLA elsewhere).  Total selection work per
iteration is O(V · maxd / 32) words instead of the K · O(V · max_colors)
bytes a per-step dense occupancy would scatter.  Chunk counts per class are
pmax-reduced, so every shard runs the same loop trip count and the collective
schedule stays uniform (a shard_map requirement).

Chunks are *degree-sorted and width-bucketed*: within its class a vertex
sorts by ELL row length (``degree``, widest first), and each chunk gathers
its rows only as wide as its widest row on any shard, rounded up to one of
the static widths of ``gather_widths(maxd)`` (powers of two from 8, then
``maxd``; defined in ``speculative.py``, whose tiles and detection chunks
use the same widths) and dispatched by ``lax.switch``.  On skewed graphs most rows are
far shorter than the hub that sets ``maxd``, so most chunks gather a small
fraction of the padded tile; entries past a row's degree are sentinels, so
the colors are bitwise those of full-width chunks.  The width is pmax-ed
over shards and ``lane_uniform``-ed over graph lanes, so the switch stays
one branch taken under every vmap; ``gather_slots`` in the stats counts the
slots gathered (rows × width over chunks and shards).  Distance 2 keeps the
one full width, and ``gather2_slots`` counts what its chunks read through
the two-hop ELL (rows × ``maxd2`` over chunks and shards; 0 at distance 1).

Color-class permutations (§3): RV (reverse), NI (non-increasing class size),
ND (non-decreasing — the paper's best), RAND (Knuth shuffle), and the hybrid
schedules ND-RAND%x / ND-RAND%2^i (``schedule_for_iteration``).

Piggybacking (§3.1) becomes *exchange-step coalescing* on TPU: a ghost color
assigned at step s is only needed by a local reader at step t>s, so the
boundary exchange after step s can be deferred to step t-1; everything
pending rides that one collective ("piggybacks"). The pre-communication of
the paper — "who receives at which step" — is the OR-reduce (pmax) of each
shard's needed-step bitmap. `needed[K]` is the end-of-iteration exchange that
carries all remaining deferred colors.  Under the sparse scheme
(`RecolorConfig.scheme`, DESIGN.md §2) the bitmap is additionally refined
*per link*: each dependency marks only the ppermute round of its writer's
ring shift, so an exchange event ships just the rounds some destination
still needs.

Asynchronous recoloring (aRC, §3): each shard *locally* orders vertices by
color class and reruns the speculative framework (conflicts possible).

Multi-iteration runs live in ``pipeline.py`` (DESIGN.md §7): the fused
``color_then_recolor`` keeps seed coloring + K iterations device-resident in
one ``lax.while_loop``; ``pipeline.recolor_iterations`` is a thin wrapper
over its recolor-only loop, with the host loop of ``recolor_step`` calls
kept behind ``fused=False`` as the bitwise reference.

Distance-2 mode (``RecolorConfig(distance=2)``, DESIGN.md §5): a class of a
valid D2 coloring is a distance-2 independent set, so the step stays
conflict-free; selection ORs the two-hop bitset and the piggyback schedule
gains the two-hop ELL rows as a second dependency source
(``_cross_deps_ell``) — a D2 reader consumes its two-hop ghosts' colors too.
Partial seed colorings need no flag here: uncolored vertices are class 0,
which every permutation ranks 0 and the step loop skips unconditionally.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import partial

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels import ops

from .comm import (AUTO, AXIS, DEFAULT_SCHEME, SCHEME_CHOICES, SPARSE,
                   AxisComm, CommConfig, make_exchange, resolve_cfg,
                   shard_uniform, stats_to_host)
from .graph import PartitionedGraph
from .programs import device_arrays, dispatch, signature
from .speculative import (ColorConfig, color_spmd, gather_widths,
                          validate_color_bounds)

RV = "rv"
NI = "ni"
ND = "nd"
RAND = "rand"
ALL_PERMS = (RV, NI, ND, RAND)
# Integer ids for the fused pipeline's traced permutation schedule
# (``pipeline.py`` resolves the per-iteration kind with ``lax.switch``).
PERM_IDS = {kind: i for i, kind in enumerate(ALL_PERMS)}

# Driver-level call counter: manual back-to-back ``recolor_step`` calls that
# fall back to the config seed must not replay the identical RAND
# permutation.  Callers that need reproducible keys pass ``key=`` explicitly.
_DEFAULT_KEY_CALLS = itertools.count()


def _default_key(seed: int):
    return jax.random.fold_in(jax.random.key(seed), next(_DEFAULT_KEY_CALLS))


@dataclasses.dataclass(frozen=True)
class RecolorConfig:
    """Static configuration of one recoloring iteration.

    Units: ``max_colors`` bounds the *seed* coloring's ids (32-aligned);
    ``chunk`` is vertices selected per ELL tile (clamped to the shard's
    row count at trace time).  Drivers: ``recolor_step`` runs one
    iteration (one device or a mesh, bitwise identical); ``arc_sim`` is
    the asynchronous variant; ``pipeline.recolor_iterations`` /
    ``pipeline.PipelineConfig`` run schedules of iterations
    device-resident.
    """

    max_colors: int = 1024         # bound on colors of the SEED coloring
    piggyback: bool = True         # paper §3.1 (False = exchange every step)
    scheme: str = DEFAULT_SCHEME   # boundary exchange: "sparse" | "allgather"
                                   # | "auto" (pick by modeled bytes at trace
                                   # time; default follows $REPRO_SCHEME)
    wire16: bool = False           # int16 boundary payloads (half ICI bytes)
    chunk: int = 256               # vertices selected per chunk (ELL tile rows)
    backend: str = "auto"          # kernels.ops backend: auto | xla | pallas
    distance: int = 1              # 1 = proper; 2 = distance-2 recoloring
                                   # (needs a halo=2 PartitionedGraph and a
                                   # valid D2 seed coloring — classes must be
                                   # distance-2 independent sets)
    seed: int = 0

    def __post_init__(self):
        validate_color_bounds(self.max_colors, self.wire16, self.backend)
        assert self.scheme in SCHEME_CHOICES, f"bad scheme {self.scheme!r}"
        assert self.chunk > 0
        assert self.distance in (1, 2), f"bad distance {self.distance}"

    @property
    def n_words(self) -> int:
        return self.max_colors // 32

    @property
    def comm_config(self) -> CommConfig:
        return CommConfig(scheme=self.scheme, wire16=self.wire16)


def class_sizes(view, n_local, n_local_max, max_colors, comm: AxisComm):
    """Global color-class sizes (max_colors,) — the NI/ND pre-communication.

    Returns ``(sizes, n_out_of_range)``.  Colors outside ``[0, max_colors)``
    are masked out of the scatter-add (JAX's default clip mode would silently
    inflate the ``max_colors - 1`` class instead) and surfaced in the global
    ``n_out_of_range`` count so a poisoned view is visible in the stats.
    """
    valid = jnp.arange(n_local_max) < n_local
    raw = view[:n_local_max]
    in_range = (raw >= 0) & (raw < max_colors)
    oor = comm.psum(jnp.sum(valid & ~in_range, dtype=jnp.int32))
    counted = valid & in_range
    idx = jnp.where(counted, raw, 0)
    local = jnp.zeros((max_colors,), jnp.int32).at[idx].add(
        counted.astype(jnp.int32))
    local = local.at[0].set(0)
    return comm.psum(local), oor


def permutation_rank(sizes, kind: str, key) -> jnp.ndarray:
    """rank[c] = recoloring step (1-based) of color class c; 0 for class 0.

    Empty classes sort to the back (their steps are no-ops past K).
    """
    mc = sizes.shape[0]
    colors = jnp.arange(mc, dtype=jnp.int32)
    present = (sizes > 0) & (colors > 0)
    big = jnp.iinfo(jnp.int32).max
    if kind == RV:
        key_v = jnp.where(present, -colors, big)
    elif kind == NI:
        key_v = jnp.where(present, -sizes, big)
    elif kind == ND:
        key_v = jnp.where(present, sizes, big)
    elif kind == RAND:
        r = jax.random.permutation(key, mc).astype(jnp.int32)
        key_v = jnp.where(present, r, big)
    else:
        raise ValueError(f"unknown permutation {kind!r}")
    # lexsort: primary = key_v, tie-break = color id (stable, overflow-free)
    order = jnp.lexsort((colors, key_v))             # colors by visit step
    rank = jnp.zeros((mc,), jnp.int32).at[order].set(
        jnp.arange(1, mc + 1, dtype=jnp.int32))
    return jnp.where(present, rank, 0).astype(jnp.int32)


def permutation_rank_traced(sizes, kind_id, key) -> jnp.ndarray:
    """``permutation_rank`` with the kind resolved as a traced branch.

    ``kind_id`` indexes ``ALL_PERMS`` (see ``PERM_IDS``); each branch is the
    static function above, so a branch is bitwise-identical to the same call
    with a static kind — the fused pipeline's schedule can live in one jitted
    program without re-tracing per permutation kind.
    """
    branches = [lambda s, ky, k=k: permutation_rank(s, k, ky)
                for k in ALL_PERMS]
    return jax.lax.switch(kind_id, branches, sizes, key)


def _cross_deps(step_of, arrs, n_local_max):
    """Per cross edge: (dep mask, reader step s_v, ghost index of the writer).

    A dependency exists where the local reader (step ``s_v``) reads a ghost
    whose writer recolors at an earlier step ``s_u``; an exchange of that
    pair must then happen in ``[s_u, s_v-1]`` — the just-in-time choice is
    ``s_v - 1``, letting every pending color piggyback.
    """
    src, dst = arrs["edge_src"], arrs["indices"]
    step_rows = jnp.concatenate(
        [step_of[:n_local_max], jnp.zeros((1,), step_of.dtype)])
    s_v = step_rows[src]
    s_u = step_of[dst]
    is_ghost = (dst >= n_local_max) & (dst < step_of.shape[0] - 1)
    dep = is_ghost & (s_u > 0) & (s_v > s_u)
    return dep, s_v, jnp.maximum(dst - n_local_max, 0)


def _cross_deps_ell(step_of, nbr2, n_local_max):
    """Cross deps over the flattened two-hop ELL rows (distance=2 readers).

    A D2 reader also consumes its two-hop ghosts' colors, so those pairs
    constrain the piggyback schedule exactly like the CSR cross edges; padded
    entries point at the sentinel (step 0) and never form a dependency.
    """
    dst = nbr2.reshape(-1)
    s_v = jnp.repeat(step_of[:n_local_max], nbr2.shape[1])
    s_u = step_of[dst]
    is_ghost = (dst >= n_local_max) & (dst < step_of.shape[0] - 1)
    dep = is_ghost & (s_u > 0) & (s_v > s_u)
    return dep, s_v, jnp.maximum(dst - n_local_max, 0)


def _dep_sources(step_of, arrs, n_local_max, distance):
    """All (dep, s_v, ghost index) contributions the piggyback schedule sees."""
    deps = [_cross_deps(step_of, arrs, n_local_max)]
    if distance == 2:
        deps.append(_cross_deps_ell(step_of, arrs["nbr2"], n_local_max))
    return deps


def _needed_exchanges(step_of, arrs, n_local_max: int, K, max_colors: int,
                      comm: AxisComm, piggyback: bool, distance: int = 1):
    """The piggybacking schedule: needed[t] = exchange event after step t.

    Entry K is the end-of-iteration exchange (always on).
    """
    # contract: K is the class count, psum-derived by every caller
    # (class_sizes), so the exchange schedule is shard-agreed
    K = shard_uniform(K)
    if piggyback:
        needed = jnp.zeros((max_colors + 1,), bool)
        for dep, s_v, _ in _dep_sources(step_of, arrs, n_local_max, distance):
            idx = jnp.where(dep, s_v - 1, 0)
            needed = needed.at[idx].max(dep)
        needed = needed.at[0].set(False)
        needed = comm.pmax(needed)                   # pre-communication
    else:
        needed = jnp.arange(max_colors + 1) <= K     # exchange every step
    needed = needed.at[max_colors].set(True)
    return needed


def _needed_exchange_rounds(step_of, arrs, n_local_max: int, K,
                            max_colors: int, comm: AxisComm, piggyback: bool,
                            P_size: int, n_rounds: int, distance: int = 1):
    """Sparse piggybacking: needed[t, r] = ``ppermute`` round r after step t.

    The paper's pre-communication ("who receives at which step") refined per
    *link*: each dependency marks only the ring shift of its writer's owner,
    so an exchange event ships only the rounds some destination still needs.
    At ``distance=2`` the two-hop ELL rows contribute dependencies too.  Row
    ``max_colors`` (end of iteration) runs every round — it leaves all
    ghosts fresh for the next iteration.
    """
    K = shard_uniform(K)             # same contract as _needed_exchanges
    if piggyback:
        needed = jnp.zeros((max_colors + 1, max(n_rounds, 1)), bool)
        for dep, s_v, gi in _dep_sources(step_of, arrs, n_local_max, distance):
            shift = (comm.index() - arrs["ghost_owner"][gi]) % P_size
            rnd = arrs["shift_to_round"][shift]      # >= 0 wherever dep holds
            idx = jnp.where(dep, s_v - 1, 0)
            rdx = jnp.where(dep, rnd, 0)
            needed = needed.at[idx, rdx].max(dep)
        needed = needed[:, :n_rounds]
        needed = needed.at[0].set(False)
        needed = comm.pmax(needed)                   # pre-communication
    else:
        needed = jnp.broadcast_to(
            (jnp.arange(max_colors + 1) <= K)[:, None],
            (max_colors + 1, n_rounds))
    needed = needed.at[max_colors].set(True)
    return needed


def recolor_pass_spmd(arrs, view, rank, n_classes, cfg: RecolorConfig,
                      P_size: int | None = None, plan_static=None,
                      axis: str = AXIS, lane_axes: tuple = ()):
    """One synchronous recoloring iteration given a precomputed class rank.

    The shared core of ``recolor_spmd`` (static permutation kind) and the
    fused ``pipeline.color_then_recolor`` loop (kind resolved as a traced
    branch): everything from the step map through the chunked hot loop.

    Hot loop: vertices are sorted by class step (then by degree, widest
    first); each class is consumed as <= ceil(pmax(class size)/chunk)
    fixed-size chunks.  A chunk gathers its ELL neighbour rows at the
    chunk's width (``gather_widths``), gathers their current colors, and
    first-fit-colors the whole chunk at once through
    ``kernels.ops.select_colors`` — no dense occupancy, no scatter over the
    edge list.  Order within a class is irrelevant (a class is an
    independent set), and the chunk schedule is identical on every shard,
    so collectives stay uniform.

    Exchanges route through ``comm.make_exchange``; under the sparse scheme
    the piggyback schedule additionally masks *which ppermute rounds* each
    exchange event ships (``_needed_exchange_rounds``) — a link with nothing
    pending costs nothing.  ``P_size``/``plan_static`` are required for the
    sparse scheme (the drivers thread them automatically).

    ``lane_axes`` (2D ``batch × shard`` meshes, DESIGN.md §10): graph lanes
    on different batch rows have different class counts and piggyback
    schedules, so the chunk trip count and every exchange gate widen to the
    lane-uniform union (``AxisComm.lane_uniform``) — every device executes
    the same collective sequence — while each lane applies ghost refreshes
    and byte accounting under its *own* schedule, keeping per-lane results
    bitwise the solo run's.
    """
    comm = AxisComm(axis, lane_axes)
    # contract: callers derive n_classes from psum-reduced class sizes, so
    # the per-class chunk schedule (and with it every exchange event) is
    # identical on all shards
    n_classes = shard_uniform(n_classes)
    n_local_max = arrs["indptr"].shape[0] - 1
    n_slots = arrs["prio"].shape[0]
    n_local = arrs["n_local"]
    nbr = arrs["nbr"]
    mc = cfg.max_colors
    # chunk size is bitwise-invariant (within-class chunks never interact:
    # a class is an independent set, so no chunk reads another's writes),
    # so clamp it to the row count — a chunk wider than the shard's vertex
    # range would gather pure padding every class step, which dominates the
    # runtime of small graphs (and of every lane of the batched pipeline).
    chunk = min(cfg.chunk, n_local_max)
    if cfg.scheme == AUTO:
        raise ValueError("scheme='auto' must be resolved by a driver "
                         "(comm.resolve_cfg) before the SPMD fn")
    sparse = cfg.scheme == SPARSE
    if sparse and (P_size is None or plan_static is None):
        raise ValueError("sparse scheme needs P_size and plan_static "
                         "(see PartitionedGraph.comm_plan)")
    if cfg.distance == 2 and "nbr2" not in arrs:
        raise ValueError("distance=2 needs the two-hop halo: partition with "
                         "partition_graph(g, P, halo=2)")

    step_of = rank[view]                              # (n_slots,) step per slot
    step_of = step_of.at[n_slots - 1].set(0)          # sentinel

    if sparse:
        n_rounds = len(plan_static[0])
        needed_rounds = _needed_exchange_rounds(
            step_of, arrs, n_local_max, n_classes, mc, comm, cfg.piggyback,
            P_size, n_rounds, cfg.distance)
        # event bitmap = any round pending (one dep scan + pmax, not two);
        # entry mc stays on so event counting matches the broadcast scheme
        needed = needed_rounds.any(axis=1).at[mc].set(True)
    else:
        needed = _needed_exchanges(step_of, arrs, n_local_max, n_classes, mc,
                                   comm, cfg.piggyback, cfg.distance)

    exchange = make_exchange(arrs, n_local_max, P_size, comm,
                             cfg.comm_config, plan_static)

    valid_local = jnp.arange(n_local_max) < n_local
    step_loc = step_of[:n_local_max]
    maxd = nbr.shape[1]
    # distance 2 gathers the two-hop rows too, so it keeps the full width
    widths = gather_widths(maxd) if cfg.distance == 1 else (maxd,)
    width_arr = jnp.asarray(widths, jnp.int32)
    degree = arrs["degree"]                  # ELL row length (real entries)

    # Step-sorted visit order + per-class chunk schedule.  rank values of
    # present classes are contiguous 1..n_classes, so classes t=1..n_classes
    # each get >= 1 chunk (pmax over shards keeps the trip count uniform).
    # With several widths, rows sort by degree within their class, so each
    # chunk holds rows of similar length and gathers only as wide as its
    # widest row (the order within a class changes no color).
    sort_key = jnp.where(valid_local, step_loc, jnp.int32(mc + 1))
    if len(widths) > 1:
        sorted_rows = jnp.lexsort((-degree, sort_key)).astype(jnp.int32)
    else:
        sorted_rows = jnp.argsort(sort_key).astype(jnp.int32)
    sorted_pad = jnp.concatenate([sorted_rows, jnp.zeros((chunk,), jnp.int32)])
    local_sizes = jnp.zeros((mc + 2,), jnp.int32).at[sort_key].add(1)[:mc + 1]
    start_local = jnp.cumsum(local_sizes) - local_sizes   # exclusive cumsum
    max_sizes = comm.pmax(local_sizes)
    chunks_per_class = (max_sizes + chunk - 1) // chunk
    t_arange = jnp.arange(mc + 1)
    chunks_per_class = jnp.where(
        (t_arange >= 1) & (t_arange <= n_classes),
        jnp.maximum(chunks_per_class, 1), 0)
    cum = jnp.cumsum(chunks_per_class)     # cum[t] = chunks through class t

    def recolor_at(w):
        """Gather the chunk's neighbour colors ``w`` slots wide, first-fit
        them and write the chunk's colors into the view.

        As a ``switch`` branch, the view goes in and comes out, so the
        compiler keeps it in on-chip memory; read only, it is left in HBM
        and the gather slows by half (v5e).  ``cols`` are the chunk's
        whole ELL rows, cut here: a gather of part of each row
        (``nbr[rows, :w]``) lowers to a loop over the rows, and the ELL
        table as a branch operand would be copied."""
        def branch(new_view, cols, rows, active):
            with jax.named_scope(tracing.NBR_GATHER):
                nbr_colors = new_view[cols[:, :w]]       # (chunk, w)
                if cfg.distance == 2:
                    nbr2_colors = new_view[arrs["nbr2"][rows]]
            if cfg.distance == 2:
                colors = ops.select_colors_d2(
                    nbr_colors, nbr2_colors, active, max_colors=mc,
                    selection=ops.FIRST_FIT, backend=cfg.backend)
            else:
                colors = ops.select_colors(nbr_colors, active, max_colors=mc,
                                           selection=ops.FIRST_FIT,
                                           backend=cfg.backend)
            idx = jnp.where(active, rows, n_slots - 1)   # park writes on the
            val = jnp.where(active, colors, 0)           # sentinel (stays 0)
            return new_view.at[idx].set(val.astype(new_view.dtype))
        return branch

    branches = [recolor_at(w) for w in widths]

    def chunk_body(ci, carry):
        new_view, n_ex, n_bytes, n_gathered = carry
        t = jnp.searchsorted(cum, ci, side="right").astype(jnp.int32)
        j = ci - (cum[t] - chunks_per_class[t])          # chunk # within class
        pos = start_local[t] + j * chunk
        active = jnp.arange(chunk, dtype=jnp.int32) < local_sizes[t] - j * chunk
        rows = jax.lax.dynamic_slice(sorted_pad, (pos,), (chunk,))
        rows = jnp.where(active, rows, 0)
        with jax.named_scope(tracing.NBR_GATHER):
            cols = nbr[rows]                             # (chunk, maxd)
        # the chunk's width: its widest active row on any shard, rounded up
        # to a static width; the switch index is shard- and lane-uniform,
        # so it stays one branch taken (a vmap over an unnamed axis would
        # run every branch instead)
        if len(widths) > 1:
            wid = comm.pmax(jnp.max(jnp.where(active, degree[rows], 0)))
            b_own = jnp.sum(width_arr < wid, dtype=jnp.int32)  # first >= wid
            new_view = jax.lax.switch(comm.lane_uniform(b_own), branches,
                                      new_view, cols, rows, active)
        else:
            b_own = jnp.int32(0)
            new_view = branches[0](new_view, cols, rows, active)
        # this lane's own slots (as in its solo run): chunks past its
        # schedule, run only for a batch-row peer, are not counted
        n_gathered = n_gathered + jnp.where(ci < cum[mc],
                                            width_arr[b_own] * chunk, 0)
        is_last = (ci + 1) == cum[t]
        is_end = t == n_classes
        do_ex = is_last & (needed[jnp.minimum(t, mc)] | is_end)
        # execute under the lane-uniform gate, apply under the lane's own:
        # a batch-row peer's exchange event must run here too (same
        # ppermute sequence mesh-wide), but this lane's ghosts only
        # refresh on its own schedule — early refreshes would de-stale
        # ghost colors the solo run still reads old
        go_ex = comm.lane_uniform(do_ex)
        if sparse:
            mask = (needed_rounds[jnp.minimum(t, mc)] | is_end) & do_ex
            ex = lambda v: exchange(v, round_mask=comm.lane_uniform(mask),
                                    apply_mask=mask)
        else:
            ex = exchange
        ex_view, b = jax.lax.cond(go_ex, ex,
                                  lambda v: (v, jnp.int32(0)), new_view)
        new_view = jnp.where(do_ex, ex_view, new_view)
        return (new_view, n_ex + do_ex.astype(jnp.int32),
                n_bytes + jnp.where(do_ex, b, 0), n_gathered)

    new_view0 = jnp.zeros((n_slots,), jnp.int32)
    # mesh-wide trip count: chunks past this lane's cum[mc] visit no active
    # rows (and never gate an exchange), so they are exact no-ops
    new_view, n_ex, n_bytes, n_gathered = jax.lax.fori_loop(
        0, comm.lane_uniform(cum[mc]), chunk_body,
        (new_view0, jnp.int32(0), jnp.int32(0), jnp.int32(0)))

    local_max = jnp.max(jnp.where(valid_local, new_view[:n_local_max], 0))
    # every chunk of this lane's schedule reads whole two-hop rows
    maxd2 = arrs["nbr2"].shape[1] if cfg.distance == 2 else 0
    stats = dict(
        n_colors=comm.pmax(local_max),
        n_colors_before=n_classes,
        n_exchanges=n_ex,
        gather_slots=n_gathered * comm.size(),  # the same on every shard
        gather2_slots=cum[mc] * (chunk * maxd2 * comm.size()),
        n_steps=n_classes,
        wire_bytes=n_bytes,
    )
    return new_view, stats


@tracing.scope(tracing.RECOLOR)
def recolor_spmd(arrs, view, key, perm_kind: str, cfg: RecolorConfig,
                 P_size: int | None = None, plan_static=None,
                 axis: str = AXIS, lane_axes: tuple = ()):
    """One synchronous recoloring iteration (per-shard SPMD).

    `view` is a valid coloring (n_slots,) with fresh ghosts. Returns the new
    view plus stats (colors, executed/possible exchanges, wire bytes); the
    step loop itself lives in ``recolor_pass_spmd``.  The fused pipeline
    threads the post-iteration ``class_sizes`` into the next iteration
    instead of recomputing it (bitwise the same array) — here the stand-alone
    call computes both ends itself.
    """
    comm = AxisComm(axis, lane_axes)
    n_local_max = arrs["indptr"].shape[0] - 1
    sizes, n_oor = class_sizes(view, arrs["n_local"], n_local_max,
                               cfg.max_colors, comm)
    n_classes = jnp.sum(sizes > 0).astype(jnp.int32)
    rank = permutation_rank(sizes, perm_kind, key)
    new_view, stats = recolor_pass_spmd(arrs, view, rank, n_classes, cfg,
                                        P_size=P_size, plan_static=plan_static,
                                        axis=axis, lane_axes=lane_axes)
    sizes_after, _ = class_sizes(new_view, arrs["n_local"], n_local_max,
                                 cfg.max_colors, comm)
    # distinct classes actually in use — the paper's quality metric (the max
    # id in ``n_colors`` can overstate it once recoloring empties classes);
    # also the fused pipeline's adaptive-stop signal
    stats["n_colors_distinct"] = jnp.sum(sizes_after > 0).astype(jnp.int32)
    stats["n_out_of_range"] = n_oor
    return new_view, stats


def arc_order_spmd(view, n_local, n_local_max, rank):
    """aRC visit order: local slots sorted by (class step, slot) — per shard."""
    step_loc = rank[view[:n_local_max]]
    valid = jnp.arange(n_local_max) < n_local
    big = jnp.iinfo(jnp.int32).max
    key_v = jnp.where(valid, step_loc, big)
    slots = jnp.lexsort((jnp.arange(n_local_max, dtype=jnp.int32),
                         key_v)).astype(jnp.int32)
    return jnp.where(key_v[slots] < big, slots, -1)


def arc_spmd(arrs, view, key, perm_kind: str, rc_cfg: RecolorConfig,
             sp_cfg: ColorConfig, P_size: int | None = None,
             plan_static=None, axis: str = AXIS, lane_axes: tuple = ()):
    """One asynchronous recoloring iteration: local class order + speculative."""
    comm = AxisComm(axis, lane_axes)
    n_local_max = arrs["indptr"].shape[0] - 1
    mc = rc_cfg.max_colors
    sizes, n_oor = class_sizes(view, arrs["n_local"], n_local_max, mc, comm)
    # independent streams: the class permutation and the speculative repair
    # must not consume the same key (identical bits would correlate the RAND
    # permutation with the tie-break randomness)
    k_rank, k_repair = jax.random.split(key)
    rank = permutation_rank(sizes, perm_kind, k_rank)
    order = arc_order_spmd(view, arrs["n_local"], n_local_max, rank)
    new_view, stats = color_spmd(arrs, order, k_repair, sp_cfg, P_size=P_size,
                                 plan_static=plan_static, axis=axis,
                                 lane_axes=lane_axes)
    stats["n_out_of_range"] = n_oor
    return new_view, stats


# ----------------------------------------------------------------- drivers --

def recolor_step(pg: PartitionedGraph, view, perm_kind: str,
                 cfg: RecolorConfig, key=None, *, mesh=None):
    """One synchronous RC iteration of ``pg``.

    ``mesh=None`` simulates the P shards on one device; a mesh runs them on
    its shard axis (``shard_axis_of(mesh)``), bitwise the same results.
    ``view`` — ``(P, n_slots)`` valid coloring with fresh ghosts (a driver
    output); ``perm_kind`` — one of ``RV``/``NI``/``ND``/``RAND``; ``key``
    defaults to a per-call-counter fold of ``cfg.seed`` (pass an explicit
    key for reproducible RAND permutations).  Returns ``(view, stats)``
    with python-int stats: ``n_colors`` (max id), ``n_colors_distinct``,
    ``n_colors_before``, ``n_exchanges`` (executed), ``gather_slots`` (ELL
    slots gathered, all shards), ``gather2_slots`` (two-hop ELL slots
    gathered, all shards; 0 at distance 1), ``n_steps`` (= class count),
    ``wire_bytes``, ``n_out_of_range``.
    """
    cfg = resolve_cfg(pg, cfg)
    sparse = cfg.scheme == SPARSE
    arrs = device_arrays(pg, sparse)
    ps = pg.plan_static if sparse else None
    if key is None:
        key = _default_key(cfg.seed)
    sig = signature(f"recolor_{perm_kind}", pg.P, cfg, ps, arrs, mesh=mesh)
    fn = partial(recolor_spmd, perm_kind=perm_kind, cfg=cfg, P_size=pg.P,
                 plan_static=ps)
    new_view, stats = dispatch(sig, fn, pg.P, mesh,
                               (arrs, jnp.asarray(view)), (key,))
    return new_view, stats_to_host(stats)


def arc_sim(pg: PartitionedGraph, view, perm_kind: str, rc_cfg: RecolorConfig,
            sp_cfg: ColorConfig, key=None):
    """One asynchronous (aRC) iteration, simulated: order by local class
    rank (``rc_cfg``, ``perm_kind``) and rerun the speculative framework
    (``sp_cfg``) — conflicts possible, hence the repair rounds.  Shapes and
    stats as ``speculative.color_graph``; the key splits into independent
    rank and repair streams.
    """
    rc_cfg, sp_cfg = resolve_cfg(pg, rc_cfg), resolve_cfg(pg, sp_cfg)
    sparse = sp_cfg.scheme == SPARSE
    arrs = device_arrays(pg, sparse)
    ps = pg.plan_static if sparse else None
    if key is None:
        key = _default_key(rc_cfg.seed)
    sig = signature(f"arc_{perm_kind}", pg.P, (rc_cfg, sp_cfg), ps, arrs,
                    stage=sp_cfg)
    fn = partial(arc_spmd, perm_kind=perm_kind, rc_cfg=rc_cfg, sp_cfg=sp_cfg,
                 P_size=pg.P, plan_static=ps)
    new_view, stats = dispatch(sig, fn, pg.P, None,
                               (arrs, jnp.asarray(view)), (key,))
    return new_view, stats_to_host(stats)


def schedule_for_iteration(it: int, base: str = ND, rand_every: int = 0,
                           rand_pow2: bool = False) -> str:
    """Permutation for iteration `it` (1-based): ND-RAND%x / ND-RAND%2^i."""
    if rand_pow2:
        return RAND if it & (it - 1) == 0 and it > 1 else base
    if rand_every and it % rand_every == 0:
        return RAND
    return base
