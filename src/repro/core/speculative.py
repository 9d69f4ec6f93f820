"""Speculative greedy distributed coloring (Bozdağ et al. framework, §2.2).

Round structure (all inside one jitted SPMD function):

  while conflicts remain:
    compact uncolored vertices to the front of the visit order
    for each superstep chunk of `superstep` vertices:
        color the chunk (local view, possibly stale ghosts) — see below
        exchange boundary colors (every `exchange_every` supersteps; =1 is the
        paper's synchronous variant, >1 models asynchronous staleness)
    final boundary exchange
    detect conflicts over the round's *frontier* (the vertices colored this
    round — see below); the lower-priority endpoint is uncolored and queued
    for the next round (random total order tie-break)

Chunk coloring has two modes (``ColorConfig.parallel_chunk``):

  parallel (default) — the whole superstep tile colors at once against the
    stale view: one ELL gather of neighbour colors, then tile-parallel bitset
    selection through ``kernels.ops.select_colors`` (Pallas on TPU).  Vertices
    inside one chunk cannot see each other, so same-chunk neighbours may
    conflict — that is *legal* in the speculative framework, and the existing
    round loop repairs it (the highest-priority endpoint always survives, so
    every round makes progress).  This is the bulk-synchronous shape of
    Bogle & Slota / Rokos et al. and the fast path on wide SIMD hardware.
  sequential — the paper-faithful scalar loop: one vertex at a time inside the
    chunk, each seeing all earlier in-chunk colors (conflicts only ever
    involve boundary vertices).  Also used for Least-Used selection, whose
    running usage histogram is inherently sequential.

Gathers are *width-bucketed*, as recolor's chunks are: a tile of the
parallel mode, and a chunk of conflict detection, gathers its ELL rows only
as wide as its widest row on any shard and lane, rounded up to one of the
static widths of ``gather_widths(maxd)`` and dispatched by ``lax.switch``.
Entries past a row's degree are sentinels (color 0, never forbidden), so
each stays bitwise the full-width program:

- coloring keeps the visit order, which decides the colors; the rows of a
  tile color together against one view, so the tile's width may follow
  its widest row;
- detection is free of order (every chunk reads the pre-detection view),
  so it reads the round's frontier sorted by degree, widest first, and
  its chunks hold rows of similar length.

The widths of a round's tiles and chunks ride the exchange gates' one pmax
a round.  ``gather_slots`` in the stats counts the slots gathered (rows ×
width over tiles, detection chunks and shards).  Distance 2 keeps the one
full width.

Communication scaling (this file + comm.py/graph.py, DESIGN.md §2):

- exchanges route through ``comm.make_exchange`` — the broadcast all-gather
  or the sparse per-neighbour ``ppermute`` schedule (``ColorConfig.scheme``),
  bitwise-identical colorings either way, measured wire bytes in the stats;
- *no-op exchange elision*: an exchange whose payloads cannot have changed
  (no shard colored a boundary vertex since the last exchange, pmax-agreed)
  is skipped.  With an interior-first visit order
  (``ordering.INTERNAL_FIRST``) the supersteps covering the interior prefix
  therefore perform no communication at all.  Skipping a no-op exchange is
  bitwise-safe: ghost values could not have changed;
- conflict detection and repair shrink to the *conflict frontier*: rounds
  after the first only rescan the vertices recolored this round (chunked,
  trip count pmax-reduced) instead of all of ``n_local_max``.  Conflicts can
  only involve this round's frontier — older colors were mutually repaired
  at the previous round's detection, and a fresh vertex always sees every
  older neighbour color (local ones directly, remote ones from the round's
  exchanges) — and in the paper's sequential mode the frontier after round 0
  is further contained in the boundary set.

The same function serves initial coloring (any order, any selection strategy
incl. Random-X Fit) and the aRC second pass (order derived from a previous
coloring's classes).

Distance-2 mode (``ColorConfig(distance=2)``, DESIGN.md §5): on a halo=2
partition the selection ORs the one-hop and two-hop forbidden bitsets
(``ops.select_colors_d2``) and conflict detection scans both ELL tiles; the
round/repair structure is unchanged.  ``partial=True`` + ``marked=`` on the
drivers colors only a marked subset (bipartite partial D2 coloring) —
unmarked vertices stay at color 0, invisible to every bitset.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.kernels import ops

from . import selection as sel
from .comm import (AUTO, AXIS, DEFAULT_SCHEME, SCHEME_CHOICES, SPARSE,
                   AxisComm, CommConfig, make_exchange, resolve_cfg,
                   stats_to_host)
from .graph import PartitionedGraph
from .programs import device_arrays, dispatch, signature


def validate_color_bounds(max_colors: int, wire16: bool, backend: str):
    """Shared config guards for ColorConfig / RecolorConfig."""
    assert max_colors % 32 == 0, "max_colors must be 32-aligned"
    if wire16:
        # int16 wire payloads carry the color value itself; anything past
        # int16 range would silently alias colors after the exchange.
        assert max_colors <= 32767, (
            f"wire16 carries colors as int16; max_colors="
            f"{max_colors} exceeds 32767")
    assert backend in ops.BACKENDS, f"bad backend {backend!r}"


@dataclasses.dataclass(frozen=True)
class ColorConfig:
    """Static configuration of one distributed coloring run.

    Units: ``superstep`` and ``tile`` are vertex counts per chunk (both
    clamped to the shard's row count at trace time); ``max_colors`` is the
    color-id bound (32-aligned — the bitset word width); ``exchange_every``
    counts supersteps between boundary exchanges; ``max_rounds`` bounds the
    speculate/repair rounds.  Driver: ``color_graph`` (one device with P
    vmap lanes, or a mesh's shard axis — the identical program);
    ``color_spmd`` is the raw per-shard SPMD function it wraps.
    """

    max_colors: int = 1024
    superstep: int = 512           # paper's superstep size (vertices per chunk)
    selection: str = sel.FIRST_FIT
    random_x: int = 10             # X for Random-X Fit
    stagger_estimate: int = 64     # initial color estimate for Staggered FF
    exchange_every: int = 1        # 1 = synchronous; k>1 = bounded staleness
    max_rounds: int = 64
    scheme: str = DEFAULT_SCHEME   # boundary exchange: "sparse" | "allgather"
                                   # | "auto" (pick by modeled bytes at trace
                                   # time; default follows $REPRO_SCHEME)
    wire16: bool = False           # int16 boundary payloads (half ICI bytes)
    parallel_chunk: bool = True    # tile-parallel supersteps (False = paper's
                                   # sequential scalar loop, bitwise-preserved)
    tile: int = 128                # vertices colored simultaneously within a
                                   # superstep; bounds speculative conflicts
                                   # while `superstep` keeps the comm cadence
    backend: str = "auto"          # kernels.ops backend: auto | xla | pallas
    distance: int = 1              # 1 = proper coloring; 2 = distance-2
                                   # (needs a halo=2 PartitionedGraph)
    partial: bool = False          # color only a marked vertex subset
                                   # (drivers take ``marked=``; bipartite
                                   # partial D2 coloring of Taş et al.)
    seed: int = 0

    def __post_init__(self):
        validate_color_bounds(self.max_colors, self.wire16, self.backend)
        assert self.scheme in SCHEME_CHOICES, f"bad scheme {self.scheme!r}"
        assert self.tile > 0
        assert self.distance in (1, 2), f"bad distance {self.distance}"

    @property
    def n_words(self) -> int:
        return self.max_colors // 32

    @property
    def comm_config(self) -> CommConfig:
        return CommConfig(scheme=self.scheme, wire16=self.wire16)

    @property
    def use_parallel_chunk(self) -> bool:
        """Least-Used chases a running histogram -> stays sequential."""
        return self.parallel_chunk and self.selection != sel.LEAST_USED

    def stagger_offset(self, p_idx):
        """Staggered First Fit start color of processor ``p_idx``."""
        return (p_idx * self.stagger_estimate) % self.max_colors


def _forbidden_words(view, indptr, indices, v, n_words):
    """Bitset of neighbour colors of local vertex `v` under current view."""
    words = jnp.zeros((n_words,), dtype=jnp.uint32).at[0].set(jnp.uint32(1))

    def body(e, words):
        return sel.set_bit(words, view[indices[e]])

    return jax.lax.fori_loop(indptr[v], indptr[v + 1], body, words)


def _forbid_ell_row(view, row, words):
    """OR the colors along one sentinel-padded ELL row into the bitset.

    Padding points at the sentinel slot (color 0 = bit 0, always set), so no
    masking is needed — used for the two-hop row in the sequential D2 path.
    """
    def body(k, words):
        return sel.set_bit(words, view[row[k]])

    return jax.lax.fori_loop(0, row.shape[0], body, words)


def _pick_color(words, usage, v_rand, p_idx, cfg: ColorConfig):
    if cfg.selection == sel.FIRST_FIT:
        return sel.first_fit(words)
    if cfg.selection == sel.STAGGERED:
        return sel.staggered(words, cfg.stagger_offset(p_idx))
    if cfg.selection == sel.LEAST_USED:
        return sel.least_used(words, usage)
    if cfg.selection == sel.RANDOM_X:
        return sel.random_x(words, cfg.random_x, v_rand)
    raise ValueError(f"unknown selection {cfg.selection!r}")


def _greedy_chunk(view, usage, order, rand_u32, start, count, arrs, p_idx,
                  cfg: ColorConfig):
    """Sequentially color `order[start:start+count]` (the superstep body)."""
    indptr, indices = arrs["indptr"], arrs["indices"]

    def body(i, carry):
        view, usage = carry
        v = order[i]
        v_safe = jnp.maximum(v, 0)
        needs = (v >= 0) & (view[v_safe] == 0)

        def color_one(args):
            view, usage = args
            words = _forbidden_words(view, indptr, indices, v_safe, cfg.n_words)
            if cfg.distance == 2:
                words = _forbid_ell_row(view, arrs["nbr2"][v_safe], words)
            c = _pick_color(words, usage, rand_u32[v_safe], p_idx, cfg)
            c = jnp.minimum(c, cfg.max_colors - 1).astype(jnp.int32)
            return view.at[v_safe].set(c), usage.at[c].add(1)

        return jax.lax.cond(needs, color_one, lambda a: a, (view, usage))

    return jax.lax.fori_loop(start, start + count, body, (view, usage))


def _tiles(cfg: ColorConfig, superstep: int) -> tuple[int, int]:
    """(tile width, tiles a superstep) of the tile-parallel superstep."""
    tile = min(cfg.tile, superstep)
    return tile, -(-superstep // tile)


def gather_widths(maxd: int) -> tuple:
    """The static widths a tile or chunk may gather its ELL rows at.

    The powers of two from 8 below ``maxd``, then ``maxd`` itself: a tile
    takes the smallest that holds its widest row, so it gathers at most
    twice its real width (or 8 slots) instead of the graph's maximum
    degree.  ``maxd <= 8`` leaves the one width ``maxd``.  The speculative
    tiles and detection chunks here and recolor's chunks share them.
    """
    out, w = [], 8
    while w < maxd:
        out.append(w)
        w *= 2
    return tuple(out) + (maxd,)


def _superstep_widths(row_deg, superstep: int, tile: int, n_tiles: int,
                      n_chunks: int):
    """Widest entry of each sub-tile of each superstep, ``(n_chunks, n_tiles)``.

    ``row_deg`` holds one ELL row length per visit position (0 where no
    row is to be colored or checked).  Sub-tile ``ti`` of superstep ``si``
    covers the ``tile`` positions from ``si * superstep + ti * tile``, as
    ``_parallel_chunk`` slices them: where ``tile`` does not divide the
    superstep, the last sub-tile reaches into the next superstep.  Past the
    visit order every position is padding (0), which also covers the
    slices ``dynamic_slice`` clamps.
    """
    rows = jnp.pad(row_deg, (0, (n_chunks + 1) * superstep
                             - row_deg.shape[0])).reshape(n_chunks + 1,
                                                          superstep)
    span = jnp.concatenate(
        [rows[:-1], rows[1:, :n_tiles * tile - superstep]], axis=1)
    return span.reshape(n_chunks, n_tiles, tile).max(axis=2)


def _frontier_by_degree(order_r, n_need, degree):
    """The round's frontier (the first ``n_need`` entries of the compacted
    visit order) stable-sorted by ELL row length, widest first; the entries
    after it keep their place.  Detection is free of order, so this changes
    no result, and each detection chunk then holds rows of similar length.
    """
    pos = jnp.arange(order_r.shape[0], dtype=jnp.int32)
    key = jnp.where(pos < n_need, -degree[jnp.maximum(order_r, 0)], 1)
    return order_r[jnp.argsort(key, stable=True)]


def _parallel_chunk(view, usage, order_pad, rand_u32, start, arrs, p_idx,
                    cfg: ColorConfig, superstep: int, widths: tuple,
                    tile_wid):
    """Color one superstep as tile-parallel sub-tiles against the stale view.

    Each sub-tile of ``cfg.tile`` vertices colors at once: one ELL-row gather
    + one bitset selection through ``kernels.ops.select_colors``.  The view
    updates between sub-tiles (so speculative conflicts stay bounded by the
    tile width), while boundary exchanges keep the ``superstep`` cadence —
    the tile is a hardware knob, the superstep the paper's comm knob.
    Conflicts within a tile are repaired by the round loop.  ``order_pad`` is
    the visit order padded by ``superstep`` entries of -1 so slices never
    clamp into unvisited territory.

    Sub-tile ``ti`` gathers its rows at the smallest of ``widths`` that
    holds ``tile_wid[ti]``, its widest row on any shard and lane; the rows
    of a tile color together against one view, so the colors are those of
    a full-width gather.
    """
    n_slots = view.shape[0]
    tile, n_tiles = _tiles(cfg, superstep)
    offset = cfg.stagger_offset(p_idx)
    width_arr = jnp.asarray(widths, jnp.int32)

    def color_at(w):
        """Gather the tile's neighbour colors ``w`` slots wide, select its
        colors and write them into the view, as ``recolor_at`` does: the
        view and usage go through the branch, and ``cols`` are the tile's
        whole ELL rows, cut here."""
        def branch(view, usage, cols, v_safe, rand, active):
            with jax.named_scope(tracing.NBR_GATHER):
                nbr_colors = view[cols[:, :w]]          # (tile, w)
                if cfg.distance == 2:
                    nbr2_colors = view[arrs["nbr2"][v_safe]]
            if cfg.distance == 2:
                colors = ops.select_colors_d2(
                    nbr_colors, nbr2_colors, active, rand,
                    max_colors=cfg.max_colors, selection=cfg.selection,
                    x=cfg.random_x, offset=offset, backend=cfg.backend)
            else:
                colors = ops.select_colors(
                    nbr_colors, active, rand, max_colors=cfg.max_colors,
                    selection=cfg.selection, x=cfg.random_x, offset=offset,
                    backend=cfg.backend)
            colors = jnp.minimum(colors, cfg.max_colors - 1).astype(jnp.int32)
            idx = jnp.where(active, v_safe, n_slots - 1)   # park writes on the
            val = jnp.where(active, colors, 0)             # sentinel (stays 0)
            view = view.at[idx].set(val.astype(view.dtype))
            usage = usage.at[jnp.where(active, colors, 0)].add(
                active.astype(jnp.int32))
            return view, usage
        return branch

    branches = [color_at(w) for w in widths]

    def tile_body(ti, carry):
        view, usage = carry
        chunk = jax.lax.dynamic_slice(order_pad, (start + ti * tile,), (tile,))
        v_safe = jnp.maximum(chunk, 0)
        active = (chunk >= 0) & (view[v_safe] == 0)
        with jax.named_scope(tracing.NBR_GATHER):
            cols = arrs["nbr"][v_safe]                  # (tile, maxd)
        args = (view, usage, cols, v_safe, rand_u32[v_safe], active)
        if len(widths) == 1:
            return branches[0](*args)
        # shard- and lane-uniform index: one branch taken under every vmap
        b = jnp.sum(width_arr < tile_wid[ti], dtype=jnp.int32)  # first >= wid
        return jax.lax.switch(b, branches, *args)

    return jax.lax.fori_loop(0, n_tiles, tile_body, (view, usage))


def _detect_conflicts_frontier(view, arrs, rows_pad, n_steps, n_need,
                               superstep: int, widths: tuple, chunk_wid,
                               backend="auto", distance: int = 1):
    """Uncolor the lower-priority endpoint of every same-color frontier edge.

    Chunked over ``rows_pad``, whose first ``n_need`` entries are the
    vertices recolored this round (the round's frontier, in any order),
    padded by ``superstep`` entries of -1: only they are rescanned
    (``n_steps`` is pmax-reduced by the caller, so the trip count is
    shard- and lane-uniform and *shrinks* with the conflict frontier).  Every chunk
    reads the same pre-detection ``view`` — identical results to one
    full-width pass, whatever the order of the frontier — and writes
    uncolorings into a separate copy.  Chunk ``si`` gathers its rows'
    colors and priorities at the smallest of ``widths`` that holds
    ``chunk_wid[si]``, its widest row on any shard and lane (a shard- and
    lane-uniform index, so one ``switch`` branch runs).  ``distance=2``
    additionally scans the two-hop ELL rows (both endpoints of a distance-2
    conflict list each other in ``nbr2``, so the repair argument is
    unchanged).  Returns (new_view, n_conflicts, any_boundary_conflict).
    """
    nbr, prio, is_internal = arrs["nbr"], arrs["prio"], arrs["is_internal"]
    n_slots = view.shape[0]
    width_arr = jnp.asarray(widths, jnp.int32)

    def detect_at(w):
        """Check the chunk's rows against neighbours ``w`` slots wide; the
        carry goes through the branch and ``cols`` are whole ELL rows, as
        in ``_parallel_chunk``."""
        def branch(carry, cols, r_safe, active):
            new_view, n_conf, bnd = carry
            with jax.named_scope(tracing.NBR_GATHER):
                tiles = (view[cols[:, :w]], prio[cols[:, :w]])
                if distance == 2:
                    nbr2 = arrs["nbr2"]
                    tiles += (view[nbr2[r_safe]], prio[nbr2[r_safe]])
            if distance == 2:
                conf = ops.detect_conflicts_d2(view[r_safe], prio[r_safe],
                                               *tiles, active, backend=backend)
            else:
                conf = ops.detect_conflicts(view[r_safe], prio[r_safe],
                                            *tiles, active, backend=backend)
            idx = jnp.where(conf, r_safe, n_slots - 1)   # sentinel stays 0
            new_view = new_view.at[idx].set(0)
            n_conf = n_conf + jnp.sum(conf, dtype=jnp.int32)
            bnd = bnd | jnp.any(conf & ~is_internal[r_safe])
            return new_view, n_conf, bnd
        return branch

    branches = [detect_at(w) for w in widths]

    def body(si, carry):
        rows = jax.lax.dynamic_slice(rows_pad, (si * superstep,),
                                     (superstep,))
        pos = si * superstep + jnp.arange(superstep, dtype=jnp.int32)
        active = (rows >= 0) & (pos < n_need)
        r_safe = jnp.maximum(rows, 0)
        with jax.named_scope(tracing.NBR_GATHER):
            cols = nbr[r_safe]                          # (superstep, maxd)
        if len(widths) == 1:
            return branches[0](carry, cols, r_safe, active)
        b = jnp.sum(width_arr < chunk_wid[si], dtype=jnp.int32)
        return jax.lax.switch(b, branches, carry, cols, r_safe, active)

    return jax.lax.fori_loop(
        0, n_steps, body, (view, jnp.int32(0), jnp.bool_(False)))


def _compact_order(order, view):
    """Stable-move still-uncolored vertices to the front of the visit order.

    Uncolored vertices are always contained in the previous round's frontier
    (detection only uncolors freshly-colored rows), so the compacted prefix
    — and with it every per-round trip count — shrinks monotonically.
    """
    v_safe = jnp.maximum(order, 0)
    needs = (order >= 0) & (view[v_safe] == 0)
    perm = jnp.argsort(~needs, stable=True)
    return order[perm], jnp.sum(needs, dtype=jnp.int32)


@tracing.scope(tracing.SPEC_COLOR)
def color_spmd(arrs, order, key, cfg: ColorConfig, P_size: int | None = None,
               plan_static=None, axis: str = AXIS, lane_axes: tuple = ()):
    """Per-shard SPMD speculative coloring. Returns (view, stats dict).

    ``P_size``/``plan_static`` (``PartitionedGraph.comm_plan.static``) are
    required for the sparse exchange scheme — the ``ppermute`` round
    schedule is static; the drivers thread them automatically.  ``axis``
    names the shard mesh axis all collectives run over (``shard_axis_of``
    derives it from a mesh; defaults to ``"workers"``); ``lane_axes`` the
    batch mesh axes control flow must additionally be uniform over on a 2D
    ``batch × shard`` mesh (``AxisComm.lane_uniform``, DESIGN.md §10) —
    loop trip counts and exchange gates widen to the mesh-wide maximum
    while every lane masks the *application* with its own local predicate,
    so per-lane results (view, stats) stay bitwise the solo run's.
    """
    comm = AxisComm(axis, lane_axes)
    n_local_max = arrs["indptr"].shape[0] - 1
    n_slots = arrs["prio"].shape[0]
    p_idx = comm.index()
    if cfg.scheme == AUTO:
        raise ValueError("scheme='auto' must be resolved by a driver "
                         "(comm.resolve_cfg) before the SPMD fn")
    if cfg.scheme == SPARSE and (P_size is None or plan_static is None):
        raise ValueError("sparse scheme needs P_size and plan_static "
                         "(see PartitionedGraph.comm_plan)")
    if cfg.distance == 2 and "nbr2" not in arrs:
        raise ValueError("distance=2 needs the two-hop halo: partition with "
                         "partition_graph(g, P, halo=2)")

    exchange = make_exchange(arrs, n_local_max, P_size, comm,
                             cfg.comm_config, plan_static)
    no_ex = lambda v: (v, jnp.int32(0))

    # Clamp the superstep (and, downstream, the tile) to the shard's row
    # count: every chunk/tile boundary at granularity >= n_local_max is
    # equivalent to one at n_local_max (a round is always a single step and
    # a single sub-tile covers every live vertex either way), so this is
    # bitwise-identical — it only stops small graphs (and every lane of the
    # batched pipeline) from gathering `superstep - n_local_max` rows of
    # pure padding per round.
    S = min(cfg.superstep, n_local_max)
    n_chunks_max = -(-n_local_max // S)
    view0 = jnp.zeros((n_slots,), jnp.int32)
    usage0 = jnp.zeros((cfg.max_colors,), jnp.int32)

    # slots a superstep reads through the two-hop ELL: every gathered row
    # of the tile-parallel coloring (the sequential path reads one row a
    # vertex it colors), and two gathers (colors, priorities) a detected row
    maxd2 = arrs["nbr2"].shape[1] if cfg.distance == 2 else 0
    tile, n_tiles = _tiles(cfg, S)
    rows2 = (tile * n_tiles if cfg.use_parallel_chunk else 0) + 2 * S

    # one-hop gather widths; distance 2 gathers the two-hop rows too, so it
    # keeps the one full width, as recolor does
    maxd = arrs["nbr"].shape[1]
    widths = gather_widths(maxd) if cfg.distance == 1 else (maxd,)
    width_arr = jnp.asarray(widths, jnp.int32)
    bucketed = len(widths) > 1
    degree = arrs["degree"]                  # ELL row length (real entries)
    n_tiles_run = n_tiles if cfg.use_parallel_chunk else 0

    def round_body(state):
        (view, usage, rnd, n_conf_in, n_ex, n_bytes, n_rnd, n_g,
         n_g2) = state
        order_r, n_need = _compact_order(order, view)
        n_need_max = comm.pmax(n_need)
        n_steps = (n_need_max + S - 1) // S
        # mesh-wide trip count: every batch lane executes the same number
        # of superstep chunks (chunks past a lane's own frontier only read
        # already-colored rows — the view[v] == 0 guard makes them no-ops)
        n_steps_all = (comm.lane_uniform(n_need_max) + S - 1) // S
        rkey = jax.random.fold_in(jax.random.fold_in(key, rnd), p_idx)
        rand_u32 = jax.random.bits(rkey, (n_slots,), jnp.uint32)
        pad = jnp.full((S,), -1, order_r.dtype)
        order_pad = jnp.concatenate([order_r, pad])

        # Which superstep chunks color at least one boundary vertex, on any
        # shard.  Chunks of interior vertices cannot change any exchange
        # payload, so the exchanges they would trigger are elided below —
        # bitwise-safe, the ghosts could not move.
        pos = jnp.arange(n_chunks_max * S, dtype=jnp.int32)
        opad = order_pad[: n_chunks_max * S]
        bnd = ((opad >= 0) & (pos < n_need)
               & ~arrs["is_internal"][jnp.maximum(opad, 0)])
        # Each detection chunk's and each coloring tile's widest row: only
        # the frontier (the first n_need entries) is checked or colored.
        # Detection reads the frontier sorted by degree, so its chunks hold
        # rows of similar length.
        det_pad = order_pad
        if bucketed:
            live = jnp.arange(n_local_max, dtype=jnp.int32) < n_need
            row_deg = lambda rows: jnp.where(
                live, degree[jnp.maximum(rows, 0)], 0)
            det_r = _frontier_by_degree(order_r, n_need, degree)
            det_pad = jnp.concatenate([det_r, pad])
            wid_local = [_superstep_widths(row_deg(det_r), S, S, 1,
                                           n_chunks_max)]
            if n_tiles_run:
                wid_local += [_superstep_widths(row_deg(order_r), S, tile,
                                                n_tiles, n_chunks_max)]
        else:
            wid_local = [jnp.full((n_chunks_max, 1 + n_tiles_run), maxd,
                                  jnp.int32)]
        # one pmax a round for the exchange gates and the widths
        red = comm.pmax(jnp.concatenate(
            [jnp.any(bnd.reshape(n_chunks_max, S), axis=1,
                     keepdims=True).astype(jnp.int32)] + wid_local, axis=1))
        chunk_bnd = red[:, 0] > 0
        wid_own = red[:, 1:]          # this lane's widths (its solo run's)
        wid = comm.lane_uniform(wid_own)
        w_own = width_arr[jnp.sum(width_arr < wid_own[..., None], axis=-1)]
        # slots this lane's own supersteps gather (one detection chunk of S
        # rows, then tiles of `tile` rows), on every shard alike
        own = jnp.arange(n_chunks_max) < n_steps
        n_rows = jnp.asarray([S] + [tile] * n_tiles_run, jnp.int32)
        g = jnp.sum(jnp.where(own[:, None], w_own * n_rows, 0),
                    dtype=jnp.int32) * comm.size()

        def superstep(si, carry):
            view, usage, n_ex, n_bytes, pending = carry
            if cfg.use_parallel_chunk:
                view, usage = _parallel_chunk(view, usage, order_pad,
                                              rand_u32, si * S,
                                              arrs, p_idx, cfg, S, widths,
                                              wid[si, 1:])
            else:
                view, usage = _greedy_chunk(view, usage, order_r, rand_u32,
                                            si * S, S, arrs, p_idx, cfg)
            pending = pending | chunk_bnd[si]
            due = ((si + 1) % cfg.exchange_every == 0) | (si == n_steps - 1)
            do_ex = due & pending
            # execute under the lane-uniform gate (a lane never skips a
            # ppermute its batch-row peers run), apply under the lane's own
            new_view, b = jax.lax.cond(comm.lane_uniform(do_ex), exchange,
                                       no_ex, view)
            view = jnp.where(do_ex, new_view, view)
            return (view, usage, n_ex + do_ex.astype(jnp.int32),
                    n_bytes + jnp.where(do_ex, b, 0), pending & ~do_ex)

        view, usage, n_ex, n_bytes, _ = jax.lax.fori_loop(
            0, n_steps_all, superstep,
            (view, usage, n_ex, n_bytes, jnp.bool_(False)))
        # the lane-uniform trip count keeps each chunk's width index
        # unbatched under a lane vmap (chunks past this lane's frontier
        # check no row)
        view, n_conf, bnd_conf = _detect_conflicts_frontier(
            view, arrs, det_pad, n_steps_all, n_need, S, widths, wid[:, 0],
            backend=cfg.backend, distance=cfg.distance)
        # publish uncolorings only if a boundary vertex lost somewhere
        do_final = comm.pmax(bnd_conf)
        new_view, b = jax.lax.cond(comm.lane_uniform(do_final), exchange,
                                   no_ex, view)
        view = jnp.where(do_final, new_view, view)
        n_conf = comm.psum(n_conf)
        # this lane's own supersteps, on every shard alike
        g2 = n_steps * rows2 * comm.size()
        if not cfg.use_parallel_chunk:
            g2 = g2 + comm.psum(n_need)
        # per-lane round count: a converged lane riding out its batch-row
        # peers' extra rounds (no-op bodies) must not count them
        return (view, usage, rnd + 1, n_conf,
                n_ex + do_final.astype(jnp.int32),
                n_bytes + jnp.where(do_final, b, 0),
                n_rnd + (n_conf_in > 0).astype(jnp.int32),
                n_g + g, n_g2 + g2 * maxd2)

    def cond(state):
        _, _, rnd, n_conf, _, _, _, _, _ = state
        return comm.lane_uniform(n_conf > 0) & (rnd < cfg.max_rounds)

    state0 = (view0, usage0, jnp.int32(0), jnp.int32(1), jnp.int32(0),
              jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0))
    # round 0 must run: seed n_conf=1
    (view, usage, _, _, n_ex, n_bytes, n_rounds, n_g,
     n_g2) = jax.lax.while_loop(cond, round_body, state0)

    local_max = jnp.max(view[:n_local_max])
    # distinct classes in use — the corrected quality metric (Staggered FF
    # spreads shards across the id range, so the max id alone can massively
    # overstate the color count); `usage` over-counts repaired vertices, so
    # derive the mask from the final view instead
    valid = jnp.arange(n_local_max) < arrs["n_local"]
    in_use = jnp.zeros((cfg.max_colors,), bool).at[
        jnp.where(valid, view[:n_local_max], 0)].max(valid)
    in_use = comm.pmax(in_use.at[0].set(False))
    stats = dict(
        n_colors=comm.pmax(local_max),
        n_colors_distinct=jnp.sum(in_use, dtype=jnp.int32),
        n_rounds=n_rounds,
        n_exchanges=n_ex,
        wire_bytes=n_bytes,
        gather_slots=n_g,
        gather2_slots=n_g2,
    )
    return view, stats


def _apply_partial(order, cfg: ColorConfig, marked):
    """Mask the visit order down to the marked subset (``cfg.partial``).

    ``marked`` is a host-side (P, n_local_max) bool mask of local slots;
    unmarked vertices become ``-1`` entries (skipped everywhere), stay at
    color 0, and — color 0 being invisible to the forbidden bitsets — act
    exactly like the uncolored through-vertices of partial/bipartite D2
    coloring.
    """
    if not cfg.partial:
        assert marked is None, "marked= requires partial=True on the config"
        return order
    assert marked is not None, "partial=True needs a marked= (P, n_local) mask"
    order = np.asarray(order)
    marked = np.asarray(marked, dtype=bool)
    keep = np.take_along_axis(marked, np.maximum(order, 0), axis=1)
    return np.where((order >= 0) & keep, order, -1)


def color_graph(pg: PartitionedGraph, order, cfg: ColorConfig, key=None, *,
                mesh=None, marked=None):
    """Run distributed speculative coloring of ``pg``.

    ``mesh=None`` simulates the P shards on one device (P vmap lanes); a
    mesh runs them on its shard axis (``shard_axis_of(mesh)``), bitwise the
    same results.  ``order`` — ``(P, n_local_max)`` int32 visit order of
    local slots, -1 = skip (``compute_order``); ``key`` — JAX key (default
    ``key(cfg.seed)``); ``marked`` — ``(P, n_local_max)`` bool host mask,
    only with ``cfg.partial``.  Returns ``(view, stats)``: ``view`` is the
    ``(P, n_slots)`` int32 device view (colors are 1-based; ghosts +
    sentinel slots after ``n_local_max``; ``colors_from_views`` flattens it
    to global ``(n,)`` colors) and ``stats`` are python ints — ``n_colors``
    (max id), ``n_colors_distinct`` (the quality metric), ``n_rounds``,
    ``n_exchanges``, ``wire_bytes`` (measured, per-shard max),
    ``gather_slots`` (ELL slots gathered by the tiles and the detection
    chunks, all shards), ``gather2_slots`` (slots read through the two-hop
    ELL, all shards; 0 at distance 1).
    """
    cfg = resolve_cfg(pg, cfg)
    sparse = cfg.scheme == SPARSE
    arrs = device_arrays(pg, sparse)
    ps = pg.plan_static if sparse else None
    if key is None:
        key = jax.random.key(cfg.seed)
    order = jnp.asarray(_apply_partial(order, cfg, marked))
    sig = signature("color", pg.P, cfg, ps, arrs, mesh=mesh)
    fn = partial(color_spmd, cfg=cfg, P_size=pg.P, plan_static=ps)
    view, stats = dispatch(sig, fn, pg.P, mesh, (arrs, order), (key,))
    return view, stats_to_host(stats)
