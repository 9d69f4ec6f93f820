"""Host-side graph substrate: global CSR + distributed partitioning.

The distributed layout mirrors the paper (§2.2): each processor owns a
contiguous block of vertices (block partitioning, as the paper uses for the
RMAT graphs); for every cross-partition edge both endpoints' processors know
the edge. Vertices whose neighbours are all local are *internal*; the rest are
*boundary*. Remote neighbours appear locally as *ghost* slots.

Device layout (per processor p, padded to common maxima so the arrays stack
into a leading-P axis for `SimComm`/`shard_map`):

  view slots  = [0, n_local_max)                local vertices
              | [n_local_max, n_local_max+g)    ghosts (stale remote colors)
              | sentinel slot (always color 0)  at index n_slots-1

  ``indices`` holds slot ids; padded entries point at the sentinel.
  ``nbr`` is the same adjacency in padded-neighbor (ELL) form: one
  ``(n_local_max, maxd)`` row of slot ids per vertex, padded with the sentinel
  slot, so a tile of vertices gathers its whole neighbourhood with one
  ``view[nbr[rows]]`` — the layout the bitset selection kernels consume
  (DESIGN.md §3). ELL trades ``n_local_max * maxd`` storage for gather-only
  (scatter-free) hot loops; ``maxd`` is the max degree over all processors.
  ``boundary`` lists local boundary slots; only boundary colors ever travel.
  Under the broadcast scheme the exchange payload of processor p is
  ``view[boundary]``: ghost g of processor p is owned by ``ghost_owner[g]``
  and lives at position ``ghost_slot[g]`` of that owner's payload, so after
  an all-gather of payloads P×max_b, ghosts refresh with one gather.
  Under the sparse scheme (``CommPlan``, built by ``build_comm_plan``) each
  processor instead ships per-destination send lists over a static
  ``ppermute`` round schedule — the faithful analogue of the paper's
  neighbour-to-neighbour boundary messages, with wire bytes that track the
  realized cross-edge structure instead of P (DESIGN.md §2).

  ``partition_graph(..., halo=2)`` widens everything to the *two-hop halo*
  for distance-2 coloring (DESIGN.md §5): ghosts cover every remote vertex
  within two hops, ``nbr2`` holds the strict two-hop ELL, and
  ``boundary``/``is_internal`` mean "read by some other shard".  The comm
  plan is halo-agnostic — depth-2 ghosts are ordinary ghost-table entries.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import os

import jax
import numpy as np

from repro import tracing


@dataclasses.dataclass(frozen=True)
class Graph:
    """Global symmetric CSR graph (host, numpy)."""

    n: int
    indptr: np.ndarray  # (n+1,) int64
    indices: np.ndarray  # (2m,) int32 (int64 past the 2**31 id bound)

    @property
    def m_directed(self) -> int:
        return int(self.indices.shape[0])

    @property
    def m(self) -> int:
        return self.m_directed // 2

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.int32)

    @property
    def max_degree(self) -> int:
        return int(self.degrees.max(initial=0))

    def validate_coloring(self, colors: np.ndarray) -> bool:
        """True iff `colors` (1-based, 0=uncolored disallowed) is proper."""
        if (colors <= 0).any():
            return False
        src = np.repeat(np.arange(self.n), self.degrees)
        return bool((colors[src] != colors[self.indices]).all())

    def num_colors(self, colors: np.ndarray) -> int:
        """Distinct positive colors in use (not the max id — recoloring can
        empty classes below the maximum, leaving gaps in the id range)."""
        c = np.unique(np.asarray(colors))
        return int((c > 0).sum())


#: per-shard slot index arrays (slots, ELL neighbours) are int32 below this.
INT32_LIMIT = 2**31
#: hard ceiling of the id layout — int64 ids cannot represent past this.
INT64_LIMIT = 2**63


@dataclasses.dataclass(frozen=True)
class IdPolicy:
    """The single id-width decision point (DESIGN.md §10).

    Two independent hazards, each with its own dtype verdict:

    - **global ids** (``gvid``, ``prio``, CSR ``indices``, the RMAT edge
      packing): int32 while ``n_global < 2**31``, int64 past it;
    - **the flattened ELL index** ``v * maxd + k`` the selection kernels
      compute per shard: int32 while ``n_local_max * max(maxd, maxd2)``
      stays under 2**31.  *Per-shard* slot ids (``nbr``, ``indices`` slot
      entries, ``boundary``) are bounded by ``n_slots`` and stay int32
      regardless — only the flat-index arithmetic widens.

    ``promoted`` is true when either verdict is int64 — the giant-graph
    regime the int32 guard used to reject outright.  ``id_policy`` is the
    only place that compares against ``INT32_LIMIT``; everything else
    (``partition_graph``, ``rmat``, roofline projections) consumes the
    policy's dtypes.
    """

    n_global: int
    ell: int                 # n_local_max * max(maxd, maxd2, 1)
    id_dtype: object         # numpy dtype for global vertex ids
    ell_dtype: object        # numpy dtype for flattened ELL indices

    @property
    def promoted(self) -> bool:
        return (np.dtype(self.id_dtype) == np.int64
                or np.dtype(self.ell_dtype) == np.int64)

    @property
    def id_itemsize(self) -> int:
        return np.dtype(self.id_dtype).itemsize


def id_policy(n_global: int, n_local_max: int, maxd: int, maxd2: int = 0,
              *, allow_int64: bool = True) -> IdPolicy:
    """Decide the id widths for a (partitioned) graph's device layout.

    Pure shape arithmetic — callable (and testable) without allocating the
    arrays it governs.  Under ``allow_int64=True`` (the default) crossing
    either int32 bound *promotes* the affected dtype to int64 instead of
    raising; ``allow_int64=False`` reproduces the historical hard guard
    (``check_int32_limits``).  int64 itself overflowing is always an error.
    """
    ell = n_local_max * max(maxd, maxd2, 1)
    if n_global >= INT64_LIMIT or ell >= INT64_LIMIT:
        raise ValueError(
            f"graph exceeds the int64 id range: n_global={n_global}, "
            f"n_local_max * maxd = {ell} (>= {INT64_LIMIT})")
    if not allow_int64:
        if n_global >= INT32_LIMIT:
            raise ValueError(
                f"graph has {n_global} vertices but device vertex ids are "
                f"int32 (< {INT32_LIMIT}); this exceeds the supported size")
        if ell >= INT32_LIMIT:
            raise ValueError(
                f"int32 ELL overflow: n_local_max * maxd = {n_local_max} * "
                f"{max(maxd, maxd2, 1)} = {ell} >= {INT32_LIMIT}; partition "
                f"over more workers (larger P) to shrink the per-shard tile")
    return IdPolicy(
        n_global=n_global, ell=ell,
        id_dtype=np.int64 if n_global >= INT32_LIMIT else np.int32,
        ell_dtype=np.int64 if ell >= INT32_LIMIT else np.int32)


def check_int32_limits(n_global: int, n_local_max: int, maxd: int,
                       maxd2: int = 0) -> None:
    """Historical hard int32 guard — now a thin ``id_policy`` wrapper.

    Raises exactly where the pre-policy guard raised; callers that can
    handle the int64 regime should consume ``id_policy`` directly.
    """
    id_policy(n_global, n_local_max, maxd, maxd2, allow_int64=False)


def _pad2(rows: list[np.ndarray], width: int, fill: int) -> np.ndarray:
    out = np.full((len(rows), width), fill, dtype=np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def _unique_pairs(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Sort index pairs by (a, b) and drop duplicates — no packed keys."""
    order = np.lexsort((b, a))
    a, b = a[order], b[order]
    keep = np.empty(a.shape[0], dtype=bool)
    keep[:1] = True
    keep[1:] = (a[1:] != a[:-1]) | (b[1:] != b[:-1])
    return a[keep], b[keep]


#: raw two-hop paths expanded at once by one task of ``_two_hop_pairs``
TWO_HOP_CHUNK = 1 << 19
#: threads that expand the two-hop paths (numpy's sort and gathers release
#: the GIL); at most this many, and never more than the host's cores
TWO_HOP_WORKERS = 8


def _two_hop_chunk(g: Graph, lo: int, a: int, b: int, key_dt
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Strict two-hop pairs of the block rows ``a .. b-1`` (local ids).

    Every length-2 path v -> w -> u becomes one key ``(v - a) * n + u``;
    one sort of the keys orders them by (row, global id), and a key equal
    to its predecessor is a duplicate.  The pairs that are no two-hop pairs
    (u adjacent to v, or u == v) are looked up in the sorted keys and
    dropped.
    """
    n = key_dt(g.n)
    e_lo, e_hi = int(g.indptr[lo + a]), int(g.indptr[lo + b])
    w = g.indices[e_lo:e_hi]
    d = g.indptr[w + 1] - g.indptr[w]
    offs = np.cumsum(d) - d
    src_dt = np.int32 if g.indptr[-1] < INT32_LIMIT else np.int64
    src = np.repeat((g.indptr[w] - offs).astype(src_dt), d)
    src += np.arange(src.shape[0], dtype=src_dt)
    rows = np.arange(b - a, dtype=key_dt)
    vkey = np.repeat(rows * n, np.diff(g.indptr[lo + a:lo + b + 1]))
    key = np.repeat(vkey, d)
    key += g.indices[src].astype(key_dt)
    key.sort()
    keep = np.empty(key.shape[0], dtype=bool)
    keep[:1] = True
    np.not_equal(key[1:], key[:-1], out=keep[1:])
    drop = np.concatenate([vkey + w.astype(key_dt),
                           rows * (n + key_dt(1)) + key_dt(lo + a)])
    at = np.searchsorted(key, drop)
    hit = at < key.shape[0]
    at, drop = at[hit], drop[hit]
    keep[at[key[at] == drop]] = False
    key = key[keep]
    return (key // n).astype(np.int32) + np.int32(a), (key % n).astype(np.int32)


def _two_hop_pairs(g: Graph, offs: np.ndarray
                   ) -> list[tuple[np.ndarray, np.ndarray]]:
    """Per block ``[offs[p], offs[p+1])``: the unique (local row, global id)
    pairs at graph distance exactly 2, sorted by (row, global id).

    Expands every length-2 path v -> w -> u from the block's vertices v (the
    middle vertex w may be local or remote), then drops u == v and the pairs
    already adjacent — the direct neighbourhood lives in ``nbr`` and the D2
    kernels OR both bitsets, so keeping strict two-hop rows only is what
    bounds the ELL width.

    One pass over each block in row chunks of about ``TWO_HOP_CHUNK`` raw
    paths (a row is never split, so a hub of degree d brings its d² paths
    into one chunk): each chunk is sorted and deduplicated on its own, and
    the chunks' results, in row order, are the block's answer.  Host memory
    tracks the chunks in flight plus the deduped pairs.  Keys are uint32
    where ``rows * n`` fits, which caps a chunk's rows at ``2**32 // n``.
    """
    rows_cap = (1 << 32) // max(g.n, 1)
    key_dt = np.uint32 if rows_cap else np.uint64
    tasks, per_block = [], []
    for p in range(len(offs) - 1):
        lo, hi = int(offs[p]), int(offs[p + 1])
        e0 = int(g.indptr[lo])
        w = g.indices[e0:int(g.indptr[hi])]
        paths = np.zeros(w.shape[0] + 1, dtype=np.int64)
        np.cumsum(g.indptr[w + 1] - g.indptr[w], out=paths[1:])
        row_paths = paths[g.indptr[lo:hi + 1] - e0]   # paths before each row
        a, n_tasks = 0, 0
        while a < hi - lo:
            last = min(hi - lo, a + rows_cap) if rows_cap else hi - lo
            b = int(np.searchsorted(row_paths, row_paths[a] + TWO_HOP_CHUNK,
                                    side="right")) - 1
            b = max(a + 1, min(b, last))
            tasks.append((lo, a, b))
            a, n_tasks = b, n_tasks + 1
        per_block.append(n_tasks)
    workers = max(1, min(TWO_HOP_WORKERS, len(tasks), os.cpu_count() or 1))
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        parts = iter(pool.map(lambda t: _two_hop_chunk(g, *t, key_dt), tasks))
    out = []
    for n_tasks in per_block:
        mine = [next(parts) for _ in range(n_tasks)]
        out.append(tuple(np.concatenate([m[i] for m in mine]
                                        + [np.zeros(0, np.int32)])
                         for i in (0, 1)))
    return out


@dataclasses.dataclass(frozen=True)
class CommPlan:
    """Static sparse-exchange schedule (paper's neighbour-to-neighbour sends).

    The processor ring is walked by *shift*: in round ``r`` every shard p
    sends one buffer to ``(p + shifts[r]) % P`` via ``ppermute``.  Only
    shifts with traffic on at least one ordered pair exist, and every round
    is padded to its own global width — so both the round count and the
    bytes scale with the realized cross-edge structure, not with P.

    **Shape stability** (DESIGN.md §2): the *padded* round widths
    (``widths``, the compiled buffer shapes and hence part of every jit
    cache key via ``static``) are quantized to pow2 rungs by default, so
    near-sized graphs share one compiled exchange program.  The true pmax
    payload counts survive as ``exact_widths``: padding rows are inert
    (sentinel slots no receiver reads), and ``arrays()`` ships the exact
    widths as *data* (``round_widths``) so measured ``wire_bytes`` stay
    those of the exact plan — bitwise what an unquantized run reports.

    ``send_slot[p, r]`` lists the local boundary slots whose colors the
    round-r destination actually reads (its ghosts owned by p, in ascending
    global id), sentinel-padded to ``widths[r]`` ≤ ``max_send``.  On the
    receive side, ghost g of shard p was sent by its owner in round
    ``shift_to_round[ghost_shift[p, g]]`` at buffer position
    ``ghost_pos[p, g]``.
    """

    shifts: tuple          # static nonzero ring shifts with any traffic
    widths: tuple          # per-shift *padded* buffer width (pow2 rung)
    exact_widths: tuple    # per-shift true pmax payload width (<= widths)
    max_send: int          # max(widths), the send_slot pad width
    n_send: np.ndarray     # (P, P) per-(src, dst) payload counts
    send_slot: np.ndarray  # (P, n_rounds, max_send) local slots, pad=sentinel
    ghost_shift: np.ndarray  # (P, max_ghost) ring shift of each ghost, pad=-1
    ghost_pos: np.ndarray    # (P, max_ghost) position in owner's send row
    shift_to_round: np.ndarray  # (P, P) shift value -> round index, -1 unused

    @property
    def static(self) -> tuple:
        """Hashable (shifts, padded widths) — part of the jit cache key."""
        return (self.shifts, self.widths)

    def arrays(self) -> dict[str, np.ndarray]:
        P = self.send_slot.shape[0]
        rw = np.zeros((max(len(self.shifts), 1),), np.int32)
        rw[:len(self.exact_widths)] = self.exact_widths
        return dict(send_slot=self.send_slot, ghost_shift=self.ghost_shift,
                    ghost_pos=self.ghost_pos,
                    shift_to_round=self.shift_to_round,
                    round_widths=np.broadcast_to(rw, (P, rw.shape[0])).copy())

    def bytes_per_exchange(self, itemsize: int = 4, round_mask=None, *,
                           padded: bool = False) -> int:
        """Per-shard wire bytes of one sparse exchange.

        ``round_mask`` (bool per round) models a partial exchange — the cost
        of shipping only the masked ``ppermute`` rounds (recolor's per-link
        piggybacking); ``None`` means a full exchange.  Default: the *exact*
        plan bytes (the paper's model; what ``stats["wire_bytes"]``
        measures).  ``padded=True`` counts the pow2-rung buffer widths the
        compiled program physically ships — the quantity the trace-time
        sparse-vs-allgather decision compares (``comm.resolve_scheme``).
        """
        ws = self.widths if padded else self.exact_widths
        if round_mask is None:
            return int(sum(ws)) * itemsize
        return int(sum(w for w, m in zip(ws, round_mask) if m)) * itemsize


@dataclasses.dataclass(frozen=True)
class PartitionedGraph:
    """Per-processor padded arrays, stacked on a leading P axis (host, numpy).

    Per-shard slot index arrays are int32; the global-id arrays
    (``gvid``/``prio``) follow ``id_policy`` — int32 below the 2**31
    vertex bound, int64 past it.  `n_slots = n_local_max + max_ghost + 1`.
    """

    P: int
    n_global: int
    n_local_max: int
    max_ghost: int
    max_boundary: int
    m_local_max: int
    maxd: int
    offs: np.ndarray           # (P+1,) block boundaries in global ids
    n_local: np.ndarray        # (P,)
    n_ghost: np.ndarray        # (P,)
    n_boundary: np.ndarray     # (P,)
    indptr: np.ndarray         # (P, n_local_max+1)
    indices: np.ndarray        # (P, m_local_max) slot ids, pad=sentinel
    nbr: np.ndarray            # (P, n_local_max, maxd) ELL slot ids, pad=sentinel
    edge_src: np.ndarray       # (P, m_local_max) local row per edge, pad=n_local_max
    boundary: np.ndarray       # (P, max_boundary) local slots, pad=sentinel
    ghost_owner: np.ndarray    # (P, max_ghost)
    ghost_slot: np.ndarray     # (P, max_ghost)
    gvid: np.ndarray           # (P, n_slots) global vertex id per slot, pad=-1
    prio: np.ndarray           # (P, n_slots) random tie-break priority, pad=-1
    is_internal: np.ndarray    # (P, n_local_max) bool
    degree: np.ndarray         # (P, n_local_max) int32 local-graph-visible degree
    halo: int = 1              # ghost depth: 1 (D1) or 2 (two-hop halo, D2)
    maxd2: int = 0             # max strict-two-hop row width (halo=2 only)
    nbr2: np.ndarray | None = None  # (P, n_local_max, maxd2) two-hop ELL
                                    # slot ids, pad=sentinel (halo=2 only)
    quantize_plan: bool = True  # pow2-rung round widths in ``comm_plan``
                                # (compile-stable plans; byte accounting
                                # stays exact — DESIGN.md §2)

    @property
    def n_slots(self) -> int:
        return self.n_local_max + self.max_ghost + 1

    @property
    def sentinel(self) -> int:
        return self.n_slots - 1

    @property
    def n_interior(self) -> np.ndarray:
        """(P,) count of interior (no ghost neighbour) local vertices."""
        return self.is_internal.sum(axis=1).astype(np.int32)

    @functools.cached_property
    def comm_plan(self) -> CommPlan:
        """Sparse-exchange schedule; built once, cached on the instance."""
        return build_comm_plan(self)

    @property
    def plan_static(self) -> tuple:
        """Hashable ``(shifts, padded widths)`` of ``comm_plan`` — the jit
        cache key, as ``GraphBucket.plan_static`` is a bucket's."""
        return self.comm_plan.static

    def arrays(self, *, sparse: bool = True) -> dict[str, np.ndarray]:
        """Device-ready dict (everything that the JAX kernels consume).

        ``sparse=False`` (all-gather-only runs) skips building and shipping
        the sparse-exchange plan arrays — they would be traced-out anyway,
        but the host-side plan build and host-to-device transfers are not.
        """
        out = dict(
            n_local=self.n_local.astype(np.int32),
            indptr=self.indptr,
            indices=self.indices,
            nbr=self.nbr,
            edge_src=self.edge_src,
            boundary=self.boundary,
            ghost_owner=self.ghost_owner,
            ghost_slot=self.ghost_slot,
            prio=self.prio,
            is_internal=self.is_internal,
            degree=self.degree,
        )
        if self.nbr2 is not None:
            out["nbr2"] = self.nbr2
        if sparse:
            out.update(self.comm_plan.arrays())
        return out

    @tracing.span(tracing.TO_GLOBAL)
    def gather_global_colors(self, local_colors: np.ndarray) -> np.ndarray:
        """(P, n_slots) or (P, n_local_max) device views -> (n_global,) colors."""
        out = np.zeros(self.n_global, dtype=local_colors.dtype)
        for p in range(self.P):
            nl = int(self.n_local[p])
            out[self.offs[p] : self.offs[p] + nl] = local_colors[p, :nl]
        return out


@tracing.span(tracing.PARTITION)
def partition_graph(g: Graph, P: int, *, seed: int = 0,
                    permute: bool = False, halo: int = 1) -> PartitionedGraph:
    """Block-partition `g` onto P processors and build the device layout.

    ``permute=True`` applies a random vertex permutation first (a stand-in for
    a different partitioner; block partitioning on RMAT matches the paper).

    ``halo=2`` builds the two-hop halo for distance-2 coloring: the ghost
    tables extend to every remote vertex within two hops, ``nbr2`` carries the
    strict two-hop neighbourhood in ELL form, and ``boundary``/``is_internal``
    widen to "this color is read by some other shard".  The comm plan and both
    exchange schemes are halo-agnostic — depth-2 ghosts are ordinary
    ghost-table entries (sorted by global id, hence owner-contiguous) and ride
    the same ring-shift ``ppermute`` schedule.
    """
    assert halo in (1, 2), f"halo must be 1 or 2, got {halo}"
    rng = np.random.default_rng(seed)
    # global-id width from n alone; the ELL verdict is re-derived below once
    # maxd is known (id_policy is the single id-width decision point)
    id_dt = id_policy(g.n, 1, 1).id_dtype
    if permute:
        perm = rng.permutation(g.n).astype(id_dt)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(g.n, dtype=id_dt)
        deg = g.degrees
        new_indptr = np.zeros(g.n + 1, dtype=np.int64)
        new_indptr[1:] = np.cumsum(deg[perm])
        new_indices = np.empty_like(g.indices)
        for new_v in range(g.n):  # pragma: no cover - only used in slow paths
            old_v = perm[new_v]
            s, e = g.indptr[old_v], g.indptr[old_v + 1]
            new_indices[new_indptr[new_v] : new_indptr[new_v + 1]] = inv[g.indices[s:e]]
        g = Graph(g.n, new_indptr, new_indices)

    offs = np.linspace(0, g.n, P + 1).astype(np.int64)
    owner_of = np.searchsorted(offs, np.arange(g.n), side="right") - 1
    prio_global = rng.permutation(g.n).astype(id_dt)  # random total order (§2.2)

    n_local = (offs[1:] - offs[:-1]).astype(np.int32)
    n_local_max = int(n_local.max())

    if halo == 2:
        with jax.profiler.TraceAnnotation(tracing.PARTITION_TWO_HOP):
            pairs2 = _two_hop_pairs(g, offs)

    with jax.profiler.TraceAnnotation(tracing.PARTITION_HALO):
        # pass 1: per-shard edge slices, halo sets (the remote vertices whose
        # colors this shard reads) and, at halo=2, the strict two-hop pair lists
        ghosts_of: list[np.ndarray] = []
        edge_of: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        hop2: list[tuple[np.ndarray, np.ndarray, np.ndarray] | None] = []
        for p in range(P):
            lo, hi = int(offs[p]), int(offs[p + 1])
            nl = hi - lo
            nbrs = g.indices[g.indptr[lo] : g.indptr[hi]]
            row = np.repeat(np.arange(nl, dtype=np.int32),
                            np.diff(g.indptr[lo : hi + 1]).astype(np.int32))
            remote = (nbrs < lo) | (nbrs >= hi)
            edge_of.append((nbrs, row, remote))
            if halo == 1:
                # ghosts: unique remote neighbours (searchsorted-friendly order)
                ghosts_of.append(np.unique(nbrs[remote]))
                hop2.append(None)
            else:
                row2, nb2 = pairs2[p]
                rem2 = (nb2 < lo) | (nb2 >= hi)
                ghosts_of.append(np.unique(np.concatenate(
                    [nbrs[remote], nb2[rem2]])))
                hop2.append((row2, nb2, rem2))

        # boundary = local vertices some other shard reads, i.e. members of
        # another shard's halo set.  At halo=1 this is exactly "has a remote
        # neighbour" (the adjacency is symmetric); at halo=2 it widens to the
        # two-hop fringe.
        read_remote = np.zeros(g.n, dtype=bool)
        for gh in ghosts_of:
            read_remote[gh] = True

        rows_indptr, rows_indices, rows_src = [], [], []
        rows_boundary, rows_gowner = [], []
        rows_internal, rows_degree = [], []
        n_ghost = np.zeros(P, dtype=np.int32)
        n_boundary = np.zeros(P, dtype=np.int32)

        for p in range(P):
            lo, hi = int(offs[p]), int(offs[p + 1])
            nl = hi - lo
            nbrs, row, remote = edge_of[p]
            gh = ghosts_of[p]
            slots = np.where(remote, 0, nbrs - lo).astype(np.int32)
            if remote.any():
                slots[remote] = (n_local_max
                                 + np.searchsorted(gh, nbrs[remote])).astype(
                                     np.int32)
            is_bnd = read_remote[lo:hi].copy()
            bnd = np.nonzero(is_bnd)[0].astype(np.int32)
            n_boundary[p] = len(bnd)
            n_ghost[p] = len(gh)

            rows_indptr.append(np.diff(g.indptr[lo : hi + 1]).astype(np.int32))
            rows_indices.append(slots)
            rows_src.append(row)
            rows_boundary.append(bnd)
            gowner = (owner_of[gh].astype(np.int32) if len(gh)
                      else np.zeros(0, np.int32))
            rows_gowner.append(gowner)
            rows_internal.append(~is_bnd)
            rows_degree.append(np.diff(g.indptr[lo : hi + 1]).astype(np.int32))

        # Resolve ghost -> (owner, slot-in-owner-boundary-payload) via one
        # global boundary-slot table (vectorized; P=512 × millions of edges
        # stays fast).
        bslot_global = np.full(g.n, -1, dtype=np.int32)
        for p in range(P):
            lo = int(offs[p])
            bslot_global[rows_boundary[p] + lo] = np.arange(
                len(rows_boundary[p]), dtype=np.int32)
        gslot_rows = [bslot_global[gh] for gh in ghosts_of]

    with jax.profiler.TraceAnnotation(tracing.PARTITION_ELL):
        max_ghost = max(1, int(n_ghost.max()))
        max_boundary = max(1, int(n_boundary.max()))
        m_local_max = max(1, max(len(r) for r in rows_indices))
        n_slots = n_local_max + max_ghost + 1
        sentinel = n_slots - 1

        indptr = np.zeros((P, n_local_max + 1), dtype=np.int32)
        gvid = np.full((P, n_slots), -1, dtype=id_dt)
        prio = np.full((P, n_slots), -1, dtype=id_dt)
        is_internal = np.zeros((P, n_local_max), dtype=bool)
        degree = np.zeros((P, n_local_max), dtype=np.int32)
        for p in range(P):
            nl = int(n_local[p])
            indptr[p, 1 : nl + 1] = np.cumsum(rows_indptr[p])
            indptr[p, nl + 1 :] = indptr[p, nl]
            gh, lo = ghosts_of[p], int(offs[p])
            gvid[p, :nl] = np.arange(lo, lo + nl, dtype=id_dt)
            gvid[p, n_local_max : n_local_max + len(gh)] = gh
            prio[p, :nl] = prio_global[lo : lo + nl]
            prio[p, n_local_max : n_local_max + len(gh)] = prio_global[gh]
            is_internal[p, :nl] = rows_internal[p]
            degree[p, :nl] = rows_degree[p]

        # remap ghost slot-ids in `indices` (they were built against per-p ghost
        # numbering which already starts at n_local_max) and pad
        indices = _pad2(rows_indices, m_local_max, sentinel)
        edge_src = _pad2(rows_src, m_local_max, n_local_max)

        # ELL form of the same adjacency: nbr[p, v, k] = k-th neighbour slot
        # of v, padded with the sentinel (color 0, ignored by the selection
        # kernels).
        maxd = max(1, max(int(r.max(initial=0)) for r in rows_indptr))
        id_policy(g.n, n_local_max, maxd)  # before the ELL allocation: raises
                                           # only past the int64 ceiling
        nbr = np.full((P, n_local_max, maxd), sentinel, dtype=np.int32)
        for p in range(P):
            deg_p = rows_indptr[p].astype(np.int64)
            starts = np.concatenate([[0], np.cumsum(deg_p)])[:-1]
            row = rows_src[p].astype(np.int64)
            col = np.arange(len(row), dtype=np.int64) - starts[row]
            nbr[p, row, col] = rows_indices[p]
        boundary = _pad2(rows_boundary, max_boundary, sentinel)
        ghost_owner = _pad2(rows_gowner, max_ghost, 0)
        ghost_slot = _pad2(gslot_rows, max_ghost, 0)

        # strict two-hop ELL (halo=2): nbr2[p, v, k] = k-th distance-2 slot
        # of v.  Rows come pre-sorted by (v, global id) from _two_hop_pairs,
        # so each vertex's entries are one contiguous run, and a row-major
        # mask of the first cnt[v] columns of each row takes them in order.
        maxd2, nbr2 = 0, None
        if halo == 2:
            slot2_rows = []
            for p in range(P):
                lo = int(offs[p])
                row2, nb2, rem2 = hop2[p]
                slot2 = np.where(rem2, 0, nb2 - lo).astype(np.int32)
                if rem2.any():
                    slot2[rem2] = (n_local_max + np.searchsorted(
                        ghosts_of[p], nb2[rem2])).astype(np.int32)
                cnt = np.bincount(row2, minlength=n_local_max)
                slot2_rows.append((cnt, slot2))
                maxd2 = max(maxd2, int(cnt.max(initial=0)))
            maxd2 = max(1, maxd2)
            id_policy(g.n, n_local_max, maxd, maxd2)
            nbr2 = np.full((P, n_local_max, maxd2), sentinel, dtype=np.int32)
            for p in range(P):
                cnt, slot2 = slot2_rows[p]
                nbr2[p][np.arange(maxd2) < cnt[:, None]] = slot2

    return PartitionedGraph(
        P=P, n_global=g.n, n_local_max=n_local_max, max_ghost=max_ghost,
        max_boundary=max_boundary, m_local_max=m_local_max, maxd=maxd,
        offs=offs, n_local=n_local, n_ghost=n_ghost, n_boundary=n_boundary,
        indptr=indptr, indices=indices, nbr=nbr, edge_src=edge_src,
        boundary=boundary, ghost_owner=ghost_owner, ghost_slot=ghost_slot,
        gvid=gvid, prio=prio, is_internal=is_internal, degree=degree,
        halo=halo, maxd2=maxd2, nbr2=nbr2,
    )


def pad_partition(pg: PartitionedGraph, *, n_local_max: int | None = None,
                  max_ghost: int | None = None, max_boundary: int | None = None,
                  m_local_max: int | None = None, maxd: int | None = None,
                  maxd2: int | None = None) -> PartitionedGraph:
    """Re-pad a partition to larger target maxima (same graph, same blocks).

    The batched multi-graph pipeline (DESIGN.md §8) stacks several
    partitioned graphs on a leading axis, which requires every padded
    dimension to agree across the batch.  This widens the device layout of
    ``pg`` to the given targets and remaps every slot id to the new
    numbering: local slots are unchanged, ghost slots shift by
    ``n_local_max - pg.n_local_max``, and the sentinel moves to the new
    ``n_slots - 1``.  New padding entries are inert by construction (ELL
    pads point at the sentinel, order/``gvid``/``prio`` pads are -1, padded
    local rows have no neighbours and are never visited), so any driver run
    on the padded partition colors the same graph.

    NOTE: padding is *not* bitwise-neutral for randomized selection —
    per-slot random draws (Random-X Fit) depend on ``n_slots``, so a padded
    run is reproducible against runs at the same padded shape, not against
    the unpadded one.  First-Fit/Staggered paths are shape-independent.
    """
    new_nlm = pg.n_local_max if n_local_max is None else int(n_local_max)
    new_mg = pg.max_ghost if max_ghost is None else int(max_ghost)
    new_mb = pg.max_boundary if max_boundary is None else int(max_boundary)
    new_ml = pg.m_local_max if m_local_max is None else int(m_local_max)
    new_maxd = pg.maxd if maxd is None else int(maxd)
    new_maxd2 = pg.maxd2 if maxd2 is None else int(maxd2)
    assert new_nlm >= pg.n_local_max and new_mg >= pg.max_ghost
    assert new_mb >= pg.max_boundary and new_ml >= pg.m_local_max
    assert new_maxd >= pg.maxd and new_maxd2 >= pg.maxd2
    if (new_nlm, new_mg, new_mb, new_ml, new_maxd, new_maxd2) == (
            pg.n_local_max, pg.max_ghost, pg.max_boundary, pg.m_local_max,
            pg.maxd, pg.maxd2):
        return pg

    P = pg.P
    old_nlm, old_sent = pg.n_local_max, pg.sentinel
    new_sent = new_nlm + new_mg
    d_ghost = new_nlm - old_nlm

    def remap(a: np.ndarray) -> np.ndarray:
        """Old-layout slot ids -> new layout (locals keep, ghosts shift)."""
        out = np.where(a >= old_nlm, a + d_ghost, a)
        return np.where(a == old_sent, new_sent, out).astype(np.int32)

    def pad_axis(a: np.ndarray, axis: int, width: int, fill) -> np.ndarray:
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, width - a.shape[axis])
        return np.pad(a, pad, constant_values=fill)

    indptr = pad_axis(pg.indptr, 1, new_nlm + 1, 0)
    indptr[:, old_nlm + 1:] = indptr[:, old_nlm:old_nlm + 1]
    indices = pad_axis(remap(pg.indices), 1, new_ml, new_sent)
    edge_src = np.where(pg.edge_src == old_nlm, new_nlm, pg.edge_src)
    edge_src = pad_axis(edge_src.astype(np.int32), 1, new_ml, new_nlm)
    nbr = pad_axis(pad_axis(remap(pg.nbr), 2, new_maxd, new_sent),
                   1, new_nlm, new_sent)
    boundary = pad_axis(remap(pg.boundary), 1, new_mb, new_sent)
    ghost_owner = pad_axis(pg.ghost_owner, 1, new_mg, 0)
    ghost_slot = pad_axis(pg.ghost_slot, 1, new_mg, 0)
    gvid = np.full((P, new_sent + 1), -1, dtype=pg.gvid.dtype)
    prio = np.full((P, new_sent + 1), -1, dtype=pg.prio.dtype)
    gvid[:, :old_nlm] = pg.gvid[:, :old_nlm]
    gvid[:, new_nlm:new_nlm + pg.max_ghost] = pg.gvid[:, old_nlm:old_sent]
    prio[:, :old_nlm] = pg.prio[:, :old_nlm]
    prio[:, new_nlm:new_nlm + pg.max_ghost] = pg.prio[:, old_nlm:old_sent]
    is_internal = pad_axis(pg.is_internal, 1, new_nlm, False)
    degree = pad_axis(pg.degree, 1, new_nlm, 0)
    nbr2 = None
    if pg.nbr2 is not None:
        nbr2 = pad_axis(pad_axis(remap(pg.nbr2), 2, max(new_maxd2, 1),
                                 new_sent), 1, new_nlm, new_sent)

    return dataclasses.replace(
        pg, n_local_max=new_nlm, max_ghost=new_mg, max_boundary=new_mb,
        m_local_max=new_ml, maxd=new_maxd, maxd2=new_maxd2,
        indptr=indptr, indices=indices, nbr=nbr, edge_src=edge_src,
        boundary=boundary, ghost_owner=ghost_owner, ghost_slot=ghost_slot,
        gvid=gvid, prio=prio, is_internal=is_internal, degree=degree,
        nbr2=nbr2)


def plan_fits(plan: CommPlan, static: tuple) -> bool:
    """True iff ``plan`` embeds into the target ``(shifts, widths)`` schedule.

    Fits = every traffic-bearing ring shift of ``plan`` exists in the
    target and the target's (pow2-rung) buffer width covers the plan's.
    A fitting partition can execute the target's compiled exchange rounds
    bitwise-inertly (sentinel rows on foreign rounds, exact
    ``round_widths`` as data) — the admission gate the continuous serving
    engine probes before swapping a new graph into a freed lane
    (DESIGN.md §11).
    """
    shifts, widths = static
    w = dict(zip(shifts, widths))
    return all(k in w and pw <= w[k]
               for k, pw in zip(plan.shifts, plan.widths))


def remap_plan_arrays(pg, static: tuple) -> dict[str, np.ndarray]:
    """``pg``'s sparse-plan arrays re-laid onto a target static schedule.

    Rounds ``pg`` has no traffic on get an all-sentinel send row (its
    ghosts never match the shift, so the round cannot move its view) and a
    zero in its ``round_widths`` vector — the traced byte-accounting
    override (``comm.exchange_sparse``) that keeps the measured
    ``wire_bytes`` identical to a solo run under ``pg``'s own *exact*
    plan.  This is the mechanism behind both the batched bucket's shared
    schedule (``_union_comm_arrays``) and the serving engine's mid-flight
    lane admission: the target schedule is trace-static, the member's
    rounds are data.  Raises ``ValueError`` when ``plan_fits`` is False.
    """
    shifts, widths = static
    pl = pg.comm_plan
    if not plan_fits(pl, static):
        raise ValueError(f"comm plan {pl.static} does not fit the target "
                         f"schedule {static}")
    P = pg.P
    max_send = max(widths, default=0)
    n_rounds = max(len(shifts), 1)
    s2r = np.full((P,), -1, dtype=np.int32)
    for r, k in enumerate(shifts):
        s2r[k] = r
    w = dict(zip(pl.shifts, pl.widths))
    ex = dict(zip(pl.shifts, pl.exact_widths))
    send = np.full((P, n_rounds, max(max_send, 1)), pg.sentinel, np.int32)
    rw = np.zeros((n_rounds,), np.int32)
    for r, k in enumerate(shifts):
        if k in w:
            rm = pl.shifts.index(k)
            send[:, r, :pl.send_slot.shape[2]] = pl.send_slot[:, rm]
            rw[r] = ex[k]
    return dict(
        send_slot=send, ghost_shift=pl.ghost_shift, ghost_pos=pl.ghost_pos,
        shift_to_round=np.broadcast_to(s2r, (P, P)).copy(),
        round_widths=np.broadcast_to(rw, (P, n_rounds)).copy())


def _union_comm_arrays(members) -> tuple[tuple, list[dict[str, np.ndarray]]]:
    """One shared sparse round schedule for a bucket of padded partitions.

    The sparse exchange unrolls a *static* ``(shifts, widths)`` schedule
    (part of the jit cache key), so every graph in a batch must execute the
    same rounds.  The shared schedule is the union of the members' ring
    shifts, each padded to the bucket-max (pow2-rung) buffer width; every
    member's arrays are then re-laid onto it with ``remap_plan_arrays``
    (sentinel rows on foreign rounds keep each lane bitwise-inert).

    Returns ``((shifts, widths), per-member array dicts)`` where each dict
    carries ``send_slot``/``ghost_shift``/``ghost_pos``/``shift_to_round``
    in the shared schedule plus ``round_widths`` ``(P, n_rounds)`` int32.
    """
    plans = [m.comm_plan for m in members]
    width_of = [dict(zip(pl.shifts, pl.widths)) for pl in plans]
    shifts = tuple(sorted({k for pl in plans for k in pl.shifts}))
    widths = tuple(max(w.get(k, 0) for w in width_of) for k in shifts)
    static = (shifts, widths)
    return static, [remap_plan_arrays(m, static) for m in members]


@dataclasses.dataclass(frozen=True)
class GraphBucket:
    """Same-shape padded partitions, stackable on a leading graph axis.

    Built by ``bucket_graphs``.  ``members[j]`` is the padded partition of
    input graph ``indices[j]``; every padded dimension (and hence every
    device-array shape) agrees across members, so ``stacked_arrays`` returns
    ``(B, P, ...)`` arrays the batched pipeline can vmap over.  The sparse
    comm schedule is the members' union (``plan_static``), with per-member
    ``round_widths`` keeping measured wire bytes exact per graph.
    """

    indices: tuple   # positions of the members in the bucket_graphs() input
    members: tuple   # PartitionedGraph instances, padded to shared dims

    @property
    def B(self) -> int:
        return len(self.members)

    @property
    def P(self) -> int:
        return self.members[0].P

    @functools.cached_property
    def _union_plan(self) -> tuple[tuple, list[dict[str, np.ndarray]]]:
        return _union_comm_arrays(self.members)

    @property
    def plan_static(self) -> tuple:
        """Hashable shared ``(shifts, widths)`` — the batch's jit cache key."""
        return self._union_plan[0]

    @property
    def max_boundary(self) -> int:
        """The members' shared (padded) boundary width."""
        return self.members[0].max_boundary

    def member_arrays(self, j: int, *, sparse: bool = True) -> dict:
        """Device dict of member ``j`` under the *shared* comm schedule."""
        out = self.members[j].arrays(sparse=False)
        if sparse:
            out = dict(out, **self._union_plan[1][j])
        return out

    def stacked_arrays(self, *, sparse: bool = True) -> dict[str, np.ndarray]:
        """All members stacked on a leading graph axis: ``(B, P, ...)``.

        Cached per ``sparse`` flag: a memoized serving bucket re-dispatches
        the same stacked inputs on every warm solo hit, so the stack copy
        must not be a per-request cost.
        """
        cache = self.__dict__.setdefault("_stacked", {})
        if sparse not in cache:
            per = [self.member_arrays(j, sparse=sparse)
                   for j in range(self.B)]
            cache[sparse] = {k: np.stack([d[k] for d in per])
                             for k in per[0]}
        return cache[sparse]


def _ceil_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def bucket_graphs(pgs, *, round_pow2: bool = True) -> list:
    """Group partitioned graphs into shape buckets for batched execution.

    Bucket key: ``(P, halo, n_local_max, maxd, maxd2)`` with the size-like
    dims rounded up to the next power of two (``round_pow2=True``, the
    default) so near-sized graphs share one bucket and one compiled program
    at <= 2x padding waste per keyed dim; ``round_pow2=False`` groups only
    exactly-matching dims.  Within a bucket every member is re-padded
    (``pad_partition``) to the bucket ceilings; the remaining pad widths
    (``max_ghost``/``max_boundary``/``m_local_max``) take the member max,
    also pow2-rounded by default — with every padded dim a power of two,
    a long-running service's bucket *shapes* are stable across request
    waves, so the compiled batch programs keep hitting the jit cache
    (``color_many(pad_batch=True)`` stabilizes the batch axis the same
    way).  Members must already share ``P`` and ``halo`` to share a bucket.

    Returns ``GraphBucket`` objects covering the input exactly;
    ``bucket.indices`` maps members back to input positions.
    """
    rnd = _ceil_pow2 if round_pow2 else int
    groups: dict[tuple, list[int]] = {}
    for i, pg in enumerate(pgs):
        key = (pg.P, pg.halo, rnd(pg.n_local_max), rnd(pg.maxd),
               rnd(pg.maxd2) if pg.halo == 2 else 0)
        groups.setdefault(key, []).append(i)
    buckets = []
    for key in sorted(groups):
        idx = groups[key]
        mem = [pgs[i] for i in idx]
        members = tuple(pad_partition(
            m, n_local_max=key[2], maxd=key[3],
            maxd2=key[4] if key[1] == 2 else 0,
            max_ghost=rnd(max(x.max_ghost for x in mem)),
            max_boundary=rnd(max(x.max_boundary for x in mem)),
            m_local_max=rnd(max(x.m_local_max for x in mem))) for m in mem)
        buckets.append(GraphBucket(indices=tuple(idx), members=members))
    return buckets


@tracing.span(tracing.COMM_PLAN)
def build_comm_plan(pg: PartitionedGraph, *,
                    quantize: bool | None = None) -> CommPlan:
    """Derive the sparse neighbour-to-neighbour schedule from the ghosts.

    Shard q's ghosts are sorted by global vertex id, and block partitioning
    makes ``owner`` monotone in the id — so the ghosts owned by one shard p
    form one contiguous, ascending run.  That run *is* p's send list to q
    (the boundary colors q actually reads), and the position of each ghost
    inside its run is the receive-side gather index.  Both sides are derived
    from the same pass, so they agree by construction.

    ``quantize`` (default ``pg.quantize_plan``, i.e. on) rounds every
    round's *buffer* width up to the next power of two so the plan's static
    part — the jit cache key — takes few distinct values across graphs of
    similar structure (DESIGN.md §2).  The padding entries are sentinel
    slots no receiver ever reads, and byte accounting keeps using the exact
    widths, so a quantized run is bitwise an exact-plan run.
    """
    P = pg.P
    n_send = np.zeros((P, P), dtype=np.int32)
    send_lists: dict[tuple[int, int], np.ndarray] = {}
    ghost_pos = np.zeros((P, pg.max_ghost), dtype=np.int32)
    ghost_shift = np.full((P, pg.max_ghost), -1, dtype=np.int32)

    for q in range(P):
        ng = int(pg.n_ghost[q])
        if ng == 0:
            continue
        owners = pg.ghost_owner[q, :ng]
        vids = pg.gvid[q, pg.n_local_max : pg.n_local_max + ng]
        # contiguous owner runs (owners monotone: vids sorted, blocks ordered)
        starts = np.flatnonzero(np.r_[True, owners[1:] != owners[:-1]])
        ends = np.r_[starts[1:], ng]
        for s, e in zip(starts, ends):
            p = int(owners[s])
            send_lists[(p, q)] = (vids[s:e] - pg.offs[p]).astype(np.int32)
            n_send[p, q] = e - s
            ghost_pos[q, s:e] = np.arange(e - s, dtype=np.int32)
            ghost_shift[q, s:e] = (q - p) % P

    # retain only ring shifts with any traffic; each round pads to its own
    # global (pmax) width, pow2-rung-rounded when the plan is quantized
    srcs, dsts = np.nonzero(n_send)
    all_shifts = (dsts - srcs) % P
    shifts = tuple(int(k) for k in np.unique(all_shifts))
    exact_widths = tuple(
        int(n_send[np.arange(P), (np.arange(P) + k) % P].max())
        for k in shifts)
    if quantize is None:
        quantize = pg.quantize_plan
    widths = (tuple(_ceil_pow2(w) for w in exact_widths) if quantize
              else exact_widths)
    max_send = max(widths, default=0)

    send_slot = np.full((P, max(len(shifts), 1), max(max_send, 1)),
                        pg.sentinel, dtype=np.int32)
    for r, k in enumerate(shifts):
        for p in range(P):
            q = (p + k) % P
            sl = send_lists.get((p, q))
            if sl is not None:
                send_slot[p, r, : len(sl)] = sl

    shift_to_round = np.full((P,), -1, dtype=np.int32)
    for r, k in enumerate(shifts):
        shift_to_round[k] = r

    return CommPlan(
        shifts=shifts, widths=widths, exact_widths=exact_widths,
        max_send=max_send, n_send=n_send,
        send_slot=send_slot, ghost_shift=ghost_shift, ghost_pos=ghost_pos,
        shift_to_round=np.broadcast_to(shift_to_round, (P, P)).copy(),
    )
