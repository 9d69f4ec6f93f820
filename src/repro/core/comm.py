"""Communication abstraction: one SPMD code path, two executors, two schemes.

Algorithms in this package are written as *per-shard* SPMD functions that
communicate exclusively through ``AxisComm`` (named-axis collectives). They
can then run

- **simulated** on a single device via ``jax.vmap(..., axis_name=AXIS)`` —
  used for the paper's quality/scaling studies (P up to 512 simulated
  processors on one CPU), and
- **sharded** on a real device mesh via ``shard_map`` — the production
  path; the multi-pod dry-run lowers exactly this.

Two interchangeable boundary-exchange schemes (``CommConfig.scheme``) produce
bitwise-identical colorings:

- ``"allgather"`` — every shard broadcasts its whole boundary payload; the
  ghost refresh gathers from the (P, max_b) table.  O(P·max_b) wire bytes per
  exchange regardless of which cross edges exist.
- ``"sparse"`` — the paper's neighbour-to-neighbour scheme: a static round
  schedule of ``ppermute`` hops (one per *ring shift* with any traffic, see
  ``graph.CommPlan``) ships each destination only the boundary colors its
  ghosts actually read.  Wire bytes scale with the realized cross-edge
  structure, not with P; a graph with zero cross edges performs zero rounds.

Every exchange returns the per-shard wire bytes it shipped (a traced scalar
accumulated through the drivers' loop carries), so benchmarks and
``launch/dryrun.py`` report *measured* comm volume next to the modeled one.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import tracing


AXIS = "workers"        # default shard (graph-partition) mesh axis
BATCH_AXIS = "batch"    # graph-batch mesh axis of 2D batch×shard meshes
LANE_AXIS = "lanes"     # vmap axis of the graph lanes one device holds

ALLGATHER = "allgather"
SPARSE = "sparse"
SCHEMES = (ALLGATHER, SPARSE)          # the two concrete exchange programs
AUTO = "auto"                          # resolve at trace time from the plan
SCHEME_CHOICES = SCHEMES + (AUTO,)

# Default exchange scheme for every config that does not set one explicitly.
# The default is AUTO: the drivers pick sparse vs allgather per graph at
# trace time from the modeled bytes (``resolve_scheme``) — the two schemes
# produce bitwise-identical colorings, so the choice is a pure cost call
# and the user flag is an override.  REPRO_SCHEME drives the CI matrix: the
# tier-1 suite runs once per scheme so both exchange paths (and the auto
# resolution itself) stay covered per push.
DEFAULT_SCHEME = os.environ.get("REPRO_SCHEME", AUTO)
assert DEFAULT_SCHEME in SCHEME_CHOICES, (
    f"REPRO_SCHEME={DEFAULT_SCHEME!r} invalid, want one of {SCHEME_CHOICES}")


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Static configuration of the boundary exchange."""

    scheme: str = DEFAULT_SCHEME   # "allgather" | "sparse" | "auto"
    wire16: bool = False           # int16 payloads (half the wire bytes)

    def __post_init__(self):
        assert self.scheme in SCHEME_CHOICES, f"bad scheme {self.scheme!r}"

    @property
    def wire_dtype(self):
        return jnp.int16 if self.wire16 else None

    @property
    def itemsize(self) -> int:
        return 2 if self.wire16 else 4


@dataclasses.dataclass(frozen=True)
class AxisComm:
    """Named-axis collectives used by the coloring SPMD kernels.

    ``axis`` is the shard (graph-partition) axis every data collective runs
    over.  ``lane_axes`` names *additional* mesh axes the program's control
    flow must be uniform over — on a 2D ``batch × shard`` mesh, graph lanes
    on different batch rows take data-dependent trip counts and exchange
    decisions, but one SPMD program spans the whole mesh, so every device
    must execute the same collective sequence.  ``lane_uniform`` widens an
    already shard-uniform control value (a loop bound, an exchange
    predicate) across the lane axes; each lane then *applies* the effect
    under its own local predicate, keeping results bitwise the solo run's
    (DESIGN.md §10).  With no lane axes (sim, 1-axis meshes) it compiles
    to nothing, so those programs are unchanged.
    """

    axis: str = AXIS
    lane_axes: tuple = ()

    def psum(self, x):
        return jax.lax.psum(x, self.axis)

    def pmax(self, x):
        return jax.lax.pmax(x, self.axis)

    def pmin(self, x):
        return jax.lax.pmin(x, self.axis)

    def all_gather(self, x):
        """per-shard (…,) -> (P, …) table, identical on every shard."""
        return jax.lax.all_gather(x, self.axis)

    def ppermute(self, x, perm):
        """Point-to-point shuffle along the axis (source, dest) pairs."""
        return jax.lax.ppermute(x, self.axis, perm)

    def index(self):
        return jax.lax.axis_index(self.axis)

    def size(self) -> int:
        """Number of shards on the axis (static: no collective)."""
        return jax.lax.axis_size(self.axis)

    def lane_uniform(self, x):
        """Max-reduce a shard-uniform control value over the lane axes.

        Identity when the mesh has none (``lane_axes == ()``); otherwise
        the mesh-wide bound/predicate every device agrees to execute
        under (bools reduce as "any lane needs it").  Only *execution* is
        widened — callers mask per-lane application with the lane's own
        local value so lane results stay bitwise.
        """
        return jax.lax.pmax(x, self.lane_axes) if self.lane_axes else x


def shard_axis_of(mesh) -> str:
    """The mesh axis the coloring core shards graph partitions over.

    The axis-name contract (DESIGN.md §10): a ``workers`` axis always wins;
    otherwise the single non-``batch`` axis; otherwise (degenerate smoke
    meshes where every axis has size 1, e.g. ``make_local_mesh``) the last
    axis.  Ambiguous multi-axis meshes raise — the caller must build its
    mesh through ``launch.mesh.MeshSpec`` so the names are explicit.
    """
    names = tuple(mesh.axis_names)
    if AXIS in names:
        return AXIS
    cands = [n for n in names if n != BATCH_AXIS]
    if len(cands) == 1:
        return cands[0]
    sized = [n for n in cands if int(mesh.shape[n]) > 1]
    if len(sized) == 1:
        return sized[0]
    if cands and not sized:          # all-size-1 smoke mesh: any axis works
        return cands[-1]
    raise ValueError(
        f"cannot infer the shard axis of mesh axes {names}: none is named "
        f"{AXIS!r} and {len(sized)} non-{BATCH_AXIS!r} axes have size > 1; "
        f"build the mesh via launch.mesh.MeshSpec")


def batch_axis_of(mesh) -> str | None:
    """The graph-batch axis of a 2D ``batch × shard`` mesh (None if 1D)."""
    return BATCH_AXIS if BATCH_AXIS in tuple(mesh.axis_names) else None


def batch_axis_size(mesh) -> int:
    """Size of the graph-batch mesh axis (1 when the mesh has none)."""
    b = batch_axis_of(mesh)
    return int(mesh.shape[b]) if b is not None else 1


def mesh_axes(mesh) -> tuple:
    """Hashable ``((axis name, axis size), ...)`` — the program-cache key
    component that pins which mesh geometry a sharded program was traced
    for (``pipeline.PlanSignature.axes``)."""
    return tuple((n, int(s)) for n, s in zip(mesh.axis_names,
                                             mesh.devices.shape))


def shard_uniform(x):
    """Identity marker: assert-by-contract that ``x`` is shard-uniform.

    Some values are uniform by *contract* rather than by construction — a
    round mask computed from a pmax-reduced schedule and passed down as a
    plain parameter, or a class count every caller derives from globally
    psum-reduced sizes.  Wrapping them in ``shard_uniform`` documents the
    contract at the consumption site and lets repro-lint's
    ``divergent-collective``/``nonuniform-loop`` rules (DESIGN.md §9)
    treat the value as uniform instead of demanding a redundant collective.
    It compiles to nothing (returns its argument unchanged).
    """
    return x


def allgather_bytes_per_exchange(P_size: int, max_boundary: int,
                                 itemsize: int = 4) -> int:
    """Per-shard wire bytes of one broadcast exchange (ring all-gather:
    every shard receives the other P-1 payloads of max_b entries).  The one
    home of the all-gather cost model — the sparse counterpart lives in
    ``graph.CommPlan.bytes_per_exchange``."""
    return (P_size - 1) * max_boundary * itemsize


def resolve_scheme(scheme: str, pg) -> str:
    """The trace-time sparse-vs-allgather decision (DESIGN.md §2).

    ``scheme`` other than ``"auto"`` is a user override and returns as-is.
    ``"auto"`` picks whichever exchange *physically ships* fewer bytes for
    this partition: the sparse plan's padded (pow2-rung) buffer widths —
    what the compiled ``ppermute`` rounds actually put on the wire —
    against the ring all-gather's ``(P-1)·max_b``.  Both schemes produce
    bitwise-identical colorings, so this is a pure cost decision; the
    result lands in the program's ``PlanSignature``/jit key, never in user
    config.  Ties go to sparse (fewer bytes *accounted* too, and zero
    rounds on cross-edge-free partitions).
    """
    if scheme != AUTO:
        return scheme
    sparse_b = pg.comm_plan.bytes_per_exchange(padded=True)
    return SPARSE if sparse_b <= allgather_bytes_per_exchange(
        pg.P, pg.max_boundary) else ALLGATHER


def stats_to_host(stats) -> dict:
    """Device stats dict -> python ints.

    Works for 0-d scalars, per-shard ``(P,)`` stacks from ``run_sim`` and
    sharded outputs alike: every stat is either shard-uniform (schedules are
    pmax-reduced) or a quantity whose shard-max is the meaningful summary.

    This is the pipeline's *single* blessed device->host exit (repro-lint's
    ``host-sync`` rule, DESIGN.md §9): the shard-maxes are launched async on
    device and the whole dict crosses in one ``device_get``, not one
    blocking ``int()`` per stat.
    """
    host = jax.device_get({k: jnp.max(v) for k, v in stats.items()})
    return {k: int(v) for k, v in host.items()}


def run_sim(fn, P_size: int, sharded_args: tuple, broadcast_args: tuple = (),
            axis: str = AXIS):
    """Execute SPMD `fn` on ONE device by vmapping over the leading P axis.

    ``sharded_args`` carry a leading axis of size ``P_size``; ``broadcast_args``
    are replicated. `fn(*sharded, *broadcast)` must only communicate via
    ``AxisComm`` (over ``axis``).
    """
    in_axes = tuple(0 for _ in sharded_args) + tuple(None for _ in broadcast_args)
    return jax.vmap(fn, in_axes=in_axes, axis_name=axis,
                    axis_size=P_size)(*sharded_args, *broadcast_args)


def run_sharded(fn, mesh, sharded_args: tuple, broadcast_args: tuple = (),
                axis: str | None = None):
    """Execute SPMD `fn` over a real mesh shard axis via shard_map.

    ``axis`` defaults to ``shard_axis_of(mesh)`` — the coloring core never
    assumes the axis is literally named ``workers``.
    """
    axis = shard_axis_of(mesh) if axis is None else axis

    def wrapped(*args):
        ns = len(sharded_args)
        sh = [jax.tree.map(lambda x: x[0], a) for a in args[:ns]]
        out = fn(*sh, *args[ns:])
        return jax.tree.map(lambda x: x[None], out)

    in_specs = tuple(P(axis) for _ in sharded_args) + tuple(
        P() for _ in broadcast_args)
    # check_vma=False: loop carries start replicated and become
    # worker-varying after the first exchange
    return jax.shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                         out_specs=P(axis), check_vma=False)(
                                *sharded_args, *broadcast_args)


def run_sharded_many(fn, mesh, sharded_args: tuple, lane_args: tuple = (),
                     axis: str | None = None):
    """Execute a lane-vmapped SPMD ``fn`` on a 2D ``batch × shard`` mesh.

    ``fn`` is the per-shard program already vmapped over a leading graph-lane
    axis (``jax.vmap(color_then_recolor)``-style).  ``sharded_args`` carry
    ``(P, B, ...)``: dim 0 shards over the shard axis, dim 1 over the batch
    axis (so each device holds ``B / batch_size`` lanes of one shard) —
    the vmap graph axis and the shard_map graph axis are distinct mesh
    dimensions instead of vmap-inside-shard_map.  ``lane_args`` carry
    ``(B, ...)`` per-lane values (RNG keys): sharded over the batch axis
    only, replicated across shards.

    On a mesh without a ``batch`` axis this defers to ``run_sharded`` with
    the lanes as broadcast args — bitwise (and program-structure-wise) the
    1-axis ``color_many_sharded`` path, which is also what a 2D mesh with
    ``batch=1`` lowers to per shard.  ``B`` must divide by the batch-axis
    size (the pipeline driver pads lanes to a multiple).
    """
    axis = shard_axis_of(mesh) if axis is None else axis
    baxis = batch_axis_of(mesh)
    if baxis is None:
        return run_sharded(fn, mesh, sharded_args, lane_args, axis=axis)
    # 2D mesh (a batch=1 axis included — every device then holds all B
    # lanes, which is exactly the 1-axis per-shard program):

    def wrapped(*args):
        ns = len(sharded_args)
        sh = [jax.tree.map(lambda x: x[0], a) for a in args[:ns]]
        out = fn(*sh, *args[ns:])
        return jax.tree.map(lambda x: x[None], out)

    in_specs = tuple(P(axis, baxis) for _ in sharded_args) + tuple(
        P(baxis) for _ in lane_args)
    return jax.shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                         out_specs=P(axis, baxis), check_vma=False)(
                                *sharded_args, *lane_args)


def exchange_boundary(view: jnp.ndarray, boundary: jnp.ndarray,
                      ghost_owner: jnp.ndarray, ghost_slot: jnp.ndarray,
                      n_local_max: int, comm: AxisComm,
                      wire_dtype=None) -> jnp.ndarray:
    """One broadcast boundary-color exchange (all-gather scheme).

    Ships only boundary colors: payload (max_b,), all-gathered to (P, max_b);
    ghost slots refresh with one gather. ``wire_dtype=jnp.int16`` halves the
    ICI bytes (colors are bounded by max_colors <= 32767, config-asserted);
    see DESIGN.md §6.
    """
    payload = view[boundary]                      # (max_b,)
    if wire_dtype is not None:
        payload = payload.astype(wire_dtype)
    table = comm.all_gather(payload)              # (P, max_b)
    ghosts = table[ghost_owner, ghost_slot]       # (max_g,)
    return jax.lax.dynamic_update_slice(view, ghosts.astype(view.dtype),
                                        (n_local_max,))


def exchange_sparse(view: jnp.ndarray, send_slot: jnp.ndarray,
                    ghost_shift: jnp.ndarray, ghost_pos: jnp.ndarray,
                    shifts: tuple, widths: tuple, P_size: int,
                    n_local_max: int, comm: AxisComm, wire_dtype=None,
                    itemsize: int = 4, round_mask=None,
                    byte_widths=None, apply_mask=None) -> jnp.ndarray:
    """One sparse neighbour-to-neighbour exchange (``ppermute`` rounds).

    Round ``r`` ships, for every shard p at once, the ``widths[r]`` boundary
    colors that destination ``(p + shifts[r]) % P`` actually reads
    (``send_slot[r]``, sentinel-padded).  The receiver refreshes exactly the
    ghosts whose owner sits ``shifts[r]`` ring positions behind it
    (``ghost_shift == shifts[r]``) from position ``ghost_pos`` of the buffer.
    The schedule (shifts, widths) is static per graph — rounds with zero
    global traffic do not exist, so a graph with no cross edges exchanges
    zero bytes.

    ``round_mask`` (optional, (n_rounds,) bool, shard-uniform) lets callers
    skip rounds no destination currently needs (the sparse form of the
    paper's piggybacking, see recolor.py); skipped rounds cost no wire bytes.

    ``apply_mask`` (optional, (n_rounds,) bool) masks which executed rounds
    this caller actually *applies* (ghost refresh + byte accounting).  On a
    2D ``batch × shard`` mesh the executed schedule is the lane-uniform
    union over batch lanes — every device must run the same ``ppermute``
    sequence — while each lane keeps its own piggyback schedule here, so a
    lane never refreshes a ghost (or accounts a byte) ahead of its solo
    schedule.  ``None`` applies every executed round (the 1-axis/sim path,
    where ``round_mask`` already *is* the lane's own schedule).

    ``byte_widths`` (optional, (n_rounds,) int32, traced) overrides the
    *accounted* payload width per round without changing the shipped buffer
    shape.  The batched multi-graph pipeline runs every graph of a bucket on
    the union round schedule (``graph._union_comm_arrays``); a graph's own
    narrower (or absent) round still ships the union-width buffer — the
    extra entries are sentinel colors no receiver reads — but its measured
    ``wire_bytes`` stay those of its own plan, bitwise matching a solo run.
    Returns ``(view, wire_bytes)``.
    """
    n_ghost_slots = view.shape[0] - n_local_max - 1
    ghosts = jax.lax.dynamic_slice(view, (n_local_max,), (n_ghost_slots,))
    # contract: the round mask comes out of the pmax-reduced piggyback
    # schedule (recolor._needed_exchange_rounds), so every shard agrees on
    # which ppermute rounds run — a shard skipping a round its peer
    # executes would deadlock the exchange.
    round_mask = shard_uniform(round_mask)
    total = jnp.int32(0)
    for r, (k, w) in enumerate(zip(shifts, widths)):
        perm = [(i, (i + k) % P_size) for i in range(P_size)]
        mine = ghost_shift == k

        def do_round(args, perm=perm, r=r, w=w, mine=mine):
            ghosts, total = args
            payload = view[send_slot[r, :w]]
            if wire_dtype is not None:
                payload = payload.astype(wire_dtype)
            buf = comm.ppermute(payload, perm)
            vals = buf[jnp.minimum(ghost_pos, w - 1)].astype(ghosts.dtype)
            b = (jnp.int32(w * itemsize) if byte_widths is None
                 else byte_widths[r].astype(jnp.int32) * itemsize)
            keep = mine
            if apply_mask is not None:
                keep = mine & apply_mask[r]
                b = jnp.where(apply_mask[r], b, jnp.int32(0))
            return jnp.where(keep, vals, ghosts), total + b

        if round_mask is None:
            ghosts, total = do_round((ghosts, total))
        else:
            ghosts, total = jax.lax.cond(round_mask[r], do_round,
                                         lambda a: a, (ghosts, total))
    view = jax.lax.dynamic_update_slice(view, ghosts.astype(view.dtype),
                                        (n_local_max,))
    return view, total


def make_exchange(arrs, n_local_max: int, P_size: int, comm: AxisComm,
                  cfg: CommConfig, plan_static):
    """Build ``exchange(view[, round_mask]) -> (view, wire_bytes)``.

    ``plan_static`` is ``(shifts, widths)`` from ``PartitionedGraph.comm_plan``
    (hashable, part of the jit cache key).  Under the all-gather scheme the
    modeled wire bytes are ``(P-1) * max_b * itemsize`` per exchange — what a
    ring all-gather makes every shard receive; ``round_mask`` is ignored
    (the broadcast always ships everything).
    """
    if cfg.scheme == SPARSE:
        shifts, widths = plan_static
        # present only for bucketed (batched multi-graph) arrays: the
        # per-graph byte-accounting override on the shared round schedule
        byte_widths = arrs.get("round_widths")

        @tracing.scope(tracing.EXCHANGE)
        def exchange(view, round_mask=None, apply_mask=None):
            return exchange_sparse(
                view, arrs["send_slot"], arrs["ghost_shift"],
                arrs["ghost_pos"], shifts, widths, P_size, n_local_max,
                comm, wire_dtype=cfg.wire_dtype, itemsize=cfg.itemsize,
                round_mask=round_mask, byte_widths=byte_widths,
                apply_mask=apply_mask)

        return exchange

    max_b = arrs["boundary"].shape[0]
    if P_size is None:
        p_count = jax.lax.psum(jnp.int32(1), comm.axis)
        bytes_per_ex = (p_count - 1) * jnp.int32(max_b * cfg.itemsize)
    else:
        bytes_per_ex = jnp.int32(
            allgather_bytes_per_exchange(P_size, max_b, cfg.itemsize))

    @tracing.scope(tracing.EXCHANGE)
    def exchange(view, round_mask=None, apply_mask=None):
        view = exchange_boundary(
            view, arrs["boundary"], arrs["ghost_owner"], arrs["ghost_slot"],
            n_local_max, comm, wire_dtype=cfg.wire_dtype)
        return view, bytes_per_ex

    return exchange
