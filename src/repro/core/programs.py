"""Compiled programs: one cache for every driver (DESIGN.md §2).

Every driver of the package looks its jitted program up here: the stage
drivers (``speculative.color_graph``, ``recolor.recolor_step``,
``recolor.arc_sim``), the fused pipeline (``pipeline.run_pipeline``), the
batched ``pipeline.color_many`` and the serving engine's programs.  The
key is a ``PlanSignature``: what the program depends on — its config, P,
the plan statics, every input's shape and dtype, and the mesh axes (or the
simulator's one vmap axis).  Two dispatches with equal signatures share
one program, so the same work compiles once per process.

Device inputs are cached too: ``device_arrays`` uploads a partition once
and serves its buffers to every later dispatch.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp

from repro import tracing

from .comm import AXIS, mesh_axes, run_spmd


def device_arrays(pg, sparse: bool) -> dict:
    """Device-resident input dict, cached on the partition instance.

    JAX arrays are immutable, so the same device buffers serve every
    dispatch of this partition — a memoized serving entry pays the
    host->device transfer once, not per warm request.  ``sparse`` adds
    the sparse plan's arrays (``PartitionedGraph.arrays``).
    """
    cache = pg.__dict__.setdefault("_device_arrays", {})
    if sparse not in cache:
        with jax.profiler.TraceAnnotation(tracing.UPLOAD):
            cache[sparse] = {k: jnp.asarray(v)
                             for k, v in pg.arrays(sparse=sparse).items()}
    return cache[sparse]


@dataclasses.dataclass(frozen=True)
class PlanSignature:
    """Hashable identity of one compiled program (DESIGN.md §2).

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

    kind: str          # program family: color | recolor_<perm> | arc_<perm>
                       # | loop | pipe | many | engine_*
    P: int
    n_local_max: int
    maxd: int
    max_colors: int
    distance: int
    scheme: str        # resolved: "sparse" | "allgather"
    rungs: tuple       # plan static (shifts, pow2 widths); () for allgather
    batch: int         # vmapped graph lanes (0 = solo program)
    cfg: object        # resolved config (trace-static)
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
    """Process-wide LRU of jitted programs keyed on PlanSignature.

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


def program_cache_contains(sig: PlanSignature) -> bool:
    """Cache-probe for the serving cost model — no counter side effects."""
    return sig in _PROGRAMS._fns


def cached_jit(sig: PlanSignature, fn, **jit_kw):
    """The program of ``sig``: on a miss, ``jax.jit(fn, **jit_kw)`` with
    its traces counted."""
    def build():
        def wrapped(*args):
            _PROGRAMS.traces += 1
            return fn(*args)
        return jax.jit(wrapped, **jit_kw)
    return _PROGRAMS.get(sig, build)


def spmd_program(sig: PlanSignature, fn, P: int, n_sharded: int, mesh=None,
                 *, lanes: bool = False, donate_argnums=()):
    """The program of ``sig`` running the per-shard ``fn`` through
    ``comm.run_spmd`` on ``mesh`` (``None`` = the one-device simulator):
    its first ``n_sharded`` arguments carry the shard axis, the rest are
    replicated (``lanes=False``) or per-lane (``lanes=True``)."""
    return cached_jit(
        sig, lambda *args: run_spmd(fn, mesh, P, args[:n_sharded],
                                    args[n_sharded:], lanes=lanes),
        donate_argnums=donate_argnums)


def dispatch(sig: PlanSignature, fn, P: int, mesh, sharded_args: tuple,
             other_args: tuple = (), *, lanes: bool = False):
    """Look the program up (building it on a miss) and run it; its first
    call compiles, inside the span."""
    with jax.profiler.TraceAnnotation(tracing.PROGRAM):
        prog = spmd_program(sig, fn, P, len(sharded_args), mesh, lanes=lanes)
        return prog(*sharded_args, *other_args)


def signature(kind: str, P: int, cfg, plan_static, arrs, *, stage=None,
              batch: int = 0, mesh=None) -> PlanSignature:
    """The ``PlanSignature`` of a ``kind`` program of ``cfg`` over the
    input dict ``arrs`` (host or device arrays; per-shard or stacked —
    ``n_local_max`` and ``maxd`` are read from the last dims).  ``stage``
    is the stage config whose ``max_colors``, ``distance`` and ``scheme``
    the signature names (default ``cfg``).  The simulator's ``axes`` are
    its one vmap axis, ``(("workers", P),)``."""
    stage = cfg if stage is None else stage
    return PlanSignature(
        kind=kind, P=P, n_local_max=int(arrs["indptr"].shape[-1]) - 1,
        maxd=int(arrs["nbr"].shape[-1]), max_colors=stage.max_colors,
        distance=stage.distance, scheme=stage.scheme,
        rungs=plan_static if plan_static is not None else (),
        batch=batch, cfg=cfg,
        dims=tuple(sorted((k, tuple(v.shape), str(v.dtype))
                          for k, v in arrs.items())),
        axes=((AXIS, P),) if mesh is None else mesh_axes(mesh), extra=mesh)
