"""The names the coloring program gives its phases in a profiler trace.

Both kinds go through JAX's own profiler, so they land in one trace on one
clock; a trace reduction reads them by these names, which are the contract
between the program and the benchmark.

Device scopes (``jax.named_scope``) are compile-time metadata only: every
operation traced under one carries the scope as a component of its JAX path
(the ``tf_op`` stat of the operation in the device trace, ``op_name`` in the
HLO metadata), at no run-time cost.  A scope name differs from every JAX
primitive name, which is the last component of such a path.

- ``spec_color`` — the speculative coloring (``speculative.color_spmd``);
- ``recolor`` — the recoloring iterations (``pipeline.recolor_loop_spmd``,
  the engine's ``pipeline_step_spmd`` and the stand-alone
  ``recolor.recolor_spmd``);
- ``nbr_gather`` — every neighbour color/priority gather that feeds a
  kernel, with the kernels' transpose and pad (``ops._kernel_operands``);
  it lies inside ``spec_color`` or ``recolor``;
- ``exchange`` — one boundary exchange (``comm.make_exchange``): the
  all-gather or ``ppermute`` rounds and the packing around them.

A scope's self time is its time minus that of the scopes inside it.

Host spans (``jax.profiler.TraceAnnotation``, ``repro.*``) time host work on
the profiler's clock, so each gap in the device trace can be named after the
host work under it.  They cost about a microsecond each while no trace is
taken.  A span's parent is the span open around it on the same thread.

- ``repro.partition`` — ``graph.partition_graph``; its children
  ``repro.partition.two_hop`` (the strict two-hop pairs of every shard,
  ``halo=2`` only), ``repro.partition.halo`` (per-shard edge slices, halo
  sets, slot ids, boundary lists and ghost tables) and
  ``repro.partition.ell`` (padding to the common maxima and the ELL build);
- ``repro.comm_plan`` — ``graph.build_comm_plan`` (built lazily, on the
  first use of a partition's sparse plan);
- ``repro.order`` — ``ordering.compute_order``;
- ``repro.upload`` — a partition's arrays built and sent to the device
  (``pipeline._pipeline_arrays``, once per partition);
- ``repro.program`` — the compiled-program lookup and the dispatch of the
  program; a compile lands here;
- ``repro.wait`` — the host blocked on the device for the run's stats;
- ``repro.to_global`` — colors mapped back to global vertex ids
  (``PartitionedGraph.gather_global_colors``).
"""
from __future__ import annotations

import functools

import jax

SPEC_COLOR = "spec_color"
RECOLOR = "recolor"
NBR_GATHER = "nbr_gather"
EXCHANGE = "exchange"
SCOPES = (SPEC_COLOR, RECOLOR, NBR_GATHER, EXCHANGE)

SPAN_PREFIX = "repro."
PARTITION = "repro.partition"
PARTITION_TWO_HOP = "repro.partition.two_hop"
PARTITION_HALO = "repro.partition.halo"
PARTITION_ELL = "repro.partition.ell"
COMM_PLAN = "repro.comm_plan"
ORDER = "repro.order"
UPLOAD = "repro.upload"
PROGRAM = "repro.program"
WAIT = "repro.wait"
TO_GLOBAL = "repro.to_global"
SPANS = (PARTITION, PARTITION_TWO_HOP, PARTITION_HALO, PARTITION_ELL,
         COMM_PLAN, ORDER, UPLOAD, PROGRAM, WAIT, TO_GLOBAL)


def scope(name: str):
    """Decorator: trace the function's body under the device scope
    ``name``.  A fresh ``jax.named_scope`` per call, so concurrent traces
    on several threads never share one context manager's state."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def span(name: str):
    """Decorator: time each call of a host function as the span ``name``."""
    return functools.partial(jax.profiler.annotate_function, name=name)
