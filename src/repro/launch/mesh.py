"""Mesh construction — the one place axis names are decided.

``MeshSpec`` is the axis-name contract (DESIGN.md §10) in code: every mesh
this repo builds — the coloring core's 1D ``workers`` mesh, the 2D
``batch × shard`` serving mesh, the LM stack's ``data``/``model`` meshes —
comes from a spec, so ``core.comm.shard_axis_of`` and the smoke tests
always agree on what each axis means.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is the cross-pod (DCN/ICI-bridge) dimension; DP and FSDP extend over it.
Coloring:   (batch, workers) — graph partitions shard over ``workers``,
graph lanes of the batched pipeline shard over ``batch``.

Functions, not module constants: importing this module never touches JAX
device state (the dry-run must set XLA_FLAGS before first device init).
"""
from __future__ import annotations

import dataclasses

import jax

from repro.core.comm import AXIS, BATCH_AXIS


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh geometry: parallel ``shape`` / ``axes`` tuples.

    ``build()`` materializes the device mesh (touching JAX device state);
    the spec itself is hashable and cheap, so program-cache keys and
    configs can carry it.  The classmethods are the repo's canonical
    layouts — call sites should not invent axis names.
    """

    shape: tuple
    axes: tuple

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    @classmethod
    def worker(cls, n_workers: int) -> "MeshSpec":
        """Flat 1-axis coloring mesh: every device is one graph shard."""
        return cls((n_workers,), (AXIS,))

    @classmethod
    def coloring(cls, n_workers: int, batch: int = 1) -> "MeshSpec":
        """2D ``batch × shard`` coloring mesh (``batch=1`` is bitwise the
        1-axis path per shard; batch>1 shards graph lanes of the batched
        pipeline over devices)."""
        return cls((batch, n_workers), (BATCH_AXIS, AXIS))

    @classmethod
    def production(cls, *, multi_pod: bool = False) -> "MeshSpec":
        if multi_pod:
            return cls((2, 16, 16), ("pod", "data", "model"))
        return cls((16, 16), ("data", "model"))

    @classmethod
    def local(cls) -> "MeshSpec":
        """Degenerate 1-device smoke mesh (both LM axes size 1)."""
        return cls((1, 1), ("data", "model"))

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= int(s)
        return n

    def build(self):
        return jax.make_mesh(
            self.shape, self.axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(self.axes))


def make_production_mesh(*, multi_pod: bool = False):
    return MeshSpec.production(multi_pod=multi_pod).build()


def make_worker_mesh(n_workers: int | None = None):
    """Flat 1-axis mesh for the coloring core (uses every device)."""
    n = n_workers or len(jax.devices())
    return MeshSpec.worker(n).build()


def make_coloring_mesh(n_workers: int | None = None, batch: int = 1):
    """2D ``(batch, workers)`` coloring mesh; needs batch × workers devices.

    ``batch`` shards the batched pipeline's graph-lane axis
    (``color_many_sharded``); solo dispatches replicate over it.
    """
    n = n_workers or len(jax.devices()) // batch
    return MeshSpec.coloring(n, batch).build()


def make_local_mesh():
    """Degenerate mesh for CPU smoke tests (1 device, both axes size 1)."""
    return MeshSpec.local().build()


def engine_lanes(mesh, lanes: int) -> int:
    """Lane count a continuous-batching engine on ``mesh`` must allocate.

    The engine's lane axis is sharded over the mesh's ``batch`` axis
    (``run_sharded_many``), so the configured ``ServeConfig.lanes`` is
    rounded up to a multiple of the batch axis size; ``mesh=None`` (sim
    executor) and 1D meshes keep it as-is.
    """
    lanes = max(1, int(lanes))
    if mesh is None:
        return lanes
    from repro.core.comm import batch_axis_size
    b = batch_axis_size(mesh)
    return -(-lanes // b) * b
