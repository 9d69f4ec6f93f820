"""Conflict-free scheduling via graph coloring — the paper's use case, live.

The paper's motivation (§1): "organizing computations so that no two
concurrent procedures access shared resources simultaneously". In a training
pipeline this appears when samples in a batch contend for the same mutable
resource — hot embedding rows updated sparsely, per-expert buffers, feature
hash buckets. Build the conflict graph (samples = vertices, shared resource =
edge), color it with the core library, and each color class becomes a
microbatch whose updates are write-conflict-free.

This module is exercised by examples/coloring_sched.py and tests.
"""
from __future__ import annotations

import numpy as np

from repro.core import colors_from_views, partition_graph, presets
from repro.core.graph import Graph


def conflict_graph(resources: list[np.ndarray] | np.ndarray,
                   n_samples: int) -> Graph:
    """Samples sharing any resource id become adjacent.

    `resources`: (n_samples, r) int array (or list of variable-length arrays)
    of resource ids each sample touches.
    """
    if isinstance(resources, np.ndarray):
        resources = [resources[i] for i in range(resources.shape[0])]
    by_res: dict[int, list[int]] = {}
    for s, rs in enumerate(resources):
        for r in np.unique(rs):
            by_res.setdefault(int(r), []).append(s)
    src, dst = [], []
    for members in by_res.values():
        m = np.asarray(members)
        if len(m) < 2:
            continue
        # clique over samples sharing the resource
        i, j = np.triu_indices(len(m), k=1)
        src.append(m[i])
        dst.append(m[j])
    if not src:
        indptr = np.zeros(n_samples + 1, np.int64)
        return Graph(n_samples, indptr, np.zeros(0, np.int32))
    from repro.core.rmat import _edges_to_graph
    return _edges_to_graph(n_samples,
                           np.concatenate(src).astype(np.int32),
                           np.concatenate(dst).astype(np.int32))


def schedule(resources, n_samples: int, *, n_workers: int = 1,
             use_quality_preset: bool = True, seed: int = 0):
    """Color the conflict graph; return (groups, n_groups, stats).

    groups: list of np arrays of sample ids — each group is conflict-free and
    can be applied as one parallel microbatch.
    """
    g = conflict_graph(resources, n_samples)
    pg = partition_graph(g, n_workers, seed=seed)
    preset = presets.quality() if use_quality_preset else presets.speed()
    view, log = presets.run_preset(pg, preset, seed=seed)
    colors = colors_from_views(pg, np.asarray(view))
    n_groups = int(colors.max(initial=0))
    groups = [np.nonzero(colors == c)[0] for c in range(1, n_groups + 1)]
    return groups, n_groups, log


def schedule_many(batches, n_samples: int, *, n_workers: int = 1,
                  n_iters: int = 1, seed: int = 0):
    """Schedule MANY sample batches at once via the batched pipeline.

    ``batches`` is a sequence of per-batch resource arrays (each as in
    ``schedule``); every batch's conflict graph is partitioned and the
    whole set is dispatched through ``core.color_many`` — bucketed padding,
    one fused program per shape bucket (DESIGN.md §8), the serving shape of
    a training pipeline that colors a fresh conflict graph per step.
    Returns one ``(groups, n_groups, stats)`` triple per batch.
    """
    from repro.core import color_many

    pgs = [partition_graph(conflict_graph(res, n_samples), n_workers,
                           seed=seed) for res in batches]
    preset = presets.quality(iters=n_iters)
    cfg = presets.pipeline_config(preset, seed=seed)
    out = []
    for r in color_many(pgs, cfg, orders=preset.ordering, pad_batch=True):
        colors = r["colors"]
        n_groups = int(colors.max(initial=0))
        groups = [np.nonzero(colors == c)[0] for c in range(1, n_groups + 1)]
        out.append((groups, n_groups, dict(color=r["color"],
                                           history=r["history"],
                                           bucket=r["bucket"])))
    return out


def validate_schedule(resources, groups) -> bool:
    """No two samples in a group share a resource."""
    if isinstance(resources, np.ndarray):
        resources = [resources[i] for i in range(resources.shape[0])]
    for grp in groups:
        seen: set[int] = set()
        for s in grp:
            rs = set(int(r) for r in np.unique(resources[int(s)]))
            if seen & rs:
                return False
            seen |= rs
    return True
