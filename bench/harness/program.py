"""The system under test, as a cell's configuration drives it.

Everything the benchmark takes from the program passes through here: the
graph type it accepts, the fused-pipeline configuration a preset makes,
the host preparation, and the executors.  ``src/`` goes on the path from
the checkout this file lies in.
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

from .spec import ROOT

sys.path.insert(0, str(ROOT / "src"))

from repro.core import (Graph, compute_order, partition_graph,  # noqa: E402
                        presets)


def job_seed(seed: int, *index: int) -> int:
    """A 31-bit seed for job ``index`` of run ``seed`` (any size of seed)."""
    ss = np.random.SeedSequence([abs(int(seed)), *index])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def as_graph(csr) -> Graph:
    """The benchmark's CSR as the program's graph type."""
    return Graph(n=csr.n, indptr=csr.indptr, indices=csr.indices)


def pipeline_config(config: dict, seed: int = 0):
    """The fused pipeline the configuration's preset names."""
    pre = config["preset"]
    if pre["name"] != "quality":
        raise ValueError(f"unknown preset {pre['name']!r}")
    preset = presets.quality(x=pre["random_x"], max_colors=pre["max_colors"],
                             superstep=pre["superstep"])
    made = dict(ordering=preset.ordering,
                selection=preset.color_cfg.selection,
                recolor_perm=preset.recolor_perm)
    for key, value in made.items():
        if value != pre[key]:
            raise ValueError(f"preset {key} is {value!r}, the "
                             f"configuration states {pre[key]!r}")
    cfg = presets.pipeline_config(preset, n_iters=pre["recolor_iters"],
                                  patience=pre["patience"], seed=seed)
    kw = dict(scheme=config["scheme"], distance=config["distance"],
              backend=config.get("backend", "auto"))
    return dataclasses.replace(
        cfg, color=dataclasses.replace(cfg.color, **kw),
        recolor=dataclasses.replace(cfg.recolor, **kw))


def prepare(g: Graph, config: dict, seed: int):
    """Host preparation of one coloring: partition and visit order."""
    pg = partition_graph(g, config["P"], seed=seed,
                         halo=config["distance"])
    return pg, compute_order(pg, config["preset"]["ordering"])
