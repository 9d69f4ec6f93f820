"""The paper's two recommended parameter sets (§4.3, §5).

  "speed"   — FIxxND0: First Fit, Internal-First ordering, no recoloring.
  "quality" — R(5–10)IxxND1: Random-X Fit (X=5..10), Internal-First ordering,
              one (or more) ND recoloring iterations.
"""
from __future__ import annotations

import dataclasses

from . import ordering, selection
from .recolor import ND, RecolorConfig
from .speculative import ColorConfig


@dataclasses.dataclass(frozen=True)
class Preset:
    name: str
    ordering: str
    color_cfg: ColorConfig
    recolor_iters: int
    recolor_perm: str = ND


def speed(max_colors: int = 1024, superstep: int = 512) -> Preset:
    return Preset(
        name="speed", ordering=ordering.INTERNAL_FIRST,
        color_cfg=ColorConfig(max_colors=max_colors, superstep=superstep,
                              selection=selection.FIRST_FIT),
        recolor_iters=0,
    )


def quality(x: int = 10, max_colors: int = 1024, superstep: int = 512,
            iters: int = 1) -> Preset:
    return Preset(
        name="quality", ordering=ordering.INTERNAL_FIRST,
        color_cfg=ColorConfig(max_colors=max_colors, superstep=superstep,
                              selection=selection.RANDOM_X, random_x=x),
        recolor_iters=iters,
    )


def pipeline_config(preset: Preset, *, n_iters: int | None = None,
                    patience: int = 0, seed: int = 0):
    """A preset as one fused-pipeline config (``run_pipeline``-ready).

    ``n_iters`` overrides the preset's recoloring budget (``patience`` adds
    the adaptive stop on top); the RNG streams match ``run_preset``'s, so
    both entry points produce identical colorings for the same seed.
    """
    from .pipeline import PipelineConfig

    return PipelineConfig(
        color=dataclasses.replace(preset.color_cfg, seed=seed),
        recolor=RecolorConfig(max_colors=preset.color_cfg.max_colors,
                              seed=seed),
        n_iters=preset.recolor_iters if n_iters is None else n_iters,
        base_perm=preset.recolor_perm, patience=patience, seed=seed)


def run_preset(pg, preset: Preset, seed: int = 0):
    """Initial coloring + recoloring per the preset; returns (view, log).

    Runs device-resident through the fused pipeline when the preset
    recolors (one jitted program; bitwise the split dispatch it replaced);
    ``log`` is one dict per stage: ``stage="initial"`` with the coloring
    stats, then one ``stage="recolor"`` entry per executed iteration.
    """
    from . import ordering as ord_mod
    from .pipeline import run_pipeline
    from .speculative import color_graph

    order = ord_mod.compute_order(pg, preset.ordering)
    if not preset.recolor_iters:
        cfg = dataclasses.replace(preset.color_cfg, seed=seed)
        view, stats = color_graph(pg, order, cfg)
        return view, [dict(stage="initial", **stats)]
    view, res = run_pipeline(pg, order, pipeline_config(preset, seed=seed))
    log = [dict(stage="initial", **res["color"])]
    log += [dict(stage="recolor", **h) for h in res["history"]]
    return view, log
