"""Quickstart: distributed graph coloring with iterative recoloring.

Colors an RMAT graph on 8 (simulated) processors with the paper's
"quality" preset — Random-X Fit seeding + ND recoloring — through the
fused device-resident pipeline: initial coloring plus every recoloring
iteration in ONE jitted program (DESIGN.md §7).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (check_coloring, colors_from_views, compute_order,
                        partition_graph, presets, rmat, run_pipeline)

# 1. a graph (16k vertices, power-law degrees) partitioned over 8 workers
g = rmat.rmat_good(14, 8, seed=1)
pg = partition_graph(g, P=8)
print(f"graph: |V|={g.n:,} |E|={g.m:,} maxdeg={g.max_degree}")

# 2. the paper's "quality" parameter set (§4.3): Random-X Fit selection,
#    Internal-First ordering, ND recoloring — as one fused pipeline config.
#    presets.speed() is the no-recoloring counterpart.
preset = presets.quality(x=10)
cfg = presets.pipeline_config(preset, n_iters=5, patience=2)
order = compute_order(pg, preset.ordering)

# 3. one device-resident program: speculative coloring + up to 5 recoloring
#    iterations (adaptive stop after 2 non-improving ones), per-iteration
#    stats unpacked once at the end
view, res = run_pipeline(pg, order, cfg)
print(f"initial: {res['color']['n_colors_distinct']} colors in "
      f"{res['color']['n_rounds']} rounds "
      f"({res['color']['n_exchanges']} boundary exchanges)")
for h in res["history"]:
    print(f"  RC iter {h['iteration']} ({h['perm']}): "
          f"{h['n_colors_distinct']} colors, "
          f"{h['n_exchanges']}/{h['n_steps']} exchanges executed")

colors = colors_from_views(pg, np.asarray(view))
final = check_coloring(g, colors)
print(f"final: {final['n_colors']} colors after {res['n_iters_run']} "
      f"iterations, valid={final['valid']}")
