"""The paper's "speed" vs "quality" presets (§4.3) + real shard_map execution.

- "speed"  : First Fit + Internal-First ordering, no recoloring
- "quality": Random-10 Fit + Internal-First + 1 ND recoloring iteration

Also runs the SAME SPMD code over a real multi-device mesh when more than one
XLA device is available (set XLA_FLAGS=--xla_force_host_platform_device_count=8).

Run:  PYTHONPATH=src python examples/distributed_coloring.py
"""
import time

import jax
import numpy as np

from repro.core import (check_coloring, colors_from_views, partition_graph,
                        presets, rmat)

g = rmat.rmat_er(14, 8, seed=1)
P = 8
pg = partition_graph(g, P)
print(f"graph: |V|={g.n:,} |E|={g.m:,} maxdeg={g.max_degree}, P={P}\n")

for preset in (presets.speed(), presets.quality(x=10)):
    t0 = time.time()
    view, log = presets.run_preset(pg, preset)
    dt = time.time() - t0
    colors = colors_from_views(pg, np.asarray(view))
    st = check_coloring(g, colors)
    print(f"preset={preset.name!r:10s} -> {st['n_colors']:3d} colors, "
          f"valid={st['valid']}, {dt:.2f}s")
    for entry in log:
        stage = entry.pop("stage")
        print(f"   {stage}: { {k: v for k, v in entry.items() if isinstance(v, (int, str))} }")

# real sharded execution if the process has multiple devices
if len(jax.devices()) >= P:
    from jax.sharding import AxisType
    from repro.core import ColorConfig, color_graph, compute_order, ordering
    mesh = jax.make_mesh((P,), ("workers",), axis_types=(AxisType.Auto,))
    order = compute_order(pg, ordering.INTERNAL_FIRST)
    view, stats = color_graph(pg, order,
                              ColorConfig(max_colors=1024, superstep=512),
                              mesh=mesh)
    print(f"\nshard_map over {P} real devices: {stats['n_colors']} colors")
else:
    print(f"\n({len(jax.devices())} device(s) — rerun with "
          f"XLA_FLAGS=--xla_force_host_platform_device_count={P} for the "
          f"real shard_map path)")
