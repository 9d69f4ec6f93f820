"""Piggybacking message accounting (§3.1 / Fig. 4)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ColorConfig, color_graph, colors_from_views,
                        compute_order, message_stats, ordering,
                        partition_graph, rmat)
from repro.core.comm import AxisComm
from repro.core.recolor import class_sizes, permutation_rank


def _setup(P=8):
    g = rmat.grid2d(32, 32, 9)
    pg = partition_graph(g, P)
    order = compute_order(pg, ordering.NATURAL)
    # paper-faithful sequential supersteps: the message-count study mirrors
    # the paper's Fig. 4 setup, whose seed coloring is the sequential one
    view, _ = color_graph(pg, order, ColorConfig(max_colors=64,
                                                     superstep=64,
                                                     parallel_chunk=False))
    colors = colors_from_views(pg, np.asarray(view))
    sizes = np.bincount(colors, minlength=64).astype(np.int32)
    sizes[0] = 0
    rank = np.asarray(permutation_rank(jnp.asarray(sizes), "nd",
                                       jax.random.key(0)))
    return g, pg, colors, rank


def test_message_stats_invariants():
    g, pg, colors, rank = _setup()
    ms = message_stats(pg, colors, rank)
    assert ms.base_total == ms.base_nonempty + ms.base_empty
    assert ms.pig_total <= ms.base_nonempty  # piggybacking merges, never adds
    assert ms.pig_total >= ms.n_pairs // 2   # every dependent pair sends >=1
    assert 0.0 <= ms.message_reduction <= 1.0
    assert ms.collective_steps_pig <= ms.collective_steps_base


def test_piggyback_removes_empty_messages():
    """Paper Fig. 1/4: all empty messages disappear under piggybacking."""
    g, pg, colors, rank = _setup()
    ms = message_stats(pg, colors, rank)
    assert ms.base_empty > 0          # the base scheme wastes messages
    # piggybacked count excludes every empty message by construction
    assert ms.pig_total <= ms.base_total - ms.base_empty


def test_more_processors_more_savings():
    g = rmat.grid2d(48, 48, 9)
    reductions = []
    for P in (2, 8):
        pg = partition_graph(g, P)
        order = compute_order(pg, ordering.NATURAL)
        view, _ = color_graph(pg, order, ColorConfig(max_colors=64,
                                                         superstep=64,
                                                         parallel_chunk=False))
        colors = colors_from_views(pg, np.asarray(view))
        sizes = np.bincount(colors, minlength=64).astype(np.int32)
        sizes[0] = 0
        rank = np.asarray(permutation_rank(jnp.asarray(sizes), "nd",
                                           jax.random.key(0)))
        ms = message_stats(pg, colors, rank)
        reductions.append(ms.message_reduction)
    assert all(r > 0 for r in reductions)
