"""Plain reference for the coloring problem, independent of the program.

A coloring of an undirected graph is proper when no edge joins two
vertices of one color and every vertex has a color (ids >= 1).  The check
reads the benchmark's own CSR (``graphs.CSR``) and nothing the program
made besides the colors it returned.  ``greedy_colors`` is the plain
sequential First Fit, whose color count sets the scale of the quality
metric.  ``control_coloring`` is that greedy with one guarantee broken:
each of the P blocks colors its own vertices and ignores the edges that
leave it, as a speculative coloring would without its conflict repair.
"""
from __future__ import annotations

import numpy as np

from .graphs import CSR


def check(g: CSR, colors) -> dict:
    """Properness of ``colors`` (``(n,)`` ints, 1-based) on ``g``."""
    colors = np.asarray(colors)
    if colors.shape != (g.n,):
        return dict(conflicts=g.directed_edges // 2 + 1, uncolored=g.n,
                    colors=0)
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    cs, cd = colors[src], colors[g.indices]
    conflicts = int(((cs > 0) & (cs == cd)).sum()) // 2
    used = colors[colors > 0]
    return dict(conflicts=conflicts, uncolored=int((colors <= 0).sum()),
                colors=int(np.unique(used).size))


def _first_fit(g: CSR, vertices, allowed=None) -> np.ndarray:
    colors = np.zeros(g.n, dtype=np.int32)
    seen = np.zeros(g.max_degree + 2, dtype=np.int64)
    for stamp, v in enumerate(vertices, start=1):
        nb = g.indices[g.indptr[v]:g.indptr[v + 1]]
        if allowed is not None:
            nb = nb[allowed[v](nb)]
        c = colors[nb]
        seen[c[c < seen.shape[0]]] = stamp
        k = 1
        while seen[k] == stamp:
            k += 1
        colors[v] = k
    return colors


def greedy_colors(g: CSR) -> int:
    """Distinct colors of sequential First Fit in vertex order."""
    return int(_first_fit(g, range(g.n)).max(initial=0))


def control_coloring(g: CSR, P: int, seed: int) -> np.ndarray:
    """First Fit in a seeded random order within each of ``P`` contiguous
    blocks, blind to the edges between blocks: proper inside a block,
    conflicting across them."""
    offs = np.linspace(0, g.n, P + 1).astype(np.int64)
    block = np.searchsorted(offs, np.arange(g.n), side="right") - 1
    inside = [(lambda nb, lo=offs[p], hi=offs[p + 1]: (nb >= lo) & (nb < hi))
              for p in range(P)]
    order = np.random.default_rng(seed).permutation(g.n)
    return _first_fit(g, order, allowed=[inside[b] for b in block])
