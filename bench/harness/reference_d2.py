"""Plain reference for distance-2 coloring, independent of the program.

A coloring is proper at distance 2 when no two vertices within two hops
of each other share a color, and every vertex has a color (ids >= 1).
That holds exactly when every closed neighbourhood N[v] (v and its
neighbours) holds distinct colors: two vertices within two hops are
adjacent, so both lie in N[one of them], or share a neighbour w, so both
lie in N[w].  On a structurally symmetric pattern with a full diagonal
this is the Curtis-Powell-Reid column partition of the Jacobian (a
structurally orthogonal partition, Gebremedhin, Manne and Pothen, SIAM
Review 2005).  The check reads the benchmark's own CSR (``graphs.CSR``)
and the colors the program returned, nothing else.
"""
from __future__ import annotations

import numpy as np

from .graphs import CSR

CHUNK_PATHS = 1 << 22      # two-hop paths expanded at once


def check(g: CSR, colors) -> dict:
    """Distance-2 properness of ``colors`` (``(n,)`` ints, 1-based) on ``g``.

    ``conflicts`` counts, over every closed neighbourhood, the pairs of
    colored members that share a color: zero exactly when the coloring is
    proper at distance 2 (a pair is counted once for each neighbourhood
    that holds both).  One sort of (vertex, color) keys: O(E log E)."""
    colors = np.asarray(colors)
    if colors.shape != (g.n,):
        return dict(conflicts=g.directed_edges + 1, uncolored=g.n, colors=0)
    c = colors.astype(np.int64)
    src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.indptr))
    owner = np.concatenate([np.arange(g.n, dtype=np.int64), src])
    member = np.concatenate([c, c[g.indices]])
    colored = member > 0
    key = owner[colored] * (int(c.max(initial=0)) + 1) + member[colored]
    _, count = np.unique(key, return_counts=True)
    used = c[c > 0]
    return dict(conflicts=int((count * (count - 1) // 2).sum()),
                uncolored=int((c <= 0).sum()),
                colors=int(np.unique(used).size))


def two_hop_pairs(g: CSR) -> int:
    """Ordered pairs (v, u) at distance exactly 2: the strict two-hop
    neighbours summed over every vertex."""
    deg = np.diff(g.indptr)
    paths = np.cumsum(deg[g.indices])          # paths through each edge
    total, v = 0, 0
    while v < g.n:
        base = paths[g.indptr[v] - 1] if g.indptr[v] else 0
        e = int(np.searchsorted(paths, base + CHUNK_PATHS, side="right"))
        stop = max(v + 1, int(np.searchsorted(g.indptr, e, side="right")) - 1)
        stop = min(stop, g.n)
        lo, hi = int(g.indptr[v]), int(g.indptr[stop])
        rows = np.repeat(np.arange(v, stop, dtype=np.int64), deg[v:stop])
        w = g.indices[lo:hi]
        d = deg[w]
        first = np.repeat(g.indptr[w] - (np.cumsum(d) - d), d)
        u = g.indices[first + np.arange(int(d.sum()))].astype(np.int64)
        key = np.repeat(rows, d) * g.n + u
        within = np.unique(np.concatenate([key, rows * g.n + w,
                                           np.arange(v, stop) * (g.n + 1)]))
        total += within.size - (hi - lo) - (stop - v)   # less N(v) and v
        v = stop
    return total


def greedy_colors_d2(g: CSR) -> int:
    """Distinct colors of sequential distance-2 First Fit in vertex order."""
    colors = np.zeros(g.n + 1, dtype=np.int64)
    seen = np.zeros(g.n + 2, dtype=np.int64)
    for v in range(g.n):
        nb = g.indices[g.indptr[v]:g.indptr[v + 1]]
        reach = np.concatenate(
            [nb, *(g.indices[g.indptr[w]:g.indptr[w + 1]] for w in nb)])
        seen[colors[reach]] = v + 1
        k = 1
        while seen[k] == v + 1:
            k += 1
        colors[v] = k
    return int(colors.max(initial=0))
