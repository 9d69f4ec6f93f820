"""Bytes a coloring needs to move, from the graph and K alone.

One pass over the graph reads, for every directed edge, the neighbour's
index and its color (4 + 4 bytes) and writes one 4-byte color per vertex.
A coloring makes 2 + K passes: the speculative coloring, its conflict
detection, and K recoloring iterations.  The count ignores the padded ELL
tile, ghost slots and repeated rounds on purpose, so a change of layout or
a gather moved into a kernel cannot move the yardstick.
"""
from __future__ import annotations

EDGE_BYTES = 8     # neighbour index + neighbour color, int32 each
VERTEX_BYTES = 4   # the vertex's own color, int32


def coloring_bytes(n: int, directed_edges: int, recolor_iters: int) -> int:
    """Bytes one coloring of an ``n``-vertex graph needs at the least."""
    passes = 2 + recolor_iters
    return passes * (EDGE_BYTES * directed_edges + VERTEX_BYTES * n)
