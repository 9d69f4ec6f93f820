"""The benchmark's own graph generators: symmetric, deduplicated CSR.

These are the inputs of every cell, made from a seed by the benchmark and
handed to the program as plain arrays, so the reference never checks a
coloring against a graph the program made itself.  The algorithms are
those of the paper's evaluation (arXiv:1407.6745 §4.1): R-MAT recursive
quadrant sampling and the 3D 27-point stencil.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class CSR:
    """Undirected graph as symmetric CSR: ``indices[indptr[v]:indptr[v+1]]``
    are the neighbours of ``v``; no self loops, no duplicate edges."""

    n: int
    indptr: np.ndarray   # (n+1,) int64
    indices: np.ndarray  # (directed edges,) int32

    @property
    def directed_edges(self) -> int:
        return int(self.indices.shape[0])

    @property
    def max_degree(self) -> int:
        return int(np.diff(self.indptr).max(initial=0))


def from_edges(n: int, src: np.ndarray, dst: np.ndarray) -> CSR:
    """Symmetrize an edge list, drop self loops and duplicates, build CSR."""
    keep = src != dst
    src, dst = src[keep].astype(np.int64), dst[keep].astype(np.int64)
    u = np.concatenate([src, dst])
    v = np.concatenate([dst, src])
    order = np.lexsort((v, u))
    u, v = u[order], v[order]
    first = np.ones(u.shape[0], dtype=bool)
    first[1:] = (u[1:] != u[:-1]) | (v[1:] != v[:-1])
    u, v = u[first], v[first]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, u + 1, 1)
    return CSR(n=n, indptr=np.cumsum(indptr), indices=v.astype(np.int32))


def rmat(scale: int, edge_factor: int, probs, seed: int) -> CSR:
    """R-MAT (Chakrabarti et al.): ``edge_factor * 2**scale`` directed edges,
    each placed by ``scale`` quadrant draws with probabilities ``probs``."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    a, b, c, _ = probs
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(m)
        right = (r >= a + b) & (r < a + b + c) | (r >= a + b + c)
        down = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src = src * 2 + right
        dst = dst * 2 + down
    return from_edges(n, src, dst)


def grid3d_27pt(nx: int, ny: int, nz: int) -> CSR:
    """The 27-point stencil on an ``nx x ny x nz`` grid (HPCG's problem)."""
    ii, jj, kk = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                             indexing="ij")
    vid = (ii.astype(np.int64) * ny + jj) * nz + kk
    src, dst = [], []
    for di in (-1, 0, 1):
        for dj in (-1, 0, 1):
            for dk in (-1, 0, 1):
                if di == dj == dk == 0:
                    continue
                ni, nj, nk = ii + di, jj + dj, kk + dk
                ok = ((ni >= 0) & (ni < nx) & (nj >= 0) & (nj < ny)
                      & (nk >= 0) & (nk < nz))
                src.append(vid[ok])
                dst.append(((ni.astype(np.int64) * ny + nj) * nz + nk)[ok])
    return from_edges(nx * ny * nz, np.concatenate(src), np.concatenate(dst))


def make(spec: dict, seed: int) -> CSR:
    """The graph a configuration (or one served request) describes."""
    kind = spec["generator"]
    if kind == "rmat":
        return rmat(spec["scale"], spec["edge_factor"], spec["probs"], seed)
    if kind == "grid3d_27pt":
        return grid3d_27pt(spec["nx"], spec["ny"], spec["nz"])
    raise ValueError(f"unknown graph generator {kind!r}")
