"""Jitted public wrappers around the color-selection kernels.

``select_colors`` is the ONE entry point the distributed hot paths
(`core.recolor`, `core.speculative`) route through.  It takes a padded
neighbour-color tile (the gather of an ELL row block, see DESIGN.md §3) and
picks a color per row with a ``backend`` switch:

  backend="pallas" — the Pallas TPU tile kernels in ``firstfit.py``.  On a
                     non-TPU backend the kernels run with ``interpret=True``,
                     which executes the kernel body unmodified in Python and
                     validates the TPU code path.
  backend="xla"    — the *same* bitset math (``select_from_words``) applied to
                     the whole tile as ordinary vectorized XLA ops.  This is
                     the fast CPU/sim path and the semantics oracle for the
                     Pallas path; equivalence is pinned by tests.
  backend="auto"   — "pallas" on TPU, "xla" elsewhere (the default the
                     drivers use, so sim runs stay fast and TPU runs hit the
                     kernels without any config change).

Strategies: "first_fit", "staggered" (per-row start offset, wraps to plain
first fit when exhausted) and "random_x" (uniform among the X smallest free
colors).  "least_used" is inherently sequential (it chases a running usage
histogram) and stays on the scalar path in ``core.speculative``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .firstfit import (TILE_V, color_select_pallas, conflict_pallas,
                       forbidden_words, select_from_words)

# Strategy names, mirroring repro.core.selection (string-equal; duplicated
# here so kernels never import core and the layering stays one-way).
FIRST_FIT = "first_fit"
STAGGERED = "staggered"
RANDOM_X = "random_x"
SELECTIONS = (FIRST_FIT, STAGGERED, RANDOM_X)

BACKENDS = ("auto", "xla", "pallas")


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}, want one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def _flatten(tiles, rows: dict):
    """Flatten leading batch dims onto the row axis.

    ``tiles`` — (…, V, D) operands; ``rows`` — (…, V) operands (scalars
    broadcast).  Rows of a (B, V, D) multi-graph tile land contiguously on
    the flat row axis: the XLA twin vectorizes the flat tile directly, the
    Pallas grid just tiles the extra rows (a per-graph grid axis) — one
    kernel launch per batch.  Returns ``(tiles, rows, unflatten)``.
    """
    tiles = tuple(jnp.asarray(t) for t in tiles)
    lead, v = tiles[0].shape[:-2], tiles[0].shape[-2]
    flat_t = tuple(jnp.reshape(t, (-1, t.shape[-1])) for t in tiles)
    flat_r = {k: jnp.reshape(jnp.broadcast_to(jnp.asarray(a), lead + (v,)),
                             (-1,)) for k, a in rows.items()}
    return flat_t, flat_r, lambda out: jnp.reshape(out, lead + (v,))


def _kernel_operands(tiles, rows, fills):
    """Pad rows to a TILE_V multiple and lay them out for the kernels.

    Tiles (V, D) become transposed (D, V_pad) — one ELL slot per row, one
    vertex per lane; per-vertex operands become (1, V_pad) rows.  ``fills``
    gives each tile's padding value (rows past V are inactive either way).
    """
    v = rows[0].shape[0]
    pad = -(-v // TILE_V) * TILE_V - v
    t_out = tuple(jnp.pad(t.T, ((0, 0), (0, pad)), constant_values=f)
                  for t, f in zip(tiles, fills))
    r_out = tuple(jnp.pad(r, (0, pad))[None, :] for r in rows)
    return t_out, r_out


def _select(tiles, active, rand_u32, offset, *, max_colors: int,
            selection: str, x: int, backend: str, interpret: bool | None):
    """Shared body of ``select_colors`` (one tile) and ``select_colors_d2``
    (one-hop + two-hop tiles, forbidden bitsets ORed)."""
    if selection not in SELECTIONS:
        raise ValueError(
            f"unknown selection {selection!r}, want one of {SELECTIONS}")
    assert max_colors % 32 == 0
    backend = resolve_backend(backend)
    tiles, rows, unflatten = _flatten(tiles, dict(
        active=jnp.asarray(active) != 0,
        rand=jnp.uint32(0) if rand_u32 is None else rand_u32,
        offset=jnp.asarray(0 if offset is None else offset, jnp.int32)))
    staggered = selection == STAGGERED
    x_eff = x if selection == RANDOM_X else 0

    if backend == "xla":
        v = rows["active"].shape[0]
        slots = [(lambda d, t=t.T: jax.lax.dynamic_slice_in_dim(t, d, 1),
                  t.shape[1]) for t in tiles]
        words = forbidden_words(slots, max_colors // 32, v)
        color = select_from_words(words, rows["rand"][None],
                                  rows["offset"][None], x=x_eff,
                                  staggered=staggered)[0]
        return unflatten(jnp.where(rows["active"], color, 0))

    if interpret is None:
        interpret = _default_interpret()
    v = rows["active"].shape[0]
    t_k, (act, rand, off) = _kernel_operands(
        tiles, (rows["active"].astype(jnp.int32), rows["rand"],
                rows["offset"]), (0,) * len(tiles))
    out = color_select_pallas(t_k, act, rand, off, max_colors=max_colors,
                              x=x_eff, staggered=staggered,
                              interpret=interpret)
    return unflatten(out[0, :v])


def select_colors(nbr_colors, active, rand_u32=None, *, max_colors: int,
                  selection: str = FIRST_FIT, x: int = 10, offset=None,
                  backend: str = "auto", interpret: bool | None = None):
    """Tile-parallel color selection over a padded neighbour tile.

    nbr_colors (V, MAXD) int32 (0 / negative / >=max_colors entries ignored);
    active (V,) bool-ish; rand_u32 (V,) uint32 (random_x only); offset scalar
    or (V,) int32 (staggered only).  Returns (V,) int32, 0 where inactive.
    Traceable — call it from inside jitted SPMD code.

    Leading batch dims are accepted on every per-row operand — e.g. a
    ``(B, V, MAXD)`` multi-graph tile with ``(B, V)`` masks returns
    ``(B, V)`` colors.  Rows are flattened onto the row axis: the XLA twin
    vectorizes the flat tile directly, and the Pallas grid simply tiles the
    extra rows (a per-graph grid axis) — one kernel launch per batch.
    """
    return _select((nbr_colors,), active, rand_u32, offset,
                   max_colors=max_colors, selection=selection, x=x,
                   backend=backend, interpret=interpret)


def select_colors_d2(nbr_colors, nbr2_colors, active, rand_u32=None, *,
                     max_colors: int, selection: str = FIRST_FIT, x: int = 10,
                     offset=None, backend: str = "auto",
                     interpret: bool | None = None):
    """Distance-2 color selection over two padded neighbour tiles.

    Same contract as ``select_colors`` (leading batch dims included) plus
    ``nbr2_colors`` (V, MAXD2) int32 — the strict two-hop neighbour colors.
    Both backends OR the one-hop and two-hop forbidden bitsets before
    selecting, so a chosen color differs from every color within graph
    distance 2.
    """
    return _select((nbr_colors, nbr2_colors), active, rand_u32, offset,
                   max_colors=max_colors, selection=selection, x=x,
                   backend=backend, interpret=interpret)


def _detect(my_color, my_prio, pairs, active, *, backend: str,
            interpret: bool | None):
    """Shared body of ``detect_conflicts`` / ``detect_conflicts_d2``:
    ``pairs`` is a tuple of (neighbour colors, neighbour prio) tiles."""
    backend = resolve_backend(backend)
    tiles = tuple(t for pair in pairs for t in pair)
    tiles, rows, unflatten = _flatten(tiles, dict(
        myc=my_color, myp=my_prio, active=jnp.asarray(active) != 0))
    myc, myp = rows["myc"], rows["myp"]
    if backend == "xla":
        lose = jnp.zeros(myc.shape, bool)
        for c, p in zip(tiles[::2], tiles[1::2]):
            lose |= ((c == myc[:, None]) & (myc[:, None] > 0)
                     & (p > myp[:, None])).any(axis=1)
        return unflatten(lose & rows["active"])
    if interpret is None:
        interpret = _default_interpret()
    v = myc.shape[0]
    t_k, (c_k, p_k, a_k) = _kernel_operands(
        tiles, (myc, myp, rows["active"].astype(jnp.int32)),
        (0, -1) * len(pairs))
    out = conflict_pallas(c_k, p_k, t_k, a_k, interpret=interpret)
    return unflatten(out[0, :v].astype(bool))


def detect_conflicts(my_color, my_prio, nbr_colors, nbr_prio, active, *,
                     backend: str = "auto", interpret: bool | None = None):
    """Tile-parallel conflict detection: row loses iff a neighbour holds the
    same (nonzero) color with strictly higher priority.  Returns (V,) bool.
    Traceable; same backend contract as ``select_colors``, leading batch
    dims accepted on every operand.
    """
    return _detect(my_color, my_prio, ((nbr_colors, nbr_prio),), active,
                   backend=backend, interpret=interpret)


def detect_conflicts_d2(my_color, my_prio, nbr_colors, nbr_prio, nbr2_colors,
                        nbr2_prio, active, *, backend: str = "auto",
                        interpret: bool | None = None):
    """Distance-2 conflict detection: row loses iff any neighbour at graph
    distance <= 2 holds the same (nonzero) color with strictly higher
    priority. Returns (V,) bool; same backend contract as ``select_colors``,
    leading batch dims accepted on every operand.
    """
    return _detect(my_color, my_prio,
                   ((nbr_colors, nbr_prio), (nbr2_colors, nbr2_prio)),
                   active, backend=backend, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_colors", "x", "interpret"))
def color_select(nbr_colors, active, rand_u32, *, max_colors: int, x: int = 0,
                 interpret: bool | None = None):
    """First Fit (x=0) / Random-X Fit (x>0) via the Pallas path (jitted)."""
    return select_colors(nbr_colors, active, rand_u32, max_colors=max_colors,
                         selection=RANDOM_X if x else FIRST_FIT, x=x,
                         backend="pallas", interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def conflict(my_color, my_prio, nbr_colors, nbr_prio, active, *,
             interpret: bool | None = None):
    """Conflict detection over a dense neighbour tile. Returns (V,) bool."""
    return detect_conflicts(my_color, my_prio, nbr_colors, nbr_prio, active,
                            backend="pallas", interpret=interpret)
