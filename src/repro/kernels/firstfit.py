"""Pallas TPU kernels for the paper's per-vertex hot loop (color selection).

The recoloring step's compute kernel is: for a tile of vertices, build the
forbidden-color set from neighbour colors and pick a color (First Fit,
Random-X Fit §3.2, or Staggered First Fit via a per-row offset operand).
On TPU we tile vertices onto the 128 VPU lanes and keep the forbidden set
as a uint32 *bitset* — ``max_colors / 32`` words per vertex, one word per
sublane row — resident in VMEM/VREGs:

  HBM  : transposed neighbour-color tile (MAXD, V) int32 — one ELL slot per
         row, one vertex per lane — streamed as (MAXD, TILE_V) blocks
  VMEM : (MAXD, TILE_V) input block + (W, TILE_V) bitset working set
  VPU  : MAXD-step reduction of one-hot word ORs (slot ``d`` is a dynamic
         *sublane* read); find-first-zero via masked sublane reductions +
         population_count (no scalar loops over vertices)

This is the TPU-native rethink of the paper's per-vertex sequential scan:
the sequential dependency *within* a color class does not exist (the class is
an independent set), so the whole tile colors in parallel — exactly why
synchronous recoloring suits wide SIMD hardware.

Every construct here is one Mosaic lowers: no scatters, no dynamic
lane-axis slices, no ``take_along_axis`` and no reductions over unsigned
integers (word picks go through an int32 bitcast).  Per-vertex operands are
``(1, V)`` rows so every block is 2-D and lane-aligned.

Grid: (V / TILE_V,).  VMEM of the widest kernel (distance-2 conflict, four
(MAXD, TILE_V) int32 tiles, double-buffered) is ``2 * 4 * MAXD * TILE_V * 4``
bytes: 2.6 MiB at MAXD=678, under v5e's 16 MiB default scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

TILE_V = 128  # vertices per grid step: one vreg of lanes

_U1 = np.uint32(1)
_FULL = np.uint32(0xFFFFFFFF)
_I32_MIN = np.int32(np.iinfo(np.int32).min)


def _rows_iota(shape) -> jnp.ndarray:
    """Row index of every element of a 2-D (rows, V) array."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _bit(c: jnp.ndarray) -> jnp.ndarray:
    """uint32 word with bit ``c mod 32`` set."""
    return _U1 << (c & 31).astype(jnp.uint32)


def forbidden_words(slots, n_words: int, v: int) -> jnp.ndarray:
    """OR neighbour colors into a (W, V) forbidden bitset.

    ``slots`` is a sequence of ``(row, maxd)``: ``row(d)`` returns ELL slot
    ``d``'s neighbour colors as a (1, V) row.  Bit 0 (color 0, uncolored)
    is always set; colors outside ``[1, 32W)`` are ignored.
    """
    wrow = _rows_iota((n_words, v))
    words = jnp.where(wrow == 0, _U1, jnp.uint32(0))

    def body(row, d, words):
        c = row(d)                                          # (1, V)
        hit = (wrow == (c >> 5)) & (c > 0) & (c < n_words * 32)
        return words | jnp.where(hit, _bit(c), jnp.uint32(0))

    for row, maxd in slots:
        words = jax.lax.fori_loop(0, maxd, functools.partial(body, row),
                                  words)
    return words


def _pick_row(vals: jnp.ndarray, rows: jnp.ndarray, idx: jnp.ndarray):
    """``vals[idx[v], v]`` of an int32 (rows, V) array as a (1, V) row."""
    sel = jnp.where(rows == idx, vals, _I32_MIN)
    return jnp.max(sel, axis=0, keepdims=True)


def _find_first_zero(words: jnp.ndarray) -> jnp.ndarray:
    """(W, V) bitset -> (1, V) lowest zero bit below the sentinel.

    Bit ``32W-1`` is reserved as a saturation sentinel (never reported free),
    so a result of ``32W-1`` unambiguously means "no permissible color" —
    mirrors ``core.selection.find_first_zero``.
    """
    n_words, v = words.shape
    wrow = _rows_iota((n_words, v))
    top = jnp.where(wrow == n_words - 1, ~jnp.uint32(0x7FFFFFFF),
                    jnp.uint32(0))
    free = ~(words | top)
    widx = jnp.min(jnp.where(free != 0, wrow, n_words), axis=0,
                   keepdims=True)
    widx_c = jnp.minimum(widx, n_words - 1)
    word = jax.lax.bitcast_convert_type(
        _pick_row(jax.lax.bitcast_convert_type(free, jnp.int32), wrow,
                  widx_c), jnp.uint32)
    lsb = word & (~word + _U1)
    bit = jax.lax.population_count(lsb - _U1).astype(jnp.int32)
    out = widx_c * 32 + bit
    return jnp.where(widx >= n_words, n_words * 32 - 1, out)


def _set_bits(words: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Set per-vertex bit ``c`` (1, V) in the (W, V) bitset."""
    wrow = _rows_iota(words.shape)
    return words | jnp.where(wrow == (c >> 5), _bit(c), jnp.uint32(0))


def _mask_below_rows(words: jnp.ndarray, off: jnp.ndarray) -> jnp.ndarray:
    """Copy of the (W, V) bitset with all bits < off[v] additionally set."""
    wrow = _rows_iota(words.shape)
    widx = off >> 5
    partial = jnp.where(wrow == widx, _bit(off) - _U1, jnp.uint32(0))
    return words | jnp.where(wrow < widx, _FULL, jnp.uint32(0)) | partial


def select_from_words(words, rand_u32, offset, *, x: int, staggered: bool):
    """(W, V) forbidden bitset -> (1, V) colors.

    The one tile-parallel selection routine: First Fit (x=0), Random-X Fit
    (x>0, uniform among the X smallest free colors via ``rand_u32``) and
    Staggered First Fit (first fit from per-vertex ``offset``, wrapping to
    plain first fit when exhausted).  ``rand_u32``/``offset`` are (1, V).
    Shared verbatim by the Pallas tile kernel and the vectorized XLA backend
    in ``kernels.ops`` — they differ only in how tiles reach the VPU, never
    in the math.
    """
    if staggered:
        c = _find_first_zero(_mask_below_rows(words, offset))
        full = c >= words.shape[0] * 32 - 1
        return jnp.where(full, _find_first_zero(words), c)
    if x == 0:
        return _find_first_zero(words)
    mc = words.shape[0] * 32
    krow = _rows_iota((x, words.shape[1]))
    cands = jnp.full(krow.shape, mc - 1, jnp.int32)

    def body(k, carry):
        words, cands = carry
        c = _find_first_zero(words)
        return _set_bits(words, c), jnp.where(krow == k, c, cands)

    _, cands = jax.lax.fori_loop(0, x, body, (words, cands))
    n_free = jnp.sum((cands < mc - 1).astype(jnp.int32), axis=0,
                     keepdims=True)
    n_free = jnp.maximum(n_free, 1).astype(jnp.uint32)
    idx = (rand_u32 % n_free).astype(jnp.int32)
    return _pick_row(cands, krow, idx)


def _select_kernel(*refs, n_tiles: int, n_words: int, x: int,
                   staggered: bool):
    """x == 0 -> First Fit; x > 0 -> Random-X Fit; staggered -> offset FF.

    ``n_tiles`` neighbour tiles (1: distance 1; 2: one-hop + two-hop) are
    ORed into one forbidden bitset.
    """
    tiles = refs[:n_tiles]
    active_ref, rand_ref, off_ref, out_ref = refs[n_tiles:]
    slots = [(lambda d, t=t: t[pl.ds(d, 1), :], t.shape[0]) for t in tiles]
    words = forbidden_words(slots, n_words, out_ref.shape[1])
    color = select_from_words(words, rand_ref[...], off_ref[...], x=x,
                              staggered=staggered)
    out_ref[...] = jnp.where(active_ref[...] != 0, color, 0)


def _conflict_kernel(*refs, n_tiles: int):
    """Row loses iff a neighbour in any (colors, prio) tile pair holds the
    same nonzero color with strictly higher priority."""
    myc_ref, myp_ref = refs[:2]
    tiles = refs[2:2 + 2 * n_tiles]
    active_ref, out_ref = refs[2 + 2 * n_tiles:]
    myc, myp = myc_ref[...], myp_ref[...]
    lose = jnp.zeros(myc.shape, jnp.int32)
    for c_ref, p_ref in zip(tiles[::2], tiles[1::2]):
        hit = (c_ref[...] == myc) & (myc > 0) & (p_ref[...] > myp)
        lose = jnp.maximum(lose, jnp.max(hit.astype(jnp.int32), axis=0,
                                         keepdims=True))
    out_ref[...] = jnp.where(active_ref[...] != 0, lose, 0)


def _specs(tiles, n_rows: int):
    tile = lambda t: pl.BlockSpec((t.shape[0], TILE_V), lambda i: (0, i))
    row = pl.BlockSpec((1, TILE_V), lambda i: (0, i))
    return [tile(t) for t in tiles], [row] * n_rows, row


def color_select_pallas(tiles, active, rand_u32, offset, *, max_colors: int,
                        x: int = 0, staggered: bool = False,
                        interpret: bool = False):
    """Tile-parallel color selection.

    ``tiles`` — one or two transposed neighbour-color tiles (MAXD_i, V)
    int32; ``active``/``rand_u32``/``offset`` — (1, V) int32/uint32/int32.
    V must be a multiple of TILE_V.  Returns (1, V) int32 chosen colors
    (0 where inactive).
    """
    assert max_colors % 32 == 0
    v = tiles[0].shape[1]
    assert v % TILE_V == 0, f"V={v} not a multiple of {TILE_V}"
    kernel = functools.partial(_select_kernel, n_tiles=len(tiles),
                               n_words=max_colors // 32, x=x,
                               staggered=staggered)
    tile_specs, row_specs, out_spec = _specs(tiles, 3)
    return pl.pallas_call(
        kernel,
        grid=(v // TILE_V,),
        in_specs=tile_specs + row_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((1, v), jnp.int32),
        interpret=interpret,
    )(*tiles, active, rand_u32, offset)


def conflict_pallas(my_color, my_prio, tiles, active, *,
                    interpret: bool = False):
    """Tile-parallel conflict detection.

    ``tiles`` — ``(colors, prio)`` transposed (MAXD_i, V) pairs, flattened
    (two tiles at distance 1, four at distance 2); ``my_color``/``my_prio``
    /``active`` — (1, V).  Returns (1, V) int32 (1 = recolor).
    """
    v = my_color.shape[1]
    assert v % TILE_V == 0, f"V={v} not a multiple of {TILE_V}"
    assert len(tiles) % 2 == 0
    tile_specs, row_specs, out_spec = _specs(tiles, 3)
    return pl.pallas_call(
        functools.partial(_conflict_kernel, n_tiles=len(tiles) // 2),
        grid=(v // TILE_V,),
        in_specs=row_specs[:2] + tile_specs + row_specs[2:],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((1, v), jnp.int32),
        interpret=interpret,
    )(my_color, my_prio, *tiles, active)
