"""ELL layout + backend-switch hot paths: equivalence and regression pins.

No hypothesis dependency — this module must collect and run on a bare
environment (jax + numpy + pytest only).

Goldens below were captured from the seed implementation (scalar greedy
chunks; dense-occupancy recolor steps) before the ELL/bitset rework, so they
pin "parallel_chunk=False == seed behavior" and "chunked recolor == seed
recolor" bitwise for fixed seeds.  The two rows that draw random numbers
(Random-X selection, RAND recoloring) were re-derived under jax 0.9, whose
partitionable threefry changed every random stream; each test checks the
coloring valid and equal across both kernel backends (the XLA twin and the
Pallas kernels) before comparing the pin.
"""
import hashlib

import jax
import numpy as np
import pytest

from repro.core import (ColorConfig, RecolorConfig, assert_valid,
                        color_graph_sim, colors_from_views, compute_order,
                        ordering, partition_graph, recolor_sim, rmat,
                        select_colors, selection)
from repro.kernels import ops, ref


def _hash(colors: np.ndarray) -> str:
    return hashlib.sha256(colors.astype(np.int32).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def graph():
    return rmat.rmat_good(10, 8, seed=3)


@pytest.fixture(scope="module")
def pgraph(graph):
    return partition_graph(graph, 4)


# ------------------------------------------------------------- ELL layout --

def test_ell_matches_csr(pgraph):
    pg = pgraph
    assert pg.nbr.shape == (pg.P, pg.n_local_max, pg.maxd)
    for p in range(pg.P):
        nl = int(pg.n_local[p])
        for v in range(0, nl, 37):          # sampled rows
            s, e = pg.indptr[p][v], pg.indptr[p][v + 1]
            csr_row = sorted(pg.indices[p][s:e].tolist())
            ell_row = pg.nbr[p, v]
            assert sorted(ell_row[: e - s].tolist()) == csr_row
            assert (ell_row[e - s:] == pg.sentinel).all()
        # padded vertex rows are all-sentinel
        assert (pg.nbr[p, nl:] == pg.sentinel).all()


# ------------------------------------------- select_colors backend switch --

@pytest.mark.parametrize("selname,kw", [
    (ops.FIRST_FIT, {}),
    (ops.RANDOM_X, dict(x=7)),
    (ops.STAGGERED, {}),
])
def test_select_backends_agree(selname, kw):
    rng = np.random.default_rng(5)
    v, d, mc = 300, 21, 128
    nbr = rng.integers(-2, mc + 8, (v, d)).astype(np.int32)
    active = rng.random(v) < 0.85
    rand = rng.integers(0, 2**32, v, dtype=np.uint32)
    off = rng.integers(0, mc, v).astype(np.int32)
    if selname == ops.STAGGERED:
        kw = dict(kw, offset=off)
    got_x = select_colors(nbr, active, rand, max_colors=mc,
                          selection=selname, backend="xla", **kw)
    got_p = select_colors(nbr, active, rand, max_colors=mc,
                          selection=selname, backend="pallas", **kw)
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(got_p))


def test_select_matches_ref_oracles():
    import jax.numpy as jnp
    rng = np.random.default_rng(9)
    v, d, mc = 257, 13, 64
    nbr = rng.integers(-2, mc + 4, (v, d)).astype(np.int32)
    active = rng.random(v) < 0.9
    rand = rng.integers(0, 2**32, v, dtype=np.uint32)
    ff = select_colors(nbr, active, max_colors=mc, backend="xla")
    np.testing.assert_array_equal(
        np.asarray(ff),
        np.asarray(ref.first_fit(jnp.asarray(nbr), jnp.asarray(active), mc)))
    rx = select_colors(nbr, active, rand, max_colors=mc,
                       selection=ops.RANDOM_X, x=5, backend="xla")
    np.testing.assert_array_equal(
        np.asarray(rx),
        np.asarray(ref.random_x(jnp.asarray(nbr), jnp.asarray(active),
                                jnp.asarray(rand), 5, mc)))


def test_detect_conflicts_backends_agree():
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    v, d, mc = 300, 17, 64
    myc = rng.integers(0, mc, v).astype(np.int32)
    myp = rng.integers(0, 10_000, v).astype(np.int32)
    nbrc = rng.integers(-2, mc + 8, (v, d)).astype(np.int32)
    nbrp = rng.integers(0, 10_000, (v, d)).astype(np.int32)
    active = rng.random(v) < 0.85
    got_x = ops.detect_conflicts(myc, myp, jnp.asarray(nbrc),
                                 jnp.asarray(nbrp), active, backend="xla")
    got_p = ops.detect_conflicts(myc, myp, jnp.asarray(nbrc),
                                 jnp.asarray(nbrp), active, backend="pallas")
    want = ref.conflict(jnp.asarray(myc), jnp.asarray(myp), jnp.asarray(nbrc),
                        jnp.asarray(nbrp), jnp.asarray(active))
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_staggered_saturation_boundary(backend):
    """In-kernel offset path: color 32W-1 is a true saturation sentinel.

    Rows whose neighbours occupy every legal color must come back as the
    sentinel ``mc-1``; a row with exactly the last legal color (``mc-2``)
    free at/above the offset must take it instead of wrapping below.
    """
    mc = 64
    full = np.arange(1, mc - 1, dtype=np.int32)      # colors 1..62
    rows = np.stack([
        full,                                        # only reserved 63 left
        np.where(full == 5, 0, full),                # free = {5, 63}
        np.where(full == mc - 2, 0, full),           # free = {62, 63}
    ])
    got = select_colors(rows, np.ones(3, bool), max_colors=mc,
                        selection=ops.STAGGERED, offset=np.full(3, 40,
                                                                np.int32),
                        backend=backend)
    np.testing.assert_array_equal(np.asarray(got), [mc - 1, 5, mc - 2])
    ff = select_colors(rows, np.ones(3, bool), max_colors=mc, backend=backend)
    np.testing.assert_array_equal(np.asarray(ff), [mc - 1, 5, mc - 2])


def test_select_rejects_unknowns():
    nbr = np.zeros((4, 2), np.int32)
    with pytest.raises(ValueError):
        select_colors(nbr, np.ones(4, bool), max_colors=64,
                      selection="least_used")
    with pytest.raises(ValueError):
        select_colors(nbr, np.ones(4, bool), max_colors=64, backend="cuda")


# --------------------------------- speculative: parallel_chunk vs the seed --

SEED_GOLD = {
    selection.FIRST_FIT: (13, "800e80e743f3eb16"),
    selection.RANDOM_X: (27, "25d0a8a5f10c1690"),
    selection.STAGGERED: (196, "159b9ed81e9a13e6"),
}


@pytest.mark.parametrize("selname", list(SEED_GOLD))
def test_sequential_mode_is_seed_behavior(graph, pgraph, selname):
    """parallel_chunk=False reproduces the pre-rework coloring bitwise."""
    order = compute_order(pgraph, ordering.NATURAL)
    runs = [color_graph_sim(pgraph, order, ColorConfig(
        max_colors=512, superstep=64, selection=selname, random_x=10, seed=0,
        parallel_chunk=False, backend=b)) for b in ("xla", "pallas")]
    (view, st), (view_p, st_p) = runs
    np.testing.assert_array_equal(np.asarray(view), np.asarray(view_p))
    assert st["n_colors"] == st_p["n_colors"]
    colors = colors_from_views(pgraph, np.asarray(view))
    assert_valid(graph, colors, what=f"sequential-{selname}")
    assert st["n_colors"] == colors.max()
    want_nc, want_hash = SEED_GOLD[selname]
    assert st["n_colors"] == want_nc
    assert _hash(colors) == want_hash


@pytest.mark.parametrize("selname", [selection.FIRST_FIT, selection.STAGGERED,
                                     selection.RANDOM_X])
def test_parallel_mode_valid_and_backends_agree(graph, pgraph, selname):
    order = compute_order(pgraph, ordering.NATURAL)
    mk = lambda b: ColorConfig(max_colors=512, superstep=64,
                               selection=selname, seed=0, backend=b)
    view_x, st_x = color_graph_sim(pgraph, order, mk("xla"))
    assert_valid(graph, colors_from_views(pgraph, np.asarray(view_x)),
                 what=f"parallel-{selname}")
    view_p, st_p = color_graph_sim(pgraph, order, mk("pallas"))
    np.testing.assert_array_equal(np.asarray(view_x), np.asarray(view_p))
    assert st_x["n_colors"] == st_p["n_colors"]


# ------------------------------------------- recolor: chunked ELL vs seed --

RC_GOLD = {
    "nd": (11, 13, "f578174af31ddb61"),
    "rv": (11, 13, "b9f1ceb928314ffc"),
    "rand": (12, 13, "740a3e16be12a2d7"),
}


@pytest.fixture(scope="module")
def seed_view(pgraph):
    order = compute_order(pgraph, ordering.NATURAL)
    view, _ = color_graph_sim(
        pgraph, order, ColorConfig(max_colors=512, superstep=64, seed=0,
                                   parallel_chunk=False))
    return view


@pytest.mark.parametrize("perm", list(RC_GOLD))
def test_recolor_chunked_is_seed_behavior(graph, pgraph, seed_view, perm):
    """Chunked ELL/bitset recolor == the seed dense-occupancy recolor."""
    runs = [recolor_sim(pgraph, seed_view, perm,
                        RecolorConfig(max_colors=512, backend=b),
                        key=jax.random.key(11)) for b in ("xla", "pallas")]
    (v2, st), (v_p, st_p) = runs
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v_p))
    assert st == st_p
    colors = colors_from_views(pgraph, np.asarray(v2))
    assert_valid(graph, colors, what=f"recolor-{perm}")
    want_nc, want_ex, want_hash = RC_GOLD[perm]
    assert st["n_colors"] == want_nc
    assert st["n_exchanges"] == want_ex
    assert _hash(colors) == want_hash


def test_recolor_backends_agree(pgraph, seed_view):
    key = jax.random.key(11)
    v_x, _ = recolor_sim(pgraph, seed_view, "nd",
                         RecolorConfig(max_colors=512, backend="xla"), key=key)
    v_p, _ = recolor_sim(pgraph, seed_view, "nd",
                         RecolorConfig(max_colors=512, backend="pallas"),
                         key=key)
    np.testing.assert_array_equal(np.asarray(v_x), np.asarray(v_p))


def test_recolor_odd_chunk_size(graph, pgraph, seed_view):
    """Chunk size must not change the result (class = independent set)."""
    key = jax.random.key(11)
    v_a, _ = recolor_sim(pgraph, seed_view, "nd",
                         RecolorConfig(max_colors=512, chunk=256), key=key)
    v_b, _ = recolor_sim(pgraph, seed_view, "nd",
                         RecolorConfig(max_colors=512, chunk=19), key=key)
    np.testing.assert_array_equal(np.asarray(v_a), np.asarray(v_b))


# ------------------------------------------------------------ wire16 guard --

def test_wire16_guard():
    """int16 wire payloads cap max_colors at 32767 (silent aliasing past it)."""
    RecolorConfig(max_colors=4096, wire16=True)          # fine
    ColorConfig(max_colors=4096, wire16=True)            # fine
    with pytest.raises(AssertionError):
        RecolorConfig(max_colors=32768, wire16=True)
    with pytest.raises(AssertionError):
        ColorConfig(max_colors=32768, wire16=True)
    # without wire16 the int32 path is unconstrained
    RecolorConfig(max_colors=32768, wire16=False)
    ColorConfig(max_colors=32768, wire16=False)
