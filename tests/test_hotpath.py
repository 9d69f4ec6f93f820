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
import functools
import hashlib

import jax
import numpy as np
import pytest

from repro.analysis.trace_audit import _param_jaxprs
from repro.core import (ColorConfig, RecolorConfig, assert_valid,
                        color_graph_sim, colors_from_views, compute_order,
                        ordering, pad_partition, partition_graph,
                        recolor_sim, rmat, select_colors, selection)
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


@pytest.mark.parametrize("halo,pad", [(1, False), (2, False), (1, True)],
                         ids=["halo1", "halo2", "padded"])
def test_degree_is_ell_row_length(graph, halo, pad):
    """``degree`` counts a row's real ELL entries, which come first: the
    recolor chunk gathers only its rows' widest ``degree`` slots."""
    pg = partition_graph(graph, 4, halo=halo)
    if pad:
        pg = pad_partition(pg, n_local_max=pg.n_local_max + 5,
                           maxd=pg.maxd + 3)
    real = np.arange(pg.maxd) < pg.degree[..., None]
    np.testing.assert_array_equal(pg.nbr != pg.sentinel, real)


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


# Colorings captured from the full-width speculative phase (before width-
# bucketed tiles and detection chunks): (graph, order, selection, superstep,
# tile) -> (n_colors, n_rounds, colors hash), and the range of gather_slots
# as a share of the full-width program's.  Tiles of 8 rows fall into
# several widths on RMAT.  The grid's tiles are its supersteps, 64 rows, so
# each holds a degree-26 row, as the benchmark's 128-row tiles do; tiles of
# a few boundary rows (degrees 7-17), or of none in a small late frontier,
# would gather narrower.
SPEC_GOLD = [
    pytest.param("rmat11", ordering.NATURAL, selection.FIRST_FIT, 64, 8,
                 (16, 4, "63a38d0e79f70c6e"), (0, 0.6), id="skewed-ff"),
    pytest.param("rmat11", ordering.NATURAL, selection.RANDOM_X, 64, 8,
                 (33, 3, "d1768c8431c43327"), (0, 0.6), id="skewed-rx"),
    pytest.param("rmat11", ordering.INTERNAL_FIRST, selection.FIRST_FIT, 64,
                 8, (15, 4, "2110ea94e4a0158c"), (0, 0.6),
                 id="skewed-ff-internal-first"),
    # 24-row tiles: the third of each superstep reaches into the next one
    pytest.param("rmat11", ordering.NATURAL, selection.RANDOM_X, 64, 24,
                 (32, 3, "6d3052a2520106e5"), (0, 0.7), id="skewed-rx-spill"),
    pytest.param("grid10", ordering.NATURAL, selection.FIRST_FIT, 64, 64,
                 (16, 8, "bc3a557754649deb"), (0.98, 1), id="grid-ff"),
    pytest.param("grid10", ordering.NATURAL, selection.RANDOM_X, 64, 64,
                 (31, 4, "642382230cbfd7cf"), (0.98, 1), id="grid-rx"),
]


def _full_width_run(monkeypatch, pg, order, cfg):
    """``color_graph_sim`` with every tile and chunk gathered at ``maxd``:
    the program before width buckets."""
    from repro.core import speculative
    monkeypatch.setattr(speculative, "gather_widths", lambda maxd: (maxd,))
    speculative._sim_fn.cache_clear()
    try:
        return color_graph_sim(pg, order, cfg)
    finally:
        monkeypatch.undo()
        speculative._sim_fn.cache_clear()


@pytest.mark.parametrize("name,order_kind,selname,superstep,tile,gold,share",
                         SPEC_GOLD)
def test_speculative_bucketed_is_full_width(monkeypatch, name, order_kind,
                                            selname, superstep, tile, gold,
                                            share):
    """Width-bucketed tiles and degree-sorted detection reproduce the
    full-width speculative coloring bitwise, on both backends, and gather
    fewer slots only where degrees are skewed."""
    g, pg, _ = _rc_input(name)
    order = compute_order(pg, order_kind)
    mk = lambda b: ColorConfig(max_colors=512, superstep=superstep,
                               tile=tile, selection=selname, seed=0,
                               backend=b)
    (v, st), (v_p, st_p) = [color_graph_sim(pg, order, mk(b))
                            for b in ("xla", "pallas")]
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_p))
    assert st == st_p
    colors = colors_from_views(pg, np.asarray(v))
    assert_valid(g, colors, what=f"speculative-{name}-{selname}")
    assert (st["n_colors"], st["n_rounds"], _hash(colors)) == gold
    v_f, st_f = _full_width_run(monkeypatch, pg, order, mk("xla"))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_f))
    lo, hi = share
    assert lo * st_f["gather_slots"] <= st["gather_slots"] <= (
        hi * st_f["gather_slots"]), (st["gather_slots"], st_f["gather_slots"])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_detection_is_free_of_order(pgraph, backend):
    """Conflict detection over the frontier in visit order, at full width,
    and sorted by degree, at each chunk's width, gives the same uncolorings
    on a view with conflicts: every chunk reads the pre-detection view."""
    import jax.numpy as jnp

    from repro.core.speculative import (_detect_conflicts_frontier,
                                        _frontier_by_degree,
                                        _superstep_widths, gather_widths)
    pg, S = pgraph, 32
    arrs = {k: jnp.asarray(v[0]) for k, v in pg.arrays(sparse=False).items()}
    rng = np.random.default_rng(7)
    n_slots, nl = arrs["prio"].shape[0], int(pg.n_local[0])
    view = rng.integers(1, 6, n_slots).astype(np.int32)
    view[-1] = 0                                     # the sentinel
    n_need = (3 * nl) // 4
    order = np.full(pg.n_local_max, -1, np.int32)
    order[:nl] = rng.permutation(nl)
    order = jnp.asarray(order)
    view = jnp.asarray(view)
    n_chunks = -(-pg.n_local_max // S)
    n_steps = -(-n_need // S)
    pad = jnp.full((S,), -1, jnp.int32)
    live = jnp.arange(pg.n_local_max) < n_need

    def detect(rows, widths):
        row_deg = jnp.where(live, arrs["degree"][jnp.maximum(rows, 0)], 0)
        wid = _superstep_widths(row_deg, S, S, 1, n_chunks)[:, 0]
        return _detect_conflicts_frontier(
            view, arrs, jnp.concatenate([rows, pad]), n_steps, n_need, S,
            widths, wid, backend=backend)

    widths = gather_widths(pg.maxd)
    assert len(widths) >= 3
    want = detect(order, (pg.maxd,))
    assert int(want[1]) > 0 and bool(want[2])
    for rows in (order, _frontier_by_degree(order, n_need, arrs["degree"])):
        got = detect(rows, widths)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        assert int(got[1]) == int(want[1]) and bool(got[2]) == bool(want[2])


# ------------------------------------------- recolor: chunked ELL vs seed --

def _seed_coloring(pg):
    order = compute_order(pg, ordering.NATURAL)
    view, _ = color_graph_sim(
        pg, order, ColorConfig(max_colors=512, superstep=64, seed=0,
                               parallel_chunk=False))
    return view


@pytest.fixture(scope="module")
def seed_view(pgraph):
    return _seed_coloring(pgraph)


RC_GRAPHS = {
    "rmat10": lambda: rmat.rmat_good(10, 8, seed=3),    # maxd 72
    "rmat11": lambda: rmat.rmat_good(11, 8, seed=3),    # maxd 80
    "grid10": lambda: rmat.grid3d(10, 10, 10),          # maxd 26
}


@functools.lru_cache(maxsize=None)
def _rc_input(name):
    g = RC_GRAPHS[name]()
    pg = partition_graph(g, 4)
    return g, pg, _seed_coloring(pg)


def _full_width_slots(pg, view, chunk):
    """ELL slots an iteration gathers with every chunk at ``maxd``: the
    chunks of each class (its largest shard share over ``chunk``, at least
    one) times ``chunk`` rows, ``maxd`` slots and P shards."""
    view = np.asarray(view)
    chunk = min(chunk, pg.n_local_max)
    n = int(view.max()) + 1
    sizes = np.stack([np.bincount(view[p, :pg.n_local[p]], minlength=n)[1:]
                      for p in range(pg.P)]).max(axis=0)
    n_chunks = int(np.sum(-(-sizes[sizes > 0] // chunk)))
    return n_chunks * chunk * pg.maxd * pg.P


# Colorings captured from the full-width recolor (before width-bucketed
# chunks): (graph, perm, chunk) -> (n_colors, n_exchanges, colors hash), and
# the range of gather_slots as a share of the full-width count.  Chunks of
# 16 rows split RMAT's classes into several chunks of falling degree, as
# 256-row chunks do at the benchmark's scale; the regular grid's chunks
# nearly all hold a degree-26 row.
RC_GOLD = [
    pytest.param("rmat10", "nd", 256, (11, 13, "f578174af31ddb61"), (0, 1),
                 id="nd"),
    pytest.param("rmat10", "rv", 256, (11, 13, "b9f1ceb928314ffc"), (0, 1),
                 id="rv"),
    pytest.param("rmat10", "rand", 256, (12, 13, "740a3e16be12a2d7"),
                 (0, 1), id="rand"),
    pytest.param("rmat11", "nd", 16, (12, 14, "97c86cd8bb31926e"),
                 (0, 0.5), id="skewed-nd"),
    pytest.param("grid10", "nd", 256, (12, 9, "224462954b2511da"),
                 (0.98, 1), id="grid-nd"),
    pytest.param("grid10", "rv", 256, (12, 9, "e534d9d8c1ccbfba"),
                 (0.98, 1), id="grid-rv"),
    pytest.param("grid10", "rand", 256, (12, 12, "348a4c38424faaa4"),
                 (0.98, 1), id="grid-rand"),
]


@pytest.mark.parametrize("name,perm,chunk,gold,share", RC_GOLD)
def test_recolor_chunked_is_seed_behavior(name, perm, chunk, gold, share):
    """Chunked ELL/bitset recolor == the seed dense-occupancy recolor, and
    width-bucketed chunks gather fewer slots only where degrees are skewed."""
    g, pg, view = _rc_input(name)
    runs = [recolor_sim(pg, view, perm,
                        RecolorConfig(max_colors=512, chunk=chunk, backend=b),
                        key=jax.random.key(11)) for b in ("xla", "pallas")]
    (v2, st), (v_p, st_p) = runs
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v_p))
    assert st == st_p
    colors = colors_from_views(pg, np.asarray(v2))
    assert_valid(g, colors, what=f"recolor-{name}-{perm}")
    want_nc, want_ex, want_hash = gold
    assert st["n_colors"] == want_nc
    assert st["n_exchanges"] == want_ex
    assert _hash(colors) == want_hash
    lo, hi = share
    full = _full_width_slots(pg, view, chunk)
    assert lo * full <= st["gather_slots"] <= hi * full, (
        st["gather_slots"], full)


def _has_gather(jaxpr, tail) -> bool:
    return any((e.primitive.name == "gather"
                and tuple(e.outvars[0].aval.shape[-2:]) == tail)
               or any(_has_gather(j, tail) for j in _param_jaxprs(e.params))
               for e in jaxpr.eqns)


def _width_dispatch(jaxpr, widths, chunk, found, stray, inside=False):
    """Collect the ``cond``s with one branch per width, each gathering a
    ``(chunk, width)`` tile, and any narrow tile gathered outside them or
    cut from the ELL table's rows by the gather itself (which XLA lowers to
    a loop over the rows)."""
    narrow = {(chunk, w) for w in widths[:-1]}
    for e in jaxpr.eqns:
        if (e.primitive.name == "gather"
                and e.invars[0].aval.shape[-1] == widths[-1]
                and 1 < e.params["slice_sizes"][-1] < widths[-1]):
            stray.append(e)
        branches = e.params.get("branches", ())
        if (e.primitive.name == "cond" and len(branches) == len(widths)
                and all(_has_gather(b.jaxpr, (chunk, w))
                        for b, w in zip(branches, widths))):
            found.append(e)
            inside_e = True
        else:
            inside_e = inside
            if (e.primitive.name == "gather" and not inside
                    and tuple(e.outvars[0].aval.shape[-2:]) in narrow):
                stray.append(e)
        for j in _param_jaxprs(e.params):
            _width_dispatch(j, widths, chunk, found, stray, inside_e)


def _fused_batch_jaxpr(pg, cfg):
    from repro.core.pipeline import pipeline_sim_program
    fn, args = pipeline_sim_program(pg, compute_order(pg, ordering.NATURAL),
                                    cfg)
    return jax.make_jaxpr(fn)(*args)


def _engine_step_jaxpr(pg, cfg, B=2):
    import jax.numpy as jnp

    from repro.core import engine_init_program, engine_step_program
    arrs = {k: jnp.asarray(v) for k, v in pg.arrays(sparse=False).items()}
    order = jnp.asarray(compute_order(pg, ordering.NATURAL))
    init = engine_init_program(pg.P, cfg, None, arrs)
    carry, _ = jax.eval_shape(init, arrs, order, jax.random.key(0))
    lanes = lambda x: jax.ShapeDtypeStruct((B,) + tuple(x.shape), x.dtype)
    arrs_b = jax.tree.map(lanes, arrs)
    step = engine_step_program(pg.P, cfg, None, arrs_b, B, 1)
    return jax.make_jaxpr(step)(arrs_b, jax.tree.map(lanes, carry),
                                jax.random.split(jax.random.key(0), B))


def _many_batch_jaxpr(pg, cfg, B=2):
    """The bucketed batch's program (``color_many``): B graph lanes."""
    import jax.numpy as jnp

    from repro.core.pipeline import _many_sim_program, _signature
    arrs = pg.arrays(sparse=False)
    lanes = lambda x: jax.ShapeDtypeStruct((B,) + np.shape(x),
                                           jnp.asarray(x).dtype)
    arrs_b = {k: lanes(v) for k, v in arrs.items()}
    order = lanes(compute_order(pg, ordering.NATURAL))
    keys = jax.random.split(jax.random.key(0), B)
    fn = _many_sim_program(_signature("many_sim", pg.P, cfg, None, arrs_b,
                                      batch=B), pg.P, cfg, None)
    return jax.make_jaxpr(fn)(arrs_b, order, keys, keys)


# Rows a gather tile holds in each bucketed phase of the dispatch tests: the
# recolor chunk, the speculative tile and the detection chunk (the
# superstep), all different so that each phase's dispatch is told apart.
DISPATCH_ROWS = dict(recolor=16, tile=8, detect=32)


def _dispatch(program, rows):
    """Width dispatches (``cond``s) and stray narrow gathers of ``rows``-row
    tiles in ``program``'s jaxpr, on a skewed graph with >= 3 widths."""
    from repro.core import PipelineConfig
    from repro.core.pipeline import program_cache_clear
    from repro.core.speculative import gather_widths
    pg = partition_graph(rmat.rmat_good(8, 8, seed=1), 2)
    widths = gather_widths(pg.maxd)
    assert len(widths) >= 3
    cfg = PipelineConfig(
        color=ColorConfig(max_colors=64, superstep=DISPATCH_ROWS["detect"],
                          tile=DISPATCH_ROWS["tile"], scheme="allgather"),
        recolor=RecolorConfig(max_colors=64, chunk=DISPATCH_ROWS["recolor"],
                              scheme="allgather"), n_iters=1)
    try:
        jx = program(pg, cfg)
    finally:
        program_cache_clear()
    found, stray = [], []
    _width_dispatch(jx.jaxpr, widths, rows, found, stray)
    return found, stray


@pytest.mark.parametrize("program", [_fused_batch_jaxpr, _engine_step_jaxpr],
                         ids=["fused_batch", "engine_step"])
def test_recolor_width_dispatch_is_one_cond(program):
    """The chunk's width is one ``cond`` with a branch per width, on an
    index that no vmap batches: no narrow gather runs outside it, as vmap's
    run-every-branch lowering (gathers merged by ``select_n``) would."""
    found, stray = _dispatch(program, DISPATCH_ROWS["recolor"])
    assert len(found) == 1, len(found)
    assert not stray


@pytest.mark.parametrize("phase", ["tile", "detect"])
@pytest.mark.parametrize("program", [_fused_batch_jaxpr, _many_batch_jaxpr],
                         ids=["fused_batch", "many_batch"])
def test_speculative_width_dispatch_is_one_cond(program, phase):
    """The speculative phase's coloring tiles and detection chunks each
    take their width through one ``cond`` with a branch per width, also
    with graph lanes under vmap, and gather no narrow tile outside it."""
    found, stray = _dispatch(program, DISPATCH_ROWS[phase])
    assert len(found) == 1, len(found)
    assert not stray


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
