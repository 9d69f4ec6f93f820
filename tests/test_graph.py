"""Graph substrate: generators, partitioning invariants (unit + property)."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: property tests
from hypothesis import given, settings, strategies as st

from repro.core import Graph, partition_graph, rmat
from repro.core import graph as graph_mod


def random_graph(n, p, seed):
    g = np.random.default_rng(seed).random((n, n)) < p
    g = np.triu(g, 1)
    g = g | g.T
    src, dst = np.nonzero(g)
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    return Graph(n, np.cumsum(indptr), dst.astype(np.int32))


class TestGenerators:
    def test_rmat_symmetric_no_selfloops(self):
        g = rmat.rmat_good(8, 8, seed=2)
        src = np.repeat(np.arange(g.n), g.degrees)
        assert (src != g.indices).all()
        # symmetry: edge set equals its transpose
        fwd = set(zip(src.tolist(), g.indices.tolist()))
        assert all((v, u) in fwd for u, v in fwd)

    def test_grid_degrees(self):
        g = rmat.grid2d(5, 5, 9)
        assert g.n == 25
        assert g.max_degree == 8  # interior of a 9-pt stencil
        g5 = rmat.grid2d(5, 5, 5)
        assert g5.max_degree == 4

    def test_grid3d(self):
        g = rmat.grid3d(4, 4, 4)
        assert g.n == 64
        assert g.max_degree == 26

    def test_suites_build(self):
        for name, fn in {**rmat.SUITE_REAL}.items():
            if "geom" in name:
                continue
            g = fn()
            assert g.n > 0 and g.m > 0

    def test_dedup_survives_scale32_coordinates(self):
        """Edge dedup at the int64-packing overflow boundary (scale >= 32).

        The former ``u * n + v`` int64 key wraps for n = 2**32 endpoints and
        decodes to negative vertices; the lexsort dedup must handle ids past
        2**31 exactly.
        """
        u = np.array([2**31, 2**31, 2**31 + 1, 0, 2**32 - 1], np.int64)
        v = np.array([5, 5, 7, 2**32 - 1, 0], np.int64)
        uu, vv = rmat._dedup_edges(u, v)
        assert list(zip(uu.tolist(), vv.tolist())) == [
            (0, 2**32 - 1), (2**31, 5), (2**31 + 1, 7), (2**32 - 1, 0)]
        # and stays identical to np.unique-packed keys in the safe range
        rng = np.random.default_rng(0)
        a = rng.integers(0, 1000, 500)
        b = rng.integers(0, 1000, 500)
        key = np.unique(a * 1000 + b)
        uu, vv = rmat._dedup_edges(a, b)
        np.testing.assert_array_equal(uu * 1000 + vv, key)


class TestPartition:
    @pytest.mark.parametrize("P", [1, 2, 3, 7, 8])
    def test_edges_preserved(self, P):
        g = rmat.rmat_er(8, 8, seed=1)
        pg = partition_graph(g, P)
        # reconstruct global adjacency from per-proc CSR
        edges = set()
        for p in range(pg.P):
            nl = int(pg.n_local[p])
            for v in range(nl):
                gv = pg.gvid[p, v]
                for e in range(pg.indptr[p, v], pg.indptr[p, v + 1]):
                    slot = pg.indices[p, e]
                    gu = pg.gvid[p, slot]
                    assert gu >= 0
                    edges.add((int(gv), int(gu)))
        src = np.repeat(np.arange(g.n), g.degrees)
        truth = set(zip(src.tolist(), g.indices.tolist()))
        assert edges == truth

    def test_ghost_maps(self):
        g = rmat.grid2d(16, 16, 9)
        pg = partition_graph(g, 4)
        for p in range(4):
            for gi in range(int(pg.n_ghost[p])):
                owner = pg.ghost_owner[p, gi]
                slot = pg.ghost_slot[p, gi]
                gvid = pg.gvid[p, pg.n_local_max + gi]
                # the owner's boundary list at `slot` is exactly this vertex
                bnd_local = pg.boundary[owner, slot]
                assert pg.gvid[owner, bnd_local] == gvid

    def test_internal_flags(self):
        g = rmat.grid2d(16, 16, 5)
        pg = partition_graph(g, 4)
        for p in range(4):
            nl = int(pg.n_local[p])
            for v in range(nl):
                remote = any(pg.indices[p, e] >= pg.n_local_max
                             for e in range(pg.indptr[p, v],
                                            pg.indptr[p, v + 1]))
                assert pg.is_internal[p, v] == (not remote)

    @pytest.mark.parametrize("P", [2, 3, 4])
    def test_two_hop_halo_matches_oracle(self, P):
        """halo=2: nbr2 rows, ghost tables and boundary vs a brute force."""
        g = rmat.rmat_good(8, 8, seed=1)
        adj = [set(g.indices[g.indptr[v]:g.indptr[v + 1]].tolist())
               for v in range(g.n)]
        d2 = []
        for v in range(g.n):
            s = set()
            for w in adj[v]:
                s |= adj[w]
            d2.append(s - adj[v] - {v})
        pg = partition_graph(g, P, halo=2)
        for p in range(P):
            lo, hi = int(pg.offs[p]), int(pg.offs[p + 1])
            nl = int(pg.n_local[p])
            for v in range(0, nl, 7):                   # sampled rows
                row = pg.nbr2[p, v]
                slots = row[row != pg.sentinel]
                assert set(pg.gvid[p, slots].tolist()) == d2[lo + v]
            assert (pg.nbr2[p, nl:] == pg.sentinel).all()
            # ghost set = all remote vertices within two hops, ascending
            want = set()
            for v in range(lo, hi):
                want |= {u for u in adj[v] | d2[v] if not lo <= u < hi}
            ng = int(pg.n_ghost[p])
            got = pg.gvid[p, pg.n_local_max : pg.n_local_max + ng]
            assert got.tolist() == sorted(want)
            # boundary = locals read by some other shard (within two hops)
            bnd = set(pg.boundary[p, : int(pg.n_boundary[p])].tolist())
            for v in range(nl):
                read = any(not lo <= u < hi for u in adj[lo + v] | d2[lo + v])
                assert (v in bnd) == read
                assert pg.is_internal[p, v] == (not read)

    @pytest.mark.parametrize("P", [2, 4])
    @pytest.mark.parametrize("chunk", [None, 64], ids=["one-chunk", "chunked"])
    @pytest.mark.parametrize("gname", ["grid_slab", "grid_cube", "rmat_good",
                                       "rmat_bad"])
    def test_two_hop_pairs_match_brute_force(self, gname, chunk, P,
                                             monkeypatch):
        """The one-pass two-hop build == a set construction, row by row and
        in order, on a 27-point slab and cube, RMAT-Good and the hub-heavy
        RMAT-Bad; ``chunked`` splits each block into many row chunks."""
        g = {"grid_slab": lambda: rmat.grid3d(12, 6, 6),
             "grid_cube": lambda: rmat.grid3d(8, 8, 8),
             "rmat_good": lambda: rmat.rmat_good(8, 8, seed=1),
             "rmat_bad": lambda: rmat.rmat_bad(8, 8, seed=1)}[gname]()
        if chunk is not None:
            monkeypatch.setattr(graph_mod, "TWO_HOP_CHUNK", chunk)
        adj = [set(g.indices[g.indptr[v]:g.indptr[v + 1]].tolist())
               for v in range(g.n)]
        offs = np.linspace(0, g.n, P + 1).astype(np.int64)
        got = graph_mod._two_hop_pairs(g, offs)
        assert len(got) == P
        for p, (row2, nb2) in enumerate(got):
            lo, hi = int(offs[p]), int(offs[p + 1])
            want = [(v - lo, u) for v in range(lo, hi)
                    for u in sorted(set().union(*(adj[w] for w in adj[v]))
                                    - adj[v] - {v})]
            assert row2.dtype == nb2.dtype == np.int32
            assert list(zip(row2.tolist(), nb2.tolist())) == want

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(6, 40), p=st.floats(0.05, 0.5),
           P=st.integers(1, 5), seed=st.integers(0, 99))
    def test_partition_roundtrip_property(self, n, p, P, seed):
        g = random_graph(n, p, seed)
        pg = partition_graph(g, P)
        assert int(pg.n_local.sum()) == n
        # every cross edge appears on both sides
        total_edges = 0
        for q in range(P):
            nl = int(pg.n_local[q])
            total_edges += int(pg.indptr[q, nl])
        assert total_edges == g.m_directed
