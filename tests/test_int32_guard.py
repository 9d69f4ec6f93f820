"""The id-width policy (repro.core.graph.id_policy) and its int32 guard.

Pure shape arithmetic at the exact ``n_local_max * maxd`` boundary — no
8GB allocations.  ``check_int32_limits`` (the historical hard guard) must
keep raising exactly where it always did; ``id_policy`` must *promote* to
int64 past the same boundaries instead.  A spy test pins that
``partition_graph`` actually consults the policy before building the ELL
arrays.
"""
import numpy as np
import pytest

from repro.core import partition_graph, rmat
from repro.core.graph import (INT32_LIMIT, INT64_LIMIT, check_int32_limits,
                              id_policy)


class TestInt32Limits:
    def test_ell_boundary_exact(self):
        # largest legal ELL tile: n_local_max * maxd == 2**31 - 1
        check_int32_limits(10, INT32_LIMIT - 1, 1)
        with pytest.raises(ValueError, match="int32 ELL overflow"):
            check_int32_limits(10, INT32_LIMIT, 1)
        # the product overflows, not either factor
        check_int32_limits(10, 2**16 - 1, 2**15 - 1)
        with pytest.raises(ValueError, match="partition over more workers"):
            check_int32_limits(10, 2**16, 2**15)

    def test_maxd2_participates(self):
        check_int32_limits(10, 2**16, 2, maxd2=2**14)
        with pytest.raises(ValueError, match="int32 ELL overflow"):
            check_int32_limits(10, 2**16, 2, maxd2=2**15)

    def test_global_id_limit(self):
        check_int32_limits(INT32_LIMIT - 1, 4, 4)
        with pytest.raises(ValueError, match="int32"):
            check_int32_limits(INT32_LIMIT, 4, 4)


class TestIdPolicyPromotion:
    """Past the guard the policy promotes instead of raising (DESIGN §10)."""

    def test_id_dtype_boundary(self):
        # just below the int32 vertex bound: everything stays int32
        pol = id_policy(INT32_LIMIT - 1, 4, 4)
        assert np.dtype(pol.id_dtype) == np.int32
        assert not pol.promoted and pol.id_itemsize == 4
        # at/above the bound: global ids promote, ELL untouched
        pol = id_policy(INT32_LIMIT, 4, 4)
        assert np.dtype(pol.id_dtype) == np.int64
        assert np.dtype(pol.ell_dtype) == np.int32
        assert pol.promoted and pol.id_itemsize == 8

    def test_ell_dtype_boundary(self):
        pol = id_policy(10, INT32_LIMIT - 1, 1)
        assert np.dtype(pol.ell_dtype) == np.int32 and not pol.promoted
        pol = id_policy(10, INT32_LIMIT, 1)
        assert np.dtype(pol.ell_dtype) == np.int64
        assert np.dtype(pol.id_dtype) == np.int32   # ids independent
        assert pol.promoted

    def test_maxd2_widens_ell(self):
        pol = id_policy(10, 2**16, 2, 2**15)
        assert np.dtype(pol.ell_dtype) == np.int64

    def test_allow_int64_false_is_the_hard_guard(self):
        with pytest.raises(ValueError, match="int32"):
            id_policy(INT32_LIMIT, 4, 4, allow_int64=False)
        with pytest.raises(ValueError, match="int32 ELL overflow"):
            id_policy(10, INT32_LIMIT, 1, allow_int64=False)

    def test_int64_ceiling_always_raises(self):
        with pytest.raises(ValueError, match="int64"):
            id_policy(INT64_LIMIT, 4, 4)
        with pytest.raises(ValueError, match="int64"):
            id_policy(10, INT64_LIMIT // 2, 4)

    def test_partition_dtypes_follow_policy_at_cpu_scale(self):
        g = rmat.grid2d(4, 4, 5)
        pg = partition_graph(g, 2)
        assert pg.gvid.dtype == np.int32 and pg.prio.dtype == np.int32
        assert g.indices.dtype == np.int32


class TestPartitionRunsThePolicy:
    def test_partition_graph_runs_the_guard(self, monkeypatch):
        from repro.core import graph as graph_mod
        calls = []
        real = id_policy

        def spy(*a, **k):
            calls.append((a, k))
            return real(*a, **k)

        monkeypatch.setattr(graph_mod, "id_policy", spy)
        g = rmat.grid2d(4, 4, 5)
        partition_graph(g, 2)
        assert calls, "partition_graph must consult the id policy"
        # every call site reasons about this graph's global id range, and
        # the ELL-guard site passes a real tile (n_local_max * maxd > 1)
        for a, _ in calls:
            assert a[0] == g.n
        assert any(a[1] * a[2] > 1 for a, _ in calls if len(a) >= 3)


@pytest.mark.parametrize("scale,P,id_dtype,ell_dtype,total,fits", [
    (31, 1024, "int64", "int32", 1704984600, True),
    (33, 4096, "int64", "int32", 1704984600, True),
    (36, 32768, "int64", "int32", 1704984600, True),
    (36, 2048, "int64", "int64", 27279753240, False),
])
def test_memory_projection_of_the_int64_regime(scale, P, id_dtype,
                                               ell_dtype, total, fits):
    """``roofline.coloring_memory_projection`` sizes the int64 regime on
    paper (DESIGN.md §10): past 2**31 vertices the ids widen and the slot
    arrays stay int32 until ``n_local_max * maxd`` crosses the bound, and
    a scale-36 RMAT graph fits a 16 GB v5e shard at P=32768, not at
    P=2048 (maxd 64)."""
    from repro.roofline import coloring_memory_projection
    proj = coloring_memory_projection(2**scale, P, maxd=64)
    assert (proj["id_dtype"], proj["ell_dtype"]) == (id_dtype, ell_dtype)
    assert proj["promoted"]
    assert proj["total_per_shard"] == total
    assert proj["fits_hbm"] is fits
