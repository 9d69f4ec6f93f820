"""Coloring-based scheduling (paper tie-in) + loop-aware roofline parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: property tests
from hypothesis import given, settings, strategies as st

from repro.data.coloring_sched import (conflict_graph, schedule,
                                       validate_schedule)
from repro.roofline import analyze_hlo, roofline_terms


class TestScheduling:
    def test_schedule_is_conflict_free(self, rng):
        res = rng.integers(0, 30, (64, 3))
        groups, n_groups, _ = schedule(res, 64, n_workers=2)
        assert validate_schedule(res, groups)
        assert sum(len(g) for g in groups) == 64

    def test_fewer_groups_than_sequential(self, rng):
        res = rng.integers(0, 100, (128, 2))
        groups, n_groups, _ = schedule(res, 128, n_workers=4)
        assert n_groups < 128  # coloring beats fully-serial execution
        g = conflict_graph(res, 128)
        assert n_groups <= g.max_degree + 1

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(4, 40), r=st.integers(1, 3), seed=st.integers(0, 99))
    def test_schedule_property(self, n, r, seed):
        res = np.random.default_rng(seed).integers(0, 12, (n, r))
        groups, _, _ = schedule(res, n, n_workers=1,
                                use_quality_preset=False)
        assert validate_schedule(res, groups)


class TestRooflineParser:
    def test_scan_trip_counts_accounted(self):
        """Scanned and unrolled versions must parse to ~equal FLOPs."""
        def body(x, w):
            return jnp.tanh(x @ w), None

        def scanned(x, ws):
            return jax.lax.scan(body, x, ws)[0]

        def unrolled(x, ws):
            for i in range(8):
                x, _ = body(x, ws[i])
            return x

        x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
        fs = analyze_hlo(jax.jit(scanned).lower(x, ws).compile().as_text())
        fu = analyze_hlo(jax.jit(unrolled).lower(x, ws).compile().as_text())
        expect = 2 * 8 * 64 * 128 * 128
        assert fs["dot_flops"] == pytest.approx(expect, rel=0.05)
        assert fu["dot_flops"] == pytest.approx(expect, rel=0.05)

    def test_nested_scan_multipliers(self):
        def inner(x, w):
            return x @ w, None

        def outer(x, ws):
            def step(c, _):
                return jax.lax.scan(inner, c, ws)[0], None
            return jax.lax.scan(step, x, None, length=5)[0]

        x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        ws = jax.ShapeDtypeStruct((3, 32, 32), jnp.float32)
        a = analyze_hlo(jax.jit(outer).lower(x, ws).compile().as_text())
        assert a["dot_flops"] == pytest.approx(2 * 15 * 32**3, rel=0.05)

    def test_terms_and_bottleneck(self):
        analysis = dict(dot_flops=197e12, dot_bytes=0.0,
                        coll_bytes={"all-reduce": 100e9}, coll_count={},
                        dynamic_whiles=0, while_trips=[])
        t = roofline_terms(analysis)
        assert t["compute_s"] == pytest.approx(1.0)
        assert t["collective_s"] == pytest.approx(1.0)  # 2x AR / 4 links
        assert t["bottleneck"] in ("compute", "collective")

    def test_unknown_device_kind_raises(self):
        analysis = dict(dot_flops=1.0, dot_bytes=1.0, coll_bytes={},
                        coll_count={}, dynamic_whiles=0, while_trips=[])
        with pytest.raises(ValueError, match="no published peaks"):
            roofline_terms(analysis, device_kind="cpu")
