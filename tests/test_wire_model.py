"""Measured wire bytes == modeled bytes, for every round_mask subset.

The drivers account comm volume through the exchange return value
(``stats["wire_bytes"]``); this module pins that measurement to the static
cost models — ``CommPlan.bytes_per_exchange(round_mask=...)`` for the sparse
scheme (any subset of ``ppermute`` rounds, the shape recolor's per-link
piggybacking produces) and ``allgather_bytes_per_exchange`` for the
broadcast — at halo depth 1 and 2.  Exhaustive over all 2^n_rounds subsets.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ColorConfig, RecolorConfig, partition_graph, rmat
from repro.core.comm import (AxisComm, CommConfig,
                             allgather_bytes_per_exchange, make_exchange,
                             run_spmd)

P = 4


@pytest.fixture(scope="module", params=[1, 2], ids=["halo1", "halo2"])
def pgraph(request):
    return partition_graph(rmat.rmat_good(8, 8, seed=3), P,
                           halo=request.param)


def _measure(pg, scheme, round_mask):
    arrs = {k: jnp.asarray(v) for k, v in pg.arrays().items()}
    views = jnp.ones((P, pg.n_slots), jnp.int32)
    mask = None if round_mask is None else jnp.asarray(round_mask)

    def fn(a, v, axis, lane_axes):
        ex = make_exchange(a, pg.n_local_max, P, AxisComm(axis, lane_axes),
                           CommConfig(scheme=scheme), pg.comm_plan.static)
        _, b = ex(v, round_mask=mask)
        return b

    out = jax.jit(lambda a, v: run_spmd(fn, None, P, (a, v)))(arrs, views)
    out = np.asarray(out)
    assert (out == out[0]).all()          # shard-uniform by construction
    return int(out[0])


def test_sparse_wire_bytes_match_model_all_subsets(pgraph):
    plan = pgraph.comm_plan
    n_rounds = len(plan.shifts)
    assert 1 <= n_rounds <= P - 1
    assert _measure(pgraph, "sparse", None) == plan.bytes_per_exchange()
    for bits in itertools.product((False, True), repeat=n_rounds):
        want = plan.bytes_per_exchange(round_mask=bits)
        assert _measure(pgraph, "sparse", np.asarray(bits)) == want
    # depth 2 reads strictly more remote colors than depth 1
    if pgraph.halo == 2:
        pg1 = partition_graph(rmat.rmat_good(8, 8, seed=3), P, halo=1)
        assert plan.bytes_per_exchange() > pg1.comm_plan.bytes_per_exchange()


def test_allgather_wire_bytes_match_model(pgraph):
    """The broadcast ships everything regardless of any round mask."""
    model = allgather_bytes_per_exchange(P, int(pgraph.max_boundary))
    n_rounds = len(pgraph.comm_plan.shifts)
    assert _measure(pgraph, "allgather", None) == model
    for bits in itertools.product((False, True), repeat=n_rounds):
        assert _measure(pgraph, "allgather", np.asarray(bits)) == model


def test_quantized_plan_bitwise_equals_exact_plan(pgraph):
    """pow2-rung width quantization is inert (DESIGN.md §2).

    The rung plan's buffer shapes only widen; padding entries are never
    read and never counted, so (a) the exact byte model is unchanged for
    every round-mask subset, and (b) a full fused pipeline run on an
    exact-plan twin is bitwise identical — views and measured wire bytes.
    """
    import dataclasses

    from repro.core import build_comm_plan, compute_order
    from repro.core.graph import _ceil_pow2
    from repro.core.pipeline import PipelineConfig, run_pipeline

    plan_q = pgraph.comm_plan                      # quantized by default
    plan_e = build_comm_plan(pgraph, quantize=False)
    assert plan_q.shifts == plan_e.shifts
    assert plan_q.exact_widths == plan_e.exact_widths == plan_e.widths
    assert plan_q.widths == tuple(_ceil_pow2(w) for w in plan_e.widths)
    n_rounds = len(plan_q.shifts)
    for bits in itertools.product((False, True), repeat=n_rounds):
        assert (plan_q.bytes_per_exchange(round_mask=bits)
                == plan_e.bytes_per_exchange(round_mask=bits))
    # only the padded accounting sees the rung waste
    assert (plan_q.bytes_per_exchange(padded=True)
            >= plan_e.bytes_per_exchange(padded=True)
            == plan_e.bytes_per_exchange())

    pg_e = dataclasses.replace(pgraph, quantize_plan=False)
    assert pg_e.comm_plan.widths == plan_e.widths
    cfg = PipelineConfig(
        color=ColorConfig(max_colors=64, scheme="sparse"),
        recolor=RecolorConfig(max_colors=64, scheme="sparse"),
        n_iters=2, patience=0)
    order = compute_order(pgraph, "internal_first")
    v_q, res_q = run_pipeline(pgraph, order, cfg)
    v_e, res_e = run_pipeline(pg_e, order, cfg)
    np.testing.assert_array_equal(np.asarray(v_q), np.asarray(v_e))
    assert res_q["color"]["wire_bytes"] == res_e["color"]["wire_bytes"]
    assert res_q["history"] == res_e["history"]    # every stat, bitwise


def test_default_scheme_follows_env(exchange_scheme):
    """The CI matrix knob: config defaults track $REPRO_SCHEME."""
    assert ColorConfig().scheme == exchange_scheme
    assert RecolorConfig().scheme == exchange_scheme
    assert CommConfig().scheme == exchange_scheme
