"""Speculative coloring + iterative recoloring: the paper's invariants."""
import jax
import numpy as np
import pytest

from repro.core import (ColorConfig, RecolorConfig, arc_sim, assert_valid,
                        check_coloring, color_graph, colors_from_views,
                        compute_order, ordering, partition_graph,
                        recolor_iterations, recolor_step, rmat, selection)

GRAPHS = {
    "grid9": lambda: rmat.grid2d(32, 32, 9),
    "rmat_good": lambda: rmat.rmat_good(10, 8, seed=3),
}


def color(g, P, *, order_kind=ordering.NATURAL, sel=selection.FIRST_FIT,
          superstep=64, x=10, max_colors=512, seed=0):
    pg = partition_graph(g, P)
    order = compute_order(pg, order_kind)
    cfg = ColorConfig(max_colors=max_colors, superstep=superstep,
                      selection=sel, random_x=x, seed=seed)
    view, stats = color_graph(pg, order, cfg)
    return pg, np.asarray(view), stats


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("P", [1, 4, 8])
def test_speculative_valid(gname, P):
    g = GRAPHS[gname]()
    pg, view, stats = color(g, P)
    colors = colors_from_views(pg, view)
    st = assert_valid(g, colors)
    assert st["n_colors"] == stats["n_colors"]
    assert st["n_colors"] <= g.max_degree + 1  # greedy bound (Alg. 1)


@pytest.mark.parametrize("sel", [selection.FIRST_FIT, selection.STAGGERED,
                                 selection.LEAST_USED, selection.RANDOM_X])
def test_selection_strategies_valid(sel):
    g = GRAPHS["rmat_good"]()
    pg, view, _ = color(g, 4, sel=sel)
    assert_valid(g, colors_from_views(pg, view), what=sel)


@pytest.mark.parametrize("order_kind", ordering.ALL_ORDERINGS)
def test_orderings_valid(order_kind):
    g = GRAPHS["grid9"]()
    pg, view, _ = color(g, 4, order_kind=order_kind)
    assert_valid(g, colors_from_views(pg, view), what=order_kind)


def test_sl_beats_natural_sequentially():
    """Table 2's expectation: SL/LF <= NAT colors on RMAT graphs (P=1).

    (On perfectly regular grids SL can lose to NAT — verified identical to
    networkx's smallest_last — so the check uses the skewed-degree suite.)"""
    g = rmat.rmat_bad(10, 8, seed=2)
    _, _, s_nat = color(g, 1, order_kind=ordering.NATURAL, max_colors=2048)
    _, _, s_lf = color(g, 1, order_kind=ordering.LARGEST_FIRST,
                       max_colors=2048)
    _, _, s_sl = color(g, 1, order_kind=ordering.SMALLEST_LAST,
                       max_colors=2048)
    assert s_lf["n_colors"] <= s_nat["n_colors"]
    assert s_sl["n_colors"] <= s_nat["n_colors"]


def test_randomx_fewer_rounds_more_colors():
    """§3.2: Random-X reduces conflicts (rounds) but costs colors."""
    g = rmat.rmat_good(11, 8, seed=5)
    _, _, s_ff = color(g, 8, sel=selection.FIRST_FIT, superstep=256)
    _, _, s_rx = color(g, 8, sel=selection.RANDOM_X, x=50, superstep=256)
    assert s_rx["n_colors"] >= s_ff["n_colors"]
    assert s_rx["n_rounds"] <= s_ff["n_rounds"] + 1


class TestRecolor:
    def setup_method(self, _):
        self.g = GRAPHS["rmat_good"]()
        self.pg, self.view, self.stats = color(self.g, 4)
        self.rcfg = RecolorConfig(max_colors=512)

    @pytest.mark.parametrize("perm", ["rv", "ni", "nd", "rand"])
    def test_permutations_valid_and_no_worse(self, perm):
        new_view, st = recolor_step(self.pg, self.view, perm, self.rcfg)
        colors = colors_from_views(self.pg, np.asarray(new_view))
        assert_valid(self.g, colors, what=f"RC-{perm}")
        # Culberson: recoloring never increases the number of colors
        assert st["n_colors"] <= self.stats["n_colors"]

    def test_multiple_iterations_monotone(self):
        view, hist = recolor_iterations(self.pg, self.view, 8, self.rcfg,
                                        base_perm="nd")
        cs = [h["n_colors"] for h in hist]
        assert all(a >= b for a, b in zip(cs, cs[1:]))
        assert_valid(self.g, colors_from_views(self.pg, np.asarray(view)))

    def test_distributed_equals_sequential(self):
        """§3: RC in distributed memory == sequential RC (same seed)."""
        c_global = colors_from_views(self.pg, self.view)
        pg1 = partition_graph(self.g, 1)
        v1 = np.zeros((1, pg1.n_slots), np.int32)
        v1[0, :pg1.n_local_max] = c_global
        key = jax.random.key(11)
        v1n, st1 = recolor_step(pg1, v1, "nd", self.rcfg, key=key)
        vPn, stP = recolor_step(self.pg, self.view, "nd", self.rcfg, key=key)
        assert (colors_from_views(pg1, np.asarray(v1n))
                == colors_from_views(self.pg, np.asarray(vPn))).all()

    def test_piggyback_equals_per_step_exchange(self):
        """Coalesced exchanges produce the identical coloring (§3.1)."""
        key = jax.random.key(3)
        v_pig, st_pig = recolor_step(self.pg, self.view, "nd",
                                     RecolorConfig(max_colors=512,
                                                  piggyback=True), key=key)
        v_all, st_all = recolor_step(self.pg, self.view, "nd",
                                     RecolorConfig(max_colors=512,
                                                  piggyback=False), key=key)
        assert (np.asarray(v_pig) == np.asarray(v_all)).all()
        assert st_pig["n_exchanges"] <= st_all["n_exchanges"]

    def test_arc_valid(self):
        view, st = arc_sim(self.pg, self.view, "nd", self.rcfg,
                           ColorConfig(max_colors=512, superstep=64))
        assert_valid(self.g, colors_from_views(self.pg, np.asarray(view)),
                     what="aRC")


def test_exchange_staleness_still_valid():
    """Asynchronous-style (stale ghosts) coloring converges to valid."""
    g = GRAPHS["rmat_good"]()
    pg = partition_graph(g, 8)
    order = compute_order(pg, ordering.NATURAL)
    cfg = ColorConfig(max_colors=512, superstep=64, exchange_every=4)
    view, stats = color_graph(pg, order, cfg)
    assert_valid(g, colors_from_views(pg, np.asarray(view)))
