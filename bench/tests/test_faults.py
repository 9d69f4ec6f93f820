"""The run's ``correct`` comes out false when the timed path is broken
underneath: a step that returns its state unchanged, half of the batch
left out, the exchange between shards left out, an answer altered where
it is produced.  The chip check is skipped; the rest of a run is driven."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness.drivers import open_loop


def _batch_patch(kind):
    def patch(st):
        execute, job = st.execute, st.job

        def unchanged(pg, order, cfg, **kw):
            view, res = execute(pg, order, cfg, **kw)
            return jnp.zeros_like(view), res

        def half(pg, order, cfg, **kw):
            view, res = execute(pg, order, cfg, **kw)
            v = np.asarray(view).copy()
            v[: pg.P // 2] = 0
            return jnp.asarray(v), res

        def altered(run, i):
            rec = job(run, i)
            c, g = rec["colors"], st.csr
            v = int(np.argmax(np.diff(g.indptr)))
            c[v] = c[g.indices[g.indptr[v]]]
            return rec

        if kind == "unchanged":
            st.execute = unchanged
        elif kind == "half":
            st.execute = half
        elif kind == "altered":
            st.job = altered
    return patch


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_batch_fault_is_not_correct(cpu_run, kind):
    out = cpu_run("rmat_good.batch", patch=_batch_patch(kind),
                  backend="xla")
    assert out["correct"] is False, out["checks"]


@pytest.fixture
def no_exchange(monkeypatch):
    """Every boundary exchange returns the view it was given."""
    import repro.core.recolor as recolor
    import repro.core.speculative as speculative

    def make_exchange(*args, **kw):
        return lambda view, round_mask=None, apply_mask=None: (
            view, jnp.int32(0))
    monkeypatch.setattr(speculative, "make_exchange", make_exchange)
    monkeypatch.setattr(recolor, "make_exchange", make_exchange)


@pytest.mark.parametrize("executor", ["sim", "sharded"])
def test_exchange_left_out_is_not_correct(cpu_run, no_exchange, executor):
    out = cpu_run("rmat_good.batch", backend="xla", executor=executor)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["conflicts"]["value"] > 0


def _serve_patch(kind):
    def patch(st):
        poll = st.svc.poll

        def patched():
            res = poll()
            for jid, out in list(res.items()):
                if kind == "half" and jid % 2:
                    del res[jid]
                elif kind == "unchanged":
                    out["colors"] = np.zeros_like(out["colors"])
                elif kind == "altered" and jid == 0:
                    out["colors"] = out["colors"].copy()
                    out["colors"][0] = -1
            return res
        st.svc.poll = patched
    return patch


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_serve_fault_is_not_correct(cpu_run, monkeypatch, kind):
    monkeypatch.setattr(open_loop, "DRAIN_S", 2.0)
    out = cpu_run("rmat_good.serve", patch=_serve_patch(kind),
                  backend="xla")
    assert out["correct"] is False, out["checks"]
