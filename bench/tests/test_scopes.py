"""The reading of the program's own names from a trace
(``bench/harness/scopes.py``): on made-up events, on the chip trace recorded
before the program had scopes, and on one recorded with them
(``data/rmat_good_batch_scale10_scopes.xplane.pb.gz``: a 0.3 s traced
window of ``rmat_good.batch`` at scale 10 on a v5e chip, kept by
``bench/tools/sweep.py --keep-trace``).  Reading a trace file needs the
``xplane_pb2`` module of an installed ``tensorflow`` package; without it
the tests of the recorded traces are skipped."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench.harness import scopes, trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
needs_pb2 = pytest.mark.skipif(
    scopes._xplane_pb2() is None,
    reason="no tensorflow.tsl.profiler.protobuf.xplane_pb2 installed")


@pytest.mark.parametrize("path,chain", [
    ("jit(wrapped)/vmap(spec_color)/while/body/nbr_gather/gather:",
     ("spec_color", "nbr_gather")),
    ("jit(<lambda>)/shard_map/recolor/while/body/cond/branch_1_fun/"
     "exchange/gather:", ("recolor", "exchange")),
    ("jit(wrapped)/vmap(vmap(recolor))/while:", ("recolor",)),
    ("jit(_threefry_seed)/concatenate:", ()),
    ("jit(exchange_like)/gather:", ()),
])
def test_scope_chain(path, chain):
    assert scopes.scope_chain(path) == chain


def test_reduce_made_up_events():
    spans = [(0, 100 * MS, "bench.window"),
             (0, 20 * MS, "bench.host_prep"),
             (2 * MS, 18 * MS, "repro.partition"),
             (20 * MS, 90 * MS, "bench.dispatch"),
             (20 * MS, 25 * MS, "repro.upload"),
             (25 * MS, 30 * MS, "repro.program"),
             (30 * MS, 90 * MS, "repro.wait"),
             (90 * MS, 100 * MS, "bench.fetch"),
             (94 * MS, 98 * MS, "repro.to_global")]
    sc, rc = ("spec_color",), ("recolor",)
    devices = {0: [(26 * MS, 1 * MS, "%copy", ()),
                   (30 * MS, 30 * MS, "%while.1", sc),
                   (35 * MS, 5 * MS, "%fusion.1", sc + ("nbr_gather",)),
                   (60 * MS, 25 * MS, "%while.2", rc),
                   (62 * MS, 8 * MS, "%fusion.2", rc + ("nbr_gather",)),
                   (71 * MS, 2 * MS, "%gather.3", rc + ("exchange",))]}
    r = scopes.reduce_events(devices, spans)
    assert r["busy_s"] == pytest.approx(0.056)
    assert r["scopes"] == pytest.approx(dict(
        spec_color=0.030, recolor=0.025, nbr_gather=0.013, exchange=0.002))
    assert r["scope_paths"] == pytest.approx({
        "spec_color": 0.030, "spec_color/nbr_gather": 0.005,
        "recolor": 0.025, "recolor/nbr_gather": 0.008,
        "recolor/exchange": 0.002})
    # idle [0,26], [27,30], [85,100], each named at its midpoint, by
    # trace.reduce_events' own rule
    assert dict(r["idle_gaps"]) == pytest.approx({
        "repro.partition": 0.026, "repro.program": 0.003,
        "bench.fetch": 0.015})
    old = trace.reduce_events(
        {0: [ev[:3] for ev in devices[0]]}, spans, {})
    assert (r["busy_s"], r["window_s"], r["idle_gaps"]) == (
        old["busy_s"], old["window_s"], old["idle_gaps"])
    assert r["span_s"]["repro.wait"] == pytest.approx(0.06)
    per = scopes.per_coloring(r)
    assert per["jobs"] == 1
    assert per["nbr_gather_ms"] == pytest.approx(13.0)
    assert per["partition_ms"] == pytest.approx(16.0)
    assert per["idle_named_repro"] == pytest.approx(29 / 44)


def test_names_are_the_programs():
    from repro import tracing

    assert scopes.SCOPES == tracing.SCOPES
    assert tracing.SPAN_PREFIX in scopes.SPAN_PREFIXES
    assert trace.SPAN_PREFIX in scopes.SPAN_PREFIXES


def test_no_protobuf_classes_reads_nothing(monkeypatch, tmp_path):
    monkeypatch.setattr(scopes, "_xplane_pb2", lambda: None)
    assert scopes.reduce(tmp_path / "absent.xplane.pb") is None


def _unzip(name, tmp_path):
    path = tmp_path / name.replace(".gz", "")
    with gzip.open(DATA / name) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@needs_pb2
def test_trace_without_scopes(tmp_path):
    """The trace of the program before it had scopes or spans: nothing to
    read, and the device figures and idle gaps are ``trace.reduce``'s."""
    path = _unzip("rmat_good_batch_scale10.xplane.pb.gz", tmp_path)
    r, old = scopes.reduce(path), trace.reduce(path)
    assert r["scopes"] == {} and r["scope_paths"] == {}
    assert (r["busy_s"], r["window_s"], r["idle_gaps"]) == (
        old["busy_s"], old["window_s"], old["idle_gaps"])
    assert r["span_n"] == dict(old["spans"])
    per = scopes.per_coloring(r)
    assert not any(k.endswith("_ms") for k in per)
    assert per["idle_named_repro"] == 0


@needs_pb2
def test_trace_with_scopes(tmp_path):
    path = _unzip("rmat_good_batch_scale10_scopes.xplane.pb.gz", tmp_path)
    r, old = scopes.reduce(path), trace.reduce(path)
    assert r["chips_seen"] == 1
    assert (r["busy_s"], r["window_s"]) == (old["busy_s"], old["window_s"])
    assert set(r["scopes"]) == set(scopes.SCOPES)
    assert all(0 < v <= r["busy_s"] for v in r["scopes"].values())
    # every neighbour gather and every exchange ran inside a coloring phase
    assert not {"nbr_gather", "exchange"} & {
        k.split("/")[0] for k in r["scope_paths"]}
    assert (r["scopes"]["spec_color"] + r["scopes"]["recolor"]
            >= 0.9 * r["busy_s"])
    gaps = dict(r["idle_gaps"])
    assert {"repro.partition.halo", "repro.upload", "repro.wait"} <= set(gaps)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert r["span_n"]["repro.partition"] == r["span_n"]["bench.host_prep"]
    assert {"repro.partition.ell", "repro.order", "repro.program",
            "repro.to_global"} <= set(r["span_s"])
    per = scopes.per_coloring(r)
    assert per["jobs"] == r["span_n"]["bench.host_prep"] > 0
    for k in ("spec_color_ms", "recolor_ms", "nbr_gather_ms", "exchange_ms",
              "partition_ms"):
        assert per[k] > 0, k
