"""The trace reduction, on made-up events and on a small trace recorded on
a v5e chip (``data/``, gzipped: one 0.3 s traced window of
``rmat_good.batch`` at scale 10, kept by ``bench/tools/sweep.py
--keep-trace``)."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench.harness import trace

DATA = Path(__file__).resolve().parent / "data"


def test_reduce_made_up_events():
    ms = 1_000_000
    spans = [(0, 100 * ms, "bench.window"),
             (0, 20 * ms, "bench.host_prep"),
             (20 * ms, 100 * ms, "bench.dispatch"),
             (90 * ms, 100 * ms, "bench.fetch")]
    devices = {
        0: [(20 * ms, 30 * ms, "fusion.1"),
            (40 * ms, 10 * ms, "custom-call.2"),
            (45 * ms, 10 * ms, "collective-permute-done"),
            (95 * ms, 20 * ms, "copy.3")],      # clipped at the window end
        1: [(20 * ms, 60 * ms, "fusion.1")],
    }
    texts = {"custom-call.2": 'custom_call_target="tpu_custom_call"'}
    r = trace.reduce_events(devices, spans, texts)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["chips_seen"] == 2
    # chip 0 busy: [20,55] + [95,100] = 40 ms; chip 1: 60 ms
    assert r["busy_s"] == pytest.approx(0.05)
    assert r["kernel_s"] == pytest.approx(0.005)
    assert r["collective_s"] == pytest.approx(0.005)
    gaps = dict(r["idle_gaps"])
    # chip 0: 20 ms in prep, 40 ms in dispatch ([55,95]); chip 1: 20 ms in
    # prep, 20 ms ([80,100], midpoint 90) in fetch, the innermost span there
    assert gaps["bench.host_prep"] == pytest.approx(0.02)
    assert gaps["bench.dispatch"] == pytest.approx(0.02)
    assert gaps["bench.fetch"] == pytest.approx(0.01)
    assert dict(r["device_ops"])["fusion.1"] == pytest.approx(0.045)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({0: []}, [], {})


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "chip.xplane.pb"
    with gzip.open(DATA / "rmat_good_batch_scale10.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    r = trace.reduce(path)
    assert r["chips_seen"] == 1
    assert r["window_s"] == pytest.approx(0.37024267)
    assert r["busy_s"] == pytest.approx(0.3275786)
    assert 0 < r["busy_s"] <= r["window_s"]
    # the three Pallas kernel call sites, not XLA's AllocateBuffer calls
    assert sorted(r["kernels"]) == ["%body.6", "%body.7", "%closed_call.14"]
    assert 0 < r["kernel_s"] <= r["busy_s"]
    assert r["collective_s"] == 0
    assert r["spans"] == {"bench.host_prep": 4, "bench.dispatch": 4,
                          "bench.fetch": 4}
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
