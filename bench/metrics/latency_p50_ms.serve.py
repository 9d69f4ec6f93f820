"""Median request latency, in ms, from when each request was due to when
``poll`` returned its answer (a missing answer waits until the drain
gives up), over every request of the window."""
import numpy as np

from bench.harness.drivers.open_loop import latencies


def read(run):
    lat = latencies(run)
    return float(np.median(lat)) * 1e3 if lat.size else None
