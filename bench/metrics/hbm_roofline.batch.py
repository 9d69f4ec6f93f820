"""Share of the HBM roofline per coloring, in %: the bytes a coloring
needs (``harness.work``: from the graph and K, not from the padded
layout) at the chips' published bandwidth, over the device busy time per
coloring from the trace.  On several chips the bytes are spread over all
of them."""
from bench.harness.peaks import peaks


def read(run):
    t, n = run.trace, run.counters.get("jobs_traced")
    nbytes = run.counters.get("work_bytes")
    if not t or not n or not nbytes or t["busy_s"] <= 0:
        return None
    bw = peaks(run.devices[0].device_kind)["hbm_bw"] * run.chips
    return 100.0 * (nbytes / bw) / (t["busy_s"] / n)
