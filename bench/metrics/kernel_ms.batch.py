"""Device time of the Pallas kernels per coloring, in ms, mean per chip:
the durations of the trace's ``tpu_custom_call`` operations in the
window over the colorings that ran in it."""


def read(run):
    t, n = run.trace, run.counters.get("jobs_traced")
    if not t or not n or not t["kernels"]:
        return None
    return 1e3 * t["kernel_s"] / n
