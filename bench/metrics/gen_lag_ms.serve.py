"""The latest any request was submitted after it was due, in ms: a load
generator that falls behind would otherwise read as a fast server."""


def read(run):
    lag = run.info.get("gen_lag_s")
    return None if lag is None else 1e3 * lag
