"""Share of the traced window in which the chip ran no operation, in %
(1 - union of the device's operation intervals / window), mean per chip."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or not t["chips_seen"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
