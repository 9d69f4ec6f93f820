"""Host preparation (partition + visit order) per coloring, in ms: the
mean of the ``bench.host_prep`` spans of the counted colorings."""


def read(run):
    d = [j["prep_s"] for j in run.jobs]
    return 1e3 * sum(d) / len(d) if d else None
