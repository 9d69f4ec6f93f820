"""Speculative rounds per coloring: the program's own counter
(``result["color"]["n_rounds"]``), mean over the counted colorings."""


def read(run):
    d = [j["spec_rounds"] for j in run.jobs]
    return sum(d) / len(d) if d else None
