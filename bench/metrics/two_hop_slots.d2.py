"""Slots read through the two-hop ELL per coloring, in millions: the
program's own counter (``gather2_slots`` of the speculative phase and of
every recolor iteration, over all shards), mean over the counted
colorings.  Nothing to read where the program keeps no such counter."""


def read(run):
    d = [j.get("gather2_slots") for j in run.jobs]
    if not d or None in d:
        return None
    return sum(d) / len(d) / 1e6
