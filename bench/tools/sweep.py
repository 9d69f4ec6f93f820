"""Run one cell at several values of one configuration or traffic key, in
one process, and print each result line: how the batch scale and the
served cell's knee were found.

    python3 bench/tools/sweep.py --workload rmat_good.batch \\
        --key config.graph.scale --values 15 16 17 --seconds 8 --seed 7
    python3 bench/tools/sweep.py --workload rmat_good.serve \\
        --key traffic.rate_per_s --values 5 10 20 --seconds 20 --seed 7

``--keep-trace DIR`` keeps each traced window's ``.xplane.pb`` under
``DIR/<value>``.  Needs the chip, like ``bench/run.py``.
"""
import argparse
import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import cli  # noqa: E402


def _setter(key: str, value):
    part, *path = key.split(".")

    def shrink(cell):
        doc = copy.deepcopy(getattr(cell, part))
        node = doc
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = value
        return dataclasses.replace(cell, **{part: doc})
    return shrink


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--key", required=True)
    ap.add_argument("--values", nargs="+", type=json.loads, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace")
    args = ap.parse_args()
    for v in args.values:
        t0 = time.perf_counter()
        keep = (None if args.keep_trace is None
                else str(Path(args.keep_trace) / str(v)))
        out = cli.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=t0,
                           shrink=_setter(args.key, v), keep_trace=keep)
        out["sweep"] = {args.key: v,
                        "run_s": time.perf_counter() - t0}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
