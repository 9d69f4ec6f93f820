"""Quartile spread of each metric over result lines, as bounds are set:
(Q3 - Q1) / median with Python's ``statistics.quantiles(values, n=4)``.

    python3 bench/tools/spread.py set_a.jsonl set_b.jsonl

Each file is one set of runs (one result line per run, as ``bench/run.py``
prints it); the widest spread of the sets is five times the bound's
basis.
"""
import json
import statistics
import sys


def spreads(path: str) -> dict:
    vals = {}
    for line in open(path):
        r = json.loads(line)
        for k, v in r["metrics"].items():
            vals.setdefault(k, []).append(v["value"])
    out = {}
    for k, xs in vals.items():
        q1, med, q3 = statistics.quantiles(xs, n=4)
        out[k] = dict(n=len(xs), median=statistics.median(xs),
                      spread=(q3 - q1) / statistics.median(xs))
    return out


def main(paths) -> None:
    sets = [spreads(p) for p in paths]
    for k in sets[0]:
        row = [s[k] for s in sets if k in s]
        widest = max(x["spread"] for x in row)
        print(f"{k:24s} medians {[round(x['median'], 5) for x in row]} "
              f"spreads {[round(x['spread'], 5) for x in row]} "
              f"-> 5x widest {5 * widest:.4f}")


if __name__ == "__main__":
    main(sys.argv[1:])
