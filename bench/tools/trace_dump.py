"""Print what a profiler trace holds: planes, lines, event counts and the
operations that took most time, with one event's stats each.

    python3 bench/tools/trace_dump.py path/to/host.xplane.pb
"""
import collections
import sys

from jax.profiler import ProfileData


def main(path: str) -> None:
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            total, count, sample = collections.Counter(), collections.Counter(), {}
            t_lo, t_hi = float("inf"), float("-inf")
            for ev in line.events:
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                t_lo, t_hi = min(t_lo, ev.start_ns), max(t_hi, ev.end_ns)
                if ev.name not in sample:
                    sample[ev.name] = [(k, str(v)[:160]) for k, v in ev.stats]
            n = sum(count.values())
            print(f"  line {line.name!r}: {n} events, span "
                  f"{t_lo:.0f}..{t_hi:.0f} ns")
            for name, ns in total.most_common(12):
                print(f"    {ns / 1e6:12.3f} ms {count[name]:8d}x {name[:90]}")
                print(f"        stats {sample[name][:8]}")


if __name__ == "__main__":
    main(sys.argv[1])
