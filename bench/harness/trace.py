"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers need.

Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per operation run on that chip.  Host planes hold the benchmark's
``bench.*`` spans (``TraceAnnotation``), on the same clock.  The traced
window is the ``bench.window`` span.  Per chip:

- busy: the union of the operation intervals inside the window (the line
  nests a loop's body operations inside the loop's own event, so operation
  totals overlap and only the union is a share of the window);
- kernels: operations that are Pallas kernels (``tpu_custom_call``);
- collectives: ``collective-permute``, ``all-gather``, ``all-reduce``,
  ``reduce-scatter`` and ``all-to-all`` operations, start and done parts;
- idle gaps: the stretches of the window in which no operation ran, each
  named after the innermost ``bench.*`` span open at its midpoint.

Chip figures are averaged over the chips that ran anything.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
KERNEL_MARKERS = ("tpu_custom_call", "pallas")
COLLECTIVES = ("collective-permute", "all-gather", "all-reduce",
               "reduce-scatter", "all-to-all")
TOP = 10


def find_xplane(log_dir) -> Path:
    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _text(ev) -> str:
    """The event's name and its string stats, lower-cased, for matching."""
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts).lower()


def short(name: str) -> str:
    """An operation's HLO name without its shapes: ``%fusion.3 = ...`` to
    ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def is_kernel(text: str) -> bool:
    return any(m in text for m in KERNEL_MARKERS)


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


def _union(intervals) -> tuple[float, list]:
    """Total length and the merged list of ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


class _Spans:
    """Host spans of one thread, nested or disjoint, sorted by start: the
    innermost span open at ``t`` is the latest-starting one holding it."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda x: (x[0], -x[1]))
        self.starts = [s for s, _, _ in self.spans]
        self.parent, stack = [], []
        for k, (s, e, _) in enumerate(self.spans):
            while stack and self.spans[stack[-1]][1] < s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(k)

    def innermost(self, t: float) -> str:
        k = bisect.bisect_right(self.starts, t) - 1
        while k >= 0 and self.spans[k][1] < t:
            k = self.parent[k]
        return self.spans[k][2] if k >= 0 else "no span"


def reduce_events(devices: dict, spans: list, texts: dict) -> dict:
    """Reduce already-read events.

    ``devices`` — ``{chip: [(start_ns, dur_ns, name), ...]}``;
    ``spans`` — ``[(start_ns, end_ns, name), ...]`` host spans;
    ``texts`` — ``{name: text}``, each operation's name and string stats.
    """
    wins = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    w0, w1 = wins[0]
    inner = [x for x in spans if x[2] != WINDOW_SPAN]
    lookup = _Spans(inner)
    chips = []
    gaps_by_span = collections.Counter()
    ops_total = collections.Counter()
    for chip, events in sorted(devices.items()):
        clipped, kern, coll = [], collections.Counter(), 0.0
        for start, dur, name in events:
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            ops_total[short(name)] += (e - s) * 1e-9
            if is_kernel(texts.get(name, name.lower())):
                kern[short(name)] += (e - s) * 1e-9
            if is_collective(name):
                coll += (e - s) * 1e-9
        if not clipped:
            continue
        busy, merged = _union(clipped)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps_by_span[lookup.innermost((a + b) / 2)] += (b - a) * 1e-9
        chips.append(dict(chip=chip, busy_s=busy * 1e-9,
                          kernel_s=sum(kern.values()), kernels=dict(kern),
                          collective_s=coll))
    n = max(len(chips), 1)
    kernels = collections.Counter()
    for c in chips:
        kernels.update(c["kernels"])
    return dict(
        window_s=(w1 - w0) * 1e-9,
        chips_seen=len(chips),
        busy_s=sum(c["busy_s"] for c in chips) / n,
        kernel_s=sum(c["kernel_s"] for c in chips) / n,
        collective_s=sum(c["collective_s"] for c in chips) / n,
        kernels={k: v / n for k, v in kernels.items()},
        device_ops=[[k, v / n] for k, v in ops_total.most_common(TOP)],
        idle_gaps=[[k, v / n] for k, v in gaps_by_span.most_common(TOP)],
        spans=collections.Counter(x[2] for x in inner),
    )


def read_xplane(path) -> tuple[dict, list, dict]:
    """Device operations, host ``bench.*`` spans and operation texts of one
    ``.xplane.pb`` (the arguments of ``reduce_events``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans, texts = {}, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    if name not in texts:
                        texts[name] = _text(ev)
                    evs.append((ev.start_ns, ev.duration_ns, name))
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    return devices, spans, texts


def reduce(path) -> dict:
    return reduce_events(*read_xplane(path))
