"""Read the program's own names from a profiler trace: its device scopes
and its ``repro.*`` host spans, beside the benchmark's ``bench.*`` spans.

The names are the program's (``repro.tracing``).  Each device operation
carries its JAX path in the ``tf_op`` stat of its event *metadata*, e.g.
``jit(wrapped)/vmap(spec_color)/while/body/nbr_gather/gather:``; a
transform wraps the scope under it (``vmap(spec_color)``).
``jax.profiler.ProfileData`` gives an event's own stats only, so this reads
the ``XSpace`` protobuf itself, with the message classes generated from
``xplane.proto`` in the installed ``tensorflow`` package, loaded by path
without importing ``tensorflow``.  Where they are missing, ``reduce``
returns ``None``.

The window, the busy time and the idle gaps are ``trace.reduce_events``'
own, fed with the host spans of both prefixes, so each idle gap is named
after the innermost ``bench.*`` or ``repro.*`` span open at its midpoint.
This module adds only what ``trace.reduce`` lacks.  Per chip, inside the
``bench.window`` span, averaged over the chips that ran anything:

- ``scopes``: for each scope, the union of the intervals of the operations
  whose path has it as a component (inclusive of the scopes inside it);
- ``scope_paths``: the same for each chain of nested scopes, e.g.
  ``spec_color/nbr_gather``; a scope's self time is its time minus that of
  the chains one scope deeper;
- ``span_s`` and ``span_n``: each host span's total seconds and count in
  the window.

A trace of a program that names no scopes gives empty ``scopes``.

    python3 bench/harness/scopes.py path/to/host.xplane.pb
"""
from __future__ import annotations

import collections
import importlib.util
import json
import re
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import trace  # noqa: E402
from bench.harness.spec import ROOT  # noqa: E402

sys.path.insert(0, str(ROOT / "src"))

from repro import tracing  # noqa: E402

SCOPES = tracing.SCOPES
SPAN_PREFIXES = (trace.SPAN_PREFIX, tracing.SPAN_PREFIX)
TF_OP = "tf_op"
_UNWRAP = re.compile(r"(?:[\w.-]*\()*(.*?)\)*")


def scope_chain(path: str) -> tuple:
    """The scopes on a JAX path, outermost first, each unwrapped from the
    transforms around it."""
    path = path.rsplit(":", 1)[0]
    out = []
    for part in path.split("/"):
        name = _UNWRAP.fullmatch(part).group(1)
        if name in SCOPES and name not in out:
            out.append(name)
    return tuple(out)


def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = (Path(spec.submodule_search_locations[0])
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    if not path.is_file():
        return None
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _stat_str(plane, stat) -> str | None:
    if stat.HasField("str_value"):
        return stat.str_value
    if stat.HasField("ref_value"):
        return plane.stat_metadata[stat.ref_value].name
    return None


def read_xplane(path):
    """``(devices, spans)`` of one ``.xplane.pb``, or ``None`` where the
    protobuf classes are missing: ``devices`` is ``{chip: [(start_ns,
    dur_ns, name, scope_chain), ...]}``, ``spans`` the host spans of both
    prefixes as ``trace.reduce_events`` takes them.  Times are whole
    nanoseconds, as ``ProfileData`` gives them."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace.FromString(Path(path).read_bytes())
    devices, spans = {}, []
    for plane in space.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            tf_op = [k for k, v in plane.stat_metadata.items()
                     if v.name == TF_OP]
            chains = {}
            for k, md in plane.event_metadata.items():
                paths = [_stat_str(plane, s) for s in md.stats
                         if s.metadata_id in tf_op]
                chains[k] = scope_chain(paths[0]) if paths else ()
            evs = []
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                for ev in line.events:
                    md = plane.event_metadata[ev.metadata_id]
                    evs.append((line.timestamp_ns + ev.offset_ps // 1000,
                                ev.duration_ps // 1000, md.name,
                                chains.get(ev.metadata_id, ())))
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            names = {k: md.name for k, md in plane.event_metadata.items()
                     if md.name.startswith(SPAN_PREFIXES)}
            for line in plane.lines:
                for ev in line.events:
                    name = names.get(ev.metadata_id)
                    if name is not None:
                        s = line.timestamp_ns + ev.offset_ps // 1000
                        spans.append((s, s + ev.duration_ps // 1000, name))
    return devices, spans


def reduce_events(devices: dict, spans: list) -> dict:
    """Reduce already-read events (``read_xplane``'s)."""
    base = trace.reduce_events(
        {c: [ev[:3] for ev in evs] for c, evs in devices.items()}, spans, {})
    w0, w1 = next((s, e) for s, e, n in spans if n == trace.WINDOW_SPAN)
    span_s, span_n = collections.Counter(), collections.Counter()
    for s, e, name in spans:
        if name != trace.WINDOW_SPAN and min(e, w1) > max(s, w0):
            span_s[name] += (min(e, w1) - max(s, w0)) * 1e-9
            span_n[name] += 1
    chips, scopes, paths = 0, collections.Counter(), collections.Counter()
    for events in devices.values():
        by_scope, by_chain = (collections.defaultdict(list),
                              collections.defaultdict(list))
        ran = False
        for start, dur, _, chain in events:
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            ran = True
            for name in chain:
                by_scope[name].append((s, e))
            for k in range(1, len(chain) + 1):
                by_chain["/".join(chain[:k])].append((s, e))
        chips += ran
        for out, ivs_by in ((scopes, by_scope), (paths, by_chain)):
            for key, ivs in ivs_by.items():
                out[key] += trace._union(ivs)[0] * 1e-9
    n = max(chips, 1)
    return dict(
        window_s=base["window_s"],
        chips_seen=base["chips_seen"],
        busy_s=base["busy_s"],
        idle_gaps=base["idle_gaps"],
        scopes={k: v / n for k, v in scopes.items()},
        scope_paths={k: v / n for k, v in sorted(paths.items())},
        span_s=dict(span_s),
        span_n=dict(span_n),
    )


def reduce(path) -> dict | None:
    read = read_xplane(path)
    return None if read is None else reduce_events(*read)


def per_coloring(r: dict) -> dict:
    """Milliseconds per coloring of each scope and of the partition span,
    over the colorings the window started (its ``bench.host_prep`` spans,
    the divisor ``jobs_traced`` of the harness), and the share of the
    window's idle time named after a ``repro.*`` span."""
    jobs = r["span_n"].get("bench.host_prep", 0)
    if not jobs:
        return {}
    out = {f"{k}_ms": 1e3 * v / jobs for k, v in r["scopes"].items()}
    if tracing.PARTITION in r["span_s"]:
        out["partition_ms"] = 1e3 * r["span_s"][tracing.PARTITION] / jobs
    idle = r["window_s"] - r["busy_s"]
    if idle > 0:
        named = sum(v for k, v in r["idle_gaps"]
                    if k.startswith(tracing.SPAN_PREFIX))
        out["idle_named_repro"] = named / idle
    out["jobs"] = jobs
    return out


def main(paths) -> None:
    for p in paths:
        r = reduce(p)
        if r is not None:
            r["per_coloring"] = per_coloring(r)
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
