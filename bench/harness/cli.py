"""One run of one cell: set-up, measured window, correctness, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced).  With ``--trace 0`` the metrics are the cell's end-to-end ones,
with ``--trace 1`` its per-layer ones, read from a profiler trace of the
window.  Each number compared with the reference is printed beside its
limit, last on standard error and last in the result line (``checks``).
A run that finds no TPU, or fewer chips than the cell asks for, prints no
result and exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

from . import spec

CACHE_DIR = spec.ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if require_tpu and len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def _use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in this checkout, unless the
    environment names one; every program is kept, however quick."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t_start: float | None = None, require_tpu: bool = True,
             shrink=None, patch=None, keep_trace=None) -> dict:
    """Run one cell once; returns the result object (not yet printed).

    For the tests: ``require_tpu=False`` runs on whatever JAX finds,
    ``shrink`` maps the loaded ``spec.Cell`` to a smaller one, and
    ``patch`` is called with the driver's state after set-up, to break the
    timed path on purpose.  ``keep_trace`` names a directory to copy the
    window's ``.xplane.pb`` into.
    """
    from .record import Recorder, Run, compile_counter

    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.load_cell(workload)
    if shrink is not None:
        cell = shrink(cell)
    drv = spec.driver(cell.traffic)
    readers = {m["name"]: spec.reader(m["name"]) for m in cell.per_layer} \
        if traced else {}
    devices = _devices(cell.chips, require_tpu)
    _use_compile_cache()
    counter = compile_counter()
    run = Run(cell=cell, seed=seed, seconds=seconds, traced=traced,
              devices=devices, rec=Recorder(traced), t_start=t_start)

    state = drv.setup(run)
    if patch is not None:
        patch(state)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    mark = counter.count
    run.setup_s = time.perf_counter() - t_start
    drv.window(run, state)
    run.compiles_in_window = counter.count - mark
    memory_peak = _memory_peak(devices)
    if traced:
        import jax

        from . import trace
        jax.profiler.stop_trace()
        try:
            xplane = trace.find_xplane(trace_dir)
            if keep_trace is not None:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(xplane, keep_trace)
            run.trace = trace.reduce(xplane)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run.counters["work_bytes"] = (drv.work_bytes(run, state)
                                  if hasattr(drv, "work_bytes") else None)
    state.__dict__.pop("svc", None)     # the program's state goes first
    gc.collect()
    drv.verify(run, state)
    run.e2e = drv.end_to_end(run, state)
    run.e2e["setup_s"] = run.setup_s

    if traced:
        wanted = {name: fn(run) for name, fn in readers.items()}
    else:
        wanted = {m["name"]: run.e2e.get(m["name"]) for m in cell.end_to_end}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {k: {"value": float(v), "unit": units[k]}
               for k, v in wanted.items() if v is not None}
    correct = all(value <= limit for _, value, limit in run.checks)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(run.jobs),
           "failed": _failed(run), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["info"] = {k: v for k, v in run.info.items()
                   if isinstance(v, (int, float, str))}
    out["info"]["compiles_in_window"] = run.compiles_in_window
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in run.checks}
    return out


def _failed(run) -> int:
    """Jobs with no answer, a failed one, or one the reference refused."""
    return sum(1 for j in run.jobs if not j.get("valid"))


def main(argv=None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except (NoChip, spec.SpecError) as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0
