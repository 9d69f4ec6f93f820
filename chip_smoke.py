"""Chip smoke test: drive the coloring system's main path once on a TPU.

  python chip_smoke.py             # one chip: batch phase, then service phase
  python chip_smoke.py --chips 4   # four chips: the sharded phase only

Batch phase: ``rmat_good(20, 8)`` (1,048,576 vertices) partitioned P=4 and
simulated on one chip, through the paper's quality preset (Random-X Fit,
X=10, then 5 fused ND recoloring iterations) and ``run_pipeline``.  The
coloring must be valid, the compiled program must hold the Pallas kernels
(``tpu_custom_call``), and the XLA twin (``backend="xla"``) must give a
bitwise-identical view.

Service phase: a ``ColoringService`` in continuous mode, on the wall clock,
answers six requests (RMAT scales 14-16).  Every future must resolve valid
and bitwise equal to its solo ``run_pipeline`` run.

Sharded phase (``--chips 4``): ``run_pipeline`` on a four-device
``workers`` mesh, under both exchange schemes, must equal its one-device
run bitwise, with each device holding its own shard.

Timings printed are bring-up readings, not benchmarks.  Any failure exits
non-zero; so does a run that finds no TPU.  The last line of standard
output is one JSON object naming the device.  Everything runs in this one
process: it starts no children, which could not reach a chip it holds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import (check_coloring, colors_from_views,  # noqa: E402
                        compute_order, partition_graph, presets, rmat,
                        run_pipeline)
from repro.core.pipeline import pipeline_program  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_worker_mesh  # noqa: E402
from repro.launch.serve_coloring import (ColoringService,  # noqa: E402
                                         ServeConfig, _traffic,
                                         default_config)

READING = "[bring-up reading, not a benchmark]"


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _log(msg: str) -> None:
    print(msg, flush=True)


def _with(cfg, **kw):
    """``cfg`` with ``kw`` set on both its color and recolor stages."""
    return dataclasses.replace(
        cfg, color=dataclasses.replace(cfg.color, **kw),
        recolor=dataclasses.replace(cfg.recolor, **kw))


def _quality_run(scale: int, P: int, n_iters: int, seed: int):
    """The batch workload: graph, partition, visit order, pipeline config."""
    g = rmat.rmat_good(scale, 8, seed=seed)
    pg = partition_graph(g, P, seed=seed)
    preset = presets.quality(x=10)
    order = compute_order(pg, preset.ordering)
    return g, pg, order, presets.pipeline_config(preset, n_iters=n_iters,
                                                 seed=seed)


def _same_run(a, b) -> bool:
    """Bitwise equality of two ``run_pipeline`` results ``(view, result)``."""
    return (np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
            and a[1] == b[1])


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def batch_phase(*, scale: int = 20, P: int = 4, n_iters: int = 5,
                seed: int = 0, backend: str = "auto") -> dict:
    """One large coloring job on one device (P shards simulated)."""
    t0 = time.perf_counter()
    g, pg, order, cfg = _quality_run(scale, P, n_iters, seed)
    cfg = _with(cfg, backend=backend)
    prep_s = time.perf_counter() - t0

    fn, args = pipeline_program(pg, order, cfg)
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")

    t0 = time.perf_counter()
    run = run_pipeline(pg, order, cfg)       # dispatches the compiled program
    jax.block_until_ready(run[0])
    run_s = time.perf_counter() - t0
    check = check_coloring(g, colors_from_views(pg, np.asarray(run[0])))
    _require(check["valid"], f"batch coloring invalid: {check}")

    t0 = time.perf_counter()
    twin = run_pipeline(pg, order, _with(cfg, backend="xla"))
    twin_s = time.perf_counter() - t0
    _require(_same_run(run, twin),
             "batch: kernel path and XLA twin differ")
    return dict(n_vertices=g.n, n_edges=g.m, maxd=pg.maxd,
                n_colors=check["n_colors"],
                n_iters_run=run[1]["n_iters_run"],
                kernel_calls_in_hlo=kernels, prep_s=prep_s,
                compile_s=compile_s, run_s=run_s,
                twin_compile_and_run_s=twin_s,
                peak_bytes_in_use=_peak_bytes())


def service_phase(*, n_requests: int = 6, scale_lo: int = 14,
                  scale_hi: int = 16, P: int = 4, n_iters: int = 8,
                  seed: int = 0) -> dict:
    """Continuous-mode service on the wall clock; each request checked
    valid and bitwise against its solo ``run_pipeline`` run."""
    graphs = _traffic(n_requests, scale_lo, scale_hi, seed)
    svc = ColoringService(P=P, validate=True,
                          cfg=default_config(n_iters=n_iters),
                          serve=ServeConfig(mode="continuous"))
    t0 = time.perf_counter()
    futures = [svc.submit_async(g) for g in graphs]
    outs = [f.result() for f in futures]
    wall_s = time.perf_counter() - t0
    for f, out in zip(futures, outs):
        _require(f.done() and f.exception() is None,
                 f"request {f.id} failed: {f.exception()}")
        _require(out["check"]["valid"], f"request {f.id} invalid")
        _require(out["route"] == "engine",
                 f"request {f.id} took route {out['route']!r}")
        m, rcfg = out["member"], out["cfg"]
        ck = jax.random.fold_in(jax.random.key(rcfg.color.seed), f.id)
        rk = jax.random.fold_in(jax.random.key(rcfg.seed), f.id)
        view, solo = run_pipeline(m, compute_order(m, svc.order_kind), rcfg,
                                  color_key=ck, recolor_key=rk)
        colors = m.gather_global_colors(np.asarray(view)[:, :m.n_local_max])
        _require(np.array_equal(colors, out["colors"])
                 and solo["history"] == out["history"]
                 and solo["n_iters_run"] == out["n_iters_run"],
                 f"request {f.id} differs from its solo run")
    st = svc.stats()
    return dict(n_requests=len(outs),
                n_vertices=[g.n for g in graphs],
                n_colors=[o["n_colors"] for o in outs],
                engines=st["engines"], program_traces=st["traces"],
                wall_s=wall_s, peak_bytes_in_use=_peak_bytes())


def sharded_phase(*, scale: int = 20, P: int = 4, n_iters: int = 5,
                  seed: int = 0) -> dict:
    """``run_pipeline`` over P devices == ``run_pipeline`` on one."""
    _require(len(jax.devices()) >= P, f"need {P} devices")
    g, pg, order, cfg = _quality_run(scale, P, n_iters, seed)
    mesh = make_worker_mesh(P)
    cfgs = {s: _with(cfg, scheme=s) for s in ("sparse", "allgather")}
    t0 = time.perf_counter()
    out = dict(n_vertices=g.n)
    for scheme, scfg in cfgs.items():
        sim = run_pipeline(pg, order, scfg)
        sh = run_pipeline(pg, order, scfg, mesh)
        _require(_same_run(sim, sh), f"{scheme}: sharded differs from sim")
        shards = sh[0].addressable_shards
        _require(sorted(s.index[0].start for s in shards) == list(range(P))
                 and all(s.data.shape[0] == 1 for s in shards)
                 and len({s.device for s in shards}) == P,
                 f"{scheme}: view is not one shard per device")
        check = check_coloring(g, colors_from_views(pg, np.asarray(sh[0])))
        _require(check["valid"], f"{scheme}: coloring invalid")
        out[scheme] = dict(n_colors=check["n_colors"],
                           n_iters_run=sh[1]["n_iters_run"],
                           devices=len(shards))
    out["compile_and_run_s"] = time.perf_counter() - t0
    return out


def _cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    _require(len(devices) >= args.chips,
             f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    # the CPU-test defaults of kernels.ops must not apply on the chip
    _require(ops.resolve_backend("auto") == "pallas",
             "backend 'auto' did not resolve to the Pallas kernels")
    _require(not ops._default_interpret(),
             "Pallas kernels would run in interpret mode")
    cache = use_compile_cache()
    before = _cache_entries(cache)
    _log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
         f"{cache} holds {before} entries")

    t0 = time.perf_counter()
    if args.chips == 4:
        r = sharded_phase(seed=args.seed)
        _log(f"{READING} sharded: {json.dumps(r)}")
    else:
        r = batch_phase(seed=args.seed)
        _log(f"{READING} batch: {json.dumps(r)}")
        _require(r["kernel_calls_in_hlo"] > 0,
                 "compiled pipeline holds no Pallas kernel")
        r = service_phase(seed=args.seed)
        _log(f"{READING} service: {json.dumps(r)}")
    _log(f"{READING} total {time.perf_counter() - t0:.1f}s; compile cache "
         f"{_cache_entries(cache) - before} new entries")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
