import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry run: lower + compile every (architecture × shape × mesh).

The two lines above MUST stay first: JAX locks the device count on first
init, and the production meshes need 512 placeholder host devices.

For every cell this script
  - builds ShapeDtypeStruct stand-ins (no allocation) with NamedShardings,
  - ``jit(step).lower(...)`` then ``.compile()`` under the mesh,
  - records ``memory_analysis()`` (per-device bytes — proves it fits),
    ``cost_analysis()`` (raw, body-once), and the loop-aware roofline
    parse of the partitioned HLO (see repro/roofline.py),
  - writes one JSON per cell to --out (default experiments/dryrun).

Also lowers the *coloring* core (the paper's contribution) over the full
mesh flattened to a 1-axis worker mesh — the production coloring config.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--coloring]
  python -m repro.launch.dryrun --coloring   # the coloring lowerings only;
                                             # exits non-zero if one fails
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, get_arch, list_archs, shape_applicable
from repro.launch.mesh import (make_coloring_mesh, make_production_mesh,
                               make_worker_mesh)
from repro.launch.steps import input_specs
from repro.roofline import analyze_hlo, model_flops, roofline_terms


def mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def dryrun_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
                out_dir: Path, force: bool = False) -> dict:
    tag = f"{arch_name}__{shape_name}__{mesh_tag(multi_pod)}"
    out_path = out_dir / f"{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape)
    rec: dict = dict(arch=arch_name, shape=shape_name,
                     mesh=mesh_tag(multi_pod), status="skipped", reason=why)
    if not ok:
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            fn, args = input_specs(arch, shape, mesh)
            lowered = jax.jit(fn).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            ma = {}
            try:
                stats = compiled.memory_analysis()
                for f in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes"):
                    ma[f] = int(getattr(stats, f, 0))
                ma["total_per_device"] = (ma["argument_size_in_bytes"]
                                          + ma["temp_size_in_bytes"]
                                          + ma["output_size_in_bytes"]
                                          - ma["alias_size_in_bytes"])
            except Exception as e:  # pragma: no cover
                ma["error"] = str(e)

            ca = {}
            try:
                raw = compiled.cost_analysis()
                ca = {k: float(v) for k, v in raw.items()
                      if k in ("flops", "bytes accessed")}
            except Exception as e:  # pragma: no cover
                ca["error"] = str(e)

            hlo = compiled.as_text()
            analysis = analyze_hlo(hlo)
            terms = roofline_terms(analysis)
            mf = model_flops(arch, shape)
            rec.update(
                status="ok",
                n_chips=n_chips,
                lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
                memory_analysis=ma, cost_analysis_raw=ca,
                coll_count=analysis["coll_count"],
                coll_bytes=analysis["coll_bytes"],
                dynamic_whiles=analysis["dynamic_whiles"],
                roofline=terms,
                model_flops_global=mf,
                model_flops_per_chip=mf / n_chips,
                useful_flops_ratio=(mf / n_chips) / terms["flops"]
                if terms["flops"] else 0.0,
                hlo_bytes=len(hlo),
            )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def dryrun_coloring(*, multi_pod: bool, out_dir: Path,
                    force: bool = False) -> dict:
    """Lower the paper's distributed coloring over the production mesh."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (ColorConfig, RecolorConfig, color_spmd,
                            partition_graph, rmat)
    from repro.core.comm import run_spmd
    from repro.core.recolor import recolor_spmd
    from functools import partial

    tag = f"coloring__rmat18__{mesh_tag(multi_pod)}"
    out_path = out_dir / f"{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    P = 512 if multi_pod else 256
    mesh = make_worker_mesh(P)
    g = rmat.rmat_er(18, 8, seed=1)          # 262k vertices over 256/512 shards
    pg = partition_graph(g, P)
    plan = pg.comm_plan
    rec: dict = dict(arch="coloring", shape=f"rmat18_P{P}",
                     mesh=mesh_tag(multi_pod), status="skipped")
    t0 = time.time()
    try:
        arrs = {k: jnp.asarray(v) for k, v in pg.arrays().items()}
        order = jnp.zeros((P, pg.n_local_max), jnp.int32)
        key = jax.random.key(0)
        cfg = ColorConfig(max_colors=256, superstep=64, scheme="allgather")
        fn = partial(color_spmd, cfg=cfg, P_size=P)
        lowered = jax.jit(
            lambda a, o, k: run_spmd(fn, mesh, P, (a, o), (k,))).lower(
                arrs, order, key)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        analysis = analyze_hlo(hlo)
        # one recoloring iteration too
        rfn = partial(recolor_spmd, perm_kind="nd",
                      cfg=RecolorConfig(max_colors=256, scheme="allgather"),
                      P_size=P)
        view = jnp.zeros((P, pg.n_slots), jnp.int32)
        lowered_rc = jax.jit(
            lambda a, v, k: run_spmd(rfn, mesh, P, (a, v), (k,))).lower(
                arrs, view, key)
        compiled_rc = lowered_rc.compile()
        analysis_rc = analyze_hlo(compiled_rc.as_text())
        # beyond-paper: int16 wire payloads (DESIGN.md §6)
        rfn16 = partial(recolor_spmd, perm_kind="nd",
                        cfg=RecolorConfig(max_colors=256, wire16=True,
                                          scheme="allgather"), P_size=P)
        compiled_rc16 = jax.jit(
            lambda a, v, k: run_spmd(rfn16, mesh, P, (a, v), (k,))).lower(
                arrs, view, key).compile()
        analysis_rc16 = analyze_hlo(compiled_rc16.as_text())
        # sparse neighbour-to-neighbour scheme (DESIGN.md §2): modeled bytes
        # always; lowered too unless the round schedule is huge (one
        # collective per ppermute round in the HLO body)
        from repro.core.comm import allgather_bytes_per_exchange
        sparse_rec = dict(
            n_rounds=len(plan.shifts),
            modeled_bytes_per_exchange=plan.bytes_per_exchange(),
            padded_bytes_per_exchange=plan.bytes_per_exchange(padded=True),
            allgather_modeled_bytes_per_exchange=allgather_bytes_per_exchange(
                P, pg.max_boundary),
        )
        # the trace-time scheme decision + the compiled-program identity the
        # modeled byte gap is attributable to (DESIGN.md §2)
        from repro.core import plan_signature, resolve_scheme
        from repro.core.pipeline import PipelineConfig as _PCfg
        decision = resolve_scheme("auto", pg)
        sig = plan_signature(pg, _PCfg(
            color=ColorConfig(max_colors=256, superstep=64, scheme="auto"),
            recolor=RecolorConfig(max_colors=256, scheme="auto"),
            n_iters=4, patience=2))
        sparse_rec["scheme_decision"] = decision
        sparse_rec["plan_signature"] = sig.describe()
        print(f"[coloring P={P}] plan signature: {sig.describe()}")
        print(f"[coloring P={P}] trace-time scheme decision: {decision} "
              f"(sparse padded "
              f"{sparse_rec['padded_bytes_per_exchange']}B vs allgather "
              f"{sparse_rec['allgather_modeled_bytes_per_exchange']}B "
              f"per exchange)")
        if len(plan.shifts) <= 64:
            rfs = partial(recolor_spmd, perm_kind="nd",
                          cfg=RecolorConfig(max_colors=256, scheme="sparse"),
                          P_size=P, plan_static=plan.static)
            compiled_sp = jax.jit(
                lambda a, v, k: run_spmd(rfs, mesh, P, (a, v), (k,))).lower(
                    arrs, view, key).compile()
            sparse_rec["recolor_coll_bytes"] = analyze_hlo(
                compiled_sp.as_text())["coll_bytes"]
        else:
            sparse_rec["lowering"] = (
                f"skipped: {len(plan.shifts)} ppermute rounds")
        # fused pipeline (DESIGN.md §7): initial coloring + K recoloring
        # iterations resident in ONE program — the paper's headline
        # experiment with zero per-iteration host round-trips
        from repro.core.pipeline import PipelineConfig, color_then_recolor
        pcfg = PipelineConfig(
            color=ColorConfig(max_colors=256, superstep=64,
                              scheme="allgather"),
            recolor=RecolorConfig(max_colors=256, scheme="allgather"),
            n_iters=4, patience=2)
        pfn = partial(color_then_recolor, cfg=pcfg, P_size=P)
        t_pipe = time.time()
        compiled_pipe = jax.jit(
            lambda a, o, ck, rk: run_spmd(pfn, mesh, P, (a, o),
                                          (ck, rk))).lower(
                arrs, order, key, key).compile()
        analysis_pipe = analyze_hlo(compiled_pipe.as_text())
        pipeline_rec = dict(
            n_iters=pcfg.n_iters, patience=pcfg.patience,
            compile_s=round(time.time() - t_pipe, 2),
            coll_count=analysis_pipe["coll_count"],
            coll_bytes=analysis_pipe["coll_bytes"],
        )
        # 2D batch × shard mesh (DESIGN.md §10): the batched pipeline with
        # graph lanes sharded over the ``batch`` mesh axis and partitions
        # over ``workers`` — the weak-scaling serving layout.  batch=2 at
        # P=256 (uses all 512 host devices); the multi-pod cell keeps
        # batch=1 (512 shards already occupy every device) but still runs
        # the 2D program structure.
        from repro.core.comm import mesh_axes
        Bm = 1 if multi_pod else 2
        mesh2d = make_coloring_mesh(P, batch=Bm)
        B = 2                                     # lanes (a multiple of Bm)
        arrs_b = {k: jnp.repeat(v[:, None], B, axis=1)
                  for k, v in arrs.items()}
        order_b = jnp.repeat(order[:, None], B, axis=1)
        keys_b = jax.random.split(key, B)
        t_2d = time.time()
        compiled_2d = jax.jit(
            lambda a, o, k1, k2: run_spmd(pfn, mesh2d, P, (a, o), (k1, k2),
                                          lanes=True)).lower(
                    arrs_b, order_b, keys_b, keys_b).compile()
        analysis_2d = analyze_hlo(compiled_2d.as_text())
        mesh2d_rec = dict(
            axes=[[n, s] for n, s in mesh_axes(mesh2d)], batch_lanes=B,
            compile_s=round(time.time() - t_2d, 2),
            coll_count=analysis_2d["coll_count"],
            coll_bytes=analysis_2d["coll_bytes"],
        )
        print(f"[coloring P={P}] 2D mesh "
              f"{'×'.join(f'{n}={s}' for n, s in mesh_axes(mesh2d))}: "
              f"batched pipeline lowered, "
              f"{analysis_2d['coll_count']} collectives")
        rec.update(
            mesh2d=mesh2d_rec,
            status="ok", n_chips=P, compile_s=round(time.time() - t0, 2),
            color_coll_count=analysis["coll_count"],
            color_coll_bytes=analysis["coll_bytes"],
            recolor_coll_count=analysis_rc["coll_count"],
            recolor_coll_bytes=analysis_rc["coll_bytes"],
            recolor_wire16_coll_bytes=analysis_rc16["coll_bytes"],
            sparse=sparse_rec,
            pipeline=pipeline_rec,
            graph=dict(n=g.n, m=g.m, P=P,
                       n_local_max=pg.n_local_max,
                       max_boundary=pg.max_boundary,
                       max_ghost=pg.max_ghost,
                       max_send=plan.max_send),
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--coloring", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    if args.coloring:
        failed = 0
        for mp in meshes:
            rec = dryrun_coloring(multi_pod=mp, out_dir=out_dir,
                                  force=args.force)
            print(json.dumps(rec)[:240])
            failed += rec["status"] != "ok"
        if failed:
            raise SystemExit(f"{failed} coloring lowering(s) failed")
        if not (args.all or args.arch):
            return

    archs = list_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.time()
                rec = dryrun_cell(arch, shape, multi_pod=mp, out_dir=out_dir,
                                  force=args.force)
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['bottleneck']} "
                             f"c={r['compute_s']:.3f}s m={r['memory_s']:.3f}s "
                             f"x={r['collective_s']:.3f}s")
                elif status == "error":
                    extra = rec.get("error", "")[:120]
                print(f"[{time.time()-t0:7.1f}s] {arch:22s} {shape:12s} "
                      f"{mesh_tag(mp):10s} {status:8s} {extra}", flush=True)


if __name__ == "__main__":
    main()
