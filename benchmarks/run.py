"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  table12   Tables 1/2: graph suite properties + sequential NAT/LF/SL
  fig2/3    sequential recoloring: orderings x permutations, randomness
  fig4      piggybacking: message counts + coalesced-exchange runtime
  fig5/6/7  distributed scaling: FSS vs +RC vs +aRC, multi-iteration RC
  fig8910   Random-X Fit time-quality trade-off, "speed"/"quality" presets
  kernel    color-selection kernels (oracle timing + pallas validation)
  hotpath   legacy scalar/dense vs ELL/bitset hot paths (BENCH_hotpath.json)
  comm      sparse vs all-gather exchange P-scaling sweep (BENCH_comm.json)
  d2        distance-2 coloring over the two-hop halo (BENCH_d2.json)
  pipeline  fused device-resident color->recolor loop vs the host loop
            (BENCH_pipeline.json)
  serve     batched multi-graph dispatch vs sequential per-graph dispatch
            on a fresh-traffic RMAT mix (BENCH_serve.json)
  roofline  per-(arch x shape x mesh) roofline terms from the dry-run
"""
import argparse
import sys
import time

from repro.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale graphs (slow); default is fast mode")
    ap.add_argument("--only", default=None,
                    help="comma list: tables,seq,piggyback,dist,randomx,"
                         "kernels,hotpath,comm,d2,pipeline,serve,roofline")
    args = ap.parse_args()
    use_compile_cache()
    fast = not args.full
    from benchmarks import (bench_comm, bench_d2, bench_distributed,
                            bench_hotpath, bench_kernels, bench_piggyback,
                            bench_pipeline, bench_randomx, bench_roofline,
                            bench_seq_recolor, bench_serve, bench_tables)
    mods = dict(tables=bench_tables, seq=bench_seq_recolor,
                piggyback=bench_piggyback, dist=bench_distributed,
                randomx=bench_randomx, kernels=bench_kernels,
                hotpath=bench_hotpath, comm=bench_comm, d2=bench_d2,
                pipeline=bench_pipeline, serve=bench_serve,
                roofline=bench_roofline)
    chosen = (args.only.split(",") if args.only else list(mods))
    print("name,us_per_call,derived")
    for name in chosen:
        t0 = time.time()
        mods[name].run(fast=fast)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr,
              flush=True)


if __name__ == "__main__":
    main()
