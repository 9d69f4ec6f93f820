"""Run a cell with the control in the program's place: the plain
reference's First Fit, blind to the edges between the P blocks (the
speculative coloring without its conflict repair).  The harness drives it
through its own window and check, so every number compared reads as it
would for a program that gave up that guarantee; ``correct`` must come out
false.  The benchmark's own runs never run this.

    python3 bench/tools/control.py --workload rmat_good.batch \\
        --seeds 1 2 3 --seconds 3
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import cli, graphs, program, reference  # noqa: E402


def _csr(g) -> graphs.CSR:
    return graphs.CSR(n=g.n, indptr=g.indptr, indices=g.indices)


class ControlService:
    """Stands where ``ColoringService`` stands in the served window."""

    def __init__(self, P: int, seed: int):
        self.P, self.seed = P, seed
        self.queue, self.next_id = [], 0

    @property
    def pending(self) -> int:
        return len(self.queue)

    def submit(self, g) -> int:
        self.queue.append((self.next_id, g))
        self.next_id += 1
        return self.next_id - 1

    def poll(self) -> dict:
        out = {}
        for jid, g in self.queue:
            c = reference.control_coloring(_csr(g), self.P,
                                           program.job_seed(self.seed, jid))
            out[jid] = dict(colors=c, n_colors=int(np.unique(c).size))
        self.queue = []
        return out

    def stats(self) -> dict:
        return {}


def control_patch(seed: int):
    def patch(st):
        P = st.config["P"]
        if hasattr(st, "svc"):
            st.svc = ControlService(P, seed)
            return

        def job(run, i):
            t0 = time.perf_counter()
            c = reference.control_coloring(st.csr, P,
                                           program.job_seed(run.seed, i))
            return dict(i=i, t0=t0, t1=time.perf_counter(), prep_s=0.0,
                        colors=c, reported_colors=int(np.unique(c).size),
                        spec_rounds=0, iters=0)
        st.job = job
    return patch


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    for seed in args.seeds:
        out = cli.run_cell(args.workload, seed, args.seconds, False,
                           t_start=time.perf_counter(),
                           patch=control_patch(seed))
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
