"""Shared helpers of the benchmark's own tests (CPU, tiny sizes)."""
import copy
import dataclasses
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.harness import spec  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
# The served cell: its driver, traffic and readers are proven on the chip,
# its entries wait here until a served cell joins BENCHMARK.json.
SERVED = json.loads((ROOT / "bench" / "tests" / "data"
                     / "rmat_good.serve.json").read_text())
CELLS = ([w["name"] for w in BENCHMARK["workloads"]]
         + [w["name"] for w in SERVED["workloads"]])


@pytest.fixture(autouse=True)
def with_served_cell(monkeypatch):
    """``spec.load_cell`` finds the served cell beside the benchmark's."""
    merged = {k: v + SERVED.get(k, []) if isinstance(v, list) else v
              for k, v in BENCHMARK.items()}
    monkeypatch.setattr(spec, "benchmark", lambda: copy.deepcopy(merged))


def tiny(cell, backend="pallas", executor=None):
    """A cell cut to a CPU test size, Pallas kernels in interpret mode;
    ``executor`` puts a batch cell on another executor (``sharded``: one
    shard per device of a four-device CPU mesh)."""
    c = copy.deepcopy(cell.config)
    g = c["graph"]
    if g["generator"] == "rmat":
        g["scale"] = 8
    else:
        g.update(nx=8, ny=8, nz=4)
    c["backend"] = backend
    t = copy.deepcopy(cell.traffic)
    if t["driver"] == "open_loop":
        t.update(rate_per_s=3.0, scales=[6, 7], weights=[0.5, 0.5])
    if executor is not None:
        t["executor"] = executor
    chips = c["P"] if t.get("executor") == "sharded" else cell.chips
    return dataclasses.replace(cell, config=c, traffic=t, chips=chips)


@pytest.fixture
def cpu_run():
    """``run_cell`` on the CPU at a tiny size; returns the result object."""
    from bench.harness import cli
    from repro.core import program_cache_clear

    def go(workload, *, seed=2**31 + 7, seconds=1.0, traced=False,
           patch=None, backend="pallas", executor=None):
        program_cache_clear()
        return cli.run_cell(workload, seed, seconds, traced,
                            require_tpu=False,
                            shrink=lambda c: tiny(c, backend, executor),
                            patch=patch)
    return go
