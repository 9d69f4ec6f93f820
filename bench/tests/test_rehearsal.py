"""Every cell of BENCHMARK.json, found by name and run through the harness
on the CPU at a tiny size with the Pallas kernels in interpret mode; and
the harness's refusals when there is no chip or no program."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import spec
from conftest import BENCHMARK, CELLS, ROOT


@pytest.mark.parametrize("cell", CELLS)
def test_every_named_file_exists(cell):
    c = spec.load_cell(cell)
    assert c.config["name"] in {x["name"] for x in BENCHMARK["configs"]}
    spec.driver(c.traffic)
    for m in c.per_layer:
        assert callable(spec.reader(m["name"]))
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_on_cpu(cpu_run, cell):
    out = cpu_run(cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    c = spec.load_cell(cell)
    assert set(out["metrics"]) == {m["name"] for m in c.end_to_end}
    assert out["info"]["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"


def test_sharded_executor_runs_on_cpu(cpu_run):
    """The batch driver's one-shard-per-chip executor, on a CPU mesh."""
    out = cpu_run("rmat_good.batch", backend="xla", executor="sharded")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("cell", ["rmat_good.batch", "rmat_good.serve"])
def test_traced_run_reads_host_metrics(cpu_run, cell):
    out = cpu_run(cell, traced=True, backend="xla")
    assert out["correct"] is True
    assert out["device"]["window_s"] > 0
    c = spec.load_cell(cell)
    names = {m["name"] for m in c.per_layer}
    # no TPU plane in a CPU trace: the device readers find nothing to read
    assert set(out["metrics"]) <= names
    assert not any("idle" in m or "roofline" in m for m in out["metrics"])
    assert any(m.startswith("compiles_in_window") for m in out["metrics"])


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmat_good.batch",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_benchmark_json_keeps_its_contract():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    bm = BENCHMARK
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= bm["run_seconds"] <= 51
    metrics = bm["end_to_end"] + bm["per_layer"]
    names = [x["name"] for x in bm["configs"] + bm["workloads"] + metrics]
    assert all(name.match(n) for n in names) and len(set(names)) == len(names)
    assert all(unit.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert all(0.01 <= m["bound"] <= 0.25 for m in bm["end_to_end"])
    pairs = [(w["config"], w["traffic"]) for w in bm["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(1, len(bm["workloads"]) // 2)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    for w in bm["workloads"]:
        mine = {n for n, m in e2e.items()
                if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in mine and len(mine) >= 2
        layers = [m for m in bm["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert layers and all(m["moves"] in mine for m in layers)
    for c in bm["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in bm["paths"]))
