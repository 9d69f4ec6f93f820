"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size.

The platform check lives in ``chip_smoke.main`` only, so the phase
functions run here on the CPU (XLA twin, Pallas kernels in interpret mode)
and the script cannot rot between chip runs.  The sharded phase needs
four devices: it runs in a subprocess with four virtual CPU devices.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("backend", ["auto", "pallas"])
def test_batch_phase(smoke, backend):
    r = smoke.batch_phase(scale=8, n_iters=2, backend=backend)
    assert r["n_vertices"] == 256 and r["n_colors"] > 1
    assert r["n_iters_run"] == 2
    assert r["compile_s"] > 0 and r["run_s"] > 0


def test_service_phase(smoke):
    r = smoke.service_phase(n_requests=3, scale_lo=6, scale_hi=7)
    assert r["n_requests"] == 3 and r["engines"] >= 1
    assert all(n > 1 for n in r["n_colors"])


def test_main_refuses_without_tpu(smoke, capsys):
    """No accelerator: non-zero exit and no result line."""
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("env_dir", [False, True], ids=["unset", "set"])
def test_compile_cache_place(monkeypatch, tmp_path, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and the code sets nothing;
    otherwise the cache goes to the fixed ``.jax_cache`` of the checkout."""
    import jax
    from repro.compile_cache import use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = use_compile_cache()
        want = str(tmp_path) if env_dir else str(ROOT / ".jax_cache")
        assert got == want
        assert jax.config.jax_compilation_cache_dir == (
            was if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_sharded_phase_on_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        r = smoke.sharded_phase(scale=8, n_iters=2)
        assert r["sparse"]["devices"] == r["allgather"]["devices"] == 4
        print("sharded phase OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded phase OK" in out.stdout
