"""``chip_smoke.py`` phases at a tiny size on the CPU, its refusal to run
without a TPU, and the compile-cache placement its entry points share."""
import os
import shutil
import subprocess
import sys

import pytest

import jax

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.utils import use_compile_cache  # noqa: E402

TINY = dict(graph="er", n=300, m=1500, n_layers=2, d_in=16, d_hidden=16,
            n_classes=5)


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """A directory holding the script and nothing else of the repo."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_serve_phase_tiny():
    session = chip_smoke.build_session("gc-s", "device", size=TINY, seed=0)
    info = chip_smoke.serve_stream(session, n_updates=200, batch=20,
                                   tenants=4, seed=1)
    assert info["updates"] == 200 and info["queries"] > 0
    assert chip_smoke.check_oracle(session, "tiny served") < chip_smoke.TOL


@pytest.mark.parametrize("workload,mix,kernel", chip_smoke.KERNEL_PHASES)
def test_kernel_phase_tiny(workload, mix, kernel):
    info = chip_smoke.kernel_phase(workload, mix, kernel, size=TINY,
                                   n_updates=60, batch=20, seed=0,
                                   on_tpu=False)
    assert info["updates"] == 60 and info["max_err"] < chip_smoke.TOL


@pytest.mark.parametrize("workload,mix", [("gc-s", (1, 1, 1)),
                                          ("gs-max", (1, 3, 1))])
def test_mesh_phase_tiny(workload, mix):
    """Both mesh engines on the one CPU device, migrated by swap_engine."""
    out = chip_smoke.mesh_phase(workload, mix, size=TINY, n_updates=40,
                                batch=20, seed=0, mesh_shapes=((1, 1),))
    assert [r["engine"] for r in out] == ["dist", "dist-rc"]
    assert all(r["max_err"] < chip_smoke.TOL for r in out)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    was = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = use_compile_cache()
            assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
