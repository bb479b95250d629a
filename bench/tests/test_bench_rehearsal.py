"""The harness without a chip: it refuses to measure, makes no device call
as it is imported, and finds a cell added as data files alone."""
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _py(code, cwd=ROOT, timeout=300):
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


def test_run_refuses_without_a_tpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "arxiv-gcs.sat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=ENV,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_import_touches_no_device():
    p = _py(f"import sys; sys.path[:0] = [{BENCH!r}, {SRC!r}]\n"
            "import jax._src.xla_bridge as xb\n"
            "import run, control\n"
            "from rbench import harness, load, traffic, trace, reference, "
            "flops, datagen, peaks\n"
            "print(len(xb._backends))")
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "0"


def test_new_cell_runs_from_added_files(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell added
    by files and entries alone are found by name and run."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((tmp_path / "bench/configs/arxiv-gcs.json").read_text())
    cfg.update(name="tiny-gcs", n=300, m=3000, d_in=16, d_hidden=16,
               n_classes=8, holdout_frac=0.5)
    (tmp_path / "bench/configs/tiny-gcs.json").write_text(json.dumps(cfg))
    traffic = json.loads((tmp_path / "bench/traffic/sat.json").read_text())
    traffic.update(chunk=5, max_batch=40, readd_after=40, capacity=160,
                   query_rate_per_s=40.0, prefill_updates=500)
    (tmp_path / "bench/traffic/tiny.sat.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/serve.batches.py").write_text(
        "def read(w):\n    return float(len(w.batch_sizes))\n")
    spec["configs"].append({"name": "tiny-gcs", "source": "test",
                            "file": "bench/configs/tiny-gcs.json",
                            "reduced": ["n", "m"], "why": "test"})
    spec["workloads"].append({"name": "tiny-gcs.sat", "config": "tiny-gcs",
                              "traffic": "tiny.sat", "chips": 1,
                              "why": "test"})
    cell = ["tiny-gcs.sat"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + cell
    spec["per_layer"].append({"name": "serve.batches", "unit": "count",
                              "better": "higher",
                              "source": "program_counter", "layer": "serve",
                              "moves": "updates_per_s", "workloads": cell})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    os.symlink(SRC, tmp_path / "src")
    p = _py("import sys, time, json\n"
            "T = time.perf_counter()\n"
            f"sys.path[:0] = [{str(tmp_path / 'bench')!r}, {SRC!r}]\n"
            "from rbench import harness\n"
            "cell = harness.load_cell('tiny-gcs.sat')\n"
            "r = harness.run(cell, seed=2**31 + 5, seconds=1.5, trace=True,"
            " t_start=T, warm=harness.WarmPolicy(0.5, 0.5, 3, 60.0))\n"
            "print(json.dumps(r))", cwd=tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, p.stderr[-3000:]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["serve.batches"]["value"] > 0
    assert out["metrics"]["engine.compiles.sat"]["value"] >= 0
    assert list(out)[-1] == "checks"
    assert "lateness" in p.stderr
    assert p.stderr.strip().splitlines()[-1].startswith("check ")
