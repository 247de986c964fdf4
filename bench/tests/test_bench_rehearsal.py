"""Each cell's rehearsal on the CPU prints the result line the benchmark's
contract asks for, and the benchmark refuses to run where it should."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.run import Bench
from bench.tests.helpers import CANDIDATES, REPO, candidates_root, rehearse

BENCH = Bench(REPO)
CELLS = [(w["name"], int(w["chips"])) for w in BENCH.spec["workloads"] + CANDIDATES]
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _check_line(result, cell, chips, traced):
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"]: m["unit"] for m in BENCH.metrics(cell, traced=traced)}
    got = {k: v["unit"] for k, v in result["metrics"].items()}
    assert got == want
    assert all(isinstance(v["value"], float) for v in result["metrics"].values())
    assert DEVICE_KEYS <= set(result["device"])
    assert result["device"]["count"] == chips
    for check in result["checks"].values():
        assert set(check) == {"value", "limit"}


@pytest.mark.parametrize("cell,chips", CELLS, ids=[c for c, _ in CELLS])
def test_rehearsal_prints_the_result_line(cell, chips, tmp_path):
    rc, result, err = rehearse(cell, tmp_path, chips=chips, root=candidates_root(tmp_path))
    assert rc == 0, err[-3000:]
    assert result is not None, err[-3000:]
    _check_line(result, cell, chips, traced=False)
    assert result["metrics"]["qps"]["value"] > 0
    # the compared numbers are the last lines of stderr, each with its limit
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and "(limit " in line for line in tail)


def test_traced_rehearsal_reports_per_layer_metrics_and_breakdown(tmp_path):
    rc, result, err = rehearse("web3hop.hotspot", tmp_path, trace=1)
    assert rc == 0, err[-3000:]
    _check_line(result, "web3hop.hotspot", 1, traced=True)
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for key in ("device_ops", "idle_gaps"):
        entries = result["breakdown"][key]
        assert 0 < len(entries) <= 10
        assert all(isinstance(n, str) and t >= 0 for n, t in entries)


def test_no_tpu_means_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "web3hop.hotspot",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, text=True, capture_output=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own files has
    no system under test: the run fails and prints no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "web3hop.hotspot",
                        "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
                       cwd=tmp_path, env=env, text=True, capture_output=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert json.loads(open(tmp_path / "BENCHMARK.json").read())["workloads"]
