"""``benchmark/run.py`` end to end on the CPU at a tiny size, as a
rehearsal only: no device metric is printed; without ``--rehearse`` and
without a TPU the command exits non-zero and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_util import ROOT, rehearse

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def _cmd(*args):
    return [sys.executable, os.path.join("benchmark", "run.py"), *args]


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", TDX_CACHE_DIR="")
    p = subprocess.run(
        _cmd("--workload", "gpt2m-train-1chip", "--seed", "1", "--seconds", "1"),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_in_a_bare_directory_it_exits_non_zero_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    p = subprocess.run(
        _cmd("--workload", CELLS[0], "--seed", "1", "--seconds", "1"),
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "torchdistx_tpu" in p.stderr


def test_an_unknown_cell_is_refused():
    p = subprocess.run(_cmd("--workload", "no-such-cell"), cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_cell_and_prints_no_device_metric(cell):
    rc, line, err = rehearse(cell, seed=2**31 + 3)
    assert rc == 0 and line is not None, err
    assert line["correct"] is True, err
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    for name, c in line["checks"].items():
        assert f"check {name}:" in err
    phases = line["notes"]["phases"]
    assert set(phases) == {"import", "backend", "materialize", "programs",
                           "warmup", "other"}
    assert sum(phases.values()) == pytest.approx(line["notes"]["setup_s"], abs=1e-6)


def test_the_train_cell_imports_nothing_of_the_serving_stack():
    env = dict(os.environ, JAX_PLATFORMS="cpu", TDX_CACHE_DIR="")
    p = subprocess.run(
        _cmd("--workload", "gpt2m-train-1chip", "--seed", "5", "--seconds",
             "1", "--rehearse"),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["notes"]["serve_modules_imported"] == 0
    assert line["notes"]["orbax_imported"] is False
