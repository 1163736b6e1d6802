"""Shared by the benchmark's rehearsal tests: run ``benchmark/run.py`` in
this process (JAX is already held to the CPU by the suite's conftest) and
read its result line."""

import contextlib
import importlib.util
import io
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_module():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rehearse(workload, seed, seconds=1.5, extra=()):
    """(exit code, result line as a dict or None, standard error)."""
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run_module().main([
            "--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--rehearse", *extra])
    lines = [l for l in out.getvalue().splitlines() if l.startswith("{")]
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


def copy_benchmark(tmp_path):
    """BENCHMARK.json and ``benchmark/`` copied into ``tmp_path / "repo"``,
    which is returned."""
    dst = tmp_path / "repo"
    dst.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmark"), dst / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    return dst


def add_dummies(dst):
    """What a later PR would add: four new files and three entries."""
    m = json.loads((dst / "BENCHMARK.json").read_text())
    src = json.loads((dst / "benchmark/configs/gpt2-medium.json").read_text())
    src["source"] = "https://example.org/dummy/config.json"
    (dst / "benchmark/configs/dummy.json").write_text(json.dumps(src))
    (dst / "benchmark/traffic/dummy-mix.json").write_text(
        json.dumps({"mode": "train", "batch": 2, "seq_len": 512}))
    (dst / "benchmark/metrics/dummy.counter.py").write_text(
        "def read(ctx):\n    return len(ctx['steps']) or None\n")
    (dst / "benchmark/limits/dummy-cell.json").write_text(
        (dst / "benchmark/limits/gpt2m-train-1chip.json").read_text())
    m["configs"].append({
        "name": "dummy", "source": src["source"],
        "file": "benchmark/configs/dummy.json", "reduced": [], "why": "a test"})
    m["workloads"].append({
        "name": "dummy-cell", "config": "dummy", "traffic": "dummy-mix",
        "chips": 1, "why": "a test"})
    for e in m["end_to_end"]:
        if e["name"] == "train_tok_s":
            e["workloads"].append("dummy-cell")
    m["per_layer"].append({
        "name": "dummy.counter", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tok_s", "workloads": ["dummy-cell"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(m))
    return m


def roots(tmp_path, grown):
    """The repository's root, or a copy of its benchmark to which a later
    PR's cell has been appended (``grown``): the pins of earlier PRs'
    entries have to hold on both."""
    if not grown:
        return ROOT
    dst = copy_benchmark(tmp_path)
    add_dummies(dst)
    return str(dst)
