"""Shared by the benchmark's rehearsal tests: run ``benchmark/run.py`` in
this process (JAX is already held to the CPU by the suite's conftest) and
read its result line."""

import contextlib
import importlib.util
import io
import json
import os

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
