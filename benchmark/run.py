#!/usr/bin/env python3
import time

T0 = time.perf_counter()  # setup_s runs from here; nothing heavy above it

"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json; its configuration
(``benchmark/configs/<config>.json``) names its kind, and the kind's
runner (``benchmark/kinds/<kind>.py``) imports the part of the program
that the cell drives and no other.  The last line of standard output is
the result as one JSON object.  Exits 2 and prints no result where JAX
finds no TPU (or fewer chips than the cell asks for), where the program's
package is not beside the benchmark, or where the cell is unknown.

``--control fp8`` is the builder's tool for setting limits (PERF.md 2).
``--rehearse`` is for this repository's CPU tests only: the configuration's
and the mix's ``rehearsal`` groups replace the sizes, JAX is held to the
CPU, and the line carries no metric.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rate-per-s", type=float, default=None,
                    help="the builder's sweep: another arrival rate than "
                         "the mix's")
    ap.add_argument("--control", default="",
                    help="also read the control (the reference in this lower "
                         "precision, e.g. fp8) and the planted faults; they "
                         "go under notes and never into correct")
    args = ap.parse_args(argv)

    # A configuration may name modules to import before anything else
    # (``import_first``): ``google.api_core`` scans every installed
    # distribution as it is imported, 4.5 s at the start of a process and
    # 25 s once jax and the serving stack are in (my chip runs, PR 25).
    listed = {}
    if os.path.exists(os.path.join(ROOT, "BENCHMARK.json")):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            listed = json.load(f)
    for w in listed.get("workloads", []):
        if w["name"] == args.workload:
            for c in listed["configs"]:
                if c["name"] == w["config"]:
                    with open(os.path.join(ROOT, c["file"])) as f:
                        for mod in json.load(f).get("import_first", []):
                            __import__(mod)
    sys.path.insert(0, ROOT)
    from benchmark import harness

    clock = harness.Clock(T0)
    try:
        manifest = harness.load_manifest(ROOT)
        cell = harness.find(manifest["workloads"], args.workload, "workload")
        conf = harness.find(manifest["configs"], cell["config"], "config")
        cfg = harness.load_json(ROOT, conf["file"])
        mix = harness.load_json(
            ROOT, f"benchmark/traffic/{cell['traffic']}.json")
        if not os.path.isdir(os.path.join(ROOT, "torchdistx_tpu")):
            raise harness.Refused(
                f"the torchdistx_tpu package is not in {ROOT}")
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
            cfg.update(cfg.get("rehearsal", {}))
            for k, v in mix.get("rehearsal", {}).items():
                mix[k] = v
        if args.rate_per_s is not None:
            mix["rate_per_s"] = args.rate_per_s
        env = {
            "root": ROOT, "manifest": manifest, "cell": cell, "cfg": cfg,
            "mix": mix, "seed": args.seed, "trace": bool(args.trace),
            "seconds": float(args.seconds if args.seconds is not None
                             else manifest["run_seconds"]),
            "rehearse": args.rehearse, "clock": clock,
            "control": args.control, "extra_notes": {},

            "cache_dir": harness.bind_cache(ROOT),
            "work_dir": os.path.join(ROOT, "benchmark", ".work"),
        }
        kind = harness.load_module(ROOT, f"benchmark/kinds/{cfg['kind']}.py")
        if kind is None:
            raise harness.Refused(f"no runner for kind {cfg['kind']!r}")
        run = kind.run(env)
    except harness.Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    return harness.emit(env, run)


if __name__ == "__main__":
    sys.exit(main())
