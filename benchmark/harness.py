"""What every cell kind shares: the set-up clock, the manifest, the look
for the chip, the per-layer metric readers and the result line.  Nothing
heavy is imported at the top: the entry's clock is already running."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

PHASES = ("import", "backend", "materialize", "programs", "warmup", "other")
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_ASK = "/jax/compilation_cache/compile_requests_use_cache"


class Refused(Exception):
    """The run cannot be made here (no chip, no package); exit 2, no line."""


class Clock:
    """One clock from the entry's first statement to the opening of the
    window.  ``lap(phase)`` books the time since the last lap on a phase;
    ``open_window()`` books the rest on ``other`` and fixes ``setup_s``,
    so the phases sum to it exactly."""

    def __init__(self, t0: float):
        self.t0 = self.last = t0
        self.phases = {p: 0.0 for p in PHASES}
        self.laps = []  # (phase, what, seconds): the detail under a phase
        self.setup_s = None

    def lap(self, phase: str, what: str = "") -> None:
        now = time.perf_counter()
        self.phases[phase] += now - self.last
        self.laps.append((phase, what, round(now - self.last, 4)))
        self.last = now

    def open_window(self) -> float:
        self.lap("other")
        self.setup_s = self.last - self.t0
        return self.last


class Compiles:
    """Counts jax's own persistent-cache events: every program of the
    process, the program's and the benchmark's alike."""

    def __init__(self):
        self.miss = self.hit = self.asked = 0
        self.setup_miss = self.setup_asked = None

    def install(self):
        import jax.monitoring

        def on(event, **kw):
            if event == CACHE_MISS:
                self.miss += 1
            elif event == CACHE_HIT:
                self.hit += 1
            elif event == CACHE_ASK:
                self.asked += 1

        jax.monitoring.register_event_listener(on)

    def close_setup(self):
        self.setup_miss, self.setup_asked = self.miss, self.asked

    def in_window(self) -> int:
        """Programs that were asked for after set-up closed, from the cache
        or not: each is a shape that the warm-up missed."""
        return self.asked - self.setup_asked


def load_manifest(root: str) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise Refused(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"{what} {name!r} is not in BENCHMARK.json")


def load_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_module(root: str, rel: str):
    """A module of the benchmark found by its file name (a cell kind, a
    metric reader, a roofline)."""
    path = os.path.join(root, rel)
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + rel.replace("/", "_").removesuffix(".py")
        .replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_names(manifest: dict, workload: str, group: str):
    """The metrics of ``group`` that this cell reports: those that list it
    under ``workloads``, and those without the key (for a per-layer metric,
    where the cell reports the end-to-end metric it moves)."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    mine = {n for n, m in e2e.items()
            if "workloads" not in m or workload in m["workloads"]}
    if group == "end_to_end":
        return [m for m in manifest["end_to_end"] if m["name"] in mine]
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def devices(env):
    """The first touch of the backend.  Refuses anything but TPUs, and
    fewer of them than the cell asks for, unless this is a rehearsal."""
    import jax

    devs = jax.devices()
    if env["rehearse"]:
        return devs
    if devs[0].platform != "tpu":
        raise Refused(f"JAX's default backend is {devs[0].platform!r}, not a "
                      f"TPU; nothing is run or reported")
    if len(devs) < env["cell"]["chips"]:
        raise Refused(f"the cell asks for {env['cell']['chips']} chips, JAX "
                      f"finds {len(devs)}")
    peaks = load_json(env["root"], "benchmark/peaks.json")
    if devs[0].device_kind not in peaks:
        raise Refused(f"no peaks known for device kind "
                      f"{devs[0].device_kind!r} (benchmark/peaks.json)")
    env["peaks"] = peaks[devs[0].device_kind]
    return devs


def bind_cache(root: str) -> str:
    """The compile cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), through the program's own resolver."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ.setdefault("TDX_CACHE_DIR", os.path.join(root, ".jax_cache"))
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.environ["TDX_CACHE_DIR"]


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def device_line(devs, peak: int) -> dict:
    """The ``device`` of the result line, as JAX reports it."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def chip_checks(checks: dict, n_interpreted: int, asked_in_window: int) -> None:
    """What voids every device number of a run: a kernel that fell back to
    interpret mode, a program first asked for inside the window."""
    checks["interpreted_kernels"] = {
        "value": n_interpreted, "limit": 0, "ok": n_interpreted == 0}
    checks["compiles_in_window"] = {
        "value": asked_in_window, "limit": 0, "ok": asked_in_window == 0}


def attach_trace(ctx: dict, device: dict, trace_dir: str, labels: dict):
    """Reduce the traced slice into ``ctx['trace']`` and the line's
    ``busy_s`` / ``window_s``; returns the breakdown."""
    from benchmark import tracing

    ctx["trace"], breakdown = tracing.reduce_dir(trace_dir, labels)
    if ctx["trace"]:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    return breakdown


def quantile(values, q: float):
    """Nearest-rank quantile of all the values; None for none."""
    if not values:
        return None
    s = sorted(values)
    import math

    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def read_per_layer(env, ctx: dict) -> dict:
    """Each per-layer metric through its own reader,
    ``benchmark/metrics/<name>.py``; one that finds nothing to read is left
    out of the line."""
    out = {}
    for m in metric_names(env["manifest"], env["cell"]["name"], "per_layer"):
        mod = load_module(env["root"], f"benchmark/metrics/{m['name']}.py")
        if mod is None:
            continue
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def emit(env, run: dict) -> int:
    """The checks' numbers on standard error, then the one result line."""
    checks = run["checks"]
    correct = bool(checks) and all(c["ok"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    manifest, cell = env["manifest"], env["cell"]
    if env["rehearse"]:
        metrics = {}
    elif env["trace"]:
        metrics = read_per_layer(env, run["ctx"])
    else:
        metrics = {}
        for m in metric_names(manifest, cell["name"], "end_to_end"):
            v = run["end_to_end"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    line = {
        "correct": correct, "attempted": run["attempted"],
        "failed": run["failed"], "metrics": metrics, "device": run["device"],
    }
    if env["trace"] and run.get("breakdown"):
        line["breakdown"] = run["breakdown"]
    if env["rehearse"]:
        line["rehearsal"] = True
    line["notes"] = run.get("notes", {})
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
