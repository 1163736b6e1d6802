"""The manifest check: BENCHMARK.json against the contract's rules of
form, and against the files that the harness will look for by name.  Run
by the tests; ``python3 benchmark/manifest.py [root]`` prints the faults."""

from __future__ import annotations

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|latent|"
                   r"state_size|proj|head_size|n_embd|n_inner|d_model|d_ff|"
                   r"expansion|experts_per_tok")
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")


def _line(s, what, faults):
    if not (isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
            and "\t" not in s):
        faults.append(f"{what}: not one line of 1 to 200 characters")


def check(root: str) -> list:
    """The faults found, as sentences; empty when the manifest is sound."""
    faults = []
    path = os.path.join(root, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        faults.append("BENCHMARK.json is over 64 KiB")
    with open(path) as f:
        m = json.load(f)
    if set(m) != TOP:
        faults.append(f"top-level keys {sorted(set(m) ^ TOP)} missing or extra")
        return faults
    if not (1 <= len(m["command"]) <= 32):
        faults.append("command: 1 to 32 words")
    for w in m["command"]:
        _line(w, f"command word {w!r}", faults)
        if w.startswith("/") or ".." in w.split("/"):
            faults.append(f"command word {w!r} leaves the repo")
    if not (1 <= len(m["paths"]) <= 16):
        faults.append("paths: 1 to 16 directories")
    for p in m["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            faults.append(f"path {p!r} is not a plain relative path")
    under = lambda rel: any(rel == p or rel.startswith(p.rstrip("/") + "/")
                            for p in m["paths"])
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        faults.append("run_seconds: a whole number from 1 to 51")

    names = set()

    def name(n, what):
        if not (isinstance(n, str) and NAME.match(n)):
            faults.append(f"{what} {n!r}: not a name")
        if (what.split()[0], n) in names:
            faults.append(f"{what} {n!r}: twice")
        names.add((what.split()[0], n))

    configs = {}
    files = set()
    if not (1 <= len(m["configs"]) <= 24):
        faults.append("configs: 1 to 24")
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')!r}: keys {sorted(c)}")
            continue
        name(c["name"], "config")
        _line(c["source"], f"config {c['name']} source", faults)
        _line(c["why"], f"config {c['name']} why", faults)
        configs[c["name"]] = c
        if not under(c["file"]) or c["file"] in files:
            faults.append(f"config {c['name']}: file {c['file']!r} is not "
                          f"under paths, or is another configuration's")
        files.add(c["file"])
        full = os.path.join(root, c["file"])
        if not os.path.exists(full):
            faults.append(f"config {c['name']}: no file {c['file']}")
        else:
            with open(full) as f:
                body = json.load(f)
            if body.get("source") != c["source"]:
                faults.append(f"config {c['name']}: the file names another source")
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                faults.append(f"config {c['name']}: the file's reduced differs")
            if "kind" not in body or not os.path.exists(os.path.join(
                    root, "benchmark", "kinds", f"{body.get('kind')}.py")):
                faults.append(f"config {c['name']}: no runner for its kind")
        if len(c["reduced"]) > 16:
            faults.append(f"config {c['name']}: over 16 reduced keys")
        for k in c["reduced"]:
            if not NAME.match(k):
                faults.append(f"config {c['name']}: reduced key {k!r} is no name")
            if WIDTH.search(k):
                faults.append(f"config {c['name']}: reduced names a width, {k!r}")

    cells = {}
    pairs = set()
    if not (1 <= len(m["workloads"]) <= 24):
        faults.append("workloads: 1 to 24")
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')!r}: keys {sorted(w)}")
            continue
        name(w["name"], "workload")
        name(w["traffic"], f"traffic-of-{w['name']}")
        _line(w["why"], f"workload {w['name']} why", faults)
        if w["config"] not in configs:
            faults.append(f"workload {w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"workload {w['name']}: its pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
        if not any(os.path.exists(os.path.join(
                root, "benchmark", "traffic", w["traffic"] + e))
                for e in TRAFFIC_EXT):
            faults.append(f"workload {w['name']}: no traffic file "
                          f"benchmark/traffic/{w['traffic']}.*")
        if not os.path.exists(os.path.join(
                root, "benchmark", "limits", w["name"] + ".json")):
            faults.append(f"workload {w['name']}: no benchmark/limits/"
                          f"{w['name']}.json")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        faults.append("over a quarter of the cells ask for four chips")
    for c in configs:
        if not any(w["config"] == c for w in cells.values()):
            faults.append(f"config {c}: used by no cell")

    e2e = {}
    if not (1 <= len(m["end_to_end"]) <= 16):
        faults.append("end_to_end: 1 to 16")
    for e in m["end_to_end"]:
        if set(e) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            faults.append(f"end-to-end {e.get('name')!r}: keys {sorted(e)}")
            continue
        name(e["name"], "metric")
        if not UNIT.match(e["unit"]):
            faults.append(f"metric {e['name']}: unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            faults.append(f"metric {e['name']}: better")
        if e["source"] not in ("host_clock", "device_trace"):
            faults.append(f"metric {e['name']}: an end-to-end source is "
                          f"host_clock or device_trace")
        if not (0.01 <= e["bound"] <= 0.1):
            faults.append(f"metric {e['name']}: bound {e['bound']} outside 1% to 10%")
        for w in e.get("workloads", []):
            if w not in cells:
                faults.append(f"metric {e['name']}: unknown cell {w!r}")
        e2e[e["name"]] = e
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        faults.append("setup_s has to be an end-to-end metric of every cell")

    def reports(cell, metric):
        e = e2e[metric]
        return "workloads" not in e or cell in e["workloads"]

    if not (1 <= len(m["per_layer"]) <= 128):
        faults.append("per_layer: 1 to 128")
    layered = {c: 0 for c in cells}
    for p in m["per_layer"]:
        if set(p) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            faults.append(f"per-layer {p.get('name')!r}: keys {sorted(p)}")
            continue
        name(p["name"], "metric")
        _line(p["layer"], f"metric {p['name']} layer", faults)
        if not UNIT.match(p["unit"]):
            faults.append(f"metric {p['name']}: unit {p['unit']!r}")
        if p["better"] not in ("lower", "higher"):
            faults.append(f"metric {p['name']}: better")
        if p["source"] not in SOURCES:
            faults.append(f"metric {p['name']}: source {p['source']!r}")
        if p["moves"] not in e2e:
            faults.append(f"metric {p['name']}: moves {p['moves']!r}, which "
                          f"is no end-to-end metric")
            continue
        for w in p.get("workloads", [c for c in cells if reports(c, p["moves"])]):
            if w not in cells:
                faults.append(f"metric {p['name']}: unknown cell {w!r}")
            elif not reports(w, p["moves"]):
                faults.append(f"metric {p['name']}: cell {w} does not report "
                              f"{p['moves']}")
            else:
                layered[w] += 1
        if p["name"].endswith("_roofline") and p["unit"] != "%":
            faults.append(f"metric {p['name']}: a roofline share is in %")
        if not os.path.exists(os.path.join(
                root, "benchmark", "metrics", p["name"] + ".py")):
            faults.append(f"metric {p['name']}: no reader "
                          f"benchmark/metrics/{p['name']}.py")
    for c in cells:
        others = [n for n in e2e if n != "setup_s" and reports(c, n)]
        if not others:
            faults.append(f"cell {c}: reports no end-to-end metric but setup_s")
        if not layered[c]:
            faults.append(f"cell {c}: reports no per-layer metric")
    return faults


if __name__ == "__main__":
    found = check(sys.argv[1] if len(sys.argv) > 1 else
                  os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    print("\n".join(found) or "the manifest is sound")
    sys.exit(1 if found else 0)
