"""The manifest: form, names, arrows, and that a later PR can add a
configuration, a traffic mix, a per-layer metric and a cell as new files
plus entries, editing nothing that is there."""

import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _manifest_mod():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_manifest", os.path.join(ROOT, "benchmark", "manifest.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_is_sound():
    assert _manifest_mod().check(ROOT) == []


def test_every_name_and_unit_within_the_allowed_characters():
    mod, m = _manifest_mod(), _load()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[group]:
            assert mod.NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert mod.UNIT.match(e["unit"]), e
    for w in m["workloads"]:
        assert mod.NAME.match(w["traffic"]) and len(w["why"]) <= 200


def test_every_moves_names_a_metric_that_each_of_its_cells_reports():
    m = _load()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    cells = [w["name"] for w in m["workloads"]]
    for p in m["per_layer"]:
        target = e2e[p["moves"]]
        for cell in p.get("workloads", cells):
            if "workloads" in target and "workloads" in p:
                assert cell in target["workloads"], (p["name"], cell)


def test_names_the_issue_fixed():
    m = _load()
    assert {e["name"] for e in m["end_to_end"]} >= {"setup_s"}
    assert {c["name"] for c in m["configs"]} <= {
        "mistral-7b-v0.3-d12", "gpt2-xl", "gpt2-medium"}
    assert all(w["chips"] == 1 for w in m["workloads"])
    phases = {p["name"] for p in m["per_layer"] if p["moves"] == "setup_s"}
    assert phases >= {f"bringup.{x}_s" for x in (
        "import", "backend", "materialize", "programs", "warmup", "other")}


@pytest.fixture
def copy(tmp_path):
    dst = tmp_path / "repo"
    dst.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmark"), dst / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    return dst


def _add_dummies(dst):
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


def test_a_cell_is_added_with_new_files_and_entries_only(copy):
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*")
              if p.is_file()}
    _add_dummies(copy)
    assert _manifest_mod().check(str(copy)) == []
    for p, body in before.items():
        assert p.read_bytes() == body, f"{p} had to be edited"


@pytest.mark.parametrize("breakage, word", [
    (lambda m: m["workloads"][-1].update(traffic="no-such-mix"), "traffic file"),
    (lambda m: m["per_layer"][-1].update(moves="out_tok_s"), "does not report"),
    (lambda m: m["per_layer"][-1].update(name="not a name"), "not a name"),
    (lambda m: m["per_layer"][-1].update(unit="tokens per second"), "unit"),
    (lambda m: m["configs"][-1].update(reduced=["hidden_size"]), "width"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m["workloads"][-1].update(chips=2), "chips"),
    (lambda m: m["per_layer"][-1].update(name="nowhere.metric"), "no reader"),
])
def test_the_check_finds_a_broken_manifest(copy, breakage, word):
    m = _add_dummies(copy)
    breakage(m)
    (copy / "BENCHMARK.json").write_text(json.dumps(m))
    faults = _manifest_mod().check(str(copy))
    assert any(word in f for f in faults), faults
