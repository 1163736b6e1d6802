"""The manifest: form, names, arrows, and that a later PR can add a
configuration, a traffic mix, a per-layer metric and a cell as new files
plus entries, editing nothing that is there."""

import json
import os

import pytest

from bench_util import ROOT, add_dummies, copy_benchmark, roots


def _manifest_mod():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_manifest", os.path.join(ROOT, "benchmark", "manifest.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
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


@pytest.mark.parametrize("grown", [False, True],
                         ids=["as-committed", "with-a-later-cell"])
def test_names_the_issue_fixed(tmp_path, grown):
    m = _load(roots(tmp_path, grown))
    assert {e["name"] for e in m["end_to_end"]} >= {"setup_s"}
    # PR 25's configurations that a cell uses are among the manifest's;
    # later PRs append theirs.
    assert {c["name"] for c in m["configs"]} >= {
        "mistral-7b-v0.3-d12", "gpt2-medium"}
    assert all(w["chips"] == 1 for w in m["workloads"])
    phases = {p["name"] for p in m["per_layer"] if p["moves"] == "setup_s"}
    assert phases >= {f"bringup.{x}_s" for x in (
        "import", "backend", "materialize", "programs", "warmup", "other")}


@pytest.fixture
def copy(tmp_path):
    return copy_benchmark(tmp_path)


def test_a_cell_is_added_with_new_files_and_entries_only(copy):
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*")
              if p.is_file()}
    add_dummies(copy)
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
    m = add_dummies(copy)
    breakage(m)
    (copy / "BENCHMARK.json").write_text(json.dumps(m))
    faults = _manifest_mod().check(str(copy))
    assert any(word in f for f in faults), faults
