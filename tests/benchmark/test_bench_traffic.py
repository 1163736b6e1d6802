"""The load generator: the same seed gives the same schedule and lengths,
another seed the same work in another order, and lateness is reported."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402

MIXES = ["chat-backlog", "doc-prefill-busy", "chat-interactive"]


def mix(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule_and_lengths(name):
    a = traffic.serving(mix(name), 2**31 + 11, 20.0, 32768)
    b = traffic.serving(mix(name), 2**31 + 11, 20.0, 32768)
    assert a == b and len(a) > 10


@pytest.mark.parametrize("name", MIXES)
def test_another_seed_is_the_same_schedule_with_other_tokens(name):
    m = mix(name)
    a = traffic.serving(m, 1, 20.0, 32768)
    b = traffic.serving(m, 2, 20.0, 32768)
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]
    shape = lambda rs: [(r["rid"], r["due_s"], len(r["tokens"]),
                         r["max_new_tokens"]) for r in rs]
    assert shape(a) == shape(b)
    other = traffic.serving(dict(m, schedule_seed=2), 1, 20.0, 32768)
    assert shape(other) != shape(a)
    assert sorted(len(r["tokens"]) for r in other) == sorted(
        len(r["tokens"]) for r in a)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_arrivals_keep_to_the_mix(name):
    m = mix(name)
    reqs = traffic.serving(m, 7, 30.0, 50257)
    for r in reqs:
        assert m["prompt"]["min"] <= len(r["tokens"]) <= m["prompt"]["max"]
        assert 1 <= r["max_new_tokens"] <= m["output"]["max"]
        assert len(r["tokens"]) + r["max_new_tokens"] <= m["max_total"]
        assert all(0 <= t < 50257 for t in r["tokens"])
        assert 0.0 <= r["due_s"] < 30.0
    assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
    if m["mode"] == "open_loop":
        rate = len(reqs) / 30.0
        assert 0.7 * m["rate_per_s"] <= rate <= 1.1 * m["rate_per_s"]
    else:
        assert all(r["due_s"] == 0.0 for r in reqs)


def test_median_prompt_is_the_mix_s_median():
    m = mix("doc-prefill-busy")
    lens = sorted(traffic.lognormal_grid(401, m["prompt"]))
    assert abs(lens[200] - m["prompt"]["median"]) <= 2


def test_the_busy_mix_at_the_window_s_length():
    """At the benchmark's own 40 s: the rate over the grid, due times in
    order, the median prompt the mix's, and the same schedule for two
    ``--seed``s as large as the driver's."""
    m = mix("doc-prefill-busy")
    a = traffic.serving(m, 2**31 + 101, 40.0, 32768)
    b = traffic.serving(m, 2**31 + 102, 40.0, 32768)
    assert 0.7 * m["rate_per_s"] <= len(a) / 40.0 <= 1.1 * m["rate_per_s"]
    assert len(a) >= 90  # a hundred requests due, where 1.25/s gave 50
    due = [r["due_s"] for r in a]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 40.0
    lens = sorted(len(r["tokens"]) for r in a)
    assert abs(lens[len(lens) // 2] - m["prompt"]["median"]) <= 64
    shape = lambda rs: [(r["rid"], r["due_s"], len(r["tokens"]),
                         r["max_new_tokens"]) for r in rs]
    assert shape(a) == shape(b)
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]


def test_the_busy_mix_is_doc_prefill_s_but_for_its_rate():
    """Every key but ``rate_per_s`` as PR 25 set it (``doc-prefill.json``,
    gone with the cell it served)."""
    m = mix("doc-prefill-busy")
    assert m["mode"] == "open_loop" and m["schedule_seed"] == 1
    assert m["prompt"] == {"median": 2048, "sigma": 0.45, "min": 1024,
                           "max": 4096}
    assert m["output"] == {"median": 32, "sigma": 0.4, "min": 16, "max": 64}
    assert m["max_total"] == 4160 and m["shared_prefix"] == 0
    assert m["engine"] == {"prefill_buckets": [1024, 2048],
                           "prefill_chunk": 2048, "max_pages_per_seq": 260}
    assert m["rate_per_s"] > 1.25
    assert not os.path.exists(
        os.path.join(ROOT, "benchmark", "traffic", "doc-prefill.json"))


def test_training_rows_differ_by_step_and_repeat_by_seed():
    m = {"batch": 4, "seq_len": 64}
    a0, a1 = (traffic.training_rows(m, 2**31 + 5, s, 50257) for s in (0, 1))
    assert a0.shape == (4, 64) and (a0 != a1).any()
    assert len({tuple(r) for r in a0}) == 4
    assert (a0 == traffic.training_rows(m, 2**31 + 5, 0, 50257)).all()


def test_lateness_is_reported():
    import importlib.util

    path = os.path.join(ROOT, "benchmark", "metrics", "loadgen.lateness_p99_s.py")
    spec = importlib.util.spec_from_file_location("lateness", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    late = [0.001 * i for i in range(100)]
    assert mod.read({"lateness": late, "mix": {"mode": "open_loop"}}) == pytest.approx(0.098)
    assert mod.read({"lateness": [], "mix": {"mode": "backlog"}}) is None
