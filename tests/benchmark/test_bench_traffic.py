"""The load generator: the same seed gives the same schedule and lengths,
another seed the same work in another order, and lateness is reported."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402

MIXES = ["chat-backlog", "doc-prefill", "chat-interactive"]


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
    m = mix("doc-prefill")
    lens = sorted(traffic.lognormal_grid(401, m["prompt"]))
    assert abs(lens[200] - m["prompt"]["median"]) <= 2


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
