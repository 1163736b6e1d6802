"""``correct`` has to come out false when the timed path is broken
underneath: the harness's look for a chip is skipped (``--rehearse``), the
rest of a run is driven, and one fault at a time is planted in the program.
The faults a one-chip cell can have: a step that returns its state
unchanged, half of the batch left out with the mean taken over the rest,
and a token altered where it is produced."""

import pytest

from bench_util import rehearse

TRAIN, SERVE = "gpt2m-train-1chip", "mistral7b-chat-backlog"


def _break_train(monkeypatch, how):
    from torchdistx_tpu.parallel import train

    real = train.make_train_step

    def broken_make(model, cfg, mesh, **kw):
        kw["donate"] = False
        init_state, step, shard = real(model, cfg, mesh, **kw)

        def unchanged(state, tokens):
            _, metrics = step(state, tokens)
            return state, metrics

        def half(state, tokens):
            return step(state, tokens[: tokens.shape[0] // 2])

        return init_state, {"unchanged": unchanged, "half": half}[how], shard

    monkeypatch.setattr(train, "make_train_step", broken_make)


@pytest.mark.parametrize("how, caught_by", [
    ("unchanged", "update_norm_gap"), ("half", "grad_norm_gap")])
def test_a_broken_train_step_is_not_correct(monkeypatch, how, caught_by):
    _break_train(monkeypatch, how)
    rc, line, err = rehearse(TRAIN, seed=11, seconds=0.5)
    assert rc == 0 and line["correct"] is False, err
    c = line["checks"][caught_by]
    assert c["value"] > c["limit"]
    if how == "unchanged":
        assert c["value"] == pytest.approx(1.0, abs=0.01)


def test_an_altered_token_is_not_correct(monkeypatch):
    from torchdistx_tpu.serve import engine

    real = engine.ServeEngine._emit
    count = {"n": 0}

    def altered(self, lane, token, logits):
        count["n"] += 1
        if count["n"] % 5 == 0:
            token = (token + 1) % self.cfg.vocab_size
        return real(self, lane, token, logits)

    monkeypatch.setattr(engine.ServeEngine, "_emit", altered)
    rc, line, err = rehearse(SERVE, seed=12, seconds=1.5)
    assert rc == 0 and line["correct"] is False, err
    c = line["checks"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_the_sound_program_is_correct_on_the_same_seeds():
    for cell, seed in ((TRAIN, 11), (SERVE, 12)):
        rc, line, err = rehearse(cell, seed=seed, seconds=0.5)
        assert rc == 0 and line["correct"] is True, err
