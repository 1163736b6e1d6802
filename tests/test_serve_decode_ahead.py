"""Decoding ahead (docs/serving.md §Decoding ahead): a replica with no
drafter whose plain tick reads nothing but its tokens dispatches each tick
before it reads the tick before, so the host's part of a tick runs while
the device works.  Only WHEN a token is handed over changes: for the same
requests the token streams are the synchronous engine's, token for token,
however a request ends.  Each case serves its requests twice on one
replica, once on the synchronous path (the test takes the replica's
``_decodes_ahead`` away) and once decoding ahead."""

import jax
import numpy as np
import pytest

from torchdistx_tpu import chaos, observe
from torchdistx_tpu.serve import Request, ServeConfig, spin_up_replica

VOCAB = 256
SHAPE = dict(max_batch=4, page_size=8, n_pages=20, max_pages_per_seq=8,
             prefill_buckets=(8, 16), prefix_cache=False, spec_decode=False)
FAMILIES = {
    "llama": ("tiny", ServeConfig(**SHAPE, prefill_chunk=16)),
    "jamba": ("tiny-jamba", ServeConfig(**SHAPE)),
    "olmo_hybrid": ("tiny-olmo-hybrid", ServeConfig(**SHAPE)),
}
CASES = ["budget", "eos", "cancel", "deadline", "preemption",
         "fault_in_decode", "chaos_raise", "drain"]
# The cases that end a lane with a tick of it in flight: its token there
# is thrown away (``tdx.serve.lane_ticks_discarded``).
DISCARDS = {"eos", "cancel", "deadline"}


def _ids(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, VOCAB, n)]


@pytest.fixture(scope="module")
def replicas():
    made = {}

    def get(family):
        if family not in made:
            preset, scfg = FAMILIES[family]
            eng = spin_up_replica(preset, serve_cfg=scfg)
            # The tiny presets' residual stream is their embedding's, so a
            # stream repeats its prompt's last token; with the layers' weights
            # five times larger it changes token, and an eos can land after
            # a tick.
            eng.install_params(jax.tree_util.tree_map_with_path(
                lambda path, w: w if w.ndim < 2 or "embed" in
                jax.tree_util.keystr(path) else w * 5.0, eng.params))
            made[family] = eng
        return made[family]

    yield get
    made.clear()
    jax.clear_caches()


class _FailsOnce:
    """The decode program failing on its ``nth`` call after it took the
    pools, as a device does (``tests/test_serve_donation.py``)."""

    def __init__(self, prog, nth):
        self.prog, self.left = prog, nth

    def __call__(self, *args):
        self.left -= 1
        if self.left == 0:
            for a in args[1:3]:
                a.delete()
            raise jax.errors.JaxRuntimeError("INTERNAL: planted device fault")
        return self.prog(*args)


def _requests(how, tag, probe=None):
    """The case's requests, fresh (the engine stamps what it submits)."""
    if how == "preemption":
        # Four lanes of 56 tokens want 28 pages of a pool of 19.
        return [Request(f"{tag}{i}", _ids(40 + i, 16 - i), max_new_tokens=40)
                for i in range(4)]
    if how == "drain":
        return [Request(f"{tag}{i}", _ids(50 + i, 3 + i), max_new_tokens=6)
                for i in range(6)]
    reqs = [Request(f"{tag}{i}", _ids(30 + i, 3 + 4 * i), max_new_tokens=n)
            for i, n in enumerate((1, 3, 9, 12))]
    if how == "eos":
        prompt, reqs[3].eos_id = probe
        reqs[3].tokens = list(prompt)
    return reqs


def _eos_probe(eng):
    """A prompt and a token of its stream, after the first, that the stream
    has not had before: a request with it as ``eos_id`` ends there, with a
    tick of it in flight.  Returns (prompt, token, its index)."""
    for seed in range(30, 60):
        prompt = _ids(seed, 15)
        toks = eng.run([Request(f"probe-{seed}", prompt,
                                max_new_tokens=10)])[f"probe-{seed}"]
        fresh = [i for i in range(1, len(toks)) if toks[i] not in toks[:i]]
        if fresh:
            return prompt, toks[fresh[0]], fresh[0]
    raise AssertionError("no prompt whose stream changes token")


def _serve(eng, how, tag, *, ahead, monkeypatch, probe=None):
    """Serve the case once; returns the streams by request (the tag
    stripped), the results, the final logits, what a drain handed back and
    the counters' moves."""
    streams, cancelled = {}, []
    names = ("tdx.serve.lane_ticks_discarded", "tdx.serve.decode_ticks_ahead",
             "tdx.serve.pool_rebuilds", "tdx.serve.preempted_requests")
    before = {n: observe.counter(n).value for n in names}
    eng.on_token = lambda rid, tok: streams.setdefault(rid, []).append(tok)
    eng.on_cancel = lambda rid, toks, active: cancelled.append((rid, toks))
    reqs = _requests(how, tag, probe)
    target = reqs[3]
    leftover = []
    with monkeypatch.context() as m:
        if not ahead:
            m.setattr(eng, "_decodes_ahead", lambda: False)
        if how == "fault_in_decode":
            m.setitem(eng._programs, "decode",
                      _FailsOnce(eng._programs["decode"], 3))
        if how == "chaos_raise":
            chaos.install(f"serve@{eng._step_no + 3}=raise")
        try:
            for r in reqs:
                eng.submit(r)
            for step in range(400):
                if not (eng.waiting or eng.active):
                    break
                if how == "drain" and step == 3:
                    leftover = [r.rid for r in eng.drain()]
                    break
                eng.step()
                if how in ("cancel", "deadline") and not cancelled and len(
                        streams.get(target.rid, ())) >= 3:
                    if how == "cancel":
                        cancelled.append((target.rid,
                                          eng.cancel(target.rid)))
                    else:
                        target._deadline_t = 0.0  # the next sweep expires it
            eng.run()  # settles what is in flight
        finally:
            chaos.clear()
            eng.on_token = eng.on_cancel = None
    assert eng._tick is None and not eng._rows
    assert not eng.active and eng.kv.pages_in_use == 0
    strip = lambda rid: rid[len(tag):]
    return {
        "streams": {strip(k): v for k, v in streams.items()},
        "results": {strip(r.rid): eng.results.get(r.rid) for r in reqs},
        "logits": {strip(r.rid): eng.final_logits.get(r.rid) for r in reqs},
        "cancelled": [(strip(rid), toks) for rid, toks in cancelled],
        "leftover": [strip(rid) for rid in leftover],
        "moved": {n.rsplit(".", 1)[1]: observe.counter(n).value - before[n]
                  for n in names},
    }


@pytest.mark.parametrize("how", CASES)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_decoding_ahead_hands_over_the_synchronous_engines_tokens(
        replicas, monkeypatch, family, how):
    eng = replicas(family)
    assert eng._decodes_ahead()
    probe = ends = None
    if how == "eos":
        prompt, eos, ends = _eos_probe(eng)
        probe = (prompt, eos)
    sync = _serve(eng, how, f"{how}-s-", ahead=False,
                  monkeypatch=monkeypatch, probe=probe)
    ahead = _serve(eng, how, f"{how}-a-", ahead=True,
                   monkeypatch=monkeypatch, probe=probe)
    assert ahead["streams"] == sync["streams"] and sync["streams"]
    assert ahead["results"] == sync["results"]
    assert ahead["cancelled"] == sync["cancelled"]
    assert ahead["leftover"] == sync["leftover"]
    for rid, want in sync["logits"].items():
        if want is None:
            assert ahead["logits"][rid] is None
        else:
            np.testing.assert_array_equal(ahead["logits"][rid], want)
    # The case happened, on both paths alike.
    if how == "eos":
        assert sync["results"]["3"][-1] == probe[1]
        assert len(sync["results"]["3"]) == ends + 1 < 12
    if how in ("cancel", "deadline"):
        assert [(rid, len(t)) for rid, t in sync["cancelled"]] == [("3", 3)]
    if how == "preemption":
        assert sync["moved"]["preempted_requests"] > 0
        assert ahead["moved"]["preempted_requests"] > 0
    if how == "fault_in_decode":
        assert sync["moved"]["pool_rebuilds"] == 1
        assert ahead["moved"]["pool_rebuilds"] == 1
    if how == "drain":
        assert sync["leftover"] == ["4", "5"]
    # The counters: ticks dispatched with the tick before unread only on
    # the path that decodes ahead; a discarded lane-tick exactly where a
    # lane ended with a tick of it in flight.
    assert sync["moved"]["decode_ticks_ahead"] == 0
    assert ahead["moved"]["decode_ticks_ahead"] > 0
    assert sync["moved"]["lane_ticks_discarded"] == 0
    assert ahead["moved"]["lane_ticks_discarded"] == (how in DISCARDS)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_tick_but_the_first_is_dispatched_ahead(replicas, family):
    """One request of ``n`` tokens: the prefill hands over the first, then
    ``n - 1`` ticks, of which every one but the first was dispatched while
    the tick before it was unread."""
    eng = replicas(family)
    ahead = observe.counter("tdx.serve.decode_ticks_ahead")
    n, ticks0, ahead0 = 7, eng.program_calls.get("decode", 0), ahead.value
    out = eng.run([Request(f"count-{family}", _ids(9, 5), max_new_tokens=n)])
    assert len(out[f"count-{family}"]) == n
    assert eng.program_calls["decode"] - ticks0 == n - 1
    assert ahead.value - ahead0 == n - 2


@pytest.mark.parametrize("preset, scfg", [
    # a drafter: it proposes for tick k from tick k - 1's token
    ("tiny", ServeConfig(**{**SHAPE, "spec_decode": True})),
    # the afmoe family's tick reads the held experts' pair counts too
    ("tiny-afmoe", ServeConfig(**SHAPE, prefill_chunk=16)),
], ids=["drafter", "afmoe"])
def test_a_replica_with_a_drafter_or_pair_counts_stays_synchronous(
        preset, scfg):
    eng = spin_up_replica(preset, serve_cfg=scfg)
    assert not eng._decodes_ahead()
    counters = [observe.counter(n) for n in (
        "tdx.serve.decode_ticks_ahead", "tdx.serve.lane_ticks_discarded")]
    before = [c.value for c in counters]
    reqs = [Request(f"sync-{i}", _ids(70 + i, 4 + i), max_new_tokens=5)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    while eng.waiting or eng.active:
        eng.step()
        assert eng._tick is None and not eng._rows  # nothing read late
    assert all(len(eng.results[r.rid]) == 5 for r in reqs)
    assert [c.value for c in counters] == before
    jax.clear_caches()
