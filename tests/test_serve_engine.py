"""The plain decode tick's contract (ROADMAP S3a): the host fetches the
lanes' greedy tokens and leaves the tick's logits on the device; a lane's
row comes to the host when the lane retires, and ``final_logits[rid]`` is
bit for bit the row that a fetch of the whole array held, however the
request ended.  One replica a family for the whole file; every case leaves
it with no active lane.
"""

import jax
import numpy as np
import pytest

from torchdistx_tpu import observe
from torchdistx_tpu.serve import (Request, ServeConfig, oracle_generate,
                                  spin_up_replica)
from torchdistx_tpu.serve.engine import _TickRow

VOCAB = 256
FAMILIES = {
    "llama": ("tiny", ServeConfig(
        max_batch=4, page_size=8, n_pages=40, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefill_chunk=16)),
    "gpt2": ("tiny-gpt2", ServeConfig(
        max_batch=4, page_size=8, n_pages=40, max_pages_per_seq=8,
        prefill_buckets=(8, 16))),
    "jamba": ("tiny-jamba", ServeConfig(
        max_batch=4, page_size=8, n_pages=40, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefix_cache=False, spec_decode=False)),
    "afmoe": ("tiny-afmoe", ServeConfig(
        max_batch=4, page_size=8, n_pages=40, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefill_chunk=16, prefix_cache=False,
        spec_decode=False)),
    "olmo_hybrid": ("tiny-olmo-hybrid", ServeConfig(
        max_batch=4, page_size=8, n_pages=40, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefix_cache=False, spec_decode=False)),
}
SPECULATING = [f for f, (_, s) in FAMILIES.items() if s.spec_decode is None]
# How a request ends, and the tick it ends in.
ENDINGS = [(f, how) for f in sorted(FAMILIES)
           for how in ("budget", "eos", "context_cap", "first_token",
                       "first_token_chunked")]
ENDINGS += [(f, "verify_tick") for f in sorted(SPECULATING)]


def _ids(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, VOCAB, n)]


@pytest.fixture(scope="module")
def replicas():
    """family -> its replica, brought up on first use.  Each one's
    ``_emit`` is wrapped to note, for every token handed over, the
    token's logits as a WHOLE fetch gives them (a plain tick's array
    brought to the host entire and indexed there) and the tick it came
    from: ``eng.seen[rid]`` is the request's last."""
    made = {}

    def get(family):
        if family not in made:
            preset, scfg = FAMILIES[family]
            eng = spin_up_replica(preset, serve_cfg=scfg)
            eng.seen = {}
            emit = eng._emit

            def record(lane, token, logits):
                if isinstance(logits, _TickRow):
                    assert isinstance(logits.logits, jax.Array)
                    want, tick = np.asarray(logits.logits)[logits.slot], "plain"
                else:
                    assert isinstance(logits, np.ndarray)
                    want = logits
                    tick = "verify" if lane.generated else "prefill"
                eng.seen[lane.req.rid] = (np.array(want), tick)
                return emit(lane, token, logits)

            eng._emit = record
            made[family] = eng
        return made[family]

    yield get
    made.clear()
    jax.clear_caches()


def _ending(eng, how):
    """Serve one request that ends ``how``; returns (rid, the tick it
    must have ended in).  But for the last case the drafter is taken away
    meanwhile, so that a speculating replica's tick is a plain one as
    surely as when nothing is proposed."""
    cap, big = eng.scfg.max_context, eng.scfg.prefill_buckets[-1]
    rid = f"{how}-{len(eng.results)}"
    if how != "verify_tick":
        drafter, eng._drafter = eng._drafter, None
        try:
            return _plain_ending(eng, how, rid, cap, big)
        finally:
            eng._drafter = drafter
    # A prompt that repeats itself makes the drafter propose; among a few
    # budgets one ends inside a verify tick.
    period = _ids(6, 4)
    reqs = [Request(f"{rid}-{n}", period * 3, max_new_tokens=n)
            for n in (5, 6, 7, 8, 9, 10, 11, 12)]
    ticks = eng.spec_verify_ticks
    eng.run(reqs)
    assert eng.spec_verify_ticks > ticks
    inside = [r.rid for r in reqs if eng.seen[r.rid][1] == "verify"]
    assert inside, {r.rid: eng.seen[r.rid][1] for r in reqs}
    return inside[0], "verify"


def _plain_ending(eng, how, rid, cap, big):
    if how == "budget":
        eng.run([Request(rid, _ids(1, 5), max_new_tokens=5)])
        assert len(eng.results[rid]) == 5
        return rid, "plain"
    if how == "eos":
        eng.run([Request(rid + "-probe", _ids(2, 6), max_new_tokens=6)])
        third = eng.results[rid + "-probe"][2]
        eng.run([Request(rid, _ids(2, 6), max_new_tokens=6, eos_id=third)])
        assert eng.results[rid][-1] == third and len(eng.results[rid]) <= 3
        return rid, "plain" if len(eng.results[rid]) > 1 else "prefill"
    if how == "context_cap":
        # Past submit's check, as a request requeued from a replica with
        # a longer context would arrive.
        late = Request(rid, _ids(3, cap - 3), max_new_tokens=12)
        late._submit_t = 0.0
        eng.waiting.append(late)
        eng.run()
        assert len(eng.results[rid]) == 4
        return rid, "plain"
    if how == "first_token":
        eng.run([Request(rid, _ids(4, 7), max_new_tokens=1)])
        return rid, "prefill"
    assert how == "first_token_chunked"
    eng.run([Request(rid, _ids(5, big + 5), max_new_tokens=1)])
    return rid, "prefill"


@pytest.mark.parametrize("family, how", ENDINGS)
def test_final_logits_are_the_row_a_whole_fetch_gave(replicas, family, how):
    eng = replicas(family)
    handed = {}
    eng.on_complete = lambda rid, toks, logits: handed.update({rid: logits})
    try:
        rid, tick = _ending(eng, how)
    finally:
        eng.on_complete = None
    want, seen_tick = eng.seen[rid]
    assert seen_tick == tick
    got = eng.final_logits[rid]
    assert got.dtype == np.float32 and got.shape == (VOCAB,)
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, want)        # bit for bit
    assert handed[rid] is got                        # what on_complete got
    assert int(np.argmax(got)) == eng.results[rid][-1]
    assert not eng.active and not eng.waiting


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_tokens_and_final_logits_equal_the_oracles(replicas, family):
    """Two short requests (the oracle runs eagerly, a trace a length: the
    families' own files hold the long comparisons)."""
    eng = replicas(family)
    tag = len(eng.results)
    reqs = [Request(f"o{tag}-{i}", _ids(10 + i, 3 + 6 * i), max_new_tokens=2 + i)
            for i in range(2)]
    out = eng.run(reqs)
    for r in reqs:
        want, want_logits = oracle_generate(
            eng.family, eng.cfg, eng.params, r.tokens, r.max_new_tokens)
        assert out[r.rid] == want
        np.testing.assert_allclose(eng.final_logits[r.rid], want_logits,
                                   atol=1e-4)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_rows_fetched_counts_the_lanes_that_retired_in_plain_ticks(
        replicas, family):
    """``tdx.serve.logit_rows_fetched`` (always on): one row a lane that
    retires in a plain decode tick; none for a retirement on a prefill's
    first token or inside a verify tick, whose logits are on the host
    already; none for the lanes that go on decoding."""
    eng = replicas(family)
    rows = observe.counter("tdx.serve.logit_rows_fetched")
    ticks = observe.counter("tdx.serve.decode_lane_ticks")
    rows0, ticks0, tag = rows.value, ticks.value, len(eng.results)
    reqs = [Request(f"c{tag}-{i}", _ids(20 + i, 4 + i), max_new_tokens=n)
            for i, n in enumerate((1, 4, 4, 6, 9, 1, 3))]
    eng.run(reqs)
    plain = sum(eng.seen[r.rid][1] == "plain" for r in reqs)
    assert plain >= (1 if family in SPECULATING else 5)
    assert rows.value - rows0 == plain
    # The mechanism engages: far fewer rows than lanes decoded.
    assert ticks.value - ticks0 > rows.value - rows0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_plain_ticks_d2h_span_carries_the_tokens_bytes(replicas, family):
    eng = replicas(family)
    tag = len(eng.results)
    vocab_row = VOCAB * 4
    observe.enable(True)
    try:
        n0 = len(observe.tracer().events)
        eng.run([Request(f"s{tag}-{i}", _ids(30 + i, 5), max_new_tokens=4)
                 for i in range(3)])
        d2h = [e["args"] for e in list(observe.tracer().events)[n0:]
               if e["ph"] == "X" and e["name"] == "serve.tick.d2h"]
    finally:
        observe.enable(None)
    decode = [a["bytes"] for a in d2h if a["program"] == "decode"]
    # int32 a lane of the batch, not the batch's float32 logits
    assert decode and set(decode) == {eng.scfg.max_batch * 4}
    # a prefill still brings its one row
    assert {a["bytes"] for a in d2h
            if a["program"].startswith("prefill")} == {vocab_row}
    for a in d2h:
        if a["program"].startswith("verify"):  # the whole array, as before
            k = int(a["program"].split("-")[1])
            assert a["bytes"] == eng.scfg.max_batch * (k + 1) * vocab_row
