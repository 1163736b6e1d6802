"""Nothing that a serving window can meet is first asked of jax's compile
cache inside it (ROADMAP S3a; PR 35 was refused for ONE such program, in
one seed of one cell).

The benchmark counts every ``compile_requests_use_cache`` event after its
set-up has closed (``benchmark/harness.py`` ``Compiles.in_window``): the
serving programs and jax's own small eager programs alike.  Its warm-up
meets two things only: every program of ``eng._all_specs()`` once on
zeros, and one request a prefill bucket (and one chunked prompt where the
mix chunks) with ``max_new_tokens=3``.  So whatever else runs on the
device in a window has to have a shape, a dtype and static arguments that
those two passes have met, whatever the traffic does.  Here the same
listener counts the same event, on the CPU, over the paths that traffic
takes rarely: each family's replica is brought up, warmed as the
benchmark warms it, and then driven through all of them.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
import torchdistx_tpu.config as tdx_config  # noqa: E402
import torchdistx_tpu.serve.engine as engine_mod  # noqa: E402
from torchdistx_tpu import compile_service, observe  # noqa: E402
from torchdistx_tpu.serve import (Request, ServeConfig,  # noqa: E402
                                  spin_up_replica)

VOCAB = 256
# preset, the replica's shape, whether the cell's mix chunks (the warm-up
# then serves one chunked prompt).  Pools so small that four long lanes
# do not fit: the drive preempts by itself.  The llama and gpt2 replicas
# speculate and share prefixes (the defaults, and Mistral's cells'); the
# other two families refuse both.
FAMILIES = {
    "llama": ("tiny", ServeConfig(
        max_batch=4, page_size=8, n_pages=20, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefill_chunk=16), True),
    "gpt2": ("tiny-gpt2", ServeConfig(
        max_batch=4, page_size=8, n_pages=20, max_pages_per_seq=8,
        prefill_buckets=(8, 16)), False),
    "jamba": ("tiny-jamba", ServeConfig(
        max_batch=4, page_size=8, n_pages=20, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefix_cache=False, spec_decode=False),
        False),
    "afmoe": ("tiny-afmoe", ServeConfig(
        max_batch=4, page_size=8, n_pages=24, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefill_chunk=16, prefix_cache=False,
        spec_decode=False), True),
    "olmo_hybrid": ("tiny-olmo-hybrid", ServeConfig(
        max_batch=4, page_size=8, n_pages=20, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefix_cache=False, spec_decode=False),
        False),
    "pangu_ultra_moe": ("tiny-pangu-ultra-moe", ServeConfig(
        max_batch=4, page_size=8, n_pages=20, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefill_chunk=16, prefix_cache=False,
        spec_decode=False), True),
}


@pytest.fixture(scope="module")
def compiles(tmp_path_factory):
    """The benchmark's own counter, with jax's persistent cache bound to a
    directory of this module's (the event is recorded only for a process
    that uses the cache; the suite runs without one)."""
    from jax._src import monitoring

    cache = tmp_path_factory.mktemp("jax_cache")
    c = harness.Compiles()
    with tdx_config.override(cache_dir=str(cache)):
        compile_service.reset_cache_binding()
        compile_service.bind_cache()
        c.install()
        listener = monitoring.get_event_listeners()[-1]
        try:
            yield c
        finally:
            jax.monitoring.unregister_event_listener(listener)
    compile_service.reset_cache_binding()


def _ids(rng, n):
    return [int(t) for t in rng.integers(0, VOCAB, size=n)]


def _zeros_pass(eng):
    """The benchmark's first pass (``benchmark/kinds/serve.py``,
    ``serve_hybrid.py``): every compiled shape once, on zeros, handed the
    engine's weights, pools and state, which the engine takes back from
    the outputs (the programs are donated them)."""
    for name, spec in eng._all_specs().items():
        if name == "cow":
            head = [eng.k_pages, eng.v_pages]
        else:
            head = [eng.params, eng.k_pages, eng.v_pages, *eng.state]
        rest = [jnp.zeros(a.shape, a.dtype) for a in spec.args[len(head):]]
        out = eng._programs[name](*head, *rest)
        del head
        if name == "cow":
            eng.k_pages, eng.v_pages = out
        else:
            _, eng.k_pages, eng.v_pages, *state = out
            eng.state = tuple(state)
        jax.block_until_ready(out)


def _requests_pass(eng, chunked, rng):
    """Its second: one request a prefill bucket, and one chunked prompt
    where the mix chunks, three tokens each."""
    lens = list(eng.scfg.prefill_buckets)
    if chunked:
        lens.append(eng.scfg.prefill_chunk + lens[0])
    eng.run([Request(f"warm-{j}", _ids(rng, n), max_new_tokens=3)
             for j, n in enumerate(lens)])
    eng.install_params(eng.params)  # forget the warm-up's prefixes
    eng.results.clear()
    eng.final_logits.clear()


def _steps(eng, reqs, *, after=None, limit=400):
    """Submit ``reqs`` and step until nothing is left; ``after(i)`` runs
    behind step ``i``.  Returns, a step, (verify ticks run, requests
    completed)."""
    for r in reqs:
        eng.submit(r)
    log = []
    for i in range(limit):
        if not (eng.waiting or eng.active):
            break
        v0, d0 = eng.spec_verify_ticks, len(eng.results)
        eng.step()
        log.append((eng.spec_verify_ticks - v0, len(eng.results) - d0))
        if after is not None:
            after(i)
    assert not (eng.waiting or eng.active)
    return log


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def warmed(request, compiles):
    """A replica of the family, brought up and warmed exactly as a cell of
    the benchmark warms it, with the set-up closed behind that."""
    preset, scfg, chunked = FAMILIES[request.param]
    asked = compiles.asked
    eng = spin_up_replica(preset, serve_cfg=scfg)
    assert compiles.asked > asked, "the listener hears no compile request"
    rng = np.random.default_rng(36)
    _zeros_pass(eng)
    merge, merged = engine_mod._merge, []
    engine_mod._merge = lambda *a: merged.append(1) or merge(*a)
    try:
        _requests_pass(eng, chunked, rng)
    finally:
        engine_mod._merge = merge
    eng.warm_merges = len(merged)  # token merges the warm-up made
    compiles.close_setup()
    yield request.param, eng, rng
    jax.clear_caches()  # many bring-ups in one process (the verify skill)


def test_no_rare_path_asks_for_a_program_after_the_warm_up(warmed, compiles):
    family, eng, rng = warmed
    scfg = eng.scfg
    small, big = scfg.prefill_buckets[0], scfg.prefill_buckets[-1]
    cap = scfg.max_context
    preempted = observe.counter("tdx.serve.preempted_requests")
    recomputed = observe.counter("tdx.serve.recomputed_tokens")
    rows = observe.counter("tdx.serve.logit_rows_fetched")
    rows0 = rows.value

    # Retirements in decode ticks: four lanes in one tick, then two and
    # one (the slots that free first are taken first, so the lanes that
    # retire sit in different slots from round to round).
    log = _steps(eng, [Request(f"four-{i}", _ids(rng, 3 + i), max_new_tokens=4)
                       for i in range(4)])
    assert max(done for _, done in log) == 4
    log = _steps(eng, [Request(f"mixed-{i}", _ids(rng, 5), max_new_tokens=n)
                       for i, n in enumerate((3, 3, 5, 7, 2, 6))])
    assert {done for _, done in log} & {1, 2, 3}

    # ... on eos_id: the third token of a request served before.
    prompt = _ids(rng, 6)
    _steps(eng, [Request("eos-probe", prompt, max_new_tokens=6)])
    third = eng.results["eos-probe"][2]
    _steps(eng, [Request("eos", prompt, max_new_tokens=6, eos_id=third)])
    assert len(eng.results["eos"]) <= 3 and eng.results["eos"][-1] == third

    # ... at max_context (a request that reached the queue past submit's
    # check, as one requeued from a replica with a longer context would).
    late = Request("cap", _ids(rng, cap - 4), max_new_tokens=12)
    late._submit_t = 0.0
    eng.waiting.append(late)
    _steps(eng, [])
    assert len(eng.results["cap"]) == 5

    # ... on a prefill's first token, one-shot and at a chunked prompt's end.
    _steps(eng, [Request("one", _ids(rng, small), max_new_tokens=1),
                 Request("one-chunked", _ids(rng, big + 3), max_new_tokens=1)])
    assert len(eng.results["one"]) == len(eng.results["one-chunked"]) == 1

    # Chunked prompts whose last chunk falls in every bucket, and one of
    # three chunks.
    chunk = eng._chunk_cap()
    _steps(eng, [Request(f"rem-{b}", _ids(rng, chunk + b), max_new_tokens=2)
                 for b in scfg.prefill_buckets if chunk + b + 2 <= cap]
           + [Request("three", _ids(rng, 2 * chunk + 1), max_new_tokens=2)])

    # A prompt that repeats itself, so that the drafter proposes and a
    # verify tick runs, with a retirement inside one.
    if eng._drafter is not None:
        ticks = eng.spec_verify_ticks
        period = _ids(rng, 4)
        log = _steps(eng, [Request(f"echo-{i}", period * 3, max_new_tokens=n)
                           for i, n in enumerate((6, 9, 12, 20))])
        assert eng.spec_verify_ticks > ticks
        assert any(v and done for v, done in log), log

    # Preemption and the second prefill: long lanes that the pool cannot
    # hold together, and every active lane sent back once besides.
    before = preempted.value, recomputed.value
    n_new = cap - big - 1

    def flap(i):
        if i == 2:
            eng.requeue_active(reason="pages")

    _steps(eng, [Request(f"long-{i}", _ids(rng, big - i), max_new_tokens=n_new)
                 for i in range(4)], after=flap)
    assert preempted.value - before[0] >= 4
    assert recomputed.value - before[1] > 0

    # The open loop's idle steps.
    for _ in range(3):
        eng.step()

    assert rows.value > rows0  # the drive retired lanes in plain ticks
    assert compiles.in_window() == 0, (
        f"{family}: {compiles.in_window()} program(s) first asked of the "
        f"compile cache after the warm-up")


def test_the_warm_up_meets_the_token_merge_of_a_tick_dispatched_ahead(
        warmed, compiles):
    """A replica that decodes ahead makes a tick's input tokens on the
    device from the tick before (``engine._merge``): the warm-up's
    requests of three tokens each run a tick dispatched while the one
    before is unread, so the merge is met there, and a drive of many
    such ticks asks the compile cache for nothing."""
    family, eng, rng = warmed
    if not eng._decodes_ahead():
        assert eng.warm_merges == 0
        return
    assert eng.warm_merges >= 1
    _steps(eng, [Request(f"merge-{i}", _ids(rng, 4 + i), max_new_tokens=9)
                 for i in range(3)])
    assert compiles.in_window() == 0


@pytest.mark.parametrize("how", ["a_count_met", "a_new_count"])
def test_the_listener_hears_a_fetch_whose_shape_follows_the_traffic(
        compiles, how):
    """The control: rows taken with an index array compile once for every
    count of rows, which is what the rule forbids and what the test above
    would not pass over."""
    x = jnp.ones((8, 100), jnp.float32) * 2.0
    np.asarray(x[np.arange(2)])
    compiles.close_setup()
    if how == "a_count_met":
        np.asarray(x[np.arange(2)])  # met: nothing new
        assert compiles.in_window() == 0
    else:
        np.asarray(x[np.arange(3)])
        assert compiles.in_window() > 0
