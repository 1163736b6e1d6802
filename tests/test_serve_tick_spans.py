"""The named moments of a serving tick (docs/observability.md): a program
call's ``serve.program`` is tiled by its ``serve.program.launch`` and
``serve.program.wait`` children; a traced tick makes the same device
calls and host waits as an untraced one; the deadline sweep and the gauge
refresh have spans of their own; and what ``serve.step`` names no child
for is a small part of it.  One replica a family for the whole file."""

import jax
import numpy as np
import pytest

import torchdistx_tpu.serve.engine as engine_mod
from torchdistx_tpu import observe
from torchdistx_tpu.serve import Request, ServeConfig, spin_up_replica

VOCAB = 256
FAMILIES = {
    "llama": ("tiny", ServeConfig(
        max_batch=4, page_size=8, n_pages=40, max_pages_per_seq=8,
        prefill_buckets=(8, 16), prefill_chunk=16, spec_decode=False)),
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
# Host waits of a plain decode tick: the tokens; the afmoe family first
# waits for the held experts' pair counts, between decode and the choice.
TICK_WAITS = {"llama": 1, "jamba": 1, "olmo_hybrid": 1, "afmoe": 2}


def _ids(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, VOCAB, n)]


@pytest.fixture(scope="module")
def replicas():
    made = {}

    def get(family):
        if family not in made:
            preset, scfg = FAMILIES[family]
            made[family] = spin_up_replica(preset, serve_cfg=scfg)
        return made[family]

    yield get
    made.clear()
    jax.clear_caches()


@pytest.fixture()
def telemetry():
    observe.reset()
    observe.enable(True)
    try:
        yield
    finally:
        observe.enable(None)
        observe.reset()


def _spans(name, events=None):
    events = list(observe.tracer().events) if events is None else events
    return [e for e in events if e["ph"] == "X" and e["name"] == name]


def _inside(outer, events):
    end = outer["ts"] + outer["dur"] + 1.0
    return sorted((e for e in events if outer["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= end), key=lambda e: e["ts"])


def _traffic(eng, tag, step=7):
    """Three requests; with the default ``step`` the longest prompt (19)
    is chunked where the family chunks at 16."""
    return [Request(f"{tag}-{i}", _ids(10 + i, 5 + step * i),
                    max_new_tokens=5) for i in range(3)]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_launch_and_wait_tile_the_program_call(replicas, telemetry, family):
    eng = replicas(family)
    eng.run(_traffic(eng, "tile"))
    events = [e for e in observe.tracer().events if e["ph"] == "X"]
    calls = _spans("serve.program", events)
    parts = [e for e in events if e["name"] in (
        "serve.program.launch", "serve.program.wait")]
    assert calls
    ahead = eng._decodes_ahead()
    covered = total = 0.0
    for call in calls:
        kids = _inside(call, parts)
        names = [(e["name"].rsplit(".", 1)[1], e["args"].get("call"))
                 for e in kids]
        # The order the host runs them in: the program's dispatch first, a
        # wait last; a plain tick's choice dispatched before its wait.  A
        # call read later (a replica that decodes ahead) makes every
        # launch before any wait, and a tick's span holds at most the wait
        # for the tick before it.
        kinds = [k for k, _ in names]
        assert names[0] == ("launch", "program")
        assert kinds == sorted(kinds) if ahead else kinds[-1] == "wait"
        if call["args"]["program"] == "decode":
            assert ("launch", "greedy") in names
            assert ("ahead" in call["args"]) == ahead
            if ahead:
                assert kinds.count("wait") == call["args"]["ahead"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0  # none overlaps
        assert {e["args"]["program"] for e in kids} == {
            call["args"]["program"]}
        covered += sum(e["dur"] for e in kids)
        total += call["dur"]
    assert covered <= total + len(calls)
    assert covered >= 0.98 * total, (covered, total)
    # Each call dispatched ahead is waited for once, later: every plain
    # tick, and the last row of each of the three prompts.
    later = [e for e in parts if e["args"].get("ahead") == 1]
    assert all(e["name"] == "serve.program.wait" for e in later)
    decodes = sum(c["args"]["program"] == "decode" for c in calls)
    assert len(later) == ((decodes + 3) if ahead else 0)


def _record(monkeypatch, eng):
    """Every device call the engine makes and every host wait, in order:
    ``("call", name)``; ``("wait", n)`` where the host syncs on an array
    it has not synced on before (a copy of one that is ready waits for
    nothing)."""
    seen, log = [], []

    def wait(a):
        if isinstance(a, jax.Array) and not any(a is s for s in seen):
            seen.append(a)
            log.append(("wait", len(seen)))

    def calls(name, fn):
        def call(*args, **kw):
            log.append(("call", name))
            return fn(*args, **kw)
        return call

    class Spy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *args, **kw):
            wait(a)
            return np.asarray(a, *args, **kw)

    block = jax.block_until_ready

    def block_until_ready(x):
        jax.tree.map(wait, x)
        return block(x)

    monkeypatch.setattr(engine_mod, "np", Spy())
    monkeypatch.setattr(jax, "block_until_ready", block_until_ready)
    monkeypatch.setattr(engine_mod, "_greedy",
                        calls("greedy", engine_mod._greedy))
    monkeypatch.setattr(engine_mod, "_merge",
                        calls("merge", engine_mod._merge))
    monkeypatch.setattr(engine_mod, "_row", calls("row", engine_mod._row))
    monkeypatch.setattr(eng, "_programs", {
        n: calls(n, p) for n, p in eng._programs.items()})
    return log


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_traced_tick_waits_where_an_untraced_one_does(
        replicas, monkeypatch, family):
    """One plain decode tick untraced and one traced, on the same lanes:
    the same device calls in the same order, the host waiting at the same
    places, as often (``TICK_WAITS``).  A replica that decodes ahead makes
    its tick's inputs from the tick before (``merge``) and waits once, for
    that tick's tokens."""
    eng = replicas(family)
    programs = dict(eng._programs)
    eng.run([])
    for r in _traffic(eng, f"wait-{family}", step=4):
        r.max_new_tokens = 8  # no lane retires in the two ticks below
        eng.submit(r)
    eng.step()  # admission and prefills
    assert len(eng.active) == 3 and not eng.waiting
    ahead = eng._decodes_ahead()
    if ahead:
        eng.step()  # the first tick, with none in flight before it
    ticks = {}
    try:
        for traced in (False, True):
            observe.enable(traced)
            with monkeypatch.context() as m:
                log = _record(m, eng)
                eng.step()
            ticks[traced] = log
    finally:
        observe.enable(None)
        eng._programs = programs
    want = [("call", "merge")] if ahead else []
    want.append(("call", "decode"))
    if family == "afmoe":
        want.append(("wait", 1))
    want += [("call", "greedy"), ("wait", TICK_WAITS[family])]
    assert ticks[False] == ticks[True] == want
    eng.run()


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("family", ["jamba", "llama", "olmo_hybrid"])
def test_a_tick_dispatched_ahead_is_waited_for_once_in_the_next_step(
        replicas, monkeypatch, family, traced):
    """Over four steps of a replica that decodes ahead, the host's one wait
    in each step is for the tokens of the tick dispatched in the step
    before, after this step's tick was dispatched; a tick is never waited
    for in the step that dispatched it."""
    eng = replicas(family)
    assert eng._decodes_ahead()
    eng.run([])
    for r in _traffic(eng, f"next-{family}", step=4):
        r.max_new_tokens = 9
        eng.submit(r)
    eng.step()  # admission and prefills
    made, log = [], []
    greedy, block = engine_mod._greedy, jax.block_until_ready

    def choose(logits):
        out = greedy(logits)
        made.append(out)
        log.append(("tick", len(made) - 1))
        return out

    def wait(a):
        for i, m in enumerate(made):
            if a is m and ("wait", i) not in log:
                log.append(("wait", i))

    class Spy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *args, **kw):
            wait(a)
            return np.asarray(a, *args, **kw)

    def block_until_ready(x):
        jax.tree.map(wait, x)
        return block(x)

    observe.enable(traced)
    try:
        with monkeypatch.context() as m:
            m.setattr(engine_mod, "np", Spy())
            m.setattr(jax, "block_until_ready", block_until_ready)
            m.setattr(engine_mod, "_greedy", choose)
            steps = []
            for _ in range(4):
                n0 = len(log)
                eng.step()
                steps.append(log[n0:])
    finally:
        observe.enable(None)
    assert steps == [[("tick", 0)]] + [
        [("tick", i), ("wait", i - 1)] for i in range(1, 4)]
    eng.run()


def test_the_deadline_sweep_has_a_span_and_counts_what_it_walked(
        replicas, telemetry):
    eng = replicas("llama")
    reqs = [Request(f"dl-{i}", _ids(40 + i, 6), max_new_tokens=3)
            for i in range(7)]
    for r in reqs:
        eng.submit(r)
    eng.step()  # four lanes admitted, three requests wait
    n0 = len(observe.tracer().events)
    active, waiting = len(eng.active), len(eng.waiting)
    eng.step()
    sweep = _spans("serve.admit.deadlines",
                   list(observe.tracer().events)[n0:])
    assert [e["args"]["scanned"] for e in sweep] == [active + waiting] == [7]
    admit = _spans("serve.admit", list(observe.tracer().events)[n0:])[0]
    assert _inside(admit, sweep) == sweep  # a child of serve.admit
    eng.run()


def test_the_step_names_nearly_all_of_itself(replicas, telemetry):
    """Over a run's decode ticks ``serve.step``'s own time (what no child
    span covers) is under a tenth of the step: the program call, the
    tables, the fetch, the emit, admission and the gauges are named."""
    eng = replicas("llama")
    eng.run([Request(f"self-{i}", _ids(60 + i, 5), max_new_tokens=24)
             for i in range(4)])
    events = [e for e in observe.tracer().events if e["ph"] == "X"]
    calls = _spans("serve.program", events)
    steps = [s for s in _spans("serve.step", events)
             if [c["args"]["program"] for c in _inside(s, calls)]
             == ["decode"]]
    assert len(steps) >= 15
    own = sum(s["args"]["self_us"] for s in steps)
    assert own < 0.10 * sum(s["dur"] for s in steps)
    names = {e["name"] for s in steps for e in _inside(s, events)}
    assert {"serve.admit", "serve.admit.deadlines", "serve.tick.tables",
            "serve.program", "serve.program.launch", "serve.program.wait",
            "serve.tick.d2h", "serve.tick.emit", "serve.gauges"} <= names


GAUGES = {
    "tdx.serve.kv_pages_in_use": lambda kv: kv.pages_in_use,
    "tdx.serve.kv_pages_free": lambda kv: kv.free_pages,
    "tdx.serve.kv_pages_shared": lambda kv: kv.shared_pages,
    "tdx.serve.kv_pool_pages": lambda kv: kv.cfg.usable_pages,
    "tdx.serve.kv_occupancy": lambda kv: round(kv.occupancy(), 4),
}
STATE_GAUGES = {
    "tdx.serve.state_slots_in_use": lambda kv: kv.state_slots_in_use,
    "tdx.serve.state_slots_peak": lambda kv: kv.state_slots_peak,
}
WINDOW_GAUGES = {
    "tdx.serve.window_pages_in_use": lambda kv: kv.window_pages_in_use,
    "tdx.serve.window_pages_peak": lambda kv: kv.window_pages_peak,
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_pool_gauges_read_the_allocator_after_every_step(
        replicas, telemetry, family):
    """Published once a step, under ``serve.gauges``, and not on each of
    the allocator's transitions: after every step each gauge reads the
    pool's state, and each is set once in the step."""
    eng = replicas(family)
    gauges = dict(GAUGES)
    if eng.kv.cfg.state is not None:
        gauges.update(STATE_GAUGES)
    if eng.kv.cfg.window is not None:
        gauges.update(WINDOW_GAUGES)
    for r in _traffic(eng, f"g-{family}"):
        eng.submit(r)
    steps = 0
    while eng.waiting or eng.active:
        n0 = len(observe.tracer().events)
        eng.step()
        steps += 1
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "gauge"}
        for name, read in gauges.items():
            assert snap[name] == read(eng.kv), (name, steps)
        samples = [e["name"] for e in list(observe.tracer().events)[n0:]
                   if e["ph"] == "C" and e["name"] in gauges]
        assert sorted(samples) == sorted(gauges)
    assert steps > 3
