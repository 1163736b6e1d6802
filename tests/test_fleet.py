"""Serve-fleet tests (ISSUE 14 tentpole): the multi-replica router +
autoscaler preserves the single-engine oracle contract — token-exact
output through storms, staggered arrivals, replica chaos-kills, and
scale-up/scale-down transitions — while the router stays fair, the
admission queue rejects typed, the autoscaler doesn't flap, drains
complete in-flight work bitwise, and a registry-warm scale-up performs
zero local compiles."""

import shutil
import tempfile
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import chaos, observe
from torchdistx_tpu import compile_service
from torchdistx_tpu.models import TransformerConfig
from torchdistx_tpu.serve import (
    AdmissionQueue,
    Autoscaler,
    FleetConfig,
    FleetRejected,
    Request,
    ServeConfig,
    ServeFleet,
    least_outstanding,
    oracle_generate,
    warm_serving,
)

LLAMA = TransformerConfig(
    vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
)
SCFG = ServeConfig(max_batch=2, page_size=8, n_pages=16,
                   max_pages_per_seq=3, prefill_buckets=(8, 16))


@pytest.fixture(scope="module")
def shared_cache(tmp_path_factory):
    """One persistent compile cache for every fleet in this module: the
    first replica compiles the tiny program set, every later replica
    (and every later test) loads it — fleet tests measure fleet
    behavior, not compile time."""
    d = str(tmp_path_factory.mktemp("fleet_cache"))
    import os

    old = os.environ.get("TDX_CACHE_MIN_COMPILE_S")
    os.environ["TDX_CACHE_MIN_COMPILE_S"] = "0"
    yield d
    if old is None:
        os.environ.pop("TDX_CACHE_MIN_COMPILE_S", None)
    else:
        os.environ["TDX_CACHE_MIN_COMPILE_S"] = old


def _check_oracle(fl, reqs, out):
    for r in reqs:
        want, want_logits = oracle_generate(
            fl.family, fl.cfg, fl.params, r.tokens, r.max_new_tokens,
            r.eos_id,
        )
        assert out[r.rid] == want, (r.rid, out[r.rid], want)
        np.testing.assert_allclose(
            fl.final_logits[r.rid], want_logits, atol=1e-4,
            err_msg=f"final logits of {r.rid}",
        )


# ---------------------------------------------------------------------------
# router (pure)
# ---------------------------------------------------------------------------


def test_least_outstanding_routes_by_work_not_count():
    """Fairness under skewed lanes: one 64-token generation must weigh
    more than two 2-token pings — dispatch follows remaining budget."""
    loads = {"a": 64, "b": 4, "c": 9}
    assert least_outstanding(["a", "b", "c"], loads.get) == "b"
    # ties break by listing order (deterministic under test)
    assert least_outstanding(["a", "b"], lambda h: 7) == "a"
    assert least_outstanding([], lambda h: 0) is None


def test_admission_queue_bound_deadline_and_requeue_priority():
    q = AdmissionQueue(max_depth=2)
    q.push(Request("a", [1], max_new_tokens=1))
    q.push(Request("b", [1], max_new_tokens=1))
    with pytest.raises(FleetRejected) as ei:
        q.push(Request("c", [1], max_new_tokens=1))
    assert ei.value.rejection.reason == "queue_full"
    # requeues are exempt from the bound and jump the line
    q.requeue(Request("dead", [1], max_new_tokens=1))
    assert q.depth() == 3
    assert q.pop().req.rid == "dead"
    # a queued entry past its deadline is expired with a typed rejection;
    # the unexpired survivors still pop in FIFO order
    q2 = AdmissionQueue(max_depth=8)
    q2.push(Request("d", [1], max_new_tokens=1), deadline_s=0.001, now=0.0)
    q2.push(Request("e", [1], max_new_tokens=1), now=0.0)
    rejs = q2.expire(now=1.0)
    assert [(r.rid, r.reason) for r in rejs] == [("d", "deadline")]
    assert q2.pop().req.rid == "e"
    assert q2.pop() is None
    # the original queue kept its FIFO intact
    assert q.pop().req.rid == "a"
    assert q.pop().req.rid == "b"
    assert q.pop() is None


# ---------------------------------------------------------------------------
# autoscaler (pure)
# ---------------------------------------------------------------------------


def test_autoscaler_hysteresis_no_flap_on_step_load():
    """A step load change produces exactly one scale-up (streak +
    cooldown), and brief idle dips never drain a replica."""
    fc = FleetConfig(min_replicas=1, max_replicas=4,
                     up_queue_per_replica=2.0, up_consecutive=3,
                     down_consecutive=4, cooldown_s=10.0)
    a = Autoscaler(fc)

    def busy(now, serving, total):
        return a.decide(now=now, queued=10, outstanding=30,
                        serving=serving, total=total)

    assert busy(1.0, 1, 1) is None        # pressure streak 1
    assert busy(2.0, 1, 1) is None        # streak 2
    assert busy(3.0, 1, 1) == "up"        # streak 3 → fire once
    # the step persists but cooldown holds: no flapping
    assert busy(4.0, 2, 2) is None
    assert busy(5.0, 2, 2) is None
    assert busy(6.0, 2, 2) is None
    # past cooldown, SUSTAINED pressure may fire again
    assert busy(14.0, 2, 2) == "up"

    idle = Autoscaler(FleetConfig(min_replicas=1, down_consecutive=4,
                                  cooldown_s=0.0))

    def quiet(now):
        return idle.decide(now=now, queued=0, outstanding=0,
                           serving=2, total=2)

    assert quiet(1.0) is None
    assert quiet(2.0) is None
    assert quiet(3.0) is None
    # one busy tick resets the idle streak — a dip is not a trend
    assert idle.decide(now=4.0, queued=1, outstanding=5,
                       serving=2, total=2) is None
    assert quiet(5.0) is None
    assert quiet(6.0) is None
    assert quiet(7.0) is None
    assert quiet(8.0) == "down"
    # never below the floor / the last replica
    floor = Autoscaler(FleetConfig(min_replicas=1, down_consecutive=1,
                                   cooldown_s=0.0))
    assert floor.decide(now=1.0, queued=0, outstanding=0,
                        serving=1, total=1) is None


def test_autoscaler_backfills_below_floor_even_with_autoscale_off():
    a = Autoscaler(FleetConfig(min_replicas=2, autoscale=False))
    assert a.decide(now=0.0, queued=0, outstanding=0,
                    serving=1, total=1) == "up"
    assert a.decide(now=0.0, queued=99, outstanding=99,
                    serving=2, total=2) is None  # autoscale off


# ---------------------------------------------------------------------------
# health aggregation (pure)
# ---------------------------------------------------------------------------


def test_readyz_fleet_aggregation():
    """fleet/* components aggregate: ready iff ≥1 replica serving, with
    the per-replica states in the probe body."""
    from torchdistx_tpu.observe import health

    health.reset()
    try:
        health.set_state("fleet/r1", "spin_up")
        health.set_state("fleet/r2", "launching")
        ok, detail = health.readiness()
        assert not ok
        assert detail["fleet"]["serving"] == 0
        assert set(detail["fleet"]["replicas"]) == {"r1", "r2"}
        health.set_state("fleet/r2", "serving")
        ok, detail = health.readiness()
        assert ok  # one serving replica is enough
        assert detail["fleet"]["serving"] == 1
        # a non-fleet component still gates individually
        health.set_state("serve", "warming")
        ok, _ = health.readiness()
        assert not ok
        health.clear_state("serve")
        ok, _ = health.readiness()
        assert ok
        # clearing the serving replica flips the fleet back to 503
        health.clear_state("fleet/r2")
        ok, detail = health.readiness()
        assert not ok and detail["not_ready"] == {"fleet": "no replica serving"}
    finally:
        health.reset()


# ---------------------------------------------------------------------------
# the fleet itself
# ---------------------------------------------------------------------------


def _fleet(shared_cache, **fc_kw):
    fc_kw.setdefault("stall_s", 60.0)
    return ServeFleet(LLAMA, family="llama", serve_cfg=SCFG,
                      fleet_cfg=FleetConfig(**fc_kw))


def test_fleet_storm_matches_oracle_across_scale_transitions(shared_cache):
    """The acceptance pin: a staggered storm over 2 replicas with ≥1
    chaos replica-kill, ≥1 scale-up, and ≥1 drain DURING the run — every
    response still equals the unbatched oracle, and the dead replica's
    requests were requeued, not dropped."""
    observe.enable(True)
    try:
        with tdx_config.override(cache_dir=shared_cache):
            with _fleet(shared_cache, min_replicas=1, max_replicas=4,
                        autoscale=False) as fl:
                fl.start(2, timeout=240.0)
                chaos.install("fleet@2=raise")
                reqs = [
                    Request(f"s{i}", [(5 * i + j) % 128 for j in
                                      range(2 + i % 6)],
                            max_new_tokens=4 + (i % 5), arrival_step=i)
                    for i in range(12)
                ]
                did_up = did_down = False
                i = 0
                deadline = time.monotonic() + 240.0
                while i < len(reqs) or fl._pending:
                    while (i < len(reqs)
                           and reqs[i].arrival_step <= fl._tick_no):
                        fl.submit(reqs[i])
                        i += 1
                    fl.tick()
                    serving = sum(1 for h in fl.handles
                                  if h.state == "serving")
                    if not did_up and i >= 6:
                        fl.scale_up()        # ≥1 scale-up mid-run
                        did_up = True
                    if did_up and not did_down and serving > 1 and i >= 10:
                        fl.scale_down()      # ≥1 drain mid-run
                        did_down = True
                    assert time.monotonic() < deadline, (
                        fl._pending, [h.state for h in fl.handles])
                    time.sleep(0.001)
                assert did_up and did_down
                out = dict(fl.results)
                assert set(out) == {r.rid for r in reqs}
                assert not fl.rejected
                _check_oracle(fl, reqs, out)
                snap = {r["name"]: r["value"]
                        for r in observe.counters().snapshot()
                        if r["type"] == "counter"}
                # the chaos kill requeued its mid-batch work
                assert snap.get("tdx.fleet.requeued_requests", 0) >= 1
                assert snap.get("tdx.fleet.scale_ups", 0) >= 3
                assert snap.get("tdx.fleet.scale_downs", 0) >= 1
    finally:
        chaos.clear()
        observe.enable(None)
        observe.health.reset()


@pytest.mark.parametrize("kind", ["raise", "preempt"])
def test_chaos_kill_requeues_onto_survivor(shared_cache, kind):
    """The fleet chaos site kills replica 2 mid-batch (raise = device
    loss, preempt = replica-thread preemption); the survivor regenerates
    every requeued request identically."""
    with tdx_config.override(cache_dir=shared_cache):
        with _fleet(shared_cache, min_replicas=1, max_replicas=2,
                    autoscale=False) as fl:
            fl.start(2, timeout=240.0)
            chaos.install(f"fleet@2={kind}")
            try:
                reqs = [Request(f"k{i}", [3 + i, 7, (11 * i) % 128],
                                max_new_tokens=5, arrival_step=i)
                        for i in range(8)]
                out = fl.run(reqs, max_seconds=240.0)
            finally:
                chaos.clear()
            assert set(out) == {r.rid for r in reqs}
            _check_oracle(fl, reqs, out)
            # replica 2 is gone; the survivor (plus backfill) served
            assert all(h.idx != 2 for h in fl.handles)


def test_drain_completes_inflight_bitwise(shared_cache):
    """Scale-down drains: the draining replica finishes its in-flight
    lanes (bitwise vs oracle), hands back unadmitted work, then frees
    its KV pool."""
    with tdx_config.override(cache_dir=shared_cache):
        with _fleet(shared_cache, min_replicas=1, max_replicas=2,
                    autoscale=False) as fl:
            fl.start(2, timeout=240.0)
            reqs = [Request(f"d{i}", [9 + i, 2, 5], max_new_tokens=12)
                    for i in range(4)]
            for r in reqs:
                fl.submit(r)
            # tick until the fleet actually has lanes in flight
            deadline = time.monotonic() + 60.0
            while not any(h.engine is not None and h.engine.active
                          for h in fl.handles):
                fl.tick()
                assert time.monotonic() < deadline
                time.sleep(0.001)
            h = fl.scale_down()
            assert h is not None
            inflight = {ln.req.rid for ln in list(h.engine.active.values())}
            out = fl.run(max_seconds=240.0)
            assert set(out) == {r.rid for r in reqs}
            _check_oracle(fl, reqs, out)
            # run() returns when the last REQUEST completes, which can
            # beat the victim's drain transition — keep ticking until
            # the controller reaps the drained handle.
            deadline = time.monotonic() + 60.0
            while any(x is h for x in fl.handles):
                fl.tick()
                assert time.monotonic() < deadline, h.state
                time.sleep(0.001)
            assert h.state == "drained"
            assert h.engine.k_pages is None and h.engine.v_pages is None
            # whatever was in flight at drain time completed
            assert inflight <= set(out)


def test_rejection_paths_are_typed_and_counted(shared_cache):
    """Every rejection is typed, recorded, and counted: invalid at the
    door, queue_full at the bound, deadline in the queue."""
    observe.enable(True)
    try:
        fl = ServeFleet(LLAMA, family="llama", serve_cfg=SCFG,
                        fleet_cfg=FleetConfig(min_replicas=0, max_queue=2,
                                              autoscale=False))
        with pytest.raises(FleetRejected) as ei:
            fl.submit(Request("bad", [], max_new_tokens=4))
        assert ei.value.rejection.reason == "invalid"
        # 20 tokens > the largest bucket now serves (chunked prefill);
        # only max_context (3 pages * 8) rejects at the door.
        with pytest.raises(FleetRejected) as ei:
            fl.submit(Request("huge", [1] * 23, max_new_tokens=2))
        assert "max_context" in ei.value.rejection.detail
        fl.submit(Request("q1", [1, 2], max_new_tokens=2))
        fl.submit(Request("q2", [1, 2], max_new_tokens=2))
        with pytest.raises(FleetRejected) as ei:
            fl.submit(Request("q3", [1, 2], max_new_tokens=2))
        assert ei.value.rejection.reason == "queue_full"
        # deadline: no replica will ever pick these up
        fl.queue.drain()
        fl._pending.clear()
        fl.submit(Request("late", [1, 2], max_new_tokens=2),
                  deadline_s=0.001)
        time.sleep(0.02)
        fl.tick()
        assert fl.rejected["late"].reason == "deadline"
        assert {r.reason for r in fl.rejected.values()} == {
            "invalid", "queue_full", "deadline"}
        total = sum(r["value"] for r in observe.counters().snapshot()
                    if r["name"] == "tdx.fleet.rejected_requests")
        assert total >= 4
    finally:
        observe.enable(None)
        observe.health.reset()


def test_hang_stall_declares_replica_dead_and_requeues(shared_cache):
    """A hung replica (chaos ``fleet@1=hang``) stops heartbeating; after
    ``stall_s`` the controller declares it dead, requeues its work onto
    the backfilled replica, and output stays oracle-exact."""
    with tdx_config.override(cache_dir=shared_cache):
        with _fleet(shared_cache, min_replicas=1, max_replicas=2,
                    autoscale=False, stall_s=0.5) as fl:
            fl.start(1, timeout=240.0)
            chaos.install("fleet@1=hang:3600")
            try:
                reqs = [Request(f"h{i}", [2 + i, 4, 6], max_new_tokens=4)
                        for i in range(3)]
                out = fl.run(reqs, max_seconds=240.0)
            finally:
                chaos.clear()
            assert set(out) == {r.rid for r in reqs}
            _check_oracle(fl, reqs, out)
            # the hung r1 was reaped; the backfill served the storm
            assert all(h.idx != 1 for h in fl.handles)


@pytest.mark.slow  # ~15 s of compiles; `make chaos-test` + fleet-smoke run it
def test_scale_up_is_registry_warm_zero_local_compiles(shared_cache):
    """The autoscaling bring-up contract, fleet edition: with a warmed
    registry and a FRESH local cache, every replica the fleet adds —
    initial start and mid-run scale-up — performs ZERO local compiles
    (every program a registry fetch) and still serves oracle-exact."""
    reg = tempfile.mkdtemp(prefix="tdx_fleet_reg_")
    warm_cache = tempfile.mkdtemp(prefix="tdx_fleet_ca_")
    fresh_cache = tempfile.mkdtemp(prefix="tdx_fleet_cb_")
    observe.enable(True)
    try:
        summary = warm_serving("llama", LLAMA, warm_cache,
                               registry_dir=reg, serve_cfg=SCFG)
        assert not summary["unwarmed"], summary
        compile_service.reset_cache_binding()
        base = {r["name"]: r["value"]
                for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        with tdx_config.override(cache_dir=fresh_cache, registry_dir=reg):
            with ServeFleet(
                LLAMA, family="llama", serve_cfg=SCFG,
                fleet_cfg=FleetConfig(min_replicas=1, max_replicas=2,
                                      autoscale=False),
            ) as fl:
                fl.start(1, timeout=240.0)
                h2 = fl.scale_up(wait=True, timeout=240.0)
                assert h2.bring_up_warm, h2.engine.bring_up_outcomes
                assert set(h2.engine.bring_up_outcomes.values()) == {"hit"}
                snap = {r["name"]: r["value"]
                        for r in observe.counters().snapshot()
                        if r["type"] == "counter"}
                miss = (snap.get("tdx.jax.compile_cache_miss", 0)
                        - base.get("tdx.jax.compile_cache_miss", 0))
                assert miss == 0, [x.engine.bring_up_outcomes
                                   for x in fl.handles]
                assert all(x.bring_up_warm for x in fl.handles)
                reqs = [Request("w", [11, 22, 33], max_new_tokens=4)]
                out = fl.run(reqs, max_seconds=240.0)
                _check_oracle(fl, reqs, out)
    finally:
        observe.enable(None)
        observe.health.reset()
        compile_service.reset_cache_binding()
        for d in (reg, warm_cache, fresh_cache):
            shutil.rmtree(d, ignore_errors=True)
