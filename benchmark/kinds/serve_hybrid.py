"""The serving kind of cell for a stack with recurrent layers:
``kinds/serve.py``'s runner, statement for statement where it can be (same
stamps, same traced slice, same ``ctx`` keys), with everything that has
the family's shape taken from ONE module that the configuration's file
names (``family_module``: sizes, weights, the adapter into the program's
types, the comparison that decides ``correct``, the FLOPs).  A later
``benchmark`` issue can move ``llama`` and ``gpt2`` onto such modules and
delete the copy (ROADMAP D11).  What this runner adds: the engine's
recurrent-state counters beside its page counters, a program call's live
lanes and prefilled positions a step, and the device time of the events
under the two recurrences' names (``benchmark/ssm_trace.py``).

``spin_up_replica`` -> ``ServeEngine.submit`` /
``step`` on one chip, driven by the mix's schedule from this one thread.

Set-up: the program's own bring-up (deferred_init -> init program ->
materialize -> pools; then the program set from the compile cache), the
benchmark's weights from ``--seed`` put in the program's place
(``install_params``), and one execution of every compiled shape.  Window:
requests are submitted when they are DUE, whatever the engine is doing
(open loop), or all at t = 0 (backlog); every token is stamped as the
engine hands it over.  After the window: outstanding requests are waited
for (a minute at most), the peak is read, the pools are freed, and a
sample of what was served is compared with the plain reference
(``benchmark/check_serve.py``)."""

from __future__ import annotations

import os
import time

TRACE_AFTER_S, TRACE_FOR_S = 2.0, 3.0
DRAIN_S = 60.0


def run(env) -> dict:
    clk = env["clock"]
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu import observe
    from torchdistx_tpu.ops._interpret import interpreted_calls
    from torchdistx_tpu.serve import Request, spin_up_replica

    from benchmark import adapters, harness, ssm_trace, traffic
    from benchmark import tracing as tr

    clk.lap("import")
    compiles = harness.Compiles()
    compiles.install()
    devs = harness.devices(env)
    clk.lap("backend")

    cfg, mix, seed, seconds = env["cfg"], env["mix"], env["seed"], env["seconds"]
    family = cfg["family"]
    fam = harness.load_module(env["root"], cfg["family_module"])
    if fam is None:
        raise harness.Refused(f"no family module {cfg['family_module']!r}")
    c = fam.dims(cfg)
    tcfg = fam.transformer_config(cfg, c)
    scfg = adapters.serve_config(cfg, mix["engine"])
    plan = traffic.serving(mix, seed, seconds, c["vocab_size"])
    if env["trace"]:
        observe.enable(True)  # the request ledger and the engine's counters

    reqs = {r["rid"]: dict(r, submit=None, first=None, last=None, n=0,
                           done=None, tokens_out=[]) for r in plan}

    handed = {"tokens": 0}

    def on_token(rid, token):
        r = reqs.get(rid)
        if r is None:  # a warm-up request
            return
        now = time.perf_counter()
        handed["tokens"] += 1
        if r["first"] is None:
            r["first"] = now
        r["last"] = now
        r["n"] += 1
        r["tokens_out"].append(token)

    def on_complete(rid, tokens, logits):
        if rid in reqs:
            reqs[rid]["done"] = time.perf_counter()

    # The program's bring-up from a fixed key, so that its init program is
    # the same for every --seed; the served weights are the benchmark's.
    try:
        eng = spin_up_replica(
            tcfg, family=family, serve_cfg=scfg, seed=0,
            param_dtype=jnp.bfloat16, warm=False, on_token=on_token,
            on_complete=on_complete)
    except ValueError as e:
        if "unknown decode family" not in str(e):
            raise
        raise harness.Refused(f"this checkout's program cannot serve the "
                              f"{family} family: {e}")
    outcomes = dict(eng.bring_up_outcomes)
    clk.lap("materialize", "spin_up_replica(warm=False): specs, init program, pools")
    for leaf in jax.tree.leaves(eng.params):
        leaf.delete()
    w = fam.make(c, seed, jnp.bfloat16)
    jax.block_until_ready(w)
    eng.install_params(fam.param_tree(w))
    clk.lap("materialize", "weights from the seed (benchmark)")

    outcomes.update(eng.warmup())
    clk.lap("programs")

    # One execution of every compiled shape, on zeros.  A call is given the
    # engine's pools and recurrent state and the engine takes them back from
    # its outputs, as its own call path does: every program of the family
    # returns (logits, k_pages, v_pages, *state).  So the loop is right
    # whether or not a program donates them.  On zero tables a program writes
    # the null page 0, which nothing reads, and the state of slot 0, which
    # the first program of a new sequence starts from zero whatever it held.
    # No local may outlive the loop with a pool or a state in it: it would
    # keep a dead copy on the device for the whole run (``held`` did, 1.19 GB).
    for name, spec in eng._all_specs().items():
        args = [eng.params if i == 0 and name != "cow" else None
                for i in range(len(spec.args))]
        pools = [i for i, a in enumerate(spec.args)
                 if getattr(a, "shape", None) == eng.k_pages.shape]
        held = {a.shape: a for a in eng.state}  # ssm and conv, by shape
        for i, a in enumerate(spec.args):
            if i in pools:
                args[i] = eng.k_pages if i == pools[0] else eng.v_pages
            elif getattr(a, "shape", None) in held:
                args[i] = held[a.shape]
            elif args[i] is None:
                args[i] = jnp.zeros(a.shape, a.dtype)
        out = eng._programs[name](*args)
        del args, held
        _, eng.k_pages, eng.v_pages, *state = out
        eng.state = tuple(state)
        jax.block_until_ready(out)
        del out, state
    clk.lap("warmup", "every compiled shape once, on zeros")
    # ... and the engine's own host path once for every prefill bucket (and
    # a chunked prompt where the mix chunks), so that no small program of
    # its bookkeeping is first met inside the window.
    ids = traffic.rng_for(seed, 5)
    lens = [min(b, mix["prompt"]["max"]) for b in scfg.prefill_buckets]
    if mix["engine"].get("prefill_chunk"):
        lens.append(min(mix["engine"]["prefill_chunk"] + lens[0],
                        mix["prompt"]["max"]))
    eng.run([Request(f"warm-{j}", [int(t) for t in ids.integers(
        0, c["vocab_size"], size=n)], max_new_tokens=3)
        for j, n in enumerate(lens)])
    eng.install_params(eng.params)  # forget the warm-up's prefixes
    eng.results.clear()
    eng.final_logits.clear()
    eng.program_calls.clear()
    clk.lap("warmup", "one request per prefill bucket through the engine")

    compiles.close_setup()
    tracing, t_trace = False, None
    trace_dir = os.path.join(env["work_dir"], "trace")
    steps, labels, dead_s = [], {}, 0.0
    order = [r["rid"] for r in plan]
    nxt = 0
    gauges = {"pages_peak": 0}
    lane_ticks = observe.counter("tdx.serve.decode_lane_ticks")
    prefilled = observe.counter("tdx.serve.prefill_tokens")

    def submit_due(now_rel):
        nonlocal nxt
        now_rel -= dead_s
        while nxt < len(order) and reqs[order[nxt]]["due_s"] <= now_rel:
            r = reqs[order[nxt]]
            # Due on the window's clock, which stands still while the profiler
            # starts and stops (a traced run only).
            r["due_abs"] = t_open + r["due_s"] + dead_s
            try:
                eng.submit(Request(r["rid"], r["tokens"],
                                   max_new_tokens=r["max_new_tokens"]))
                r["submit"] = time.perf_counter()
            except ValueError as e:  # refused at the door: a failure
                r["refused"] = str(e)
            nxt += 1

    def one_step(i):
        before = dict(eng.program_calls)
        lanes0, prefilled0 = lane_ticks.value, prefilled.value
        t0 = time.perf_counter()
        eng.step()
        t1 = time.perf_counter()
        calls = {k: v - before.get(k, 0) for k, v in eng.program_calls.items()
                 if v != before.get(k, 0)}
        decoding = [l.length for l in eng.active.values() if not l.prefilling]
        gauges["pages_peak"] = max(gauges["pages_peak"], eng.kv.pages_in_use)
        steps.append({"i": i, "t0": t0, "t1": t1, "calls": calls,
                      "ctx_tokens": sum(decoding), "lanes": len(decoding),
                      "decode_lanes": int(lane_ticks.value - lanes0),
                      "prefill_tokens": int(prefilled.value - prefilled0)})
        return "+".join(sorted(calls)) or "idle"

    t_open = clk.open_window()
    i = 0
    while True:
        now = time.perf_counter()
        if now - t_open >= seconds:
            break
        submit_due(now - t_open)
        if env["trace"] and not env["rehearse"]:
            if not tracing and not labels and now - t_open >= TRACE_AFTER_S:
                tr.start(trace_dir)
                tracing, t_trace = True, time.perf_counter()
                dead_s += t_trace - now
            elif tracing and now - t_trace >= TRACE_FOR_S:
                jax.profiler.stop_trace()
                tracing = False
                dead_s += time.perf_counter() - now
        if eng.waiting or eng.active:
            if tracing:
                with jax.profiler.TraceAnnotation("bench.step", i=i):
                    labels[str(i)] = one_step(i)
            else:
                one_step(i)
            i += 1
        elif nxt < len(order):
            wait = reqs[order[nxt]]["due_s"] - (
                time.perf_counter() - t_open - dead_s)
            if wait > 0:
                time.sleep(min(wait, 0.002))
        else:
            time.sleep(0.002)
    t_close = time.perf_counter()
    tokens_in_window = handed["tokens"]
    if tracing:
        jax.profiler.stop_trace()
    # What the profiler took to start and to write its file is no part of
    # the traced run's rates (the end-to-end run has no profiler).
    window_s = t_close - t_open - dead_s
    n_steps_window = len(steps)
    at_close = {"waiting": len(eng.waiting), "active": len(eng.active)}

    # Past the close: an open loop waits until every request it sent has
    # its first token (late is late, not wrong); what is then still decoding
    # is cut, as a backlog is, which was made to outlast the window.
    if mix["mode"] == "open_loop":
        owed = lambda: any(r["submit"] is not None and r["first"] is None
                           for r in reqs.values())
        while owed() and (eng.waiting or eng.active) and (
                time.perf_counter() - t_close < DRAIN_S):
            one_step(i)
            i += 1
    at_close["drain_s"] = time.perf_counter() - t_close

    peak = harness.memory_peak(devs)
    mem_stats = devs[0].memory_stats()
    window_misses = compiles.in_window()
    n_interp = interpreted_calls()
    engine_stats = {
        "program_calls": dict(eng.program_calls),
        "spec_drafted": eng.spec_drafted, "spec_accepted": eng.spec_accepted,
        "spec_verify_ticks": eng.spec_verify_ticks,
        "usable_pages": eng.kv.cfg.usable_pages,
        "pages_peak": gauges["pages_peak"],
        "preemptions": int(observe.counter(
            "tdx.serve.preempted_requests").value) if env["trace"] else None,
        "state_lanes": getattr(eng.kv.cfg.state, "lanes", None),
        "state_slots_peak": getattr(eng.kv, "state_slots_peak", None),
        "state_resets": int(observe.counter("tdx.serve.state_resets").value),
        "recomputed_tokens": int(observe.counter(
            "tdx.serve.recomputed_tokens").value),
    }
    # Which instructions of each compiled program lie under the two
    # recurrences' names: the scope is in the HLO's op_name metadata and in
    # no event of the trace (PERF.md 5), so the reader needs this map.
    scoped = ssm_trace.scoped_instructions(eng._programs) if (
        env["trace"] and not env["rehearse"]) else None
    ledger = None
    if env["trace"]:
        from torchdistx_tpu.observe import reqledger

        ledger = [s for s in reqledger._TAIL]

    eng.active.clear()  # a backlog is cut at the close: drop what is in flight
    eng.waiting.clear()
    eng.release_kv()
    eng._programs.clear()
    del eng

    sent = [r for r in reqs.values() if r["submit"] is not None
            or "refused" in r]
    finished = [r for r in sent if r["done"] is not None]
    failed = [r for r in sent if "refused" in r or (
        mix["mode"] == "open_loop" and r["first"] is None)]
    in_window = lambda t: t is not None and t <= t_close
    tpots = [(r["last"] - r["first"]) / (r["n"] - 1) for r in finished
             if in_window(r["done"]) and r["n"] > 1]
    ttfts = [(r["first"] - r["due_abs"]) if r["first"] is not None
             else 1e9 for r in sent] if mix["mode"] == "open_loop" else []
    lateness = [r["submit"] - r["due_abs"] for r in sent
                if r["submit"] is not None]

    checks = fam.check(env, c, w, finished)
    checks["requests_unanswered"] = {"value": len(failed), "limit": 0,
                                     "ok": not failed}
    if not env["rehearse"]:
        harness.chip_checks(checks, n_interp, window_misses)

    device = harness.device_line(devs, peak)
    ctx = {
        "clock": clk, "compiles": compiles, "c": c, "mix": mix,
        "steps": steps[:n_steps_window], "window_s": window_s,
        "requests": sent, "t_close": t_close, "tpots": tpots, "ttfts": ttfts,
        "lateness": lateness, "engine": engine_stats, "ledger": ledger,
        "peaks": env.get("peaks"), "memory_peak_bytes": peak, "trace": None,
        "family": fam, "ssm_trace": None,
    }
    breakdown = None
    if env["trace"] and labels:
        # before attach_trace, which removes the trace's directory
        ctx["ssm_trace"] = ssm_trace.reduce_dir(trace_dir, scoped)
        breakdown = harness.attach_trace(ctx, device, trace_dir, labels)
        ctx["traced_steps"] = [s for s in steps if str(s["i"]) in labels]
    return {
        "checks": checks, "attempted": len(sent), "failed": len(failed),
        "end_to_end": {
            "out_tok_s": tokens_in_window / window_s,
            "tpot_p50_s": harness.quantile(tpots, 0.5),
            "ttft_p90_s": harness.quantile(ttfts, 0.9),
            "ttft_p50_s": harness.quantile(ttfts, 0.5),
            "setup_s": clk.setup_s},
        "device": device, "ctx": ctx, "breakdown": breakdown,
        "notes": {"window_s": window_s, "steps": n_steps_window,
                  "setup_s": clk.setup_s,
                  "sent": len(sent), "finished": len(finished),
                  "finished_in_window": len(tpots),
                  "tokens_in_window": tokens_in_window,
                  "outcomes": outcomes,
                  "cache_misses_setup": compiles.setup_miss,
                  "cache_hits": compiles.hit, "memory_stats": mem_stats,
                  "engine": engine_stats, "phases": clk.phases,
                  "laps": clk.laps, "at_close": at_close,
                  "profiler_dead_s": dead_s,
                  "ttft_p50_s": harness.quantile(ttfts, 0.5),
                  "ttft_p90_s": harness.quantile(ttfts, 0.9),
                  "lateness_max_s": max(lateness, default=0.0),
                  "ssm_trace": ctx["ssm_trace"],
                  "step_s": {q: harness.quantile(
                      [s["t1"] - s["t0"] for s in steps[:n_steps_window]], p)
                      for q, p in (("p50", 0.5), ("p90", 0.9),
                                   ("p99", 0.99), ("max", 1.0))},
                  "traced_slice": {
                      "steps": len(ctx.get("traced_steps", [])),
                      "decode_ticks": sum(
                          s["calls"].get("decode", 0)
                          for s in ctx.get("traced_steps", [])),
                      "prefill_calls": sum(
                          n for s in ctx.get("traced_steps", [])
                          for k, n in s["calls"].items() if k != "decode"),
                      "decode_lanes": sum(
                          s["decode_lanes"]
                          for s in ctx.get("traced_steps", [])),
                      "prefill_tokens": sum(
                          s["prefill_tokens"]
                          for s in ctx.get("traced_steps", []))},
                  **env["extra_notes"]},
    }
