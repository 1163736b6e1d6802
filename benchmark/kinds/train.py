"""The training kind of cell: deferred_init -> materialize ->
``make_train_step``'s ``init_state`` / ``train_step`` on one chip.

Imports the training path of the program and nothing of
``torchdistx_tpu.serve``.  Set-up builds ONE object, the compiled step
with its state, drives it through its first three steps on rows drawn from
the seed, and hands that same object to the window; the comparison with
the plain reference (``benchmark/check_train.py``) follows those three
steps after the window has closed."""

from __future__ import annotations

import os
import sys
import time

FIRST_STEPS = 3
TRACE_AFTER_S, TRACE_FOR_S = 1.0, 3.0


def run(env) -> dict:
    clk = env["clock"]
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu import abstract
    from torchdistx_tpu.models import decoder_lm_plan, make_gpt2
    from torchdistx_tpu.ops import make_flash_attention
    from torchdistx_tpu.ops._interpret import interpreted_calls
    from torchdistx_tpu.parallel import make_mesh
    from torchdistx_tpu.parallel.train import make_train_step

    from benchmark import (adapters, check_train, configs, harness, traffic,
                           weights)
    from benchmark import tracing as tr

    clk.lap("import")
    compiles = harness.Compiles()
    compiles.install()
    devs = harness.devices(env)
    clk.lap("backend")

    cfg, mix, seed = env["cfg"], env["mix"], env["seed"]
    if cfg["family"] != "gpt2":
        raise harness.Refused("the train kind drives models/gpt2.py only")
    c = configs.dims(cfg)
    tcfg = adapters.transformer_config(cfg, c)
    mesh = make_mesh({"dp": 1}, devices=devs[:1])
    attn = (make_flash_attention(mesh=mesh)
            if cfg["train_config"]["attention"] == "flash" else None)
    model = make_gpt2(tcfg, **({"attn_fn": attn} if attn else {}))
    rows = lambda step: traffic.training_rows(mix, seed, step, c["vocab_size"])

    # The program's own chain, from a fixed key (its compiled init program
    # is then the same for every --seed); the values it made are dropped
    # for the benchmark's own, which the reference can make again.
    fakes = abstract.deferred_init(
        model.init, jax.random.PRNGKey(0), jnp.asarray(rows(0)[:1, :8]))
    params = abstract.materialize(
        fakes, mesh=mesh, plan=decoder_lm_plan(fsdp=None, tp=None, ep=None))
    jax.block_until_ready(params)
    clk.lap("materialize", "deferred_init -> materialize (program)")
    program_dtypes = {str(x.dtype) for x in jax.tree.leaves(params)}
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    del params, fakes
    w = weights.make("gpt2", c, seed, jnp.float32)
    jax.block_until_ready(w)
    clk.lap("materialize", "weights from the seed (benchmark)")

    init_state, train_step, shard_batch = make_train_step(
        model, tcfg, mesh, donate=cfg["train_config"]["donate"])
    state = init_state(adapters.param_tree("gpt2", w))
    del w
    jax.block_until_ready(state)
    clk.lap("programs")

    b1 = cfg["train_config"]["optimizer"]["b1"]
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})
    diff = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in a})
    sample = jax.jit(lambda t: {k: v.reshape(-1)[:check_train.SAMPLE].astype(
        jnp.float32) / (1.0 - b1) for k, v in t.items()})
    observed = {"loss": []}
    step_no = 0

    def one_step(state):
        nonlocal step_no
        state, m = train_step(state, shard_batch(rows(step_no)))
        step_no += 1
        return state, float(m["loss"])

    for i in range(FIRST_STEPS):
        state, loss = one_step(state)
        clk.lap("warmup", f"step {i + 1}")
        observed["loss"].append(loss)
        if i == 0:
            mu = adapters.flat_from_tree("gpt2", state["opt"][0].mu)
            observed["grad_norm"] = {
                k: float(v) / (1.0 - b1) for k, v in norms(mu).items()}
            observed["grad_sample"] = jax.device_get(sample(mu))
    # The starting weights again (the step took the first ones by donation,
    # and a copy kept beside the step's 10.7 GB of scratch does not fit).
    w0 = weights.make("gpt2", c, seed, jnp.float32)
    now = adapters.flat_from_tree("gpt2", state["params"])
    observed["update_norm"] = {k: float(v) for k, v in diff(now, w0).items()}
    for leaf in jax.tree.leaves(w0):
        leaf.delete()
    del w0, now
    clk.lap("warmup")

    compiles.close_setup()
    tokens_per_step = mix["batch"] * mix["seq_len"]
    seconds, tracing = env["seconds"], False
    trace_dir = os.path.join(env["work_dir"], "trace")
    steps, labels, dead_s = [], {}, 0.0
    t_open = clk.open_window()
    while True:
        t = time.perf_counter()
        if t - t_open >= seconds:
            break
        if env["trace"] and not env["rehearse"]:
            if not tracing and not labels and t - t_open >= TRACE_AFTER_S:
                tr.start(trace_dir)
                tracing, t_trace = True, time.perf_counter()
                dead_s += t_trace - t
            elif tracing and t - t_trace >= TRACE_FOR_S:
                jax.profiler.stop_trace()
                tracing = False
                dead_s += time.perf_counter() - t
            t = time.perf_counter()
        if tracing:
            labels[str(step_no)] = "train_step"
            with jax.profiler.TraceAnnotation("bench.step", i=step_no):
                state, loss = one_step(state)
        else:
            state, loss = one_step(state)
        steps.append((t, time.perf_counter(), loss))
    t_close = time.perf_counter()
    if tracing:
        jax.profiler.stop_trace()
    # What the profiler took to start and to write its file is no part of
    # the traced run's rates (the end-to-end run has no profiler).
    window_s = t_close - t_open - dead_s
    peak = harness.memory_peak(devs)
    mem_stats = devs[0].memory_stats()
    window_misses = compiles.in_window()
    n_interp = interpreted_calls()
    finite = all(l == l and abs(l) < 1e9 for _, _, l in steps)

    for leaf in jax.tree.leaves(state):
        leaf.delete()
    del state

    checks = check_train.check(env, c, observed)
    checks["losses_finite"] = {"value": 0 if finite else 1, "limit": 0,
                               "ok": finite}
    if not env["rehearse"]:
        harness.chip_checks(checks, n_interp, window_misses)

    device = harness.device_line(devs, peak)
    ctx = {
        "clock": clk, "compiles": compiles, "c": c, "mix": mix,
        "steps": steps, "window_s": window_s, "peaks": env.get("peaks"),
        "memory_peak_bytes": peak, "trace": None,
    }
    breakdown = None
    if env["trace"] and labels:
        breakdown = harness.attach_trace(ctx, device, trace_dir, labels)
    return {
        "checks": checks, "attempted": len(steps),
        "failed": 0 if finite else sum(1 for _, _, l in steps if l != l),
        "end_to_end": {
            "train_tok_s": len(steps) * tokens_per_step / window_s,
            "setup_s": clk.setup_s},
        "device": device, "ctx": ctx, "breakdown": breakdown,
        "notes": {"steps": len(steps), "window_s": window_s,
                  "first_losses": observed["loss"], "setup_s": clk.setup_s,
                  "serve_modules_imported": sum(
                      m.startswith("torchdistx_tpu.serve") for m in sys.modules),
                  "orbax_imported": "orbax" in sys.modules,
                  "program_param_dtypes": sorted(program_dtypes),
                  "cache_misses_setup": compiles.setup_miss,
                  "cache_hits": compiles.hit, "memory_stats": mem_stats,
                  "phases": clk.phases, "laps": clk.laps,
                  "profiler_dead_s": dead_s, **env["extra_notes"]},
    }
