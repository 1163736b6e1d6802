"""Soak-fuzz driver: run the replay-correctness oracles over large seed
ranges in parallel worker processes.

The pytest suite runs a fixed, small seed window per oracle (fast, part of
CI); this tool is the long-running companion that found most of the
round-2 regressions (see docs/CHANGES.md): it streams fresh seeds through
the same oracles in ``tests/test_fuzz_replay.py`` until a wall-clock
budget expires, and reports every failing seed so it can be pinned as a
regression test.

    python tools/soak.py --seconds 3600 --start 300000
    python tools/soak.py --modes bridge,serialize --seeds 5000

The ``elastic`` mode soaks the chaos-hardened recovery loop instead of a
replay oracle: each seed runs ``run_elastic`` under a fault plan
(``--fault-plan``, or a seeded-random one) and asserts the final state
equals the fault-free run — including across the documented
relaunch-with-``resume=True`` contract.  On real hardware (a
``tpu_watch`` window) this exercises recovery against the actual
accelerator runtime:

    python tools/soak.py --modes elastic --seconds 600 \\
        --fault-plan 'save@2=corrupt:truncate;step@3=raise'

The ``materialize`` mode soaks the self-healing materialization pipeline
the same way: each seed deferred-inits a randomized heterogeneous model,
injects a fault plan into the record→compile→execute pipeline (sites
``lower``/``compile``/``execute``/``cache``, including real on-disk
compile-cache corruption and SIGTERM preemption drains), retries through
the partial-progress resume contract, and asserts the final materialized
parameters are bitwise-equal to the fault-free run:

    python tools/soak.py --modes materialize --seconds 300 \\
        --fault-plan 'compile@1=raise;cache@2=corrupt:truncate'

The ``registry`` mode soaks the pod-scale compile-artifact registry
(docs/registry.md): each seed publishes a randomized model's init
programs through one materialization, then re-materializes from a fresh
local cache through the shared registry under an injected ``registry``
fault plan (flaky fetch/publish, slow shared filesystem, artifact
bit-rot caught by CRC self-verification and quarantine) and asserts the
final parameters are bitwise-equal to the fault-free run — registry
trouble must only ever cost local compiles, never correctness:

    python tools/soak.py --modes registry --seconds 300 \\
        --fault-plan 'registry@1=raise;registry@2=corrupt:flip'

The ``serve`` mode soaks the inference-serving runtime
(docs/serving.md): each seed spins up a randomized tiny replica,
submits a randomized staggered request mix through the
continuous-batching engine under an injected ``serve`` fault plan
(replica faults mid-batch, slow steps) and a deliberately tight page
pool (so preemption-and-requeue fires for real), and asserts every
request's generated tokens equal the unbatched no-cache oracle —
batching, paging, preemption, and faults must never change a token:

    python tools/soak.py --modes serve --seconds 300 \\
        --fault-plan 'serve@2=raise;serve@5=slow:0.1'

The ``fleet`` mode soaks the multi-replica serve fleet one layer up
(docs/serving.md §Fleet): each seed brings up a randomized fleet,
drives a randomized staggered storm through the router while an
aggressive autoscaler oscillates the replica count, injects a ``fleet``
fault plan (replica kills mid-batch — raise / thread-preempt / hang
caught by stall detection), forces at least one scale-up and one
drain-based scale-down mid-storm, and asserts every response equals the
unbatched oracle and nothing was rejected — replica loss and scale
churn must never change a token:

    python tools/soak.py --modes fleet --seconds 300 \\
        --fault-plan 'fleet@2=raise'

The ``guardrails`` mode soaks the guardrail layer on top of the fleet
(docs/serving.md §Guardrails): each seed arms every guardrail (circuit
breakers with quarantine-and-respawn, end-to-end deadlines with
mid-decode cancellation, hedged dispatch, priority brownout), drives a
randomized mixed-priority storm — some requests carrying generous
deadlines, some hopeless ones — through a fleet with a flapping replica
(the intermittent-fault mode kill-detection never catches), and asserts
the guardrail invariant: every request either completes bitwise-equal
to the unbatched oracle or carries exactly one typed rejection
(``deadline`` rejections' delivered tokens must be an oracle prefix),
with no KV page leaked and no hedge left unsettled:

    python tools/soak.py --modes guardrails --seconds 300 \\
        --fault-plan 'fleet@2=flap:0.6'

The ``reshard`` mode soaks the topology-migrating checkpoint
redistributor (docs/robustness.md §Resharding): each seed saves a
randomized state, rechunk-copies it through a randomized pair of
(mesh, sharding-plan) topologies with a randomized chunk budget, and
asserts the final restore is bitwise-equal to the original; half the
seeds inject a ``reshard``-site fault plan and assert
degrade-never-corrupt instead (typed ``ReshardError``, source intact,
no destination left behind):

    python tools/soak.py --modes reshard --seconds 300 \\
        --fault-plan 'reshard@2=corrupt:flip'

Failures are appended to ``tools/soak_failures.jsonl`` (seed + mode +
exception) and the exit code is non-zero if any occurred.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODES = ("whole", "single", "bridge", "bridge_single", "serialize",
         "geom", "geom_single", "geom_bridge", "elastic", "materialize",
         "registry", "serve", "fleet", "guardrails", "reshard")

_FAULT_PLAN: "str | None" = None  # --fault-plan, set per worker via initargs


def _init_worker(fault_plan: "str | None" = None,
                 platform: str = "cpu") -> None:
    global _FAULT_PLAN
    _FAULT_PLAN = fault_plan
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "tests"))
    # One thread per worker: the fuzz tensors are tiny, and N workers ×
    # ncpu intra-op threads would oversubscribe the box.
    os.environ["OMP_NUM_THREADS"] = "1"
    # The materialize oracle's models compile in milliseconds; persist
    # them anyway so cache-corruption faults have real entries to damage.
    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    if platform == "default":
        # --platform default (elastic-only soaks under a tpu_watch
        # window): leave the backend alone so recovery is exercised
        # against the REAL accelerator runtime.  The fuzz oracles never
        # run in this configuration (main() forces cpu when any is
        # selected), so torch stays unimported too.
        return
    import torch

    torch.set_num_threads(1)
    # The jax-bridge oracles are CPU reproductions; pin the platform
    # through the config API before any backend initializes, whatever
    # the environment says (soak throughput wants no accelerator
    # either).
    import jax

    jax.config.update("jax_platforms", "cpu")


def _elastic_oracle(seed: int, plan_text: "str | None"):
    """One chaos-recovery run: inject a fault plan into ``run_elastic``
    over a deterministic scalar-sum workload and assert the final state
    equals the fault-free run's — surviving raises, hangs, corruption,
    slow saves, preemption drains, and the relaunch-with-resume contract
    when an in-process rewind exceeds the replay window."""
    import random
    import shutil
    import tempfile

    import jax.numpy as jnp

    from torchdistx_tpu import chaos
    from torchdistx_tpu.utils.failures import ReplayWindowExceeded, run_elastic

    rng = random.Random(seed)
    n = rng.randrange(6, 13)
    every = rng.randrange(1, 4)
    if plan_text:
        plan = chaos.parse_plan(plan_text)
    else:
        kind = rng.choice(["raise", "hang", "preempt", "corrupt", "slow"])
        if kind == "corrupt":
            # Corruption only matters if something restores from it:
            # damage the newest save before an injected failure.  Never
            # step 0 — corrupting the only checkpoint is unrecoverable
            # in-process by design (run_elastic raises; a fresh start is
            # the only remedy), which is not the contract soaked here.
            save_step = every * rng.randrange(1, n // every)
            fail_step = rng.randrange(save_step + 1, n + 1)
            text = f"save@{save_step}=corrupt:truncate;step@{fail_step}=raise"
        elif kind == "slow":
            text = f"save@{every * rng.randrange(0, n // every + 1)}=slow:0.05"
        else:
            arg = ":2" if kind == "hang" else ""
            text = f"step@{rng.randrange(1, n + 1)}={kind}{arg}"
        plan = chaos.parse_plan(text)
    expected = float(sum(range(1, n + 1)))
    batches = [jnp.float32(i) for i in range(1, n + 1)]

    def stepf(state, b):
        return {"x": state["x"] + b}, {}

    d = tempfile.mkdtemp(prefix="tdx_soak_elastic_")
    try:
        chaos.install(plan)
        steps = 0
        resume = False
        out = None
        for _attempt in range(4):  # preempt drain / relaunch contract
            try:
                out, steps, _ = run_elastic(
                    stepf, {"x": jnp.float32(0.0)}, batches,
                    checkpoint_dir=d, checkpoint_every=every,
                    max_restarts=8, step_deadline=0.5, resume=resume,
                    probe_on_restart=False,
                )
            except ReplayWindowExceeded:
                pass  # documented contract: relaunch with resume=True
            resume = True
            if steps >= n:
                break
        if steps < n:
            return ("error", f"did not complete: steps={steps}/{n} plan={plan!r}")
        if float(out["x"]) != expected:
            return ("mismatch",
                    f"final x={float(out['x'])} != {expected} plan={plan!r}")
    finally:
        chaos.clear()
        shutil.rmtree(d, ignore_errors=True)
    return None


def _materialize_oracle(seed: int, plan_text: "str | None"):
    """One self-healing materialization run: inject a fault plan into the
    record→compile→execute pipeline over a seeded heterogeneous model and
    assert the final materialized parameters are bitwise-equal to the
    fault-free run — surviving raises, hangs (via the compile watchdog),
    slow stages, on-disk compile-cache corruption, and SIGTERM preemption
    drains resumed through the partial-progress manifest."""
    import random
    import shutil
    import tempfile

    import numpy as np
    import torch

    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import chaos
    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu.jax_bridge import (
        MaterializationError,
        materialize_module_jax,
    )
    from torchdistx_tpu import compile_service

    rng = random.Random(seed)
    k = rng.randrange(9, 13)
    widths = [8 + 4 * rng.randrange(1, 8) for _ in range(k)]

    class Model(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.layers = torch.nn.ModuleList(
                torch.nn.Linear(widths[i], widths[(i + 1) % k])
                for i in range(k)
            )

    if plan_text:
        plan = chaos.parse_plan(plan_text)
    else:
        site = rng.choice(["lower", "compile", "execute", "cache"])
        # `corrupt` needs on-disk cache entries; the warm pass below
        # guarantees them.  `hang` leans on the watchdog deadline.
        kind = rng.choice(["raise", "hang", "slow", "corrupt", "preempt"])
        arg = {"hang": ":30", "slow": ":0.1", "corrupt": ":truncate"}.get(
            kind, "")
        group = rng.randrange(1, 4)
        plan = chaos.parse_plan(f"{site}@{group}={kind}{arg}")

    cache_dir = tempfile.mkdtemp(prefix="tdx_soak_mat_cache_")
    resume_dir = tempfile.mkdtemp(prefix="tdx_soak_mat_resume_")
    try:
        module = deferred_init(Model)
        with tdx_config.override(materialize_pipeline="off"):
            baseline = {
                k_: np.asarray(v) for k_, v in
                materialize_module_jax(module, seed=seed).items()
            }
        # Warm pass (also validates the fault-free pipelined run) so
        # cache-corruption faults have real entries to damage.
        compile_service.reset_cache_binding()
        with tdx_config.override(
            materialize_pipeline="auto", cache_dir=cache_dir,
            compile_workers=2,
        ):
            materialize_module_jax(module, seed=seed)

        chaos.install(plan)
        params = None
        with tdx_config.override(
            materialize_pipeline="auto", cache_dir=cache_dir,
            compile_workers=2, compile_deadline_s=5.0,
            materialize_retries=2, materialize_resume_dir=resume_dir,
        ):
            compile_service.reset_cache_binding()
            for _attempt in range(4):  # drain / resume contract
                try:
                    params = materialize_module_jax(module, seed=seed)
                    break
                except MaterializationError:
                    continue
        if params is None:
            return ("error", f"did not materialize after 4 attempts "
                             f"plan={plan!r}")
        for name, want in baseline.items():
            got = np.asarray(params[name])
            if not np.array_equal(want, got):
                return ("mismatch", f"{name} differs plan={plan!r}")
    finally:
        chaos.clear()
        compile_service.reset_cache_binding()
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(resume_dir, ignore_errors=True)
    return None


def _reshard_oracle(seed: int, plan_text: "str | None"):
    """One randomized plan-pair reshard: save a seeded state, rechunk it
    through two random (mesh, plan) topologies, and assert the final
    restore is bitwise-equal to the original — params and optimizer-like
    leaves, bf16 included.  Half the seeds additionally inject a
    ``reshard``-site fault (raise / slow / corrupt) and then assert the
    degrade-never-corrupt contract instead: typed ``ReshardError``, the
    source still verifies, no committed destination left behind.

    The whole oracle is device-free (offline resharding is pure
    tensorstore I/O against :class:`~torchdistx_tpu.reshard.MeshSpec`
    targets), so it soaks in a plain single-device CPU worker."""
    import random
    import shutil
    import tempfile
    from pathlib import Path

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchdistx_tpu import chaos, reshard
    from torchdistx_tpu.parallel.sharding import (
        ShardingPlan, fsdp_plan, gspmd_2d_plan,
    )
    from torchdistx_tpu.utils.checkpoint import (
        restore_checkpoint, save_checkpoint, verify_checkpoint,
    )

    rng = random.Random(seed)

    def rand_mesh_plan():
        kind = rng.choice(["replicated", "fsdp", "gspmd2d"])
        if kind == "replicated":
            return reshard.MeshSpec({"fsdp": rng.choice([2, 4])}), ShardingPlan()
        if kind == "fsdp":
            return (reshard.MeshSpec({"fsdp": rng.choice([2, 4, 8])}),
                    fsdp_plan(min_size=1))
        return (reshard.MeshSpec({"fsdp": rng.choice([2, 4]),
                                  "tp": rng.choice([2, 4])}),
                gspmd_2d_plan(min_size=1))

    # Seeded leaves: dims are multiples of 8 so every mesh size divides.
    def rand_leaf():
        dt = rng.choice([jnp.float32, jnp.bfloat16, jnp.int32])
        shape = tuple(8 * rng.randrange(1, 4)
                      for _ in range(rng.randrange(1, 3)))
        n = int(np.prod(shape))
        return jnp.asarray(
            np.random.RandomState(seed ^ n).randn(*shape) * 100, dtype=dt)

    state = {"leaf_%d" % i: rand_leaf() for i in range(rng.randrange(2, 5))}
    state["step"] = jnp.int32(rng.randrange(100))
    mesh_a, plan_a = rand_mesh_plan()
    mesh_b, plan_b = rand_mesh_plan()
    chunk_mb = rng.choice([0.0005, 0.002, 0.01, None])

    if plan_text:
        fault = plan_text
    elif rng.random() < 0.5:
        kind = rng.choice(["raise", "slow", "corrupt"])
        arg = {"raise": "", "slow": ":0.02", "corrupt": ":flip"}[kind]
        fault = f"reshard@{rng.randrange(1, 6)}={kind}{arg}"
    else:
        fault = None

    d = Path(tempfile.mkdtemp(prefix="tdx_soak_reshard_"))
    try:
        save_checkpoint(d / "src", state)
        # Leg 1 (fault-free) lays the checkpoint out under plan A so leg
        # 2 migrates a genuinely sharded chunk grid.
        a = reshard.reshard_checkpoint(d / "src", plan_a, mesh_a, d / "a")
        try:
            chaos.install(fault)
            b = reshard.reshard_checkpoint(a, plan_b, mesh_b, d / "b",
                                           chunk_mb=chunk_mb)
        except reshard.ReshardError:
            if fault is None:
                raise
            # Degrade-never-corrupt: source intact, destination gone.
            ok, reason = verify_checkpoint(a)
            if not ok:
                return ("mismatch", f"source damaged after failed "
                                    f"reshard ({fault}): {reason}")
            if (d / "b").exists():
                return ("mismatch",
                        f"failed reshard left a destination ({fault})")
            return None
        finally:
            chaos.clear()
        out = restore_checkpoint(b, target=jax.tree_util.tree_map(
            lambda x: jnp.zeros_like(x), state))
        for k in state:
            want = np.asarray(state[k]).reshape(-1).view(np.uint8)
            got = np.asarray(out[k]).reshape(-1).view(np.uint8)
            if not np.array_equal(want, got):
                return ("mismatch",
                        f"{k} differs after {mesh_a}->{mesh_b} "
                        f"(chunk_mb={chunk_mb}, fault={fault})")
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return None


def _registry_oracle(seed: int, plan_text: "str | None"):
    """One registry-degradation run: publish a seeded model's init
    programs through the shared artifact registry, then re-materialize
    from a FRESH local cache through the registry under an injected
    ``registry`` fault plan (raise / slow / corrupt on fetch and
    publish) and assert the final parameters are bitwise-equal to the
    fault-free run — a flaky or bit-rotted shared filesystem degrades to
    local compiles (quarantined + counted), never to an error or a wrong
    value."""
    import random
    import shutil
    import tempfile

    import numpy as np
    import torch

    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import chaos
    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu.jax_bridge import materialize_module_jax
    from torchdistx_tpu import compile_service

    rng = random.Random(seed)
    k = rng.randrange(9, 13)
    widths = [8 + 4 * rng.randrange(1, 8) for _ in range(k)]

    class Model(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.layers = torch.nn.ModuleList(
                torch.nn.Linear(widths[i], widths[(i + 1) % k])
                for i in range(k)
            )

    if plan_text:
        plan = chaos.parse_plan(plan_text)
    else:
        kind = rng.choice(["raise", "slow", "corrupt"])
        arg = {"slow": ":0.1", "corrupt": ":" + rng.choice(
            ["truncate", "flip"])}.get(kind, "")
        group = rng.randrange(1, 4)
        count = rng.randrange(1, 3)
        plan = chaos.parse_plan(f"registry@{group}={kind}{arg} x{count}")

    reg_dir = tempfile.mkdtemp(prefix="tdx_soak_reg_")
    cache_a = tempfile.mkdtemp(prefix="tdx_soak_reg_ca_")
    cache_b = tempfile.mkdtemp(prefix="tdx_soak_reg_cb_")
    try:
        module = deferred_init(Model)
        with tdx_config.override(materialize_pipeline="off"):
            baseline = {
                k_: np.asarray(v) for k_, v in
                materialize_module_jax(module, seed=seed).items()
            }
        # Publish pass: fault-free, fills the registry (corrupt faults
        # need real artifacts to damage).
        compile_service.reset_cache_binding()
        with tdx_config.override(
            materialize_pipeline="auto", cache_dir=cache_a,
            registry_dir=reg_dir, compile_workers=2,
        ):
            materialize_module_jax(module, seed=seed)

        chaos.install(plan)
        compile_service.reset_cache_binding()
        with tdx_config.override(
            materialize_pipeline="auto", cache_dir=cache_b,
            registry_dir=reg_dir, compile_workers=2,
            materialize_retries=2,
        ):
            params = materialize_module_jax(module, seed=seed)
        for name, want in baseline.items():
            got = np.asarray(params[name])
            if not np.array_equal(want, got):
                return ("mismatch", f"{name} differs plan={plan!r}")
    finally:
        chaos.clear()
        compile_service.reset_cache_binding()
        shutil.rmtree(reg_dir, ignore_errors=True)
        shutil.rmtree(cache_a, ignore_errors=True)
        shutil.rmtree(cache_b, ignore_errors=True)
    return None


def _serve_oracle(seed: int, plan_text: "str | None"):
    """One serving-correctness run: a randomized tiny replica serves a
    randomized staggered request mix through the continuous-batching
    engine — under a ``serve`` fault plan and a page pool tight enough
    to force preemption — and every request's tokens must equal the
    unbatched oracle's."""
    import random

    from torchdistx_tpu import chaos
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        Request,
        ServeConfig,
        ServeEngine,
        oracle_generate,
        serve_program_specs,
    )
    from torchdistx_tpu.serve.programs import compile_serving_program

    import jax
    import jax.numpy as jnp

    rng = random.Random(seed)
    cfg = TransformerConfig(
        vocab_size=rng.choice([96, 128]),
        d_model=rng.choice([32, 48]),
        n_layers=rng.randrange(1, 3),
        n_heads=4,
        n_kv_heads=rng.choice([2, 4]),
        d_ff=64,
        max_seq_len=64,
        dtype=jnp.float32,
    )
    scfg = ServeConfig(
        max_batch=rng.randrange(2, 4),
        page_size=rng.choice([4, 8]),
        n_pages=rng.randrange(8, 14),  # deliberately tight
        max_pages_per_seq=4,
        prefill_buckets=(8,),
        # Exercise the chunked-prefill scheduler at every size, the
        # prefix-sharing hot path, and the sharing-off control arm.
        prefill_chunk=rng.choice([None, 2, 3, 5, 8]),
        prefix_cache=rng.random() < 0.75,
    )
    resolved = scfg.resolve(cfg)
    family = "llama"
    specs = serve_program_specs(family, cfg, scfg, seed=seed % 7)
    init = specs[0]
    compiled, _ = compile_serving_program(init)
    params = jax.tree.unflatten(init.treedef, list(compiled()))

    # A randomized fraction of requests shares a page-aligned preamble
    # so COW, tree eviction, and refcounted free all fire under chaos.
    shared_frac = rng.choice([0.0, 0.5, 0.8])
    preamble = [rng.randrange(cfg.vocab_size)
                for _ in range(resolved.page_size)]
    n_req = rng.randrange(3, 6)
    reqs = []
    for i in range(n_req):
        if rng.random() < shared_frac:
            prompt = preamble + [rng.randrange(cfg.vocab_size) for _ in
                                 range(rng.randrange(0, 4))]
        else:
            prompt = [rng.randrange(cfg.vocab_size) for _ in
                      range(rng.randrange(1, 8))]
        budget = rng.randrange(1, 1 + min(
            8, resolved.max_context - len(prompt)))
        reqs.append(Request(
            f"r{i}", prompt, max_new_tokens=budget,
            arrival_step=rng.randrange(0, 4),
        ))

    if plan_text:
        plan = chaos.parse_plan(plan_text)
    else:
        entries = []
        for _ in range(rng.randrange(1, 3)):
            kind = rng.choice(["raise", "raise", "slow"])
            if kind == "slow":
                arg = ":0.05"
            else:
                # Half the raises land BETWEEN prefill chunks.
                arg = ":chunk" if rng.random() < 0.5 else ""
            entries.append(f"serve@{rng.randrange(1, 6)}={kind}{arg}")
        plan = chaos.parse_plan(";".join(entries))

    chaos.install(plan)
    try:
        eng = ServeEngine(family, cfg, params, serve_cfg=scfg,
                          seed=seed % 7)
        out = eng.run(reqs)
    finally:
        chaos.clear()
    for r in reqs:
        want, _ = oracle_generate(family, cfg, params, r.tokens,
                                  r.max_new_tokens, r.eos_id)
        if out.get(r.rid) != want:
            return ("mismatch",
                    f"{r.rid}: engine={out.get(r.rid)} oracle={want} "
                    f"plan={plan!r}")
    eng.drain()
    if eng.kv.pages_in_use != 0:
        return ("leak",
                f"{eng.kv.pages_in_use} pages live after drain "
                f"plan={plan!r}")
    return None


def _fleet_oracle(seed: int, plan_text: "str | None"):
    """One fleet-correctness run: a randomized storm through a
    randomized multi-replica fleet under replica-kill chaos and forced
    scale oscillation (≥1 scale-up + ≥1 drain mid-storm, plus whatever
    the aggressive autoscaler adds) — every response must equal the
    unbatched oracle and nothing may be rejected."""
    import random
    import shutil
    import tempfile
    import time as _time

    from torchdistx_tpu import chaos
    from torchdistx_tpu import config as tdx_config
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        FleetConfig,
        Request,
        ServeConfig,
        ServeFleet,
        oracle_generate,
        serve_program_specs,
    )
    from torchdistx_tpu.serve.programs import compile_serving_program

    import jax
    import jax.numpy as jnp

    rng = random.Random(seed)
    cfg = TransformerConfig(
        vocab_size=rng.choice([96, 128]),
        d_model=rng.choice([32, 48]),
        n_layers=rng.randrange(1, 3),
        n_heads=4,
        n_kv_heads=rng.choice([2, 4]),
        d_ff=64,
        max_seq_len=64,
        dtype=jnp.float32,
    )
    scfg = ServeConfig(
        max_batch=rng.randrange(2, 4),
        page_size=rng.choice([4, 8]),
        n_pages=rng.randrange(10, 16),
        max_pages_per_seq=4,
        prefill_buckets=(8,),
    )
    resolved = scfg.resolve(cfg)
    family = "llama"
    # Independent oracle params: the seed identity with the fleet's
    # replicas (same deferred-init seed → identical params) is exactly
    # what makes cross-replica token equality meaningful.
    specs = serve_program_specs(family, cfg, scfg, seed=seed % 7)
    init = specs[0]
    compiled, _ = compile_serving_program(init)
    params = jax.tree.unflatten(init.treedef, list(compiled()))

    n_req = rng.randrange(4, 9)
    reqs = []
    for i in range(n_req):
        prompt = [rng.randrange(cfg.vocab_size) for _ in
                  range(rng.randrange(1, 8))]
        budget = rng.randrange(1, 1 + min(
            8, resolved.max_context - len(prompt)))
        reqs.append(Request(
            f"r{i}", prompt, max_new_tokens=budget,
            arrival_step=rng.randrange(0, 7),
        ))

    if plan_text:
        plan = chaos.parse_plan(plan_text)
    else:
        entries = []
        for _ in range(rng.randrange(1, 3)):
            kind = rng.choice(["raise", "preempt", "hang"])
            arg = ":3600" if kind == "hang" else ""
            entries.append(f"fleet@{rng.randrange(1, 4)}={kind}{arg}")
        plan = chaos.parse_plan(";".join(entries))

    fc = FleetConfig(
        min_replicas=1, max_replicas=3,
        dispatch_per_replica=1.0,           # backlog visible → pressure
        up_queue_per_replica=2.0, up_consecutive=1,
        down_consecutive=3, cooldown_s=0.05,
        stall_s=0.75,                       # hang kills get declared fast
        autoscale=True,
    )
    cache = tempfile.mkdtemp(prefix="tdx_soak_fleet_")
    chaos.install(plan)
    old_min = os.environ.get("TDX_CACHE_MIN_COMPILE_S")
    os.environ["TDX_CACHE_MIN_COMPILE_S"] = "0"
    try:
        with tdx_config.override(cache_dir=cache):
            with ServeFleet(cfg, family=family, serve_cfg=scfg,
                            seed=seed % 7, fleet_cfg=fc) as fl:
                fl.start(rng.randrange(1, 3), timeout=240.0)
                arrivals = sorted(reqs, key=lambda r: r.arrival_step)
                did_up = did_down = False
                i = 0
                deadline = _time.monotonic() + 240.0
                while i < len(arrivals) or fl._pending:
                    while (i < len(arrivals)
                           and arrivals[i].arrival_step <= fl._tick_no):
                        fl.submit(arrivals[i])
                        i += 1
                    fl.tick()
                    serving = sum(1 for h in fl.handles
                                  if h.state == "serving")
                    if not did_up and i >= n_req // 2:
                        fl.scale_up()       # forced ≥1 scale-up
                        did_up = True
                    if did_up and not did_down and serving > 1 and i >= n_req:
                        fl.scale_down()     # forced ≥1 drain
                        did_down = True
                    if _time.monotonic() > deadline:
                        return ("hang",
                                f"fleet storm stuck: pending={fl._pending} "
                                f"states={[h.state for h in fl.handles]} "
                                f"plan={plan!r}")
                    _time.sleep(0.001)
                out = dict(fl.results)
                if fl.rejected:
                    return ("mismatch",
                            f"unexpected rejections {fl.rejected} "
                            f"plan={plan!r}")
    finally:
        chaos.clear()
        compile_service.reset_cache_binding()
        if old_min is None:
            os.environ.pop("TDX_CACHE_MIN_COMPILE_S", None)
        else:
            os.environ["TDX_CACHE_MIN_COMPILE_S"] = old_min
        shutil.rmtree(cache, ignore_errors=True)
    for r in reqs:
        want, _ = oracle_generate(family, cfg, params, r.tokens,
                                  r.max_new_tokens, r.eos_id)
        if out.get(r.rid) != want:
            return ("mismatch",
                    f"{r.rid}: fleet={out.get(r.rid)} oracle={want} "
                    f"plan={plan!r}")
    return None


def _guardrails_oracle(seed: int, plan_text: "str | None"):
    """One guardrail-invariant run: a randomized mixed-priority storm —
    deadlines generous and hopeless, a flapping replica — through a
    fleet with every guardrail armed (breaker + quarantine, mid-decode
    deadline cancellation, hedged dispatch, brownout).  The invariant
    (docs/serving.md §Guardrails): every request either completes
    bitwise-equal to the unbatched oracle or carries exactly one typed
    rejection; ``deadline`` rejections' delivered tokens are an oracle
    prefix; no KV page leaks; no hedge stays unsettled."""
    import random
    import shutil
    import tempfile

    from torchdistx_tpu import chaos
    from torchdistx_tpu import config as tdx_config
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        FleetConfig,
        GuardrailConfig,
        Request,
        ServeConfig,
        ServeFleet,
        oracle_generate,
        serve_program_specs,
    )
    from torchdistx_tpu.serve.programs import compile_serving_program
    from torchdistx_tpu.serve.router import REJECT_REASONS

    import jax
    import jax.numpy as jnp

    rng = random.Random(seed)
    cfg = TransformerConfig(
        vocab_size=rng.choice([96, 128]),
        d_model=rng.choice([32, 48]),
        n_layers=rng.randrange(1, 3),
        n_heads=4,
        n_kv_heads=rng.choice([2, 4]),
        d_ff=64,
        max_seq_len=64,
        dtype=jnp.float32,
    )
    scfg = ServeConfig(
        max_batch=rng.randrange(2, 4),
        page_size=rng.choice([4, 8]),
        n_pages=rng.randrange(10, 16),
        max_pages_per_seq=4,
        prefill_buckets=(8,),
    )
    resolved = scfg.resolve(cfg)
    family = "llama"
    specs = serve_program_specs(family, cfg, scfg, seed=seed % 7)
    init = specs[0]
    compiled, _ = compile_serving_program(init)
    params = jax.tree.unflatten(init.treedef, list(compiled()))

    n_req = rng.randrange(5, 9)
    reqs = []
    for i in range(n_req):
        prompt = [rng.randrange(cfg.vocab_size) for _ in
                  range(rng.randrange(1, 8))]
        budget = rng.randrange(1, 1 + min(
            8, resolved.max_context - len(prompt)))
        # Mostly deadline-less or generous; an occasional hopeless
        # deadline must resolve as a typed rejection, never a hang.
        roll = rng.random()
        deadline = (None if roll < 0.5 else
                    60.0 if roll < 0.9 else 0.02)
        reqs.append(Request(
            f"r{i}", prompt, max_new_tokens=budget,
            priority=rng.randrange(0, 2), deadline_s=deadline,
            arrival_step=rng.randrange(0, 5),
        ))

    if plan_text:
        plan = chaos.parse_plan(plan_text)
    else:
        duty = rng.choice([0.3, 0.5, 0.6, 0.8])
        plan = chaos.parse_plan(f"fleet@{rng.randrange(1, 3)}=flap:{duty}")

    gc = GuardrailConfig(
        breaker_trip_faults=rng.randrange(2, 5), breaker_window_s=60.0,
        quarantine_s=0.1, quarantine_max_s=2.0,
        hedging=True, hedge_wait_frac=0.9,
        brownout=True, brownout_queue_per_replica=50.0,
    )
    fc = FleetConfig(min_replicas=2, max_replicas=3, autoscale=False,
                     stall_s=60.0, guardrails=gc)
    cache = tempfile.mkdtemp(prefix="tdx_soak_guard_")
    chaos.install(plan)
    old_min = os.environ.get("TDX_CACHE_MIN_COMPILE_S")
    os.environ["TDX_CACHE_MIN_COMPILE_S"] = "0"
    try:
        with tdx_config.override(cache_dir=cache):
            with ServeFleet(cfg, family=family, serve_cfg=scfg,
                            seed=seed % 7, fleet_cfg=fc) as fl:
                fl.start(2, timeout=240.0)
                out = fl.run(reqs, max_seconds=240.0)
                rejected = dict(fl.rejected)
                leaked = [
                    h.idx for h in fl.handles
                    if h.engine is not None and h.engine.k_pages is not None
                    and h.engine.kv.pages_in_use != 0
                ]
                unsettled = bool(fl.partial) or bool(fl._hedges)
    finally:
        chaos.clear()
        compile_service.reset_cache_binding()
        if old_min is None:
            os.environ.pop("TDX_CACHE_MIN_COMPILE_S", None)
        else:
            os.environ["TDX_CACHE_MIN_COMPILE_S"] = old_min
        shutil.rmtree(cache, ignore_errors=True)
    for r in reqs:
        if r.rid in out:
            if r.rid in rejected:
                return ("mismatch",
                        f"{r.rid} both completed and rejected "
                        f"({rejected[r.rid]!r}) plan={plan!r}")
            want, _ = oracle_generate(family, cfg, params, r.tokens,
                                      r.max_new_tokens, r.eos_id)
            if out[r.rid] != want:
                return ("mismatch",
                        f"{r.rid}: fleet={out[r.rid]} oracle={want} "
                        f"plan={plan!r}")
        elif r.rid in rejected:
            rej = rejected[r.rid]
            if rej.reason not in REJECT_REASONS:
                return ("mismatch", f"{r.rid}: untyped rejection {rej!r}")
            if rej.reason == "deadline" and rej.tokens:
                want, _ = oracle_generate(family, cfg, params, r.tokens,
                                          r.max_new_tokens, r.eos_id)
                if list(rej.tokens) != want[:len(rej.tokens)]:
                    return ("mismatch",
                            f"{r.rid}: delivered tokens {rej.tokens} not an "
                            f"oracle prefix of {want} plan={plan!r}")
        else:
            return ("mismatch",
                    f"{r.rid} neither completed nor rejected plan={plan!r}")
    if leaked:
        return ("mismatch", f"KV pages leaked on replicas {leaked} "
                            f"plan={plan!r}")
    if unsettled:
        return ("mismatch", f"unsettled hedge/partial state plan={plan!r}")
    return None


def _run_seed(mode: str, seed: int):
    """Run one oracle; returns None on pass/skip, (kind, message) else."""
    import random

    import pytest
    import torch

    import test_fuzz_replay as F

    try:
        if mode == "whole":
            # Delegate to the pytest oracle so the soak can never drift
            # from what CI pins (rng + data ops, seeded 777).
            F.test_data_ops_and_value_reads_match_eager(seed)
        elif mode == "single":
            # Superset of test_single_tensor_replay_matches_eager:
            # data ops are allowed here too.
            steps = F._gen_program(
                random.Random(seed), allow_rng_ops=False, allow_data_ops=True
            )
            eager = F.run(steps)
            pick = random.Random(seed).randrange(len(eager))
            fakes = F.deferred_init(F.run, steps)
            t = fakes[pick]
            real = (
                F._graph.materialize(t, retain_context=True)
                if F.is_fake(t)
                else t
            )
            if not torch.equal(eager[pick], real):
                return ("mismatch", f"pool[{pick}]")
        elif mode == "bridge":
            F._jax_bridge_oracle(seed, allow_data_ops=True)
        elif mode == "bridge_single":
            F._jax_bridge_oracle(seed, allow_data_ops=True, single_pick=True)
        elif mode == "geom":
            # Geometry-changing in-place ops + any-donor .data + RNG +
            # value reads: whole-program oracle (seed protocol: stream
            # runs uninterrupted through recording-time flushes).
            F.test_geometry_ops_whole_program_matches_eager(seed)
        elif mode == "geom_single":
            F.test_geometry_ops_single_tensor_matches_eager(seed)
        elif mode == "geom_bridge":
            F._jax_bridge_oracle(seed, allow_data_ops=True,
                                 allow_geom_ops=True)
        elif mode == "elastic":
            r = _elastic_oracle(seed, _FAULT_PLAN)
            if r is not None:
                return r
        elif mode == "materialize":
            r = _materialize_oracle(seed, _FAULT_PLAN)
            if r is not None:
                return r
        elif mode == "registry":
            r = _registry_oracle(seed, _FAULT_PLAN)
            if r is not None:
                return r
        elif mode == "serve":
            r = _serve_oracle(seed, _FAULT_PLAN)
            if r is not None:
                return r
        elif mode == "fleet":
            r = _fleet_oracle(seed, _FAULT_PLAN)
            if r is not None:
                return r
        elif mode == "guardrails":
            r = _guardrails_oracle(seed, _FAULT_PLAN)
            if r is not None:
                return r
        elif mode == "reshard":
            r = _reshard_oracle(seed, _FAULT_PLAN)
            if r is not None:
                return r
        elif mode == "serialize":
            import tempfile
            from pathlib import Path

            with tempfile.TemporaryDirectory() as d:
                F.test_serialize_roundtrip_matches_eager(seed, Path(d))
        else:  # pragma: no cover
            raise ValueError(mode)
    except pytest.skip.Exception:
        return None
    except AssertionError as e:
        return ("mismatch", str(e)[:400])
    except Exception as e:
        return ("error", f"{type(e).__name__}: {e}"[:400] + "\n"
                + traceback.format_exc(limit=6)[-800:])
    return None


def _worker(task):
    mode, seed = task
    r = _run_seed(mode, seed)
    return (mode, seed, r)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=600.0,
                    help="wall-clock budget")
    ap.add_argument("--seeds", type=int, default=10**9,
                    help="max seeds per mode (budget usually binds first)")
    ap.add_argument("--start", type=int, default=1_000_000,
                    help="first seed (use fresh ranges across soaks)")
    ap.add_argument("--modes", default=",".join(MODES))
    ap.add_argument("--workers", type=int,
                    default=max(2, min(8, (os.cpu_count() or 4) - 2)))
    ap.add_argument("--log", default=os.path.join(REPO, "tools",
                                                  "soak_failures.jsonl"))
    ap.add_argument("--fault-plan", default=None,
                    help="chaos plan for --modes elastic/materialize/"
                         "registry/serve/fleet/guardrails/reshard (grammar: "
                         "torchdistx_tpu.chaos / docs/robustness.md); "
                         "default: a seeded-random plan per seed")
    ap.add_argument("--platform", choices=("cpu", "default"), default="cpu",
                    help="jax backend for elastic-only soaks: 'default' "
                         "soaks recovery on the real accelerator "
                         "(tpu_watch windows); fuzz modes always force "
                         "cpu regardless")
    args = ap.parse_args()
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    for m in modes:
        if m not in MODES:
            ap.error(f"unknown mode {m!r} (choose from {MODES})")

    def tasks():
        for i in range(args.seeds):
            for m in modes:
                yield (m, args.start + i)

    t0 = time.time()
    done = {m: 0 for m in modes}
    failures = 0
    ctx = mp.get_context("spawn")
    # No with-block: Pool.__exit__ re-JOINS a terminated pool, which can
    # deadlock on py3.12 spawn pools whose worker died mid-send (observed:
    # a 2h soak hung 40+ min past its budget, summary never printed).
    # Cleanup is an unconditional terminate (never join) in the finally
    # below, plus a hard os._exit at the __main__ site so interpreter
    # atexit can't re-join either.
    platform = ("cpu" if any(m != "elastic" for m in modes)
                else args.platform)
    pool = ctx.Pool(args.workers, initializer=_init_worker,
                    initargs=(args.fault_plan, platform))
    try:
        # chunksize must stay 1: with chunksize>1 imap_unordered returns
        # a plain unchunking generator without .next(timeout) (py3.12).
        it = pool.imap_unordered(_worker, tasks())
        while True:
            # next(timeout=...) so the budget fires even if a worker
            # hangs (an XLA compile deadlock must not run the soak past
            # budget).
            remaining = args.seconds - (time.time() - t0)
            if remaining <= 0:
                break
            try:
                mode, seed, r = it.next(timeout=max(1.0, remaining))
            except (mp.TimeoutError, StopIteration):
                break
            done[mode] += 1
            if r is not None:
                failures += 1
                rec = {"mode": mode, "seed": seed, "kind": r[0],
                       "detail": r[1], "ts": time.time()}
                print(f"FAIL {mode} seed={seed}: {r[1][:160]}", flush=True)
                with open(args.log, "a") as f:
                    f.write(json.dumps(rec) + "\n")
            n = sum(done.values())
            if n % 500 == 0:
                rate = n / (time.time() - t0)
                print(f"[{time.time()-t0:7.0f}s] {n} programs "
                      f"({rate:.1f}/s), {failures} failures", flush=True)
    finally:
        pool.terminate()  # every exit path: budget, exhaustion, exception
    total = sum(done.values())
    print(json.dumps({"programs": total, "failures": failures,
                      "seconds": round(time.time() - t0, 1),
                      "per_mode": done}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Hard exit, skipping interpreter teardown: see the pool-creation
    # comment — atexit's re-join of the terminated spawn pool can
    # deadlock; everything worth keeping is already flushed.
    os._exit(rc)
