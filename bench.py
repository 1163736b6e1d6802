"""Benchmark: HF model init → weights resident (and usable) on device.

Primary metric (BASELINE.md config 1): HF GPT-2 125M `deferred_init` →
materialized on the default jax device, against the baseline a reference-
(torchdistX)-style user pays — eager torch CPU initialization of the full
model followed by host→device transfer of every parameter.  Both paths
end with the same "touch" computation (sum of squares of every parameter
on device) so the timed region proves the weights are genuinely resident
and usable, and both run in their own subprocess so peak host RSS is
per-path (BASELINE.md requires RSS).

Extra phases (reported as extra JSON fields, best-effort):

* ``llama``  — largest Llama-class config that comfortably fits the
  single TPU chip: deferred_init → materialize, wall + RSS.
* ``flash``  — pallas flash-attention forward vs stock attention on the
  real chip, achieved TFLOP/s (compiled, not interpret mode); the
  ``flash_bwd`` (training-step fwd+grad) and ``flash_bias`` (T5
  relative-position operand) flavors measure the backward and bias
  kernels the same way.

Output contract: the LAST stdout line is ONE compact JSON headline
{"metric", "value", "unit", "vs_baseline", MFU/speedup keys...} kept
under 1800 bytes so the driver's ~2000-char tail capture always holds a
parseable record (round 4's single giant line outgrew it).  The full
detail JSON precedes it on line 1 and is also written to
``bench_full.json``.  value is the framework path's wall time and
vs_baseline is the speedup factor (baseline_seconds / ours_seconds;
> 1 means faster).

The framework path compiles through the program's persistent compilation
cache, wherever ``torchdistx_tpu.config.compile_cache_dir()`` resolves it
(``JAX_COMPILATION_CACHE_DIR``, else ``TDX_CACHE_DIR``, else
``<checkout>/.jax_cache`` — never committed: what a run compiles is built
from the files git holds).  ``warm_compile_cache`` reports whether the
run actually HIT (no substantial cache entry was written during the timed
region).  The detection is sound for every program this bench compiles —
their entries are 100KB+ and their compiles far exceed the 0.1s
persistence threshold; only a program small enough that cold and warm
differ immaterially (<0.1s compile or <32KB entry) could stamp wrong.

One process for each chip: the parent never initializes a jax backend
(importing the package imports jax, which takes no device) — every phase is
a child process, run one at a time, and a parent that had touched jax
would hold the chip its children need.  No accelerator and no explicit
``TDX_BENCH_PLATFORM`` is a failed run (non-zero exit, no number), never
a CPU run under a device metric's name.
"""

from __future__ import annotations

import json
import os
import resource
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:  # `python /abs/path/bench.py` from another cwd
    sys.path.insert(0, REPO)

# Stdlib-only telemetry (no torch/jax at import): every phase emits spans
# and provenance events through the shared tracer, so with TDX_TRACE_DIR
# set a bench round leaves a Perfetto-loadable trace (summarize with
# tools/tdx_trace.py).  No-ops when telemetry is off.
from torchdistx_tpu import config as tdx_config, observe  # noqa: E402


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _peak_tflops(device_kind: str):
    """Dense bf16 peak TFLOP/s per chip; the table lives with the rest of
    the telemetry (observe.step.PEAK_TFLOPS) so bench MFU and the train
    loop's mfu_est gauge can never disagree.  Unknown kinds return None —
    MFU is omitted, not guessed."""
    return observe.peak_tflops_for(device_kind)


def _cache_entries(min_bytes: int = 32768) -> set:
    """Substantial persistent-cache entries (the init programs are
    ~100 KB+; trivial helpers like the touch reduction are a few KB and
    only get persisted when a loaded host pushes their compile time over
    the persistence threshold — counting those would flap the warm
    stamp run to run)."""
    d = tdx_config.compile_cache_dir() or ""
    try:
        return {
            f for f in os.listdir(d)
            if os.path.getsize(os.path.join(d, f)) >= min_bytes
        }
    except OSError:
        return set()


def _init_jax(cache: bool = False):
    """Import jax, honoring an explicit TDX_BENCH_PLATFORM (e.g. cpu for
    a smoke run) through the config API before backend init.  With
    ``cache`` the program's own persistent compile cache is bound
    (:func:`torchdistx_tpu.config.compile_cache_dir` decides where)."""
    import jax

    plat = os.environ.get("TDX_BENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    if cache:
        from torchdistx_tpu import compile_service

        compile_service.bind_cache()
    return jax


def _virtual_cpu_init(n_devices: int, cache: bool = False):
    """Shared preamble for virtual-mesh phases: an ``n_devices`` CPU
    topology, forced CPU platform, jax initialized."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    os.environ["TDX_BENCH_PLATFORM"] = "cpu"
    return _init_jax(cache=cache)


def _touch(jax, arrays) -> float:
    """Consume every array on device; returns a scalar (and proves the
    parameters are real, resident, and usable)."""
    import jax.numpy as jnp

    total = sum(jnp.sum(jnp.square(a.astype(jnp.float32))) for a in arrays)
    return float(total)


# -- phases (each runs in its own subprocess) -------------------------------


def _phase_baseline(model_cls, config) -> dict:
    """Eager torch init on host + transfer of every parameter + touch —
    the path a reference-style (torchdistX) user pays."""
    jax = _init_jax()
    import torch

    jax.devices()  # backend init outside the timed region
    t0 = time.perf_counter()
    torch.manual_seed(0)
    eager = model_cls(config)
    moved = [jax.device_put(p.detach().numpy()) for p in eager.state_dict().values()]
    jax.block_until_ready(moved)
    _touch(jax, moved)
    return {"t": time.perf_counter() - t0, "rss_mb": _rss_mb()}


def _phase_ours(model_cls, config, param_dtype=None) -> dict:
    """deferred_init (no allocation) → compiled JAX materialization +
    touch.  The timed region is also broken down (record / materialize /
    touch) so a low GB/s figure is attributable: a small model's wall
    time is dominated by the fixed record+dispatch overhead, a large
    model's by the materialize program itself (docs/benchmarks.md
    §Warm-path breakdown)."""
    jax = _init_jax(cache=True)
    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu.jax_bridge import materialize_module_jax

    kw = {}
    if param_dtype is not None:
        import jax.numpy as jnp

        kw["param_dtype"] = getattr(jnp, param_dtype)
    before = _cache_entries()
    jax.devices()
    t0 = time.perf_counter()
    with observe.span("bench.record", category="bench"):
        m = deferred_init(model_cls, config)
    t_record = time.perf_counter() - t0
    with observe.span("bench.materialize", category="bench") as _sp:
        params = materialize_module_jax(m, seed=0, **kw)
        _sp.block_on(params)
    jax.block_until_ready(params)
    t_mat = time.perf_counter() - t0 - t_record
    # Engine-phase split (trace/lower vs compile vs execute) so the
    # reported GB/s stops conflating compile time with transfer: a warm
    # run's execute_s IS the device-side materialize; a cold run's wall
    # is mostly compile.
    from torchdistx_tpu.jax_bridge import materialize as _mat

    stats = _mat.last_run_stats()
    with observe.span("bench.touch", category="bench"):
        _touch(jax, params.values())
    t = time.perf_counter() - t0
    # Warm = the run actually HIT: entries existed and none were added
    # (a cold compile writes its entry; a shipped-but-mismatched cache
    # must not be stamped warm just for existing).
    warm = bool(before) and _cache_entries() == before
    observe.instant(
        "bench.cache_provenance", category="bench",
        warm=warm, backend=jax.default_backend(),
    )
    n_bytes = sum(int(v.size) * v.dtype.itemsize for v in params.values())
    # Measured link bandwidth (probed AFTER the timed region — a few
    # device_puts) turns the GB/s figure into a utilization fraction:
    # the ROADMAP's 100×-gap headline with a real denominator.
    from torchdistx_tpu.observe import costmodel

    link_gbps = costmodel.link_bandwidth_gbps()
    gbps = n_bytes / t / 1e9
    return {
        "t": t,
        "record_s": round(t_record, 3),
        "materialize_s": round(t_mat, 3),
        "touch_s": round(t - t_record - t_mat, 3),
        "rss_mb": _rss_mb(),
        "warm": warm,
        "n_params": sum(int(v.size) for v in params.values()),
        **({"param_dtype": param_dtype} if param_dtype else {}),
        # Parameter bytes landed in device memory per second of the
        # timed region (conservative: the region also includes the
        # touch reduction) — the materialize-throughput figure the
        # charter's single-chip judging asks for.
        "materialize_gbps": round(gbps, 3),
        **({
            "link_bandwidth_gbps": round(link_gbps, 3),
            "materialize_link_utilization": round(gbps / link_gbps, 5),
        } if link_gbps else {}),
        # Compiler-reported accounting for the init program(s): measured
        # FLOPs and the largest single-program device footprint
        # (observe.costmodel via materialize.last_run_stats).
        **({"materialize_xla_gflops": round(stats["xla_flops"] / 1e9, 3)}
           if stats.get("xla_flops") else {}),
        **({"materialize_peak_hbm_mb": round(stats["xla_peak_bytes"] / 1e6, 1)}
           if stats.get("xla_peak_bytes") else {}),
        **({
            "materialize_mode": stats.get("mode"),
            "materialize_n_programs": stats.get("n_programs"),
            "materialize_lower_s": round(stats.get("lower_s", 0.0), 3),
            "materialize_compile_s": round(stats.get("compile_s", 0.0), 3),
            "materialize_execute_s": round(stats.get("execute_s", 0.0), 3),
            "materialize_overlap": stats.get("overlap"),
            # Bytes over EXECUTE time alone: the device-side rate,
            # comparable warm-to-warm across rounds regardless of how
            # much compile the cold path paid.  Suppressed for cold
            # PIPELINED runs: there execute_s is only the execution not
            # hidden behind concurrent compiles, so bytes/execute_s
            # would overstate the true device rate.
            **({"materialize_exec_gbps": round(
                n_bytes / stats["execute_s"] / 1e9, 3)}
               if stats.get("execute_s") and (
                   stats.get("mode") == "monolithic"
                   or set(stats.get("cache", {})) == {"hit"}
               ) else {}),
            # Transport-layer accounting (docs/performance.md
            # §transport): donated commit bytes, commit/transfer time
            # hidden behind other groups' execution, and per-sharding
            # batched device_put dispatches (resume path).
            **({"materialize_bytes_donated": int(stats["bytes_donated"])}
               if stats.get("bytes_donated") is not None else {}),
            **({"materialize_transfer_overlap": stats["transfer_overlap"]}
               if stats.get("transfer_overlap") is not None else {}),
            **({"materialize_device_put_batches":
                int(stats["device_put_batches"])}
               if stats.get("device_put_batches") is not None else {}),
        } if stats else {}),
    }


def phase_gpt2_baseline() -> dict:
    from transformers import GPT2Config, GPT2LMHeadModel

    return _phase_baseline(GPT2LMHeadModel, GPT2Config())


def phase_gpt2_ours() -> dict:
    from transformers import GPT2Config, GPT2LMHeadModel

    return _phase_ours(GPT2LMHeadModel, GPT2Config())


def _llama_config():
    """~1.9B-parameter Llama-class config — comfortably fits one v5e chip
    in f32 while being ~15x GPT-2 (BASELINE config 2 scaled to the chip
    this driver actually has)."""
    from transformers import LlamaConfig

    return LlamaConfig(
        vocab_size=64128,
        hidden_size=2048,
        intermediate_size=5504,
        num_hidden_layers=24,
        num_attention_heads=16,
        num_key_value_heads=16,
        max_position_embeddings=4096,
    )


def phase_llama_ours() -> dict:
    from transformers import LlamaForCausalLM

    return _phase_ours(LlamaForCausalLM, _llama_config())


def _llama_big_config():
    """The Llama-2-7B card (6.74B params) — the largest llama-class
    config that fits one v5e chip under the bridge's bf16 param policy.

    HBM-fit math (VERDICT r4 weak #5, BASELINE config 2 v5e-adjusted):
    v5e exposes 16 GB HBM.  Llama-3-8B is 8.03B params = 16.06 GB in
    bf16 — over the ceiling before workspace, so the 8B card cannot fit
    a v5e chip in ANY dtype this framework could honestly claim; the
    v5p chip BASELINE names has 95 GB and takes it easily.  Llama-2-7B
    at 6.74B params = 13.48 GB bf16 leaves ~2.5 GB for the init
    program's workspace (the bf16 cast happens INSIDE the program —
    materialize.py:_cast_outputs — so f32 copies of the params never
    exist in HBM).  TDX_BIG_LLAMA_LAYERS overrides the depth for
    smaller-HBM smoke runs."""
    from transformers import LlamaConfig

    return LlamaConfig(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=11008,
        num_hidden_layers=int(os.environ.get("TDX_BIG_LLAMA_LAYERS", "32")),
        num_attention_heads=32,
        num_key_value_heads=32,
        max_position_embeddings=4096,
    )


def phase_llama_big_ours() -> dict:
    from transformers import LlamaForCausalLM

    return _phase_ours(LlamaForCausalLM, _llama_big_config(),
                       param_dtype="bfloat16")


def phase_llama_baseline() -> dict:
    from transformers import LlamaForCausalLM

    return _phase_baseline(LlamaForCausalLM, _llama_config())


def _phase_sharded(model_cls, config) -> dict:
    """deferred_init → sharded materialization over an 8-device virtual
    CPU mesh (BASELINE configs 4-5 run on pod slices; the virtual mesh
    proves the same sharded program end-to-end on this single-host
    driver).  Runs in a subprocess with the forced CPU platform."""
    jax = _virtual_cpu_init(8, cache=True)
    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu.jax_bridge import materialize_module_jax
    from torchdistx_tpu.parallel import fsdp_plan, make_mesh

    mesh = make_mesh({"fsdp": 4, "tp": 2})
    # HF torch param names (encoder.block.0...weight) — use the
    # name-agnostic size-based plan, as a torchdistX user would.
    plan = fsdp_plan(min_size=4096)
    before = _cache_entries()
    t0 = time.perf_counter()
    m = deferred_init(model_cls, config)
    params = materialize_module_jax(m, mesh=mesh, plan=plan, seed=0)
    jax.block_until_ready(params)
    t = time.perf_counter() - t0
    return {
        "t": t,
        "rss_mb": _rss_mb(),
        "warm": bool(before) and _cache_entries() == before,
        "n_params": sum(int(v.size) for v in params.values()),
        "n_sharded": sum(
            1 for v in params.values()
            if not getattr(v.sharding, "is_fully_replicated", True)
        ),
    }


def phase_t5_sharded() -> dict:
    from transformers import T5Config, T5ForConditionalGeneration

    # T5-11B's structure at a virtual-mesh-friendly size (BASELINE cfg 4).
    return _phase_sharded(
        T5ForConditionalGeneration,
        T5Config(d_model=512, d_ff=2048, num_layers=8, num_heads=8,
                 vocab_size=32128, d_kv=64),
    )


def phase_mixtral_sharded() -> dict:
    from transformers import MixtralConfig, MixtralForCausalLM

    # Mixtral 8x7B's structure: 8 experts per layer (BASELINE cfg 5).
    return _phase_sharded(
        MixtralForCausalLM,
        MixtralConfig(hidden_size=256, intermediate_size=512,
                      num_hidden_layers=4, num_attention_heads=8,
                      num_key_value_heads=4, vocab_size=32000,
                      num_local_experts=8, num_experts_per_tok=2),
    )


def phase_llama70b_lower() -> dict:
    """North-star host-side half (BASELINE config 3): deferred_init a TRUE
    Llama-3-70B (70.6B params, zero storage) and lower its complete
    64-way-sharded (fsdp×tp) init program — what a login host does before
    shipping the program to a v5p-64.  Budgets: <60 s wall, <32 GB RSS."""
    _host64_init()
    from transformers import LlamaConfig, LlamaForCausalLM

    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu.parallel import fsdp_plan, make_mesh

    cfg = LlamaConfig(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64, num_key_value_heads=8,
        max_position_embeddings=8192,
    )
    t0 = time.perf_counter()
    m = deferred_init(LlamaForCausalLM, cfg)
    t_record = time.perf_counter() - t0
    n_params = sum(p.numel() for p in m.parameters())

    import jax as _jax

    from torchdistx_tpu.jax_bridge.materialize import (
        _init_and_shardings,
        named_fake_tensors,
    )

    mesh = make_mesh({"fsdp": 8, "tp": 8})
    names, init_fn, out_shardings = _init_and_shardings(
        named_fake_tensors(m), mesh, fsdp_plan(min_size=65536)
    )
    jitted = _jax.jit(init_fn, out_shardings=out_shardings)
    return _lower_export_tpu(
        jitted, names, t_record, n_params, _jax.random.PRNGKey(0)
    )


def _host64_init() -> None:
    """True-scale host-side preamble: the 64-device pod-slice topology."""
    _virtual_cpu_init(64)


def _lower_export_tpu(jitted, names, t_record, n_params, *args) -> dict:
    """Shared host-side tail for the true-scale phases: time
    ``jitted.lower`` (trace+lowering) and then ONLY the cross-platform
    export/serialize of the same program (no re-trace hidden in the
    number), returning the common key schema."""
    from jax import export as jax_export

    from torchdistx_tpu.jax_bridge.export import _wrap_payload

    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    exp = jax_export.export(jitted, platforms=["tpu"])(*args)
    payload = _wrap_payload(exp, list(names), ("tpu",))
    t_export = time.perf_counter() - t0
    assert lowered is not None  # both artifacts exist
    return {
        "record_s": round(t_record, 2),
        "lower_s": round(t_lower, 2),
        "export_tpu_s": round(t_export, 2),
        "export_mb": round(len(payload) / 1e6, 2),
        "n_params": n_params,
        "n_outputs": len(names),
        "rss_mb": round(_rss_mb(), 1),
    }


def phase_t5_11b_lower() -> dict:
    """BASELINE config 4 at TRUE scale: deferred_init HF T5-11B (11.3B
    params, zero storage) and lower + export-for-TPU its complete 64-way
    GSPMD **2D**-sharded (fsdp×tp on the two largest dims of every
    tensor) init program — what a login host ships to the pod slice."""
    _host64_init()
    import jax as _jax
    from transformers import T5Config, T5ForConditionalGeneration

    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu.jax_bridge.materialize import (
        _init_and_shardings,
        named_fake_tensors,
    )
    from torchdistx_tpu.parallel import gspmd_2d_plan, make_mesh

    # True T5-11B card: d_model 1024, d_ff 65536, 24+24 layers, 128 heads
    # of d_kv 128 (the 11B head count exceeds d_model/d_kv by design).
    cfg = T5Config(
        vocab_size=32128, d_model=1024, d_kv=128, d_ff=65536,
        num_layers=24, num_heads=128,
    )
    t0 = time.perf_counter()
    m = deferred_init(T5ForConditionalGeneration, cfg)
    t_record = time.perf_counter() - t0
    n_params = sum(p.numel() for p in m.parameters())

    mesh = make_mesh({"fsdp": 8, "tp": 8})
    names, init_fn, out_shardings = _init_and_shardings(
        named_fake_tensors(m), mesh, gspmd_2d_plan(min_size=65536)
    )
    jitted = _jax.jit(init_fn, out_shardings=out_shardings)
    return _lower_export_tpu(
        jitted, names, t_record, n_params, _jax.random.PRNGKey(0)
    )


def phase_mixtral_8x7b_lower() -> dict:
    """BASELINE config 5 at TRUE scale, via the JAX-native frontend:
    record Mixtral-8×7B's init (46.7B params) as DeferredArrays and
    lower + export-for-TPU the 64-way (ep×fsdp) init program.  The
    stacked expert dim [L, E, ...] is sharded over ``ep`` — true
    PER-EXPERT sharding, each expert's weights materializing directly
    on its expert-parallel group."""
    _host64_init()
    import jax as _jax
    import jax.numpy as _jnp

    from torchdistx_tpu.abstract import build_materialize_fn
    from torchdistx_tpu.abstract import deferred_init as jx_deferred_init
    from torchdistx_tpu.abstract import is_fake
    from torchdistx_tpu.models import MIXTRAL_8X7B, decoder_lm_plan, make_mixtral
    from torchdistx_tpu.parallel import make_mesh

    model = make_mixtral(MIXTRAL_8X7B)
    toks = _jnp.zeros((1, 8), _jnp.int32)
    t0 = time.perf_counter()
    fakes = jx_deferred_init(model.init, _jax.random.PRNGKey(0), toks)
    t_record = time.perf_counter() - t0
    leaves = [f for f in _jax.tree.leaves(fakes, is_leaf=is_fake)]
    n_params = sum(int(f.size) for f in leaves)

    mesh = make_mesh({"ep": 8, "fsdp": 8})
    jitted, _ = build_materialize_fn(
        fakes, mesh=mesh, plan=decoder_lm_plan(tp=None)
    )
    return _lower_export_tpu(
        jitted, [f.path for f in leaves], t_record, n_params
    )


def _chain_iters(env_name: str, default: str):
    """(n_lo, n_hi) trip counts for the chain scheme, validated."""
    n_lo, n_hi = _env_ints(env_name, default, 2)
    if n_hi <= n_lo:
        raise ValueError(f"{env_name}: need n_hi > n_lo, got {n_lo},{n_hi}")
    return n_lo, n_hi


def _chain_time(jnp, g, carry, n_lo: int, n_hi: int,
                repeats: int | None = None) -> float:
    """Per-iteration seconds via the chain scheme: ``g(carry, n)`` runs
    n data-dependent steps inside ONE jitted program (dynamic trip
    count — a single compile serves both n values); differencing the
    two wall times cancels dispatch and fetch latency.
    THE timing harness for every chained phase (flash flavors,
    train_mfu) — methodology edits land here once.

    The lo/hi pair is repeated and the smallest positive delta wins,
    mirroring autotune._measure: a single host hiccup (a GC
    pause) during one trip must not shift a published
    number — train_mfu differences only n_hi-n_lo=3 steps, where one
    spike moves the charter-judged MFU noticeably.  All-nonpositive
    deltas are pure noise; raise rather than publish junk."""
    if repeats is None:
        repeats = int(os.environ.get("TDX_CHAIN_REPEATS", "3"))
    lo = jnp.asarray(n_lo, jnp.int32)
    hi = jnp.asarray(n_hi, jnp.int32)
    float(g(carry, lo))  # compile + warm
    float(g(carry, hi))
    deltas = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(g(carry, lo))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(g(carry, hi))
        t_hi = time.perf_counter() - t0
        deltas.append((t_hi - t_lo) / (n_hi - n_lo))
    pos = [d for d in deltas if d > 0]
    if not pos:
        raise RuntimeError(
            f"chain timing produced no positive delta across {repeats} "
            f"repeats ({deltas}): host noise swamped the measurement"
        )
    return min(pos)


def _env_ints(name: str, default: str, n: int):
    raw = os.environ.get(name) or default
    vals = [int(x) for x in raw.split(",")]
    if len(vals) != n:
        raise ValueError(f"{name}={raw!r}: expected {n} comma-separated ints")
    return vals


def _first_fitting_blocks(bench_fn, mk_step, mk_flash, ladder):
    """Measure the first (block_q, block_k) candidate that actually
    compiles, walking ``ladder`` in preference order.

    Mosaic rejects block configs whose operand tiles overrun the chip's
    scoped vmem (v5e: 16MB — the [1024, 1024] bias flavor lost by 576K
    in the round-4 hardware capture), and the budget varies by chip
    generation, so a static table can't be trusted.  Returns
    ``(seconds, (bq, bk), demote_reason)`` where ``demote_reason`` is
    None, or — when a larger candidate failed to fit — the
    classification trigger plus message tail, so a helper-subprocess
    crash with a NON-vmem cause that rode the broad trigger is
    auditable in the published JSON; re-raises the last error if none
    fit."""
    from torchdistx_tpu.ops.autotune import _vmem_trigger

    last_err = None
    reason = None
    for bq, bk in ladder:
        try:
            t = bench_fn(mk_step(mk_flash(block_q=bq, block_k=bk)))
            return t, (bq, bk), reason
        except Exception as e:
            trigger = _vmem_trigger(e)
            if trigger is None:
                raise  # only a vmem overrun is a reason to step down
            last_err = e
            if reason is None:
                reason = f"{trigger}: …{str(e)[-90:]}"
    raise last_err


def _flash_phase(mode: str) -> dict:
    """Shared runner for the flash kernel phases (one schema, one timing
    methodology, three workloads):

    * ``fwd``  — causal forward, the model hot loop;
    * ``bwd``  — forward + grad wrt (q, k, v), the training-step shape;
    * ``bias`` — non-causal forward with a [H, S, S] f32 additive bias
      (T5 relative positions), the kernels' fourth operand stream.

    Timing methodology: dispatch is asynchronous and a value fetch pays
    a host round-trip, so each measurement
    chains N data-dependent iterations inside one jit (the attention
    output feeds back as q; in bwd mode all three cotangents feed back so
    no backward kernel can be hoisted) and differences two N values —
    constant latency and dispatch cost cancel, leaving pure device time
    per iteration.

    Dynamic trip count: ONE compiled program serves both N values
    (fori_loop with a traced bound lowers to while_loop), so each
    attention flavor pays a single Mosaic/XLA compile — cold compiles
    are the dominant cost.
    """
    jax = _init_jax(cache=True)
    import jax.numpy as jnp
    from jax import lax

    from torchdistx_tpu.models.layers import default_attention
    from torchdistx_tpu.ops.flash_attention import make_flash_attention

    # Overridable so the phases can be driven end-to-end off-accelerator
    # (pallas interpret mode is far too slow at the real shape on CPU).
    B, H, S, D = _env_ints("TDX_FLASH_SHAPE", "4,16,2048,64", 4)

    # Block sizes: per-workload defaults measured on v5e at the default
    # shape IN THIS PHASE'S chained-step context (see docs/benchmarks.md
    # §Block sizes): isolated-kernel sweep winners did not transfer —
    # fwd (2048, 2048) measured 2.3x faster standalone but vmem-demoted
    # or hung the phase's fori_loop program, and bwd (512, 2048)'s
    # standalone 2.6x inverted to 0.8x in the realistic
    # fwd+3-cotangent chain — so fwd/bwd keep the reliably-landing
    # 1024x1024 and only the bias flavor (512x1024, 15% better MFU
    # on-chip in-phase) changes.  On an UNKNOWN accelerator kind — or
    # when TDX_BENCH_TUNE=1 — run the cached autotuner so the phase
    # reports the chip's best blocks instead of another chip's; on
    # known kinds skip it (each candidate costs a cold Mosaic
    # compile).  Configs that don't fit a chip's vmem demote
    # down the ladder below.
    kind = jax.devices()[0].device_kind
    bq, bk = {
        "fwd": (1024, 1024), "bwd": (1024, 1024), "bias": (512, 1024),
    }[mode]
    autotuned = False
    known = any(s in kind.lower() for s in ("v5 lite", "v5e", "v5litepod"))
    if jax.default_backend() != "cpu" and (
        os.environ.get("TDX_BENCH_TUNE") == "1" or not known
    ):
        from torchdistx_tpu.ops.autotune import tune_flash_blocks

        try:
            bq, bk = tune_flash_blocks(
                batch=B, seq_len=S, heads=H, head_dim=D,
                causal=(mode != "bias"), dtype=jnp.bfloat16,
                workload=mode,  # time THIS phase's kernels, not fwd's
            )
            autotuned = True
        except Exception:
            pass  # defaults are sound on every kind tested so far
    forced_blocks = os.environ.get("TDX_FLASH_BLOCKS")
    if forced_blocks:
        # Experiment knob (tools/flash_inphase_probe.py): measure THIS
        # config in the honest chained context instead of the default.
        # The demotion ladder below still applies from the forced start.
        bq, bk = _env_ints("TDX_FLASH_BLOCKS", forced_blocks, 2)
        autotuned = False
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D), jnp.bfloat16)
    bias = (
        jax.random.normal(jax.random.PRNGKey(3), (H, S, S), jnp.float32)
        if mode == "bias" else None
    )

    # 2 FLOP/MAC x 2 matmuls, S^2/2 useful plane under causal masking
    # (full plane for the non-causal bias flavor); backward adds 5
    # matmuls (dq, dk, dv + 2 recomputes) for 7 total.
    flops = {
        "fwd": 2.0, "bwd": 7.0, "bias": 4.0,
    }[mode] * B * H * S * S * D

    # bias rides the carry (a jit argument), NOT a closure capture — jit
    # lowers captured jax.Arrays as embedded program constants, and a
    # [H, S, S] f32 constant would bloat exactly the cold compile the
    # methodology note above calls dominant.
    init_carry = (q, k, v) if bias is None else (q, k, v, bias)

    def make_step(fn):
        causal = mode != "bias"
        if mode == "bwd":
            def step(carry):
                x, kk, vv = carry

                def loss(qq, kk, vv):
                    return fn(qq, kk, vv, causal=True).astype(jnp.float32).sum()

                dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(x, kk, vv)
                # Feed every cotangent back so none of the backward
                # kernels can be hoisted or dead-code-eliminated.
                return (
                    (x + 1e-6 * dq).astype(x.dtype),
                    (kk + 1e-6 * dk).astype(kk.dtype),
                    (vv + 1e-6 * dv).astype(vv.dtype),
                )

            return step

        def step(carry):
            x, kk, vv, *rest = carry
            out = fn(
                x, kk, vv, causal=causal, bias=rest[0] if rest else None
            ).astype(x.dtype)
            return (out, kk, vv, *rest)

        return step

    n_lo, n_hi = _chain_iters("TDX_FLASH_ITERS", "2,34")

    def bench(step):
        @jax.jit
        def g(carry, n):
            out = lax.fori_loop(0, n, lambda i, c: step(c), carry)
            return sum(leaf.sum() for leaf in jax.tree.leaves(out))

        return _chain_time(jnp, g, init_carry, n_lo, n_hi)

    # A demotion step needs a smaller estimated tile footprint, which is
    # NOT just the bq*bk scores/bias tile: the k/v (and dk/dv) tiles
    # scale with bk alone, so an equal-product candidate with smaller
    # block_k — e.g. (1024, 512) when (512, 1024) fails — can fit where
    # the failing config did not.  Admit strictly-smaller products plus
    # equal products at smaller block_k; anything equal-or-larger on
    # both axes can only fail the same budget again (at the cost of
    # another cold Mosaic compile).
    ladder = [(bq, bk)] + [
        c for c in ((1024, 1024), (1024, 512), (512, 1024), (512, 512),
                    (512, 256), (256, 256))
        if c[0] * c[1] < bq * bk or (c[0] * c[1] == bq * bk and c[1] < bk)
    ]
    t_flash, (bq, bk), demote_reason = _first_fitting_blocks(
        bench, make_step, make_flash_attention, ladder
    )
    t_ref = bench(make_step(default_attention))
    peak = _peak_tflops(kind)
    out = {
        "flash_ms": round(t_flash * 1e3, 3),
        "ref_ms": round(t_ref * 1e3, 3),
        "flash_tflops": round(flops / t_flash / 1e12, 2),
        "ref_tflops": round(flops / t_ref / 1e12, 2),
        "speedup": round(t_ref / t_flash, 3),
        "device_kind": kind,
        "blocks": [bq, bk],
        **({"autotuned": True} if autotuned else {}),
        **({"blocks_forced": True} if forced_blocks else {}),
        **({"vmem_demoted": True, "demote_reason": demote_reason}
           if demote_reason else {}),
    }
    if peak is not None:
        # Achieved / peak dense-bf16 — the MFU the charter judges.
        out["mfu"] = round(flops / t_flash / 1e12 / peak, 4)
        out["ref_mfu"] = round(flops / t_ref / 1e12 / peak, 4)
    return out


def phase_flash() -> dict:
    return _flash_phase("fwd")


def phase_flash_bwd() -> dict:
    return _flash_phase("bwd")


def phase_flash_bias() -> dict:
    return _flash_phase("bias")


def phase_train_mfu() -> dict:
    """End-to-end single-chip training MFU on a llama-class model — the
    model-level complement to the flash phases' kernel-level MFU (the
    charter judges single-chip MFU).

    Default config (TDX_TRAIN_SHAPE=B,S,d_model,layers,heads): ~370M
    params (d=1024, L=24, H=16, SwiGLU d_ff=2816, vocab 32000), bf16
    compute / f32 params+Adam, full remat, flash-attention blocks at
    the chip defaults, B=4 x S=2048 tokens per step.  The step is the
    REAL production path: `make_train_step`'s jitted AdamW update
    (value_and_grad over the model, optax update, new state).

    Timing: the chain scheme (state threads through `lax.fori_loop`,
    two trip counts differenced) — identical methodology to the flash
    phases, so dispatch latency cancels.

    FLOP accounting (reported, so the MFU is auditable):
    ``6 * N_matmul * tokens`` for the parameter matmuls (fwd 2 + bwd 4;
    N_matmul excludes the embedding gather but includes the untied LM
    head) plus the causal attention term ``6 * B*H*S^2*Dh * L`` (2 fwd
    + 4 bwd USEFUL matmuls over the S^2/2 plane; the flash backward's
    2 recompute matmuls are implementation cost, excluded).  Remat's
    recompute FLOPs are NOT counted either — MFU counts useful work,
    so rematerialisation honestly lowers it."""
    jax = _init_jax(cache=True)
    import numpy as np
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh

    from torchdistx_tpu.models import make_llama
    from torchdistx_tpu.models.configs import TransformerConfig
    from torchdistx_tpu.ops import make_flash_attention
    from torchdistx_tpu.parallel.train import make_train_step

    B, S, d, L, H = _env_ints("TDX_TRAIN_SHAPE", "4,2048,1024,24,16", 5)
    d_ff = 11 * d // 4  # SwiGLU sizing (~2.75x)
    # remat is a measurement knob (TDX_TRAIN_REMAT=none|full): at this
    # size (~370M params, ~4.4 GB f32 state) the no-remat activations
    # may fit the 16 GB chip, and since the FLOP accounting never
    # counts recompute, remat=none would raise the honest MFU — the
    # capture session measures both and keeps the better REAL number
    # (the JSON records which policy produced it).
    remat = os.environ.get("TDX_TRAIN_REMAT", "full")
    cfg = TransformerConfig(
        vocab_size=32000, d_model=d, n_layers=L, n_heads=H, d_ff=d_ff,
        max_seq_len=S, remat=remat,
    )
    # TDX_TRAIN_FLASH_BLOCKS=bq,bk feeds a probe-confirmed flash config
    # into the charter metric's attention (tools/flash_inphase_probe.py
    # finds candidates; only in-phase-confirmed winners belong here).
    tb = os.environ.get("TDX_TRAIN_FLASH_BLOCKS")
    if tb:
        tbq, tbk = _env_ints("TDX_TRAIN_FLASH_BLOCKS", tb, 2)
        attn = make_flash_attention(block_q=tbq, block_k=tbk)
    else:
        attn = make_flash_attention()
    model = make_llama(cfg, attn_fn=attn)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size
    )
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    init_state, train_step, shard_batch = make_train_step(
        model, cfg, mesh, attn_fn=attn,
    )
    state = init_state(params)
    tokens = shard_batch(tokens)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))

    # Spread 2,10: differencing 8 steps (not r4's 3) amortizes any
    # single host hiccup on top of _chain_time's repeat-and-min
    # (ADVICE r4 #2) — ~36 extra steps per run, well under a minute.
    n_lo, n_hi = _chain_iters("TDX_TRAIN_ITERS", "2,10")

    @jax.jit
    def g(state, n):
        out = lax.fori_loop(0, n, lambda i, st: train_step(st, tokens)[0],
                            state)
        # One leaf suffices to gate the fetch; the while-loop body
        # computes the full carry every iteration regardless.
        return jax.tree.leaves(out["params"])[0].sum()

    t = _chain_time(jnp, g, state, n_lo, n_hi)

    Dh = cfg.head_size
    n_matmul = L * (4 * d * d + 3 * d * d_ff) + d * cfg.vocab_size
    # Useful attention matmuls fwd+bwd = 2 + 4 = 6 over the S^2/2
    # causal plane (1 unit == B*H*S^2*Dh flops, matching the flash
    # fwd=2 convention).  NOT the flash_bwd phase's 7: its 2 recompute
    # matmuls are implementation cost, excluded like remat's.
    flops = 6.0 * n_matmul * B * S + 6.0 * B * H * S * S * Dh * L
    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind)
    out = {
        "step_ms": round(t * 1e3, 3),
        "tokens_per_s": round(B * S / t),
        "tflops": round(flops / t / 1e12, 2),
        "n_params": n_params,
        "remat": remat,
        "device_kind": kind,
        "rss_mb": round(_rss_mb(), 1),
    }
    if peak is not None:
        out["mfu"] = round(flops / t / 1e12 / peak, 4)
    # Compiler-derived complement to the analytic accounting above: AOT
    # compile the SAME jitted step once (the persistent cache makes it a
    # one-time cost per device kind) and read XLA's own FLOP count and
    # peak device footprint.  XLA counts FLOPs the hardware RUNS: under
    # remat that includes recompute, so mfu_xla is HFU-flavored and
    # reads high vs the analytic mfu above (which excludes recompute by
    # convention) — both are reported, neither replaces the other.
    # mfu_xla uses measured FLOPs over the same
    # measured step time — the number SimpleFSDP/veScale-style
    # validation wants.  TDX_BENCH_XLA_COST=0 opts out.
    if os.environ.get("TDX_BENCH_XLA_COST", "1") != "0":
        try:
            from torchdistx_tpu.observe import costmodel

            compiled_step = train_step.lower(state, tokens).compile()
            costs = costmodel.program_costs(compiled_step) or {}
            if costs.get("flops"):
                out["xla_flops_per_step"] = costs["flops"]
                out["tflops_xla"] = round(costs["flops"] / t / 1e12, 2)
                if peak is not None:
                    out["mfu_xla"] = round(
                        costs["flops"] / t / 1e12 / peak, 4
                    )
            if costs.get("peak_bytes"):
                out["step_peak_hbm_mb"] = round(costs["peak_bytes"] / 1e6, 1)
        except Exception as e:  # noqa: BLE001 — accounting is best-effort
            out["xla_cost_error"] = f"{type(e).__name__}: {e}"[-120:]
    return out


def phase_materialize_pipeline() -> dict:
    """Materialization-engine A/B on the CPU harness (the acceptance
    phase for the pipelined engine, and `make bench-smoke`'s regression
    gate): cold (fresh empty persistent cache per variant)
    ``materialize_module_jax`` with TDX_MATERIALIZE_PIPELINE=off vs
    =auto on a heterogeneous multi-group model, then a warm =auto pass
    over the auto variant's cache.

    The model's layers all differ in shape (pyramid widths), so instance
    batching cannot collapse them and the monolithic program carries one
    unique chain per layer — the regime where XLA compile goes
    superlinear in module size and the per-group split pays off even
    before thread-level overlap (which needs cores; `n_cpus` is reported
    so a single-core container's ratio is read in context).  Outputs are
    checked bitwise-equal across engines; a mismatch raises, so CI fails
    on parity regressions, not just slowdowns."""
    import shutil
    import tempfile

    # Persist EVERY compiled program regardless of compile speed: on a
    # fast host the small per-group programs compile under jax's 0.1 s
    # persistence threshold and the warm pass would record zero hits.
    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np
    import torch

    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu.jax_bridge import materialize_module_jax
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.jax_bridge import materialize as mat

    K = int(os.environ.get("TDX_PIPE_BENCH_LAYERS", "128"))

    class Pyramid(torch.nn.Module):
        def __init__(self):
            super().__init__()
            widths = [32 + 8 * i for i in range(K)]
            self.layers = torch.nn.ModuleList(
                torch.nn.Linear(widths[i], widths[(i + 1) % K])
                for i in range(K)
            )

    jax.devices()  # backend init outside every timed region
    # Repeat-and-min, interleaved off/auto (the _chain_time rationale: a
    # host hiccup during one rep must not shift the published ratio, and
    # interleaving keeps drift from loading one side).  Every cold rep
    # gets a FRESH empty persistent cache dir.
    reps = int(os.environ.get("TDX_PIPE_BENCH_REPEATS", "3"))
    out = {"n_layers": K, "n_cpus": os.cpu_count(), "repeats": reps}
    values = {}
    times = {"off": [], "auto": []}
    rep_stats = {"off": [], "auto": []}
    last_auto_cache = None
    caches = []
    try:
        for rep in range(reps):
            for mode in ("off", "auto"):
                cache = tempfile.mkdtemp(prefix=f"tdx_pipe_{mode}_")
                caches.append(cache)
                compile_service.reset_cache_binding()  # variants: no shared latch
                with tdx_config.override(
                    materialize_pipeline=mode, cache_dir=cache
                ):
                    m = deferred_init(Pyramid)
                    t0 = time.perf_counter()
                    params = materialize_module_jax(m, seed=0)
                    jax.block_until_ready(params)
                    times[mode].append(time.perf_counter() - t0)
                rep_stats[mode].append(mat.last_run_stats())
                if mode == "auto":
                    last_auto_cache = cache
                if rep == 0:
                    values[mode] = {
                        k: np.asarray(v) for k, v in params.items()
                    }
        _publish_pipeline_phase(out, times, rep_stats)
        # Warm pass: rerun over the last auto cache — per-group entries
        # hit.
        compile_service.reset_cache_binding()
        with tdx_config.override(
            materialize_pipeline="auto", cache_dir=last_auto_cache
        ):
            m = deferred_init(Pyramid)
            t0 = time.perf_counter()
            params = materialize_module_jax(m, seed=0)
            jax.block_until_ready(params)
            out["warm_auto_s"] = round(time.perf_counter() - t0, 3)
        out["warm_cache"] = mat.last_run_stats().get("cache")
    finally:
        # A mid-phase failure must not orphan tmpdirs of compiled XLA
        # binaries or leave the process latched onto one of them.
        compile_service.reset_cache_binding()
        for cache in caches:
            shutil.rmtree(cache, ignore_errors=True)
    bitwise = set(values["off"]) == set(values["auto"]) and all(
        np.array_equal(values["off"][k], values["auto"][k])
        for k in values["off"]
    )
    if not bitwise:
        raise RuntimeError(
            "pipelined materialization is not bitwise-equal to the "
            "monolithic engine on the bench model"
        )
    out["bitwise_equal"] = True
    out["pipeline_speedup"] = round(out["cold_off_s"] / out["cold_auto_s"], 3)
    out["backend"] = "cpu"
    return out


def _publish_pipeline_phase(out: dict, times: dict, rep_stats: dict) -> None:
    """Fold the cold-rep measurements into the phase record.  The
    published breakdown comes from the ARGMIN rep of each mode, so the
    phase split always decomposes the wall time it sits next to (a
    last-rep hiccup must not publish sums exceeding the min wall)."""
    for mode in ("off", "auto"):
        best = min(range(len(times[mode])), key=times[mode].__getitem__)
        stats = rep_stats[mode][best]
        out[f"cold_{mode}_s"] = round(times[mode][best], 3)
        for k in ("lower_s", "compile_s", "execute_s"):
            out[f"cold_{mode}_{k}"] = round(stats.get(k, 0.0), 3)
        if mode == "auto":
            out["n_programs"] = stats.get("n_programs")
            out["workers"] = stats.get("workers")
            out["overlap"] = stats.get("overlap")
        out[f"cold_{mode}_all_s"] = [round(t, 2) for t in times[mode]]


def phase_materialize_bandwidth() -> dict:
    """Transport-layer bandwidth phase (docs/performance.md §transport;
    the ROADMAP's "raw materialize bandwidth" gate): how fast the
    materialize path MOVES bytes once compile is warm and the init math
    is trivially cheap — constant-fill slabs, because threefry RNG on a
    host CPU would measure compute, not transport, and the transport
    layer's roofline target is the link, not the ALU.

    Flow: cold-compile the slab model once per program set (pipelined,
    monolith, bf16-transport) into one shared cache, then
    repeat-and-best a WARM default-config materialize →
    ``materialize_gbps``; probe the host→device link (swept buffer
    sizes) → ``materialize_link_utilization`` with the chosen probe
    size reported; A/B the variants that exercise REAL transport paths
    — overlap depth 1, the monolithic engine, and the bf16 fast path
    with its donated commit program (the slab model carries a buffer so
    a pass-through slot actually donates) — every variant pinned
    bitwise-equal to the default.  The slab fills are small integers,
    exactly representable in bf16, so even the fast path's gate is
    strict equality.  (The per-leaf resume transfer knob has no code
    path in a clean run; tests/test_materialize_transport.py covers
    it.)"""
    import shutil
    import tempfile

    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np
    import torch

    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu.deferred_init import deferred_init
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.jax_bridge import materialize as mat
    from torchdistx_tpu.jax_bridge import materialize_module_jax
    from torchdistx_tpu.observe import costmodel

    total_mb = int(os.environ.get("TDX_BW_BENCH_MB", "256"))
    n_slabs = int(os.environ.get("TDX_BW_BENCH_SLABS", "32"))
    reps = int(os.environ.get("TDX_BW_BENCH_REPEATS", "3"))
    base = max(1024, total_mb * (1 << 20) // 4 // n_slabs)

    class Slabs(torch.nn.Module):
        def __init__(self):
            super().__init__()
            # Distinct sizes defeat instance batching → a real
            # multi-group split, so the double-buffered dispatcher has
            # groups to overlap; one broadcast store per slab keeps the
            # program bandwidth-bound.
            self.slabs = torch.nn.ParameterList(
                torch.nn.Parameter(torch.full((base + 128 * i,),
                                              float(i + 1)))
                for i in range(n_slabs)
            )
            # An f32 BUFFER: ineligible for the init-dtype cast, so the
            # bf16 variant's donated commit program gets a pass-through
            # slot that genuinely aliases+consumes its buffer.
            self.register_buffer("slab_scale", torch.ones(base))

    # The overlap-depth A/B rides the bf16 variant: only groups with
    # commit work enter the double-buffered queue, so depth is inert in
    # default config (which stays fully async by design).
    variants = {
        "default": {},
        "monolith": {"materialize_pipeline": "off"},
        "bf16": {"materialize_init_dtype": "bf16"},
        "bf16_no_overlap": {"materialize_init_dtype": "bf16",
                            "materialize_overlap_depth": 1},
    }
    cache = tempfile.mkdtemp(prefix="tdx_bw_")
    jax.devices()  # backend init outside every timed region
    out = {"n_slabs": n_slabs, "repeats": reps}
    values = {}
    stats = {}
    try:
        compile_service.reset_cache_binding()
        best = {}
        for name, kw in variants.items():
            # resume/registry pinned OFF: an ambient
            # TDX_MATERIALIZE_RESUME_DIR would turn later reps into
            # disk loads and silently change what the promoted
            # bandwidth headline measures.
            over = {"cache_dir": cache, "materialize_pipeline": "auto",
                    "materialize_resume_dir": None, "registry_dir": None}
            over.update(kw)
            if name in ("default", "monolith", "bf16"):
                # The three distinct program SETS; the overlap variant
                # reuses the bf16 set's cache entries (the knob never
                # changes program content — the point of the A/B).
                with tdx_config.override(**over):
                    materialize_module_jax(deferred_init(Slabs), seed=0)
            times = []
            # Same rep count everywhere: ratios between variants must
            # compare best-of-N against best-of-N, not against a single
            # run.
            for _ in range(reps):
                with tdx_config.override(**over):
                    m = deferred_init(Slabs)
                    t0 = time.perf_counter()
                    params = materialize_module_jax(m, seed=0)
                    jax.block_until_ready(params)
                    times.append(time.perf_counter() - t0)
            stats[name] = mat.last_run_stats()
            values[name] = {k: np.asarray(v) for k, v in params.items()}
            best[name] = min(times)  # unrounded: the math below uses it
            out[f"warm_{name}_s"] = round(best[name], 3)
    finally:
        compile_service.reset_cache_binding()
        shutil.rmtree(cache, ignore_errors=True)

    bitwise = all(
        set(values[n]) == set(values["default"]) and all(
            np.array_equal(values[n][k], values["default"][k])
            for k in values["default"]
        )
        for n in variants
    )
    if not bitwise:
        raise RuntimeError(
            "transport variants are not bitwise-equal on the bandwidth "
            "bench model"
        )
    out["bitwise_equal"] = True
    n_bytes = sum(
        int(v.size) * v.dtype.itemsize for v in values["default"].values()
    )
    gbps = n_bytes / best["default"] / 1e9
    out["n_bytes_mb"] = round(n_bytes / 1e6, 1)
    out["materialize_gbps"] = round(gbps, 3)
    out["overlap_speedup"] = round(
        best["bf16_no_overlap"] / best["bf16"], 3
    )
    # Overlap needs a second core to run the commit stream against; on a
    # 1-core container the ratio lands ~0.9-1.0 and reads as a fake
    # regression (ROADMAP), so stamp the record with the context needed
    # to discard it.
    out["host_cpu_count"] = os.cpu_count()
    out["overlap_speedup_reliable"] = (os.cpu_count() or 1) > 1
    link = costmodel.link_bandwidth_gbps()
    if link:
        out["link_bandwidth_gbps"] = round(link, 3)
        out["link_probe_mb"] = costmodel.link_probe_size_mb()
        out["materialize_link_utilization"] = round(gbps / link, 5)
    out["n_programs"] = stats["default"].get("n_programs")
    out["warm_execute_s"] = round(stats["default"].get("execute_s", 0.0), 3)
    # Transport accounting comes from the VARIANT that has transport
    # work: default config runs fully async (bytes_donated 0, overlap 0
    # by design — no phantom metrics), the bf16 variant runs the
    # donated commit pipeline.
    out["bytes_donated"] = stats["bf16"].get("bytes_donated")
    out["transfer_overlap"] = stats["bf16"].get("transfer_overlap")
    out["device_put_batches"] = stats["default"].get("device_put_batches")
    out["backend"] = "cpu"
    return out


def phase_reshard() -> dict:
    """Offline topology-migration throughput (docs/robustness.md
    §Resharding): save a transport-bound checkpoint under an fsdp=4
    layout, rechunk-copy it to a 2x2 gspmd2d layout with
    :func:`torchdistx_tpu.reshard.reshard_checkpoint` (post-copy bitwise
    verify INCLUDED in the timed region — the contract never commits an
    unverified destination, so an honest rate cannot exclude it), and
    report ``reshard_gbps`` over the bytes moved plus the bounded host
    staging peak.  Slab fills, not RNG: the engine's job is moving and
    rechunking bytes through a budgeted staging buffer, so the roofline
    target is disk+memcpy, not the ALU."""
    import shutil
    import tempfile

    jax = _virtual_cpu_init(8)
    import jax.numpy as jnp
    import numpy as np

    from torchdistx_tpu import reshard
    from torchdistx_tpu.parallel.mesh import make_mesh
    from torchdistx_tpu.parallel.sharding import fsdp_plan, gspmd_2d_plan
    from torchdistx_tpu.utils.checkpoint import (
        leaf_storage_name, save_checkpoint,
    )

    total_mb = int(os.environ.get("TDX_RESHARD_BENCH_MB", "128"))
    n_slabs = int(os.environ.get("TDX_RESHARD_BENCH_SLABS", "16"))
    reps = int(os.environ.get("TDX_RESHARD_BENCH_REPEATS", "2"))
    rows = max(8, total_mb * (1 << 20) // 4 // n_slabs // 256)

    mesh_a = make_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    mesh_b = make_mesh({"fsdp": 2, "tp": 2}, devices=jax.devices()[:4])
    plan_a, plan_b = fsdp_plan(min_size=1), gspmd_2d_plan(min_size=1)
    state = {
        f"slab_{i}": jnp.full((rows + 8 * i, 256), float(i + 1), jnp.float32)
        for i in range(n_slabs)
    }
    flat, td = jax.tree_util.tree_flatten_with_path(state)
    state = jax.tree_util.tree_unflatten(td, [
        jax.device_put(
            leaf, plan_a.sharding_for(leaf_storage_name(kp), leaf.shape, mesh_a))
        for kp, leaf in flat
    ])

    d = tempfile.mkdtemp(prefix="tdx_bench_reshard_")
    try:
        save_checkpoint(os.path.join(d, "src"), state)
        best = None
        bytes_moved = peak = chunks = None
        for r in range(reps):
            dst = os.path.join(d, f"dst_{r}")
            t0 = time.perf_counter()
            reshard.reshard_checkpoint(
                os.path.join(d, "src"), plan_b, mesh_b, dst)
            dt = time.perf_counter() - t0
            pl = reshard.plan_reshard(os.path.join(d, "src"), plan_b, mesh_b)
            bytes_moved, chunks = pl.moved_bytes, pl.total_chunks
            peak = reshard.last_transfer_peak_bytes()
            best = dt if best is None else min(best, dt)
            shutil.rmtree(dst, ignore_errors=True)
        total = sum(np.asarray(v).nbytes for v in jax.tree_util.tree_leaves(state))
        return {
            "reshard_gbps": total / best / 1e9,
            "reshard_bytes_moved": bytes_moved,
            "reshard_bytes_total": total,
            "reshard_chunks": chunks,
            "reshard_peak_host_bytes": peak,
            "reshard_s": best,
            "n_leaves": len(jax.tree_util.tree_leaves(state)),
            "repeats": reps,
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def phase_serving() -> dict:
    """Inference-serving phase (docs/serving.md): decode tokens/s
    through the continuous-batching engine, and time-to-first-token for
    a COLD replica bring-up (every program XLA-compiled) vs a
    REGISTRY-WARM one (every program fetched from a pre-published
    artifact registry into a fresh local cache) — the autoscaling story
    the serving runtime exists for, measured.

    Gates (raise ⇒ CI fails, not just a slow number): every request's
    tokens equal the unbatched no-cache oracle, and the warm bring-up
    performs ZERO local compiles."""
    import shutil
    import tempfile

    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np

    import jax.numpy as jnp
    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import observe
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        Request, ServeConfig, oracle_generate, spin_up_replica,
        warm_serving,
    )

    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=8, n_kv_heads=4,
        d_ff=128, max_seq_len=64, dtype=jnp.float32,
    )
    scfg = ServeConfig(max_batch=4, page_size=8, n_pages=48,
                       max_pages_per_seq=4, prefill_buckets=(8, 16))

    def mix():
        rng = np.random.RandomState(0)
        return [
            Request(f"r{i}", [int(t) for t in
                              rng.randint(0, cfg.vocab_size,
                                          size=2 + int(rng.randint(12)))],
                    max_new_tokens=8 + int(rng.randint(8)),
                    arrival_step=i // 2)
            for i in range(8)
        ]

    jax.devices()
    out = {"model_d": cfg.d_model, "n_layers": cfg.n_layers,
           "max_batch": scfg.max_batch, "page_size": scfg.page_size}
    reg = tempfile.mkdtemp(prefix="tdx_serve_bench_reg_")
    caches = []

    def fresh_cache(tag):
        d = tempfile.mkdtemp(prefix=f"tdx_serve_bench_{tag}_")
        caches.append(d)
        return d

    first_token_t = {}

    def on_token(rid, _tok):
        first_token_t.setdefault(rid, time.perf_counter())

    try:
        # COLD: empty cache, no registry — bring-up pays every compile.
        compile_service.reset_cache_binding()
        with tdx_config.override(cache_dir=fresh_cache("cold")):
            t0 = time.perf_counter()
            eng = spin_up_replica(cfg, family="llama", serve_cfg=scfg,
                                  on_token=on_token)
            out["bring_up_cold_s"] = round(time.perf_counter() - t0, 3)
            probe = Request("probe", [7, 3, 11], max_new_tokens=2)
            eng.run([probe])
            out["ttft_cold_s"] = round(first_token_t["probe"] - t0, 3)
            # Throughput: a scripted storm through the warm engine.
            reqs = mix()
            t0 = time.perf_counter()
            results = eng.run(reqs)
            dt = time.perf_counter() - t0
            n_tok = sum(len(results[r.rid]) for r in reqs)
            out["decode_tokens_per_s"] = round(n_tok / dt, 2)
            out["storm_requests"] = len(reqs)
            out["storm_tokens"] = n_tok
            # Measured latency percentiles over the storm (the SLO
            # windows the engine feeds every tick — docs/observability.md
            # §SLOs): what a fleet operator would page on.
            out["slo"] = {
                name: {k: (round(v, 6) if isinstance(v, float) else v)
                       for k, v in stats.items()}
                for name, stats in eng.slo.snapshot().items()
            }
            for r in reqs:
                want, _ = oracle_generate("llama", cfg, eng.params,
                                          r.tokens, r.max_new_tokens)
                if results[r.rid] != want:
                    raise RuntimeError(
                        f"serving output diverged from the unbatched "
                        f"oracle on {r.rid}"
                    )
        out["oracle_equal"] = True

        # WARM: publish the program set, then bring up from a FRESH
        # local cache through the registry.
        compile_service.reset_cache_binding()
        warm_serving("llama", cfg, fresh_cache("pub"), registry_dir=reg,
                     serve_cfg=scfg)
        compile_service.reset_cache_binding()
        observe.enable(True)
        base = {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        with tdx_config.override(cache_dir=fresh_cache("warm"),
                                 registry_dir=reg):
            first_token_t.clear()
            t0 = time.perf_counter()
            eng = spin_up_replica(cfg, family="llama", serve_cfg=scfg,
                                  on_token=on_token)
            out["bring_up_warm_s"] = round(time.perf_counter() - t0, 3)
            probe = Request("probe", [7, 3, 11], max_new_tokens=2)
            eng.run([probe])
            out["ttft_warm_s"] = round(first_token_t["probe"] - t0, 3)
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        miss = (snap.get("tdx.jax.compile_cache_miss", 0)
                - base.get("tdx.jax.compile_cache_miss", 0))
        out["warm_local_compiles"] = int(miss)
        out["warm_bring_up_outcomes"] = eng.bring_up_outcomes
        if miss:
            raise RuntimeError(
                f"registry-warm bring-up paid {int(miss)} local compiles"
            )
        out["ttft_warm_speedup"] = round(
            out["ttft_cold_s"] / out["ttft_warm_s"], 3
        )
    finally:
        observe.enable(None)
        compile_service.reset_cache_binding()
        shutil.rmtree(reg, ignore_errors=True)
        for d in caches:
            shutil.rmtree(d, ignore_errors=True)
    out["backend"] = "cpu"
    return out


def phase_serving_fleet() -> dict:
    """Fleet-serving phase (docs/serving.md §Fleet): the autoscaling
    story measured end to end.  A COLD single-replica bring-up (every
    program XLA-compiled) is the scale-up latency a fleet WITHOUT the
    registry would pay; a registry-warm mid-run ``ServeFleet.scale_up``
    (fresh local cache, every program fetched) is what ours pays —
    ``fleet_scaleup_warm_speedup`` is their ratio.  Then a fixed request
    storm is replayed through the router at 1 → 2 → 4 replicas
    (autoscale pinned off so the replica count is the only variable) for
    decode tokens/s; ``fleet_scaling_efficiency_2r`` = tps@2 / tps@1.

    Gates (raise ⇒ CI fails, not just a slow number): every storm
    response equals the unbatched no-cache oracle — including one more
    2-replica storm with a chaos kill (``fleet@2=raise``) mid-batch
    where the router must requeue onto survivors — every post-publish
    bring-up performs ZERO local compiles, and the warm scale-up is
    faster than the cold one."""
    import shutil
    import tempfile

    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np

    import jax.numpy as jnp
    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import chaos, observe
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        FleetConfig, Request, ServeConfig, ServeFleet, oracle_generate,
        spin_up_replica, warm_serving,
    )

    # Heavier per-token math than phase_serving's model: decode steps
    # must dominate the controller/GIL overhead for replica-thread
    # parallelism (XLA releases the GIL while executing) to show up in
    # tokens/s.
    cfg = TransformerConfig(
        vocab_size=256, d_model=96, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=192, max_seq_len=64, dtype=jnp.float32,
    )
    scfg = ServeConfig(max_batch=2, page_size=8, n_pages=32,
                       max_pages_per_seq=4, prefill_buckets=(8, 16))

    def storm(tag):
        rng = np.random.RandomState(7)
        return [
            Request(f"{tag}{i}", [int(t) for t in
                                  rng.randint(0, cfg.vocab_size,
                                              size=2 + int(rng.randint(12)))],
                    max_new_tokens=12 + int(rng.randint(5)),
                    arrival_step=0)
            for i in range(16)
        ]

    def check_oracle(fl, reqs, results):
        for r in reqs:
            want, _ = oracle_generate("llama", cfg, fl.params,
                                      r.tokens, r.max_new_tokens)
            if results[r.rid] != want:
                raise RuntimeError(
                    f"fleet output diverged from the unbatched oracle "
                    f"on {r.rid}"
                )

    jax.devices()
    out = {"model_d": cfg.d_model, "n_layers": cfg.n_layers,
           "max_batch": scfg.max_batch,
           "host_cpu_count": os.cpu_count()}
    reg = tempfile.mkdtemp(prefix="tdx_fleet_bench_reg_")
    caches = []

    def fresh_cache(tag):
        d = tempfile.mkdtemp(prefix=f"tdx_fleet_bench_{tag}_")
        caches.append(d)
        return d

    try:
        # COLD: empty cache, no registry — the scale-up latency a fleet
        # without artifact sharing pays for every new replica.
        compile_service.reset_cache_binding()
        with tdx_config.override(cache_dir=fresh_cache("cold")):
            t0 = time.perf_counter()
            spin_up_replica(cfg, family="llama", serve_cfg=scfg)
            out["bring_up_cold_s"] = round(time.perf_counter() - t0, 3)

        # Publish the program set once, then every fleet below brings
        # replicas up through the registry into one fresh local cache.
        # Between stages, drop jax's in-memory executable caches: this
        # one process runs ~11 replica bring-ups plus per-shape oracle
        # programs, and the retained JIT code regions pile up mappings
        # until mmap hits vm.max_map_count (ENOMEM with RAM to spare).
        # Rebuilds stay off the compiler — they re-load from the local
        # disk cache, so the zero-local-compile gate is unaffected.
        jax.clear_caches()
        compile_service.reset_cache_binding()
        warm_serving("llama", cfg, fresh_cache("pub"), registry_dir=reg,
                     serve_cfg=scfg)
        compile_service.reset_cache_binding()
        observe.enable(True)
        base = {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        fleet_cache = fresh_cache("fleet")

        # Warm mid-run scale-up, timed per replica by the fleet itself.
        with tdx_config.override(cache_dir=fleet_cache, registry_dir=reg):
            with ServeFleet(cfg, family="llama", serve_cfg=scfg,
                            fleet_cfg=FleetConfig(min_replicas=1,
                                                  max_replicas=2,
                                                  autoscale=False,
                                                  stall_s=120.0)) as fl:
                fl.start(1, timeout=240.0)
                h = fl.scale_up(wait=True, timeout=240.0)
                out["fleet_scale_up_warm_s"] = round(h.bring_up_seconds, 3)
                if not h.bring_up_warm:
                    raise RuntimeError(
                        f"warm scale-up hit the compiler: "
                        f"{h.engine.bring_up_outcomes}"
                    )
        out["fleet_scaleup_warm_speedup"] = round(
            out["bring_up_cold_s"] / out["fleet_scale_up_warm_s"], 3
        )
        if out["fleet_scaleup_warm_speedup"] <= 1:
            raise RuntimeError(
                f"registry-warm scale-up not faster than cold compile: "
                f"{out['fleet_scale_up_warm_s']}s vs "
                f"{out['bring_up_cold_s']}s"
            )

        # The same storm through 1 → 2 → 4 replicas, autoscale off.
        tps = {}
        with tdx_config.override(cache_dir=fleet_cache, registry_dir=reg):
            for n in (1, 2, 4):
                jax.clear_caches()
                with ServeFleet(cfg, family="llama", serve_cfg=scfg,
                                fleet_cfg=FleetConfig(min_replicas=n,
                                                      max_replicas=n,
                                                      autoscale=False,
                                                      stall_s=120.0)) as fl:
                    fl.start(n, timeout=240.0)
                    reqs = storm(f"s{n}_")
                    t0 = time.perf_counter()
                    results = fl.run(reqs, max_seconds=240.0)
                    dt = time.perf_counter() - t0
                    check_oracle(fl, reqs, results)
                    n_tok = sum(len(results[r.rid]) for r in reqs)
                    tps[n] = round(n_tok / dt, 2)
            out["fleet_tokens_per_s"] = {str(n): v for n, v in tps.items()}
            out["storm_requests"] = 16
            out["storm_tokens"] = n_tok
            out["fleet_scaling_efficiency_2r"] = round(tps[2] / tps[1], 3)
            if (os.cpu_count() or 1) >= 2 and tps[2] <= tps[1]:
                raise RuntimeError(
                    f"2 replicas no faster than 1: {tps[2]} <= {tps[1]} "
                    f"tokens/s"
                )

            # Chaos: the same storm with replica 2 killed mid-batch —
            # the fault may cost latency, never a token.
            jax.clear_caches()
            with ServeFleet(cfg, family="llama", serve_cfg=scfg,
                            fleet_cfg=FleetConfig(min_replicas=2,
                                                  max_replicas=2,
                                                  autoscale=False,
                                                  stall_s=120.0)) as fl:
                fl.start(2, timeout=240.0)
                chaos.install("fleet@2=raise")
                try:
                    reqs = storm("k")
                    results = fl.run(reqs, max_seconds=240.0)
                finally:
                    chaos.clear()
                check_oracle(fl, reqs, results)
                if fl.rejected:
                    raise RuntimeError(
                        f"chaos storm rejected requests: {fl.rejected}"
                    )
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        out["chaos_requeued"] = int(
            snap.get("tdx.fleet.requeued_requests", 0)
            - base.get("tdx.fleet.requeued_requests", 0))
        if out["chaos_requeued"] < 1:
            raise RuntimeError("chaos kill never forced a requeue")
        miss = (snap.get("tdx.jax.compile_cache_miss", 0)
                - base.get("tdx.jax.compile_cache_miss", 0))
        out["warm_local_compiles"] = int(miss)
        if miss:
            raise RuntimeError(
                f"registry-warm fleet paid {int(miss)} local compiles"
            )
        out["oracle_equal"] = True
    finally:
        observe.enable(None)
        compile_service.reset_cache_binding()
        shutil.rmtree(reg, ignore_errors=True)
        for d in caches:
            shutil.rmtree(d, ignore_errors=True)
    out["backend"] = "cpu"
    return out


def phase_guardrails() -> dict:
    """Guardrail phase (docs/serving.md §Guardrails): the SAME
    mixed-priority storm is driven twice through a 2-replica fleet whose
    replica 2 flaps on seven of every eight batches
    (``fleet@2=flap:0.875`` — intermittent enough that kill-detection
    never fires), once with guardrails disarmed and once with the full
    guardrail set (circuit breaker + quarantine-and-respawn, hedged
    dispatch, priority brownout).  Disarmed, the flapping replica keeps
    its share of the queue through endless requeue/replay cycles and the
    storm's tail queues behind it; armed, the breaker trips within two
    faults, the replica is ejected and a registry-warm respawn restores
    capacity, and brownout sheds queued low-priority work.
    ``guardrails_p95_ttft_improvement`` is the HIGH-priority p95
    time-to-first-token ratio (disarmed / armed) — the guardrail claim
    is precisely that faults cost tail latency, and the breaker refunds
    it.

    Gates (raise ⇒ CI fails, not just a slow number): every completed
    response equals the unbatched no-cache oracle in BOTH runs, the
    disarmed run completes the whole storm with zero rejections, the
    armed run completes every high-priority request and rejects nothing
    untyped (brownout sheds only), the breaker trips at least once, its
    respawn is warm with ZERO local compiles fleet-wide, and the armed
    p95 beats the disarmed one."""
    import shutil
    import tempfile

    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np

    import jax.numpy as jnp
    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import chaos, observe
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        FleetConfig, GuardrailConfig, Request, ServeConfig, ServeFleet,
        oracle_generate, spin_up_replica, warm_serving,
    )

    cfg = TransformerConfig(
        vocab_size=256, d_model=96, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=192, max_seq_len=64, dtype=jnp.float32,
    )
    scfg = ServeConfig(max_batch=2, page_size=8, n_pages=32,
                       max_pages_per_seq=4, prefill_buckets=(8, 16))

    # Short generations keep each batch inside the flap's clean window
    # (duty 0.875 fires on 7 of every 8 serve-loop hits and the hit
    # phase advances one per retry cycle; a requeued lane re-earns
    # prompt + 2 tokens on its admit step and needs ONE clean decode
    # step to finish), so the DISARMED run terminates — slowly, after
    # up to 8 replay cycles per batch — instead of livelocking.  48
    # requests against max_batch=2 put the pressure where the
    # guardrails act (the admission queue) and give the p95 24
    # high-priority samples.
    def storm(tag):
        rng = np.random.RandomState(13)
        return [
            Request(f"{tag}{i}", [int(t) for t in
                                  rng.randint(0, cfg.vocab_size,
                                              size=2 + int(rng.randint(10)))],
                    max_new_tokens=3, priority=i % 2, arrival_step=0)
            for i in range(48)
        ]

    oracle_cache = {}

    def check_oracle(fl, reqs, results):
        for r in reqs:
            if r.rid not in results:
                continue
            key = (tuple(r.tokens), r.max_new_tokens)
            if key not in oracle_cache:
                oracle_cache[key] = oracle_generate(
                    "llama", cfg, fl.params, r.tokens, r.max_new_tokens)[0]
            if results[r.rid] != oracle_cache[key]:
                raise RuntimeError(
                    f"fleet output diverged from the unbatched oracle "
                    f"on {r.rid}"
                )

    def csnap():
        return {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}

    def flap_storm(tag, gc):
        """One storm through a flapping 2-replica fleet; returns the
        high-priority p95 TTFT plus the facts the gates check."""
        ttft = {}
        fl = ServeFleet(cfg, family="llama", serve_cfg=scfg,
                        fleet_cfg=FleetConfig(min_replicas=2,
                                              max_replicas=3,
                                              autoscale=False,
                                              stall_s=120.0,
                                              guardrails=gc),
                        on_token=lambda rid, tok: ttft.setdefault(
                            rid, time.perf_counter()))
        with fl:
            fl.start(2, timeout=240.0)
            chaos.install("fleet@2=flap:0.875")
            try:
                reqs = storm(tag)
                t0 = time.perf_counter()
                results = fl.run(reqs, max_seconds=240.0)
            finally:
                chaos.clear()
            check_oracle(fl, reqs, results)
            facts = {
                "rejected": {rid: rej.reason
                             for rid, rej in fl.rejected.items()},
                # Tri-state per respawn: True warm, False compiled, None
                # when the storm drained before its bring-up finished
                # (the fleet-wide zero-local-compile gate still covers
                # that one).
                "respawn_warm": [h.bring_up_warm for h in fl.handles
                                 if h.idx >= 3],
            }
        highs = [ttft[r.rid] - t0 for r in reqs
                 if r.priority == 1 and r.rid in results]
        if len(highs) < 24:
            raise RuntimeError(
                f"{tag}: only {len(highs)}/24 high-priority requests "
                f"completed: {facts['rejected']}"
            )
        return float(np.percentile(highs, 95)), results, facts

    jax.devices()
    out = {"model_d": cfg.d_model, "n_layers": cfg.n_layers,
           "storm_requests": 48, "host_cpu_count": os.cpu_count()}
    reg = tempfile.mkdtemp(prefix="tdx_guard_bench_reg_")
    caches = []

    def fresh_cache(tag):
        d = tempfile.mkdtemp(prefix=f"tdx_guard_bench_{tag}_")
        caches.append(d)
        return d

    try:
        # COLD bring-up: what a breaker respawn would cost WITHOUT the
        # artifact registry (every program XLA-compiled from scratch).
        compile_service.reset_cache_binding()
        with tdx_config.override(cache_dir=fresh_cache("cold")):
            t0 = time.perf_counter()
            spin_up_replica(cfg, family="llama", serve_cfg=scfg)
            out["bring_up_cold_s"] = round(time.perf_counter() - t0, 3)

        # Publish once; both fleets (and the breaker's respawn) bring
        # replicas up through the registry into one fresh local cache.
        # clear_caches() between stages for the same reason as the
        # serving_fleet phase: retained JIT code regions pile up mmap
        # mappings until vm.max_map_count says ENOMEM.
        jax.clear_caches()
        compile_service.reset_cache_binding()
        warm_serving("llama", cfg, fresh_cache("pub"), registry_dir=reg,
                     serve_cfg=scfg)
        compile_service.reset_cache_binding()
        observe.enable(True)
        base = csnap()
        fleet_cache = fresh_cache("fleet")

        with tdx_config.override(cache_dir=fleet_cache, registry_dir=reg):
            # DISARMED: the flapping replica holds its share of the
            # queue and replays it; the fault may cost (a lot of)
            # latency, never a token and never a rejection.
            p95_off, res_off, facts_off = flap_storm("off", None)
            if facts_off["rejected"]:
                raise RuntimeError(
                    f"disarmed storm rejected requests: "
                    f"{facts_off['rejected']}"
                )
            if len(res_off) != 48:
                raise RuntimeError(
                    f"disarmed storm incomplete: {len(res_off)}/48"
                )

            # ARMED: the breaker trips after 2 faults, quarantine backs
            # off, a registry-warm respawn restores capacity; brownout
            # may shed queued LOW-priority work (typed) under the
            # 48-deep burst.  Hedging stays armed but only fires past a
            # 5 s queue wait.
            jax.clear_caches()
            gc = GuardrailConfig(breaker_trip_faults=2,
                                 breaker_window_s=60.0,
                                 quarantine_s=0.1, quarantine_max_s=2.0,
                                 hedging=True, hedge_wait_s=5.0,
                                 brownout=True)
            p95_on, res_on, facts_on = flap_storm("on", gc)
            for rid, reason in facts_on["rejected"].items():
                if reason != "shed":
                    raise RuntimeError(
                        f"armed storm rejection not a brownout shed: "
                        f"{rid} -> {reason}"
                    )
            if not facts_on["respawn_warm"]:
                raise RuntimeError("the breaker never respawned a replica")
            if any(w is False for w in facts_on["respawn_warm"]):
                raise RuntimeError("breaker respawn hit the compiler")

        snap = csnap()
        out["guardrails_breaker_trips"] = int(
            snap.get("tdx.fleet.breaker_trips", 0)
            - base.get("tdx.fleet.breaker_trips", 0))
        if out["guardrails_breaker_trips"] < 1:
            raise RuntimeError("the flap storm never tripped the breaker")
        out["guardrails_hedged"] = int(
            snap.get("tdx.fleet.hedged_requests", 0)
            - base.get("tdx.fleet.hedged_requests", 0))
        out["guardrails_shed_low"] = int(
            snap.get("tdx.fleet.shed_requests", 0)
            - base.get("tdx.fleet.shed_requests", 0))
        miss = (snap.get("tdx.jax.compile_cache_miss", 0)
                - base.get("tdx.jax.compile_cache_miss", 0))
        out["warm_local_compiles"] = int(miss)
        if miss:
            raise RuntimeError(
                f"registry-warm fleets paid {int(miss)} local compiles"
            )
        out["guardrails_off_p95_ttft_s"] = round(p95_off, 3)
        out["guardrails_on_p95_ttft_s"] = round(p95_on, 3)
        out["guardrails_p95_ttft_improvement"] = round(p95_off / p95_on, 3)
        if out["guardrails_p95_ttft_improvement"] <= 1:
            raise RuntimeError(
                f"guardrails did not improve high-priority p95 TTFT: "
                f"disarmed {p95_off:.3f}s vs armed {p95_on:.3f}s"
            )
        out["oracle_equal"] = True
    finally:
        observe.enable(None)
        compile_service.reset_cache_binding()
        shutil.rmtree(reg, ignore_errors=True)
        for d in caches:
            shutil.rmtree(d, ignore_errors=True)
    out["backend"] = "cpu"
    return out


def phase_serving_prefix() -> dict:
    """Prefix-sharing + chunked-prefill phase (docs/serving.md §Prefix
    sharing & chunked prefill): the SAME 48-request storm — 80% of
    requests sharing a two-page preamble — is driven twice through one
    replica shape, once with the prefix cache OFF (every prompt pays its
    full prefill) and once ON (followers map the preamble's KV pages
    copy-on-write and prefill only their suffix).
    ``prefix_tokens_per_s_improvement`` and
    ``prefix_p95_ttft_improvement`` are the on/off ratios — the sharing
    claim is precisely that reused prefix tokens cost ZERO prefill
    FLOPs, and both throughput and tail TTFT show it.

    A second A/B drives a long-prompt storm (prompts LONGER than the
    largest prefill bucket — served chunked, where the seed engine
    rejected them) at a coarse chunk (the whole largest bucket per tick,
    the closest thing to the old single-shot) vs a fine chunk, and
    measures a concurrent short request's TTFT:
    ``prefix_chunked_short_ttft_improvement`` is coarse / fine — bounded
    per-tick prefill work is what lets the short request's first token
    through.

    Gates (raise ⇒ CI fails, not just a slow number): every output in
    every arm equals the unbatched no-cache oracle, the ON arm reuses
    pages (prefix hits > 0), both headline ratios exceed 1, the
    oversized prompts complete (not reject), and every arm drains to
    ZERO live pages."""
    import shutil
    import tempfile

    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np

    import jax.numpy as jnp
    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import observe
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        Request, ServeConfig, oracle_generate, spin_up_replica,
    )

    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=256, max_seq_len=160, dtype=jnp.float32,
    )

    def scfg(**kw):
        return ServeConfig(max_batch=4, page_size=8, n_pages=64,
                           max_pages_per_seq=10,
                           prefill_buckets=(8, 64), **kw)

    # 48 requests, 80% sharing a 48-token (six-page) preamble.  Suffixes
    # land in the 8-bucket; the full prompts land in the 64-bucket — the
    # FLOP gap sharing refunds.  Short generations keep decode (whose
    # cost is identical in both arms) from drowning the prefill signal.
    preamble = [(31 * i + 7) % cfg.vocab_size for i in range(48)]
    rng = np.random.RandomState(29)
    prompts = []
    for i in range(48):
        if i % 5 == 4:  # the 20% unshared floor
            prompts.append([int(t) for t in
                            rng.randint(0, cfg.vocab_size,
                                        size=3 + int(rng.randint(8)))])
        else:
            prompts.append(preamble + [int(t) for t in
                                       rng.randint(0, cfg.vocab_size,
                                                   size=2 + int(rng.randint(7)))])

    # One generated token per request: decode cost (identical in both
    # arms — the page-table gather is the tick's fixed price) would
    # otherwise drown the prefill delta that sharing refunds.
    def storm(tag):
        return [Request(f"{tag}{i}", prompts[i],
                        max_new_tokens=1, arrival_step=i // 4)
                for i in range(48)]

    oracle_cache = {}

    def check_oracle(eng, reqs, results):
        for r in reqs:
            key = (tuple(r.tokens), r.max_new_tokens)
            if key not in oracle_cache:
                oracle_cache[key] = oracle_generate(
                    "llama", cfg, eng.params, r.tokens, r.max_new_tokens)[0]
            if results.get(r.rid) != oracle_cache[key]:
                raise RuntimeError(
                    f"serving output diverged from the unbatched oracle "
                    f"on {r.rid}"
                )

    def csnap():
        return {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}

    def run_storm(eng, reqs):
        """(tokens/s, p95 TTFT) for one storm through ``eng``."""
        ttft = {}
        prev = eng.on_token
        eng.on_token = lambda rid, tok: ttft.setdefault(
            rid, time.perf_counter())
        try:
            t0 = time.perf_counter()
            results = eng.run(reqs)
            dt = time.perf_counter() - t0
        finally:
            eng.on_token = prev
        check_oracle(eng, reqs, results)
        n_tok = sum(len(results[r.rid]) for r in reqs)
        p95 = float(np.percentile([ttft[r.rid] - t0 for r in reqs], 95))
        eng.drain()
        if eng.kv.pages_in_use != 0:
            raise RuntimeError(
                f"{eng.kv.pages_in_use} pages still live after drain"
            )
        return n_tok / dt, p95

    jax.devices()
    out = {"model_d": cfg.d_model, "n_layers": cfg.n_layers,
           "storm_requests": 48, "shared_fraction": 0.8,
           "host_cpu_count": os.cpu_count()}
    cache = tempfile.mkdtemp(prefix="tdx_prefix_bench_")
    try:
        compile_service.reset_cache_binding()
        observe.enable(True)
        with tdx_config.override(cache_dir=cache):
            # OFF: every prompt pays its full (bucketed) prefill.  The
            # bring-up compiles the shared program set into the local
            # cache; every later engine is a pure cache hit, so the
            # timed storms never see the compiler.
            eng = spin_up_replica(cfg, family="llama",
                                  serve_cfg=scfg(prefix_cache=False))
            tps_off, p95_off = run_storm(eng, storm("off"))

            # ON: followers map the cached preamble pages and prefill
            # only their suffix.
            base = csnap()
            eng = spin_up_replica(cfg, family="llama", serve_cfg=scfg())
            tps_on, p95_on = run_storm(eng, storm("on"))
            snap = csnap()
            for short, name in (("hits", "prefix_hits"),
                                ("tokens_reused", "prefix_tokens_reused"),
                                ("cow", "cow_copies")):
                out[f"prefix_{short}"] = int(
                    snap.get(f"tdx.serve.{name}", 0)
                    - base.get(f"tdx.serve.{name}", 0))
            if out["prefix_hits"] < 24 or out["prefix_tokens_reused"] < 24 * 48:
                raise RuntimeError(
                    f"the 80%-shared storm should hit the prefix cache "
                    f"~38 times at 48 tokens each, saw "
                    f"{out['prefix_hits']} / {out['prefix_tokens_reused']}"
                )

            # Chunked prefill: prompts LONGER than the largest bucket
            # (the seed engine rejected these), coarse chunk vs fine,
            # with one short request stuck behind the long storm.
            def chunk_storm(tag, chunk):
                eng = spin_up_replica(
                    cfg, family="llama",
                    serve_cfg=scfg(prefill_chunk=chunk, prefix_cache=False))
                longs = [Request(
                    f"{tag}L{i}",
                    [int(t) for t in rng.randint(0, cfg.vocab_size, size=68)],
                    max_new_tokens=2) for i in range(3)]
                short = Request(f"{tag}S", [9, 2, 9], max_new_tokens=4,
                                arrival_step=1)
                ttft = {}
                eng.on_token = lambda rid, tok: ttft.setdefault(
                    rid, time.perf_counter())
                t0 = time.perf_counter()
                results = eng.run(longs + [short])
                check_oracle(eng, longs + [short], results)
                eng.drain()
                if eng.kv.pages_in_use != 0:
                    raise RuntimeError(
                        f"{tag}: pages leaked after the chunked storm"
                    )
                return ttft[short.rid] - t0

            # Best-of-5 per arm: a single short-request TTFT is a ~10 ms
            # sample on a shared host; the structural gap (how much
            # prefill work each tick runs before the short request's
            # turn) is deterministic, so min() strips scheduler noise.
            base = csnap()
            short_coarse = min(chunk_storm(f"coarse{n}", 64)
                               for n in range(5))
            short_fine = min(chunk_storm(f"fine{n}", 8) for n in range(5))
            chunks = int(csnap().get("tdx.serve.prefill_chunks", 0)
                         - base.get("tdx.serve.prefill_chunks", 0))
            # The fine arms alone need ceil(68/8)=9 chunks per long
            # prompt per repetition.
            if chunks < 5 * 27:
                raise RuntimeError(
                    f"oversized prompts did not prefill chunked "
                    f"({chunks} chunks)"
                )
            out["prefill_chunks"] = chunks
    finally:
        observe.enable(None)
        compile_service.reset_cache_binding()
        shutil.rmtree(cache, ignore_errors=True)

    out["prefix_off_tokens_per_s"] = round(tps_off, 2)
    out["prefix_on_tokens_per_s"] = round(tps_on, 2)
    out["prefix_tokens_per_s_improvement"] = round(tps_on / tps_off, 3)
    out["prefix_off_p95_ttft_s"] = round(p95_off, 4)
    out["prefix_on_p95_ttft_s"] = round(p95_on, 4)
    out["prefix_p95_ttft_improvement"] = round(p95_off / p95_on, 3)
    out["chunked_short_ttft_coarse_s"] = round(short_coarse, 4)
    out["chunked_short_ttft_fine_s"] = round(short_fine, 4)
    out["prefix_chunked_short_ttft_improvement"] = round(
        short_coarse / short_fine, 3)
    if out["prefix_tokens_per_s_improvement"] <= 1:
        raise RuntimeError(
            f"prefix sharing did not improve throughput: "
            f"{tps_off:.1f} -> {tps_on:.1f} tok/s"
        )
    if out["prefix_p95_ttft_improvement"] <= 1:
        raise RuntimeError(
            f"prefix sharing did not improve p95 TTFT: "
            f"{p95_off:.4f}s -> {p95_on:.4f}s"
        )
    if out["prefix_chunked_short_ttft_improvement"] <= 1:
        raise RuntimeError(
            f"fine chunking did not improve the short request's TTFT: "
            f"coarse {short_coarse:.4f}s vs fine {short_fine:.4f}s"
        )
    out["oracle_equal"] = True
    out["backend"] = "cpu"
    return out


def phase_serving_spec() -> dict:
    """Speculative-decoding A/B (docs/serving.md §Speculative decoding):
    the SAME decode-heavy shared-preamble storm is driven through one
    replica shape twice — speculation OFF (one token per lane per tick)
    and ON (the n-gram drafter proposes up to ``spec_k`` tokens per lane
    and one bucketed ``verify-<k>`` tick scores them all).  The storm
    repeats each distinct prompt several times: greedy decode is
    deterministic, so the first instance teaches the drafter the exact
    continuation the repeats then draft — the shared-preamble traffic
    shape the radix tree already exploits for prefill, now paying off
    at decode time.

    ``spec_tokens_per_s_improvement`` is the on/off throughput ratio;
    ``spec_accepted_per_verify`` is the mean number of ACCEPTED draft
    tokens per verify tick — the structural claim: each verify tick
    delivers accepted+1 tokens for one program call, so >1 accepted per
    verify means the batch genuinely outruns plain decode's
    token-per-tick ceiling.

    Gates (raise ⇒ CI fails): every output in both arms equals the
    unbatched no-cache oracle (speculation is a throughput knob, never a
    sampling change), the ON arm actually speculates (verify ticks > 0),
    both headline numbers exceed 1, and every arm drains to ZERO live
    pages."""
    import shutil
    import tempfile

    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np

    import jax.numpy as jnp
    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import observe
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        Request, ServeConfig, oracle_generate, spin_up_replica,
    )

    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=256, max_seq_len=160, dtype=jnp.float32,
    )

    def scfg(**kw):
        return ServeConfig(max_batch=4, page_size=8, n_pages=64,
                           max_pages_per_seq=10,
                           prefill_buckets=(8, 64), **kw)

    # 8 distinct prompts sharing a 16-token preamble, each repeated 5
    # times (prompt-major, so every repeat arrives after its original
    # taught the drafter), 12 generated tokens each: decode dominates
    # the storm, which is exactly where speculation pays.
    preamble = [(13 * i + 5) % cfg.vocab_size for i in range(16)]
    rng = np.random.RandomState(31)
    distinct = [preamble + [int(t) for t in
                            rng.randint(0, cfg.vocab_size,
                                        size=2 + int(rng.randint(5)))]
                for _ in range(8)]
    prompts = [p for _ in range(5) for p in distinct]

    def storm(tag):
        return [Request(f"{tag}{i}", prompts[i],
                        max_new_tokens=12, arrival_step=i // 4)
                for i in range(len(prompts))]

    oracle_cache = {}

    def check_oracle(eng, reqs, results):
        for r in reqs:
            key = (tuple(r.tokens), r.max_new_tokens)
            if key not in oracle_cache:
                oracle_cache[key] = oracle_generate(
                    "llama", cfg, eng.params, r.tokens, r.max_new_tokens)[0]
            if results.get(r.rid) != oracle_cache[key]:
                raise RuntimeError(
                    f"serving output diverged from the unbatched oracle "
                    f"on {r.rid} (speculation must be invisible in the "
                    f"tokens)"
                )

    def run_storm(eng, reqs):
        t0 = time.perf_counter()
        results = eng.run(reqs)
        dt = time.perf_counter() - t0
        check_oracle(eng, reqs, results)
        n_tok = sum(len(results[r.rid]) for r in reqs)
        eng.drain()
        if eng.kv.pages_in_use != 0:
            raise RuntimeError(
                f"{eng.kv.pages_in_use} pages still live after drain"
            )
        return n_tok / dt

    jax.devices()
    out = {"model_d": cfg.d_model, "n_layers": cfg.n_layers,
           "storm_requests": len(prompts), "distinct_prompts": len(distinct),
           "gen_tokens": 12, "host_cpu_count": os.cpu_count()}
    cache = tempfile.mkdtemp(prefix="tdx_spec_bench_")
    spec_drafted = spec_accepted = spec_ticks = 0
    try:
        compile_service.reset_cache_binding()
        observe.enable(True)
        with tdx_config.override(cache_dir=cache):
            # Best-of-3 per arm: the structural gap (program calls per
            # delivered token) is deterministic; max() strips scheduler
            # noise on a shared host.  The first bring-up compiles the
            # shared program set — including every verify bucket — into
            # the local cache, so later engines (both arms) are pure
            # cache hits and the timed storms never see the compiler.
            tps_off = 0.0
            for n in range(3):
                eng = spin_up_replica(cfg, family="llama",
                                      serve_cfg=scfg(spec_decode=False))
                if eng.scfg.spec_decode or eng._drafter is not None:
                    raise RuntimeError("OFF arm is speculating")
                tps_off = max(tps_off, run_storm(eng, storm(f"off{n}_")))

            tps_on = 0.0
            for n in range(3):
                eng = spin_up_replica(cfg, family="llama",
                                      serve_cfg=scfg(spec_decode=True))
                tps_on = max(tps_on, run_storm(eng, storm(f"on{n}_")))
                spec_drafted += eng.spec_drafted
                spec_accepted += eng.spec_accepted
                spec_ticks += eng.spec_verify_ticks
            if spec_ticks == 0 or spec_drafted == 0:
                raise RuntimeError(
                    "the ON arm never speculated (no verify ticks)"
                )
    finally:
        observe.enable(None)
        compile_service.reset_cache_binding()
        shutil.rmtree(cache, ignore_errors=True)

    out["spec_off_tokens_per_s"] = round(tps_off, 2)
    out["spec_on_tokens_per_s"] = round(tps_on, 2)
    out["spec_tokens_per_s_improvement"] = round(tps_on / tps_off, 3)
    out["spec_drafted"] = spec_drafted
    out["spec_accepted"] = spec_accepted
    out["spec_verify_ticks"] = spec_ticks
    out["spec_accept_rate"] = round(spec_accepted / spec_drafted, 4)
    out["spec_accepted_per_verify"] = round(spec_accepted / spec_ticks, 3)
    if out["spec_tokens_per_s_improvement"] <= 1:
        raise RuntimeError(
            f"speculative decoding did not improve throughput: "
            f"{tps_off:.1f} -> {tps_on:.1f} tok/s"
        )
    if out["spec_accepted_per_verify"] <= 1:
        raise RuntimeError(
            f"verify ticks accepted <=1 draft token on average "
            f"({out['spec_accepted_per_verify']}) — speculation is not "
            f"beating the one-token-per-tick ceiling"
        )
    out["oracle_equal"] = True
    out["backend"] = "cpu"
    return out


def phase_serving_ledger() -> dict:
    """Request-ledger overhead A/B + tail attribution
    (docs/observability.md §Per-request ledger): the SAME 48-request
    storm — shared preambles, multi-token decodes, so every ledger hook
    (enqueue/admit/chunk/decode/COW/finish) is on the hot path — is
    driven through one replica shape with full telemetry enabled, three
    times with the per-request ledger OFF
    (``tdx_config.override(request_ledger=False)``, the
    ``TDX_REQUEST_LEDGER=0`` kill switch) and three times ON,
    interleaved.  ``ledger_overhead_ratio`` = best ON tokens/s / best
    OFF tokens/s is THE overhead claim: attribution-by-construction
    costs ≤ 2% throughput (gated in-phase at 0.98).

    The ON arm also publishes the tail-attribution keys that ride
    ``BENCH_r*.json``: per-stage p50/p99 seconds, mean stage shares,
    and the p99-blame breakdown from ``reqledger.tail_report()``.

    Gates: every output in every arm equals the unbatched oracle, the
    OFF arms record NOTHING (kill switch verified), the ON arms record
    every request with stage sums matching end-to-end latency within
    5 ms, the overhead ratio stays ≥ 0.98, and every arm drains to zero
    live pages."""
    import shutil
    import tempfile

    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np

    import jax.numpy as jnp
    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import observe
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.observe import reqledger
    from torchdistx_tpu.serve import (
        Request, ServeConfig, oracle_generate, spin_up_replica,
    )

    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=256, max_seq_len=160, dtype=jnp.float32,
    )
    scfg = ServeConfig(max_batch=4, page_size=8, n_pages=64,
                       max_pages_per_seq=10, prefill_buckets=(8, 64))

    # 48 requests: 60% share a two-page preamble (prefix/COW hooks fire),
    # 4 generated tokens each (the per-lane decode-tick hook — the
    # hottest ledger call site — dominates, exactly the overhead that
    # must stay under 2%).
    preamble = [(31 * i + 7) % cfg.vocab_size for i in range(16)]
    rng = np.random.RandomState(31)
    prompts = []
    for i in range(48):
        if i % 5 >= 3:
            prompts.append([int(t) for t in
                            rng.randint(0, cfg.vocab_size,
                                        size=3 + int(rng.randint(8)))])
        else:
            prompts.append(preamble + [int(t) for t in
                                       rng.randint(0, cfg.vocab_size,
                                                   size=2 + int(rng.randint(7)))])

    def storm(tag):
        return [Request(f"{tag}{i}", prompts[i],
                        max_new_tokens=4, arrival_step=i // 4)
                for i in range(48)]

    oracle_cache = {}

    def check_oracle(eng, reqs, results):
        for r in reqs:
            key = (tuple(r.tokens), r.max_new_tokens)
            if key not in oracle_cache:
                oracle_cache[key] = oracle_generate(
                    "llama", cfg, eng.params, r.tokens, r.max_new_tokens)[0]
            if results.get(r.rid) != oracle_cache[key]:
                raise RuntimeError(
                    f"serving output diverged from the unbatched oracle "
                    f"on {r.rid}"
                )

    def run_storm(tag, ledger_on):
        with tdx_config.override(request_ledger=ledger_on):
            eng = spin_up_replica(cfg, family="llama", serve_cfg=scfg)
            reqs = storm(tag)
            t0 = time.perf_counter()
            results = eng.run(reqs)
            dt = time.perf_counter() - t0
            check_oracle(eng, reqs, results)
            n_tok = sum(len(results[r.rid]) for r in reqs)
            eng.drain()
            if eng.kv.pages_in_use != 0:
                raise RuntimeError(
                    f"{tag}: {eng.kv.pages_in_use} pages live after drain"
                )
        return n_tok / dt

    jax.devices()
    out = {"model_d": cfg.d_model, "n_layers": cfg.n_layers,
           "storm_requests": 48, "reps_per_arm": 3,
           "host_cpu_count": os.cpu_count()}
    cache = tempfile.mkdtemp(prefix="tdx_ledger_bench_")
    try:
        compile_service.reset_cache_binding()
        observe.enable(True)
        with tdx_config.override(cache_dir=cache):
            # Warm-up arm: compiles the program set into the local cache
            # so neither timed arm ever sees the compiler.
            run_storm("warm", False)
            reqledger.reset()
            tps_off, tps_on = [], []
            for rep in range(3):  # interleaved: host drift hits both arms
                before = reqledger.requests_report(limit=1)["finished"]
                tps_off.append(run_storm(f"off{rep}", False))
                after = reqledger.requests_report(limit=1)["finished"]
                if after != before:
                    raise RuntimeError(
                        "kill switch leak: the ledger recorded "
                        f"{after - before} requests with "
                        f"request_ledger=False"
                    )
                tps_on.append(run_storm(f"on{rep}", True))
                if reqledger.requests_report(limit=1)["finished"] != after + 48:
                    raise RuntimeError(
                        "ledger-on arm did not record all 48 requests")
            # Attribution contract on the last ON storm: the four stages
            # sum to end-to-end latency (within clock-read slack).
            recent = reqledger.requests_report(limit=48)["recent"]
            for r in recent:
                ssum = sum(r[f"{st}_s"] for st in reqledger.STAGES)
                if abs(ssum - r["e2e_s"]) > 5e-3:
                    raise RuntimeError(
                        f"stage attribution of {r['rid']} does not sum to "
                        f"e2e: {ssum:.6f} vs {r['e2e_s']:.6f}"
                    )
            tail = reqledger.tail_report()
    finally:
        observe.enable(None)
        compile_service.reset_cache_binding()
        shutil.rmtree(cache, ignore_errors=True)

    out["ledger_off_tokens_per_s"] = round(max(tps_off), 2)
    out["ledger_on_tokens_per_s"] = round(max(tps_on), 2)
    out["ledger_overhead_ratio"] = round(max(tps_on) / max(tps_off), 3)
    for st, d in (tail.get("stages") or {}).items():
        out[f"ledger_stage_{st}_p50_s"] = d["p50"]
        out[f"ledger_stage_{st}_p99_s"] = d["p99"]
        out[f"ledger_stage_{st}_share"] = d["mean_share"]
    for st, share in (tail.get("p99_blame") or {}).items():
        out[f"ledger_p99_blame_{st}"] = share
    if tail.get("e2e_s"):
        out["ledger_e2e_p99_s"] = tail["e2e_s"]["p99"]
    if out["ledger_overhead_ratio"] < 0.98:
        raise RuntimeError(
            f"request ledger costs more than 2% throughput: "
            f"{max(tps_off):.1f} -> {max(tps_on):.1f} tok/s "
            f"(ratio {out['ledger_overhead_ratio']})"
        )
    out["oracle_equal"] = True
    out["backend"] = "cpu"
    return out


def phase_serving_rollover() -> dict:
    """Blue-green rollover phase (docs/serving.md §Weight rollover):
    what a live weight roll costs the storm it interrupts.  The SAME
    request storm runs twice through a 2-replica registry-warm fleet —
    once steady-state, once with a mid-storm blue-green roll onto a
    committed next-step checkpoint (GREEN bring-up, bitwise canary
    gate, traffic shift, one-at-a-time BLUE drain) — and the ratio of
    decode tokens/s is the headline (``rollover_tokens_per_s_ratio``),
    along with the p95 TTFT both ways and the wall-clock of the roll.

    Both storms are OPEN-LOOP: requests are submitted on a wall-clock
    schedule at ~55% of the fleet's measured closed-loop capacity, the
    way a production fleet sees load.  That is the regime where "a
    roll is a background activity, not a brownout" is a falsifiable
    claim — the roll's bring-up/canary/drain work must fit in the
    serving headroom; at closed-loop saturation every roll cycle is a
    decode cycle by construction and the ratio only measures host core
    count.  The roll's latency cost still shows up undamped in the
    reported p95 TTFT.

    Gates (raise ⇒ CI fails): the roll completes; a deterministic
    sample of responses from each arm equals the unbatched oracle FOR
    THE WEIGHT VERSION IT WAS SERVED UNDER (the every-request bitwise
    invariant is pinned in tests/test_rollover.py — the bench
    spot-checks, because the per-call-retracing oracle is too
    mmap-hungry for a full sweep on the CI host); zero typed
    rejections; zero local compiles (the GREEN replica comes up
    registry-warm); and the mid-roll storm keeps ≥0.9× the
    steady-state delivered tokens/s."""
    import shutil
    import tempfile

    os.environ.setdefault("TDX_CACHE_MIN_COMPILE_S", "0")
    jax = _virtual_cpu_init(1)
    import numpy as np

    import jax.numpy as jnp
    import torchdistx_tpu.config as tdx_config
    from torchdistx_tpu import observe
    from torchdistx_tpu import compile_service
    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import (
        FleetConfig, Request, RolloverConfig, ServeConfig, ServeFleet,
        oracle_generate, warm_serving,
    )
    from torchdistx_tpu.utils.checkpoint import save_checkpoint

    cfg = TransformerConfig(
        vocab_size=256, d_model=96, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=192, max_seq_len=128, dtype=jnp.float32,
    )
    # Page budget for ~90-token generations: long decodes amortize the
    # per-request Python overhead so the open-loop schedule is decode-
    # dominated.
    scfg = ServeConfig(max_batch=2, page_size=8, n_pages=64,
                       max_pages_per_seq=8, prefill_buckets=(8, 16))
    # The storm must OUTLAST the roll for the ratio to mean anything:
    # a roll costs a roughly fixed ~20-30s of background work (GREEN
    # bring-up, canary decode + judge, staggered drains), so a storm
    # much shorter than that charges the whole roll to a few seconds
    # of traffic.  300 paced requests ≈ 30s at half capacity.
    N_STORM = 300
    N_CHECK = 5  # oracle spot-check per storm (see check_oracle)

    def storm(tag, n=N_STORM, new_lo=24, new_hi=32):
        rng = np.random.RandomState(11)
        return [
            Request(f"{tag}{i}", [int(t) for t in
                                  rng.randint(0, cfg.vocab_size,
                                              size=2 + int(rng.randint(12)))],
                    max_new_tokens=new_lo + int(rng.randint(
                        new_hi - new_lo + 1)))
            for i in range(n)
        ]

    def p95(vals):
        if not vals:
            return None
        s = sorted(vals)
        return round(s[min(len(s) - 1, int(0.95 * len(s)))], 4)

    def check_oracle(fl, reqs, results):
        """Zero rejections + a deterministic N_CHECK-request bitwise
        spot-check against the per-served-version oracle.  A sample,
        not a sweep: the unbatched oracle retraces ``model.apply``
        every call, so every sequence length recompiles PER CALL and
        the executables pile up in jax's dispatch caches — a full
        40-request sweep leaks enough LLVM JIT mappings to run a
        1-CPU host out of ``vm.max_map_count`` (segfault, not a clean
        raise).  ``jax.clear_caches()`` between checks releases them;
        the fleet's own programs are registry-loaded executable
        handles and unaffected.  The EVERY-request invariant is pinned
        where it belongs, in tests/test_rollover.py."""
        if fl.rejected:
            raise RuntimeError(f"storm rejected requests: {fl.rejected}")
        stride = max(1, len(reqs) // N_CHECK)
        for j, r in enumerate(reqs[::stride][:N_CHECK]):
            v = fl.served_version.get(r.rid)
            want, _ = oracle_generate("llama", cfg, fl.version_params[v],
                                      r.tokens, r.max_new_tokens)
            if results[r.rid] != want:
                raise RuntimeError(
                    f"output diverged from the version-{v} oracle on "
                    f"{r.rid}")
            if j % 2 == 1:
                jax.clear_caches()
        jax.clear_caches()

    def run_closed(fl, reqs):
        """Closed-loop burst: the fleet's capacity, tokens/s.  Only a
        rejection gate here — the measured open-loop arms carry the
        oracle spot-checks."""
        t0 = time.perf_counter()
        results = fl.run(reqs, max_seconds=300.0)
        dt = time.perf_counter() - t0
        if fl.rejected:
            raise RuntimeError(f"probe rejected requests: {fl.rejected}")
        return sum(len(results[r.rid]) for r in reqs) / dt

    def run_open(fl, reqs, rate_tok_s):
        """Open-loop storm: each request is submitted at its wall-clock
        slot (cumulative offered tokens ÷ rate); returns delivered
        tokens/s over the whole schedule + drain tail, and p95 TTFT."""
        first_tok = {}

        def on_token(rid, _tok):
            if rid not in first_tok:
                first_tok[rid] = time.perf_counter()

        fl.on_token = on_token
        slots, acc = [], 0.0
        for r in reqs:
            slots.append(acc)
            acc += r.max_new_tokens / rate_tok_s
        t0 = time.perf_counter()
        nxt = 0
        deadline = t0 + 300.0
        while nxt < len(reqs) or fl._pending:
            now = time.perf_counter()
            while nxt < len(reqs) and now - t0 >= slots[nxt]:
                fl.submit(reqs[nxt])
                nxt += 1
            fl.tick()
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"open-loop storm stuck: {len(fl._pending)} pending")
            time.sleep(0.001)
        dt = time.perf_counter() - t0
        results = dict(fl.results)
        check_oracle(fl, reqs, results)
        ttfts = [first_tok[r.rid] - r._submit_t for r in reqs
                 if r.rid in first_tok]
        n_tok = sum(len(results[r.rid]) for r in reqs)
        return round(n_tok / dt, 2), p95(ttfts)

    jax.devices()
    out = {"model_d": cfg.d_model, "n_layers": cfg.n_layers,
           "storm_requests": N_STORM, "host_cpu_count": os.cpu_count()}
    reg = tempfile.mkdtemp(prefix="tdx_roll_bench_reg_")
    cache = tempfile.mkdtemp(prefix="tdx_roll_bench_cache_")
    ckpt_dir = tempfile.mkdtemp(prefix="tdx_roll_bench_ckpt_")
    try:
        compile_service.reset_cache_binding()
        warm_serving("llama", cfg, cache, registry_dir=reg, serve_cfg=scfg)
        compile_service.reset_cache_binding()
        observe.enable(True)
        base = {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        fc = FleetConfig(min_replicas=2, max_replicas=4, autoscale=False,
                         stall_s=120.0)
        with tdx_config.override(cache_dir=cache, registry_dir=reg):
            # Steady state: measure closed-loop capacity, then the
            # open-loop baseline at half of it — the load level the
            # roll arm must hold.
            jax.clear_caches()
            with ServeFleet(cfg, family="llama", serve_cfg=scfg,
                            fleet_cfg=fc) as fl:
                fl.start(2, timeout=240.0)
                capacity = run_closed(fl, storm("c", n=12))
                rate = 0.5 * capacity
                tps_steady, ttft_steady = run_open(fl, storm("s"), rate)
            out["capacity_tokens_per_s"] = round(capacity, 2)
            out["offered_tokens_per_s"] = round(rate, 2)

            # Mid-storm roll: commit the next-step weights, then run
            # the SAME open-loop storm with the roll racing it
            # tick-for-tick at the same offered rate.
            jax.clear_caches()
            with ServeFleet(cfg, family="llama", serve_cfg=scfg,
                            fleet_cfg=fc) as fl:
                fl.start(2, timeout=240.0)
                new_params = jax.tree.map(lambda x: x * 1.01, fl.params)
                ckpt = os.path.join(ckpt_dir, "step_2")
                save_checkpoint(ckpt, new_params)
                # Two short probes: the canary judge replays them
                # through the per-call-retracing oracle ON the tick
                # thread, so probe decode length is tick-loop stall —
                # the bench keeps the gate's bitwise teeth but trims
                # its CPU bill (the default probe set is exercised by
                # tests/ and the smoke).
                rcfg = RolloverConfig(
                    probe_prompts=((1, 2, 3), (5, 4, 3, 2, 1, 6, 7)),
                    probe_new_tokens=4, canary_timeout_s=240.0)
                ctl = fl.start_rollover(ckpt, cfg=rcfg)
                t_roll = time.perf_counter()
                tps_roll, ttft_roll = run_open(fl, storm("r"), rate)
                deadline = time.monotonic() + 240.0
                while ctl.outcome is None:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"roll incomplete after storm (stage="
                            f"{ctl.stage})")
                    fl.tick()
                    time.sleep(0.002)
                out["rollover_roll_s"] = round(
                    time.perf_counter() - t_roll, 3)
                if ctl.outcome != "completed":
                    raise RuntimeError(
                        f"roll {ctl.outcome} at {ctl.stage}: {ctl.error}")
                if any(h.weight_version != ctl.version
                       for h in fl.handles):
                    raise RuntimeError("a BLUE replica survived the roll")
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        miss = (snap.get("tdx.jax.compile_cache_miss", 0)
                - base.get("tdx.jax.compile_cache_miss", 0))
        out["warm_local_compiles"] = int(miss)
        if miss:
            raise RuntimeError(
                f"registry-warm roll paid {int(miss)} local compiles")
        out["steady_tokens_per_s"] = tps_steady
        out["rollover_tokens_per_s"] = tps_roll
        out["rollover_tokens_per_s_ratio"] = round(tps_roll / tps_steady, 3)
        out["steady_p95_ttft_s"] = ttft_steady
        out["rollover_p95_ttft_s"] = ttft_roll
        if out["rollover_tokens_per_s_ratio"] < 0.9:
            raise RuntimeError(
                f"mid-roll storm lost more than 10% throughput: "
                f"{tps_roll} vs {tps_steady} tokens/s "
                f"(ratio {out['rollover_tokens_per_s_ratio']})")
        out["oracle_equal"] = True
    finally:
        observe.enable(None)
        compile_service.reset_cache_binding()
        shutil.rmtree(reg, ignore_errors=True)
        shutil.rmtree(cache, ignore_errors=True)
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    out["backend"] = "cpu"
    return out


def phase_pp_bubble() -> dict:
    """STATIC schedule analysis (no hardware, no wall clocks — tick
    counts and buffer sizes are properties of the schedule tables, so
    they are exact and environment-independent; labeled `schedule_*` to
    keep them apart from measured seconds).  Compares GPipe, flat 1F1B
    and interleaved 1F1B at reference pp/microbatch shapes: tick counts
    in equal chunk-work units, bubble fraction, and peak live activation
    stash (in microbatch-activation units)."""
    from torchdistx_tpu.parallel.interleave import (
        flat_1f1b_ticks, interleaved_schedule,
    )

    out = {}
    for pp, v, m in [(4, 2, 8), (8, 2, 16), (8, 4, 32)]:
        s = interleaved_schedule(pp, v, m)
        flat = interleaved_schedule(pp, 1, m)  # v=1 == flat ordering
        flat_equiv = flat_1f1b_ticks(pp, m) * v
        out[f"pp{pp}_v{v}_m{m}"] = {
            # GPipe stores EVERY microbatch's stage activations: stash m;
            # ticks (fwd+bwd via jax.grad) ~ 2*(m + pp - 1) stage units.
            "gpipe_ticks_equiv": 2 * (m + pp - 1) * v,
            "gpipe_peak_stash_mb": m,
            "flat_1f1b_ticks_equiv": flat_equiv,
            "flat_1f1b_bubble_fraction": flat.bubble_fraction,
            "flat_1f1b_peak_stash_mb": min(m, 2 * (pp - 1) + 1),
            "interleaved_ticks": s.T,
            "interleaved_bubble_fraction": s.bubble_fraction,
            # stash entries are chunk-inputs: 1/v the layers but full
            # activation size, so the unit matches the flat schedule's.
            "interleaved_peak_stash_mb": s.peak_stash,
            "interleaved_vs_flat_ticks": round(flat_equiv / s.T, 3),
        }
    # A static analysis has no backend; say so.
    return {"schedule_analysis": out, "backend": "none (static analysis)"}


# Reference shapes for the measured schedule phase.  ``pp8_v4`` is the
# ISSUE-11 headline shape (the analytic model's decisive-win regime);
# ``pp4_v2`` keeps continuity with the r01–r05 records; ``pp2_v2`` is
# the bench-smoke fast-depth slice.  Fields: mesh, chunking, batch and a
# chain-iter pair lean enough for the shape's per-step cost.
_SCHED_SHAPES = {
    "pp2_v2": dict(pp=2, dp=4, v=2, m=4, B=8, S=64, d=64, ff=176,
                   L=4, heads=4, iters="2,6"),
    "pp4_v2": dict(pp=4, dp=2, v=2, m=4, B=8, S=128, d=128, ff=352,
                   L=8, heads=4, iters="2,6"),
    "pp8_v4": dict(pp=8, dp=1, v=4, m=8, B=8, S=128, d=128, ff=352,
                   L=32, heads=4, iters="1,3"),
}


def phase_schedule_measured() -> dict:
    """MEASURED per-schedule step time — the wall-clock half the static
    `pp_bubble` analysis cannot give (VERDICT r4 weak #7).  Times the
    SAME jitted train step under gpipe / flat 1F1B / interleaved on
    8-device virtual CPU meshes, chain-scheme differenced, at the
    shapes of ``_SCHED_SHAPES`` (``TDX_SCHED_SHAPES`` selects).  CPU-
    mesh seconds carry no ICI cost, so the RATIOS are schedule-overhead
    comparisons on one XLA backend, not TPU predictions — labeled
    accordingly.

    ISSUE-11 upgrades (docs/performance.md §The schedule executor):

    * the fused schedules run the phase-specialized ``segmented``
      executor; ``interleaved_uniform_step_ms`` keeps the historical
      uniform-tick executor's number next to it (the A/B the refactor
      is judged by);
    * per-segment wall timings for the headline interleaved schedule
      (truncated-program differencing via ``_run_segments``) plus the
      static segment boundaries;
    * ``measured_vs_analytic`` — measured interleaved-vs-gpipe speedup
      over the analytic unit model's prediction (1.0 = the executor
      delivers exactly what the schedule math promises);
    * ``TDX_SCHED_PARITY=1`` gates the segmented executor bitwise
      against the uniform one before anything is timed (bench-smoke
      runs this on the ``pp2_v2`` slice);
    * ``host_cpu_count`` is stamped on the record — 1-core containers
      serialize XLA's intra-op parallelism and the compile pool, so
      absolute ms there are not comparable across hosts.
    """
    # No persistent cache: a measured phase should compile fresh per
    # run, and the chain scheme excludes compile time from the
    # differenced region anyway.
    jax = _virtual_cpu_init(8)
    import numpy as np

    import jax.numpy as jnp
    from jax import lax

    from torchdistx_tpu.abstract import deferred_init, materialize
    from torchdistx_tpu.models import decoder_lm_plan, make_llama
    from torchdistx_tpu.models.configs import TransformerConfig
    from torchdistx_tpu.parallel import make_mesh
    from torchdistx_tpu.parallel.interleave import (
        analytic_step_units_flat, analytic_step_units_gpipe,
        interleaved_schedule,
    )
    from torchdistx_tpu.parallel.pipeline import (
        pipeline_plan_overrides, pipeline_train_1f1b,
        pipeline_train_interleaved,
    )
    from torchdistx_tpu.parallel.sharding import ShardingPlan
    from torchdistx_tpu.parallel.train import make_train_step

    shape_names = [
        s.strip()
        for s in os.environ.get("TDX_SCHED_SHAPES", "pp4_v2,pp8_v4").split(",")
        if s.strip()
    ]
    unknown = [s for s in shape_names if s not in _SCHED_SHAPES]
    if unknown:
        raise ValueError(
            f"TDX_SCHED_SHAPES: unknown shapes {unknown}; "
            f"choose from {sorted(_SCHED_SHAPES)}"
        )
    want_parity = os.environ.get("TDX_SCHED_PARITY") == "1"
    want_segments = os.environ.get("TDX_SCHED_SEGMENTS", "1") == "1"

    out = {
        "host_cpu_count": os.cpu_count(),
        "executor": os.environ.get("TDX_PP_EXECUTOR", "segmented"),
        "shapes": {},
    }

    def _bitwise_equal(a, b) -> bool:
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        return len(la) == len(lb) and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(la, lb)
        )

    for shape_name in shape_names:
        sh = _SCHED_SHAPES[shape_name]
        pp, v, m = sh["pp"], sh["v"], sh["m"]
        cfg = TransformerConfig(
            vocab_size=512, d_model=sh["d"], n_layers=sh["L"],
            n_heads=sh["heads"], d_ff=sh["ff"], max_seq_len=sh["S"],
            # f32 on the CPU mesh: bf16 + any pipelined schedule aborts
            # XLA:CPU's compiler (guarded with a clear error in
            # make_train_step; bf16 pipelines are a TPU path).
            dtype=jnp.float32,
        )
        model = make_llama(cfg)
        mesh = make_mesh({"pp": pp, "dp": sh["dp"]})
        plan = ShardingPlan(
            pipeline_plan_overrides()
            + [(p.pattern, s)
               for p, s in decoder_lm_plan(fsdp=None, ep=None,
                                           tp=None).rules]
        )
        toks = jax.random.randint(jax.random.PRNGKey(1), (sh["B"], sh["S"]),
                                  0, cfg.vocab_size)
        fakes = deferred_init(model.init, jax.random.PRNGKey(0), toks)
        params = materialize(fakes, mesh=mesh, plan=plan)
        n_lo, n_hi = _chain_iters("TDX_SCHED_ITERS", sh["iters"])
        decomp = model.pipeline_decomposition()
        sched = interleaved_schedule(pp, v, m)
        rec = {
            "pp": pp, "dp": sh["dp"], "v": v, "m": m, "B": sh["B"],
            "S": sh["S"], "d_model": sh["d"], "n_layers": sh["L"],
        }

        if want_parity:
            # Bitwise gate FIRST: the segmented executor must reproduce
            # the uniform-tick executor's (metrics, grads) exactly on
            # both fused schedules before any of its numbers are kept.
            for sched_label, fused in (
                ("flat_1f1b", lambda p_, t_, ex: jax.jit(
                    lambda p__, t__: pipeline_train_1f1b(
                        cfg, p__, t__, mesh, decomp=decomp,
                        n_microbatches=m, executor=ex,
                    ))(p_, t_)),
                ("interleaved", lambda p_, t_, ex: jax.jit(
                    lambda p__, t__: pipeline_train_interleaved(
                        cfg, p__, t__, mesh, decomp=decomp,
                        n_microbatches=m, n_chunks=v, executor=ex,
                    ))(p_, t_)),
            ):
                seg = fused(params, toks, "segmented")
                uni = fused(params, toks, "uniform")
                if not _bitwise_equal(seg, uni):
                    raise RuntimeError(
                        f"{shape_name}/{sched_label}: segmented executor "
                        f"is NOT bitwise-equal to the uniform baseline"
                    )
            rec["parity_bitwise"] = True

        for label, kw in (
            ("gpipe", dict(pipeline_schedule="gpipe")),
            ("flat_1f1b", dict(pipeline_schedule="1f1b")),
            ("interleaved",
             dict(pipeline_schedule="interleaved", n_chunks=v)),
            ("interleaved_uniform",
             dict(pipeline_schedule="interleaved", n_chunks=v,
                  pipeline_executor="uniform")),
        ):
            init_state, train_step, shard_batch = make_train_step(
                model, cfg, mesh, pipeline=True, n_microbatches=m, **kw
            )
            state = init_state(params)
            batch = shard_batch(toks)

            @jax.jit
            def g(state, n):
                res = lax.fori_loop(
                    0, n, lambda i, st: train_step(st, batch)[0], state
                )
                return jax.tree.leaves(res)[0].sum()

            t = _chain_time(jnp, g, state, n_lo, n_hi)
            rec[f"{label}_step_ms"] = round(t * 1e3, 2)

        rec["interleaved_vs_flat_measured"] = round(
            rec["flat_1f1b_step_ms"] / rec["interleaved_step_ms"], 3
        )
        rec["interleaved_vs_gpipe_measured"] = round(
            rec["gpipe_step_ms"] / rec["interleaved_step_ms"], 3
        )
        rec["segmented_vs_uniform"] = round(
            rec["interleaved_uniform_step_ms"] / rec["interleaved_step_ms"],
            3,
        )

        # ---- analytic model & the measured-vs-analytic headline --------
        units_inter = sched.analytic_step_units()
        units_gpipe = analytic_step_units_gpipe(pp, v, m)
        analytic_speedup = units_gpipe / units_inter
        rec["analytic_units"] = {
            "gpipe": units_gpipe,
            "flat_1f1b": analytic_step_units_flat(pp, v, m),
            "interleaved": units_inter,
            "interleaved_uniform": sched.uniform_step_units(),
        }
        rec["interleaved_vs_gpipe_analytic"] = round(analytic_speedup, 3)
        rec["measured_vs_analytic"] = round(
            rec["interleaved_vs_gpipe_measured"] / analytic_speedup, 3
        )

        # ---- segment boundaries + measured per-segment wall times ------
        segs = sched.segments()
        rec["segments"] = [
            {"t0": s.t0, "t1": s.t1, "ticks": s.ticks, "role": s.role,
             "archetype": s.archetype}
            for s in segs
        ]
        if want_segments:
            seg_ms = _measure_interleaved_segments(
                jax, np, cfg, params, toks, mesh, decomp, m, v, segs
            )
            for s, ms in zip(segs, seg_ms):
                # keys: tdx.pp.segment_{warmup,steady,cooldown}_ms
                rec[f"segment_{s.role}_ms"] = ms
            from torchdistx_tpu import observe
            if observe.enabled():  # pragma: no cover - telemetry path
                for s, ms in zip(segs, seg_ms):
                    observe.counters().gauge(
                        f"tdx.pp.segment_{s.role}_ms", shape=shape_name
                    ).set(ms)

        out["shapes"][shape_name] = rec

    # Promote the LAST shape (the headline one) to the record top level
    # so the driver's flat-key comparisons keep working across rounds.
    head = out["shapes"][shape_names[-1]]
    for k in ("gpipe_step_ms", "flat_1f1b_step_ms", "interleaved_step_ms",
              "interleaved_uniform_step_ms", "interleaved_vs_flat_measured",
              "interleaved_vs_gpipe_measured", "segmented_vs_uniform",
              "interleaved_vs_gpipe_analytic", "measured_vs_analytic"):
        if k in head:
            out[k] = head[k]
    out["headline_shape"] = shape_names[-1]
    out["platform_note"] = (
        "8-device virtual CPU mesh: schedule-overhead ratios on one XLA "
        "backend, no ICI cost; absolute ms not comparable across hosts "
        f"(host_cpu_count={out['host_cpu_count']})"
    )
    return {"schedule_measured": out, "backend": "cpu"}


def _measure_interleaved_segments(jax, np, cfg, params, toks, mesh, decomp,
                                  m, v, segs):
    """Per-segment wall times of the segmented interleaved executor by
    truncated-program differencing: jit the fused step truncated to its
    first k segments (``_run_segments=k``), time each, and difference
    consecutive bests.  Every program carries the same setup/epilogue
    cost, so the deltas isolate the segments; k=0 (no segments at all)
    anchors the overhead.  Returns ms per segment, clamped at 0 (host
    noise can produce a slightly negative delta on a tiny segment)."""
    from torchdistx_tpu.parallel.pipeline import pipeline_train_interleaved

    reps = int(os.environ.get("TDX_SCHED_SEG_REPEATS", "3"))
    bests = []
    for k in range(len(segs) + 1):
        fn = jax.jit(
            lambda p, t, _k=k: pipeline_train_interleaved(
                cfg, p, t, mesh, decomp=decomp, n_microbatches=m,
                n_chunks=v, executor="segmented", _run_segments=_k,
            )
        )
        jax.block_until_ready(fn(params, toks))  # compile + warm
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(params, toks))
            times.append(time.perf_counter() - t0)
        bests.append(min(times))
    return [
        round(max(0.0, (b - a)) * 1e3, 2)
        for a, b in zip(bests[:-1], bests[1:])
    ]


# Engine-phase breakdown keys _phase_ours reports (and main() carries
# into the detail record; renamed cpu_fresh_* when a cached hardware
# headline is promoted over a fresh CPU run).
_ENGINE_SPLIT_KEYS = (
    "materialize_mode", "materialize_n_programs", "materialize_lower_s",
    "materialize_compile_s", "materialize_execute_s", "materialize_overlap",
    "materialize_exec_gbps",
    "materialize_bytes_donated", "materialize_transfer_overlap",
    "materialize_device_put_batches",
    # Cost-model fields ride the same promote/rename machinery: a
    # CPU-fresh link utilization must never sit unrenamed next to a
    # promoted hardware headline.
    "link_bandwidth_gbps", "materialize_link_utilization",
    "materialize_xla_gflops", "materialize_peak_hbm_mb",
)

def phase_platform() -> dict:
    """The device this process's default backend runs on — the
    preflight's question, answered by a child so the parent never
    holds the chip."""
    jax = _init_jax()
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


PHASES = {
    "platform": phase_platform,
    "gpt2_baseline": phase_gpt2_baseline,
    "gpt2_ours": phase_gpt2_ours,
    "llama_ours": phase_llama_ours,
    "llama_baseline": phase_llama_baseline,
    "llama_big_ours": phase_llama_big_ours,
    "t5_sharded": phase_t5_sharded,
    "mixtral_sharded": phase_mixtral_sharded,
    "llama70b_lower": phase_llama70b_lower,
    "t5_11b_lower": phase_t5_11b_lower,
    "mixtral_8x7b_lower": phase_mixtral_8x7b_lower,
    "flash": phase_flash,
    "flash_bwd": phase_flash_bwd,
    "flash_bias": phase_flash_bias,
    "pp_bubble": phase_pp_bubble,
    "schedule_measured": phase_schedule_measured,
    "serving": phase_serving,
    "serving_fleet": phase_serving_fleet,
    "serving_prefix": phase_serving_prefix,
    "serving_spec": phase_serving_spec,
    "serving_ledger": phase_serving_ledger,
    "serving_rollover": phase_serving_rollover,
    "guardrails": phase_guardrails,
    "train_mfu": phase_train_mfu,
    "materialize_pipeline": phase_materialize_pipeline,
    "materialize_bandwidth": phase_materialize_bandwidth,
    "reshard": phase_reshard,
}


def _run_phase(name: str, timeout: float = 600.0):
    """Run one phase in a child process (the parent stays off jax: a
    chip belongs to one process at a time).  A failed phase is reported
    as ``{"error": ...}``; there is no other source of numbers."""
    with observe.span(
        "bench.phase", category="bench", phase=name, timeout_s=timeout
    ) as _sp:
        return _run_phase_inner(name, timeout, _sp)


def _run_phase_inner(name: str, timeout: float, _sp):
    err = None
    res = None
    # NOT subprocess.run(timeout=.., capture_output=True): run() kills
    # only the direct child on timeout and then blocks draining the
    # captured pipes, which any grandchild that inherited them can hold
    # open.  run_in_killable_group is the hang-proof recipe: own
    # session, file-backed stdio (no EOF needed to read back),
    # process-group kill on timeout and success alike — so a timed-out
    # phase cannot keep holding the chip the next phase needs.
    from torchdistx_tpu._probe import run_in_killable_group

    argv = [sys.executable, os.path.abspath(__file__), "--phase", name]
    # Causal handoff: a flow-start inside this bench.phase span plus a
    # TDX_TRACE_PARENT env token makes the merged Chrome trace draw an
    # arrow from this span to the subprocess's first span.
    if observe.enabled():
        from torchdistx_tpu.observe import tracectx

        child_env = tracectx.child_env(tracectx.flow_start("bench.spawn"))
    else:
        child_env = None
    out_f = tempfile.TemporaryFile(mode="w+", encoding="utf-8",
                                   errors="replace")
    err_f = tempfile.TemporaryFile(mode="w+", encoding="utf-8",
                                   errors="replace")
    try:
        rc = run_in_killable_group(argv, timeout, stdout=out_f,
                                   stderr=err_f, cwd=REPO, env=child_env)
        if rc is None:
            err = {"error": f"phase {name} timed out after {timeout:.0f}s",
                   "timeout_s": timeout}
        else:
            out_f.seek(0)
            err_f.seek(0)
            res = subprocess.CompletedProcess(
                argv, rc, out_f.read(), err_f.read()
            )
    except (OSError, subprocess.SubprocessError) as e:
        err = {"error": f"phase {name} failed to spawn: {e}"}
    finally:
        out_f.close()
        err_f.close()
    if err is None and res.returncode != 0:
        err = {"error": (res.stderr or res.stdout).strip()[-400:]}
    if err is None:
        try:
            parsed = json.loads(res.stdout.strip().splitlines()[-1])
        except Exception:
            err = {"error": f"unparseable phase output: {res.stdout[-200:]!r}"}
    if err is None:
        # The phase subprocess reports the backend it ACTUALLY ran on
        # (not the env var), returned to main() so an unforced phase
        # that found itself on the CPU fails the run.
        backend = parsed.pop("backend", None)
        if backend is not None:
            parsed["_backend"] = backend
        _sp.set(outcome="fresh", backend=backend)
        return parsed
    _sp.set(outcome="error", error=err["error"][-120:])
    return err


def _merge_flash_result(out: dict, name: str, result: dict) -> None:
    """Merge a flash-phase result into the output JSON under the phase's
    key scheme: flash_ms stays flash_ms for the fwd phase and becomes
    flash_bwd_ms / flash_bias_ms for the flavors (no key stutter)."""
    if name == "flash":
        mapped = {
            f"flash_{k}" if not k.startswith(("flash", "ref")) else k: v
            for k, v in result.items()
        }
    else:
        mapped = {
            (f"{name}{k[5:]}" if k.startswith("flash_") else f"{name}_{k}"): v
            for k, v in result.items()
        }
    out.update(mapped)


def _merge_big_llama(out: dict, result: dict) -> None:
    """llama_big_* key scheme."""
    out["llama_big_ours_s"] = round(result["t"], 3)
    out["llama_big_rss_mb"] = round(result.get("rss_mb", 0.0), 1)
    out["llama_big_n_params"] = result.get("n_params")
    out["llama_big_param_dtype"] = result.get("param_dtype")
    out["llama_big_warm"] = bool(result.get("warm"))
    for k in ("record_s", "materialize_s", "materialize_gbps"):
        if result.get(k) is not None:
            out[f"llama_big_{k}"] = result[k]


def _merge_train_result(out: dict, result: dict) -> None:
    """train_* key scheme."""
    out.update({f"train_{k}": v for k, v in result.items()
                if k != "device_kind"})


def _preflight_platform() -> dict:
    """The device the unforced phases will run on, asked of a throwaway
    child (the parent stays off jax).  No accelerator is a failed run:
    non-zero exit, nothing on stdout.  An explicit TDX_BENCH_PLATFORM
    (tests, ``make bench-smoke``) is a choice, not a fallback, and is
    reported as such."""
    forced = os.environ.get("TDX_BENCH_PLATFORM")
    if forced:
        return {"platform": forced, "forced": True}
    dev = _run_phase("platform", timeout=300.0)
    if "error" in dev:
        sys.exit(f"bench: backend init failed: {dev['error'][-400:]}")
    dev.pop("_backend", None)
    if dev["platform"] == "cpu":
        sys.exit(
            "bench: JAX found no accelerator (platform 'cpu') and "
            "TDX_BENCH_PLATFORM is not set; refusing to publish CPU "
            "numbers under device metrics"
        )
    return dev


def _require_device(name: str, result: dict, device: dict) -> None:
    """An unforced phase that reports the cpu backend lost the
    accelerator the preflight saw: fail the run, publish nothing.
    Consumes the phase's ``_backend`` stamp."""
    if result.pop("_backend", None) == "cpu" and not device.get("forced"):
        sys.exit(f"bench: phase {name} ran on the cpu backend although "
                 f"the preflight saw {device}")


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--phase":
        res = PHASES[sys.argv[2]]()
        if "backend" not in res and "jax" in sys.modules:
            # Report the TRUE backend of a phase that initialized jax;
            # a phase that never touched it (static analyses) has none,
            # and importing it here would take the chip for nothing.
            res["backend"] = sys.modules["jax"].default_backend()
        print(json.dumps(res))
        return

    device = _preflight_platform()
    forced = bool(device.get("forced"))

    ours = _run_phase("gpt2_ours", timeout=900.0)
    if "error" in ours:
        sys.exit(f"bench: headline phase gpt2_ours failed: "
                 f"{ours['error'][-400:]}")
    _require_device("gpt2_ours", ours, device)
    base = _run_phase("gpt2_baseline", timeout=900.0)
    _require_device("gpt2_baseline", base, device)
    out = {
        "metric": "gpt2-125m deferred_init→device materialize+touch wall time",
        "value": round(ours["t"], 3),
        "unit": "s",
        "device": device,
        "vs_baseline": round(base["t"] / ours["t"], 3) if "t" in base else None,
        "baseline_s": round(base.get("t", 0.0), 3),
        "ours_rss_mb": round(ours["rss_mb"], 1),
        "baseline_rss_mb": round(base.get("rss_mb", 0.0), 1),
        "warm_compile_cache": bool(ours.get("warm")),
        **(
            {"materialize_gbps": ours["materialize_gbps"]}
            if ours.get("materialize_gbps") is not None else {}
        ),
        # Engine-phase split: which engine ran, and where the wall went
        # (trace/lower vs compile vs execute) — materialize_exec_gbps is
        # the device-side rate alone, so cold-compile cost can no longer
        # masquerade as transfer slowness.
        **{
            k: ours[k] for k in _ENGINE_SPLIT_KEYS if ours.get(k) is not None
        },
    }

    llama_ours = _run_phase("llama_ours")
    if "error" not in llama_ours:
        _require_device("llama_ours", llama_ours, device)
        llama_base = _run_phase("llama_baseline")
        _require_device("llama_baseline", llama_base, device)
        out["llama_1p9b_ours_s"] = round(llama_ours["t"], 3)
        out["llama_1p9b_ours_rss_mb"] = round(llama_ours["rss_mb"], 1)
        out["llama_1p9b_n_params"] = llama_ours.get("n_params")
        if llama_ours.get("materialize_gbps") is not None:
            out["llama_1p9b_materialize_gbps"] = llama_ours["materialize_gbps"]
        if "error" not in llama_base:
            out["llama_1p9b_baseline_s"] = round(llama_base["t"], 3)
            out["llama_1p9b_baseline_rss_mb"] = round(llama_base["rss_mb"], 1)
            out["llama_1p9b_vs_baseline"] = round(
                llama_base["t"] / llama_ours["t"], 3
            )
        elif "timeout_s" in llama_base:
            # The eager path (torch CPU init of 1.5B params + 5.9 GB
            # of host→device transfers) did not finish inside the
            # budget; report the measured lower bound instead.
            out["llama_1p9b_baseline_s"] = None
            out["llama_1p9b_baseline_timeout_s"] = llama_base["timeout_s"]
            out["llama_1p9b_vs_baseline_at_least"] = round(
                llama_base["timeout_s"] / llama_ours["t"], 1
            )
        else:
            out["llama_baseline_error"] = llama_base["error"][-160:]
    else:
        out["llama_error"] = llama_ours["error"][-160:]

    # 6.74B bf16 — sized for the 16 GB chip (see _llama_big_config);
    # on a forced-CPU smoke run the full-depth program is hours of
    # host RNG, so require an explicit depth override there.
    if forced and not os.environ.get("TDX_BIG_LLAMA_LAYERS"):
        out["llama_big_skipped"] = (
            "forced-cpu smoke (set TDX_BIG_LLAMA_LAYERS for a small run)"
        )
    else:
        big = _run_phase("llama_big_ours", timeout=1200.0)
        if "error" in big:
            out["llama_big_error"] = big["error"][-160:]
        else:
            _require_device("llama_big_ours", big, device)
            _merge_big_llama(out, big)

    for name in ("t5_sharded", "mixtral_sharded"):
        r = _run_phase(name, timeout=420.0)
        if "error" not in r:
            out[f"{name}_s"] = round(r["t"], 3)
            out[f"{name}_rss_mb"] = round(r["rss_mb"], 1)
            out[f"{name}_n_params"] = r.get("n_params")
            out[f"{name}_n_sharded"] = r.get("n_sharded")
            out[f"{name}_warm"] = bool(r.get("warm"))
        else:
            out[f"{name}_error"] = r["error"][-160:]

    for prefix, phase in (("llama70b", "llama70b_lower"),
                          ("t5_11b", "t5_11b_lower"),
                          ("mixtral_8x7b", "mixtral_8x7b_lower")):
        r = _run_phase(phase, timeout=420.0)
        r.pop("_backend", None)  # host-side phases: backend is irrelevant
        if "error" not in r:
            out.update({f"{prefix}_{k}": v for k, v in r.items()})
        else:
            out[f"{prefix}_error"] = r["error"][-160:]

    mp = _run_phase("materialize_pipeline", timeout=600.0)
    mp.pop("_backend", None)  # forced-CPU engine A/B: cpu by design
    if "error" not in mp:
        out["materialize_pipeline"] = mp
        # Promoted headline key: cold monolithic vs pipelined engine.
        if mp.get("pipeline_speedup") is not None:
            out["pipeline_speedup"] = mp["pipeline_speedup"]
    else:
        out["materialize_pipeline_error"] = mp["error"][-160:]

    mb = _run_phase("materialize_bandwidth", timeout=600.0)
    mb.pop("_backend", None)  # forced-CPU transport A/B: cpu by design
    if "error" not in mb:
        out["materialize_bandwidth"] = mb
        # Promoted headline keys: the transport-layer rate and its
        # fraction of the measured link (the ROADMAP bandwidth-gap
        # metric, measured warm on a transport-bound model — distinct
        # from the gpt2 headline's record+compile-laden GB/s).
        if mb.get("materialize_gbps") is not None:
            out["materialize_bandwidth_gbps"] = mb["materialize_gbps"]
        if mb.get("materialize_link_utilization") is not None:
            out["materialize_bandwidth_utilization"] = (
                mb["materialize_link_utilization"]
            )
    else:
        out["materialize_bandwidth_error"] = mb["error"][-160:]

    rs = _run_phase("reshard", timeout=600.0)
    rs.pop("_backend", None)  # host-side tensorstore copy: cpu by design
    if "error" not in rs:
        out["reshard"] = rs
        # Promoted headline keys: the topology-migration rate and the
        # bytes a mesh-shrink would move (docs/robustness.md
        # §Resharding) — tracked by tools/bench_trend.py from r06 on.
        if rs.get("reshard_gbps") is not None:
            out["reshard_gbps"] = rs["reshard_gbps"]
        if rs.get("reshard_bytes_moved") is not None:
            out["reshard_bytes_moved"] = rs["reshard_bytes_moved"]
    else:
        out["reshard_error"] = rs["error"][-160:]

    bb = _run_phase("pp_bubble", timeout=120.0)
    bb.pop("_backend", None)  # static schedule analysis: no backend
    if "error" not in bb:
        out["schedule_analysis"] = bb.get("schedule_analysis")
    else:
        out["pp_bubble_error"] = bb["error"][-160:]

    sm = _run_phase("schedule_measured", timeout=600.0)
    sm.pop("_backend", None)  # virtual-mesh phase: backend is cpu by design
    if "error" not in sm:
        out["schedule_measured"] = sm.get("schedule_measured")
    else:
        out["schedule_measured_error"] = sm["error"][-160:]

    sv = _run_phase("serving", timeout=600.0)
    sv.pop("_backend", None)  # forced-CPU serving A/B: cpu by design
    if "error" not in sv:
        out["serving"] = sv
        # Promoted headline key: cold-compile vs registry-warm TTFT.
        if sv.get("ttft_warm_speedup") is not None:
            out["serving_ttft_warm_speedup"] = sv["ttft_warm_speedup"]
    else:
        out["serving_error"] = sv["error"][-160:]

    sf = _run_phase("serving_fleet", timeout=900.0)
    sf.pop("_backend", None)  # forced-CPU fleet scaling A/B: cpu by design
    if "error" not in sf:
        out["serving_fleet"] = sf
        # Promoted headline keys: cold-compile vs registry-warm scale-up,
        # and router throughput scaling 1 -> 2 replicas.
        if sf.get("fleet_scaleup_warm_speedup") is not None:
            out["fleet_scaleup_warm_speedup"] = sf["fleet_scaleup_warm_speedup"]
        if sf.get("fleet_scaling_efficiency_2r") is not None:
            out["fleet_scaling_efficiency_2r"] = sf["fleet_scaling_efficiency_2r"]
    else:
        out["serving_fleet_error"] = sf["error"][-160:]

    sp = _run_phase("serving_prefix", timeout=900.0)
    sp.pop("_backend", None)  # forced-CPU sharing A/B: cpu by design
    if "error" not in sp:
        out["serving_prefix"] = sp
        # Promoted headline keys: the SAME 80%-shared storm, prefix
        # cache off / on.
        for key in ("prefix_tokens_per_s_improvement",
                    "prefix_p95_ttft_improvement"):
            if sp.get(key) is not None:
                out[key] = sp[key]
    else:
        out["serving_prefix_error"] = sp["error"][-160:]

    ss = _run_phase("serving_spec", timeout=900.0)
    ss.pop("_backend", None)  # forced-CPU speculation A/B: cpu by design
    if "error" not in ss:
        out["serving_spec"] = ss
        # Promoted headline keys: spec-on vs spec-off tokens/s on the
        # same storm, and the realized draft accept rate.
        for key in ("spec_tokens_per_s_improvement", "spec_accept_rate"):
            if ss.get(key) is not None:
                out[key] = ss[key]
    else:
        out["serving_spec_error"] = ss["error"][-160:]

    sl = _run_phase("serving_ledger", timeout=900.0)
    sl.pop("_backend", None)  # forced-CPU ledger A/B: cpu by design
    if "error" not in sl:
        out["serving_ledger"] = sl
        # Promoted headline key: tokens/s with the per-request ledger
        # on vs off, same storm (the ≤2% overhead claim).
        if sl.get("ledger_overhead_ratio") is not None:
            out["ledger_overhead_ratio"] = sl["ledger_overhead_ratio"]
    else:
        out["serving_ledger_error"] = sl["error"][-160:]

    sr = _run_phase("serving_rollover", timeout=900.0)
    sr.pop("_backend", None)  # forced-CPU rollover A/B: cpu by design
    if "error" not in sr:
        out["serving_rollover"] = sr
        # Promoted headline key: mid-roll tokens/s over steady-state —
        # a blue-green roll must cost the storm <10% throughput.
        if sr.get("rollover_tokens_per_s_ratio") is not None:
            out["rollover_tokens_per_s_ratio"] = (
                sr["rollover_tokens_per_s_ratio"])
    else:
        out["serving_rollover_error"] = sr["error"][-160:]

    gr = _run_phase("guardrails", timeout=900.0)
    gr.pop("_backend", None)  # forced-CPU guardrail A/B: cpu by design
    if "error" not in gr:
        out["guardrails"] = gr
        # Promoted headline key: high-priority p95 TTFT under the same
        # flap storm, guardrails disarmed / armed.
        if gr.get("guardrails_p95_ttft_improvement") is not None:
            out["guardrails_p95_ttft_improvement"] = (
                gr["guardrails_p95_ttft_improvement"])
    else:
        out["guardrails_error"] = gr["error"][-160:]

    for name in ("flash", "flash_bwd", "flash_bias"):
        r = _run_phase(name, timeout=900.0)
        if "error" in r:
            out[f"{name}_error"] = r["error"][-160:]
        else:
            # A forced TDX_BENCH_PLATFORM=cpu smoke run keeps its
            # interpret-mode numbers (labeled by out["device"]).
            _require_device(name, r, device)
            _merge_flash_result(out, name, r)
    r = _run_phase("train_mfu", timeout=1500.0)
    if "error" in r:
        out["train_mfu_error"] = r["error"][-160:]
    else:
        _require_device("train_mfu", r, device)
        _merge_train_result(out, r)

    _emit(out)


# Keys promoted to the final compact headline line, in priority order
# (later entries are dropped first if the line somehow outgrows the
# bound).  Everything else stays on the full-detail line / file.
_HEADLINE_KEYS = (
    "metric", "value", "unit", "vs_baseline", "device", "baseline_s",
    "warm_compile_cache",
    "materialize_gbps", "materialize_link_utilization", "pipeline_speedup",
    "materialize_bandwidth_gbps", "materialize_bandwidth_utilization",
    "reshard_gbps", "reshard_bytes_moved",
    "fleet_scaleup_warm_speedup", "fleet_scaling_efficiency_2r",
    "guardrails_p95_ttft_improvement",
    "prefix_tokens_per_s_improvement", "prefix_p95_ttft_improvement",
    "spec_tokens_per_s_improvement", "spec_accept_rate",
    "ledger_overhead_ratio",
    "rollover_tokens_per_s_ratio",
    "train_mfu", "train_mfu_xla", "train_tokens_per_s", "train_step_ms",
    "train_mfu_error",
    "flash_mfu", "flash_speedup", "flash_bwd_mfu", "flash_bwd_speedup",
    "flash_bias_mfu", "flash_bias_speedup",
    "llama_1p9b_vs_baseline", "llama_1p9b_ours_s", "llama_1p9b_n_params",
    "llama_1p9b_materialize_gbps",
    "llama_big_n_params", "llama_big_ours_s", "llama_big_materialize_gbps",
    "llama_big_param_dtype",
    "t5_11b_n_params", "t5_11b_rss_mb",
    "mixtral_8x7b_n_params", "mixtral_8x7b_rss_mb",
)

# The driver records only the last ~2000 characters of stdout; round 4's
# single giant JSON line outgrew that and the scoreboard lost its
# headline (`BENCH_r04.json` parsed: null).  Keep the final line well
# under the window.
_HEADLINE_BUDGET = 1800


def _headline(out: dict, detail_file: str | None) -> dict:
    """Compact scoreboard record: headline metric + MFU + speedup keys
    only, guaranteed to serialize within _HEADLINE_BUDGET bytes.
    ``detail_file`` names where the full record landed (None if the
    write failed — never point consumers at a stale file)."""
    h = {k: out[k] for k in _HEADLINE_KEYS if k in out}
    if detail_file is not None:
        h["detail"] = detail_file
    while len(json.dumps(h)) > _HEADLINE_BUDGET and len(h) > 1:
        for k in reversed(list(h)):
            if k != "detail":
                del h[k]
                break
    return h


def _emit(out: dict) -> None:
    """Full detail first (line 1 + bench_full.json for humans), then the
    compact headline as the LAST stdout line for the driver's tail
    capture."""
    full = json.dumps(out)
    detail_file = "bench_full.json"
    try:
        with open(os.path.join(REPO, detail_file), "w") as f:
            f.write(full + "\n")
    except OSError:
        detail_file = None
    print(full)
    print(json.dumps(_headline(out, detail_file)))
    observe.flush()  # trace/metrics files when TDX_TRACE_DIR etc. are set


if __name__ == "__main__":
    main()
