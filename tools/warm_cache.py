"""Warm the persistent XLA compilation cache with init programs.

The cold half of the north-star workflow: a login host deferred-inits a
model (fakes, zero storage), lowers its init programs, and compiles them
into the persistent cache directory (``--cache-dir``; default: wherever
``torchdistx_tpu.config.compile_cache_dir()`` resolves —
``JAX_COMPILATION_CACHE_DIR``, else ``TDX_CACHE_DIR``, else
``<checkout>/.jax_cache``).  A cache placed from outside with
``JAX_COMPILATION_CACHE_DIR`` is the only one the program will bind, so
a different ``--cache-dir`` is refused rather than silently ignored.  A
later ``materialize_module_jax`` on any host sharing that cache — the pod
restart path, a CI cold start — then hits every entry instead of paying
XLA compilation, the dominant cost of the cold path.

BOTH program sets are warmed so either engine mode starts hot:

* the whole-model monolithic program (``TDX_MATERIALIZE_PIPELINE=off``,
  also the export path's program);
* the per-group programs the pipelined engine
  (``TDX_MATERIALIZE_PIPELINE=auto``, default) will request — the split
  is deterministic for a given recording and config, so the compiled set
  matches exactly.  Warm with the same ``TDX_COMPILE_WORKERS`` (and mesh
  / plan / param_dtype) the consumer will run with.

**Pod-scale sharded warm** (``--hosts N --host-id i --registry-dir R``,
docs/registry.md): run one invocation per host against a shared
registry directory and each host compiles only its deterministic shard
of the program set, publishes the executables, and fills the rest from
what the other hosts published — O(model / hosts) compile per host.  A
program whose owner never publishes is stolen after ``--steal-after``
seconds, so a dead host degrades the warm instead of hanging it.  With
``--registry-dir`` alone (hosts=1) the warm still publishes everything,
seeding the registry for later consumers.

Every program reports its own outcome (``published`` / ``compiled`` /
``fetched`` / ``cached`` / ``stolen`` / ``unwarmed``), one line each,
followed by a summary JSON line; the exit status is non-zero if ANY
program ended unwarmed.

**Serving-program warm** (``--decode``, docs/serving.md): warm a
replica's WHOLE bring-up program set — the deferred-init parameter
program, every prefill bucket, and the continuous-batching decode
program — so ``serve.spin_up_replica`` of the same shape performs zero
local compiles end to end.  ``--model`` then names a model-zoo preset
(``tiny``, ``tiny-gpt2``, ``gpt2-125m``, ``llama3-8b``, ...) and the
serve shape knobs (``--serve-batch`` / ``--page-size`` / ``--pages`` /
``--max-pages-per-seq`` / ``--prefill-buckets``) must match the
consumer's ``ServeConfig`` — they are part of the programs' registry
identity by design.

Usage::

    python tools/warm_cache.py --model gpt2 --cache-dir .jax_cache
    python tools/warm_cache.py --model llama-1b9 --cache-dir /nfs/cache \\
        --host-devices 8 --mesh fsdp=4,tp=2 --param-dtype bfloat16
    python tools/warm_cache.py --module mypkg.models:build --cache-dir d
    python tools/warm_cache.py --model gpt2 --cache-dir .jax_cache \\
        --registry-dir /nfs/tdx_registry --hosts 4 --host-id 2
    python tools/warm_cache.py --decode --model tiny --cache-dir d \\
        --registry-dir /nfs/tdx_registry --serve-batch 4 --page-size 16

Cache-key caveats: entries are keyed on backend, topology, and compile
options — warm on the platform (and device count) the consumer will see.
XLA:CPU entries are additionally host-ISA-specific AOT code: do not
carry a CPU-warmed directory to a host with another CPU.  The
registry composes the same identity into its keys (``registry.env_key``),
so a mismatched fetch is impossible by construction.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default=None,
                   help="named model: gpt2 | llama-1b9 | t5-small | demo")
    p.add_argument("--module", default=None,
                   help="custom factory 'pkg.mod:fn' returning an "
                        "(eagerly constructible) torch.nn.Module; recorded "
                        "under deferred_init")
    p.add_argument("--cache-dir", default=None,
                   help="persistent compilation cache directory to fill "
                        "(default: config.compile_cache_dir())")
    p.add_argument("--mesh", default=None,
                   help="mesh axes, e.g. fsdp=4,tp=2 (omit for single-device)")
    p.add_argument("--plan", default="fsdp", choices=("fsdp", "gspmd2d"),
                   help="sharding plan used with --mesh (default fsdp)")
    p.add_argument("--param-dtype", default=None,
                   help="cast policy, e.g. bfloat16 (matches the "
                        "materialize-time param_dtype)")
    p.add_argument("--host-devices", type=int, default=0,
                   help="force an N-device virtual CPU topology (login "
                        "hosts warming for a pod slice shape)")
    p.add_argument("--skip-groups", action="store_true",
                   help="warm only the whole-model program")
    p.add_argument("--skip-whole", action="store_true",
                   help="warm only the per-group programs")
    p.add_argument("--registry-dir", default=None,
                   help="shared compile-artifact registry directory "
                        "(docs/registry.md); programs are fetched from and "
                        "published to it")
    p.add_argument("--hosts", type=int, default=1,
                   help="total hosts participating in a sharded warm "
                        "(requires --registry-dir when > 1)")
    p.add_argument("--host-id", type=int, default=0,
                   help="this host's 0-based id in [0, hosts)")
    p.add_argument("--spawn-shards", action="store_true",
                   help="single-machine pod rehearsal ON THE CPU: spawn "
                        "all --hosts shard invocations as concurrent "
                        "subprocesses pinned to the cpu platform (a chip "
                        "belongs to one process; N children taking the "
                        "default backend would fight over it), each with "
                        "its --host-id; hand each the causal "
                        "trace context (TDX_TRACE_PARENT), and exit "
                        "non-zero if any shard does — the merged Chrome "
                        "trace then draws flow arrows from this parent's "
                        "spawn span to every shard's compile spans")
    p.add_argument("--steal-after", type=float, default=120.0,
                   help="seconds to wait for another host's artifact "
                        "before compiling it locally (work stealing)")
    p.add_argument("--poll", type=float, default=0.5,
                   help="registry polling interval during the fill phase")
    p.add_argument("--decode", action="store_true",
                   help="warm the SERVING program set (init + prefill "
                        "buckets + decode) for a model-zoo preset named "
                        "by --model (docs/serving.md)")
    p.add_argument("--serve-batch", type=int, default=4,
                   help="--decode: decode batch lanes (ServeConfig."
                        "max_batch)")
    p.add_argument("--page-size", type=int, default=16,
                   help="--decode: KV page size in tokens")
    p.add_argument("--pages", type=int, default=64,
                   help="--decode: KV pool pages (incl. the null page)")
    p.add_argument("--max-pages-per-seq", type=int, default=0,
                   help="--decode: page-table width (0 = fit max_seq_len)")
    p.add_argument("--prefill-buckets", default=None,
                   help="--decode: comma-separated prompt buckets "
                        "(default: powers of two up to the context cap)")
    p.add_argument("--seed", type=int, default=0,
                   help="--decode: replica init seed (part of the init "
                        "program's identity)")
    return p.parse_args(argv)


def _model_factory(args):
    if (args.model is None) == (args.module is None):
        raise SystemExit("exactly one of --model / --module is required")
    if args.module:
        modname, _, fn = args.module.partition(":")
        if not fn:
            raise SystemExit("--module must be 'pkg.mod:factory'")
        factory = getattr(importlib.import_module(modname), fn)
        return lambda: factory()
    name = args.model
    if name == "demo":
        return _demo_model
    if name == "gpt2":
        from transformers import GPT2Config, GPT2LMHeadModel

        return lambda: GPT2LMHeadModel(GPT2Config())
    if name == "llama-1b9":
        from transformers import LlamaConfig, LlamaForCausalLM

        return lambda: LlamaForCausalLM(LlamaConfig(
            vocab_size=64128, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=24, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=4096,
        ))
    if name == "t5-small":
        from transformers import T5Config, T5ForConditionalGeneration

        return lambda: T5ForConditionalGeneration(T5Config(
            d_model=512, d_ff=2048, num_layers=6, num_heads=8,
            vocab_size=32128, d_kv=64,
        ))
    raise SystemExit(f"unknown --model {name!r}")


def _demo_model():
    """Tiny heterogeneous stack (distinct widths → several structural
    groups) — exercises the full warm→hit round trip in seconds; used by
    the test suite."""
    import torch

    widths = [32 + 8 * i for i in range(12)]

    class Demo(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.layers = torch.nn.ModuleList(
                torch.nn.Linear(widths[i], widths[(i + 1) % len(widths)])
                for i in range(len(widths))
            )

    return Demo()


def _parse_mesh(spec):
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return axes


def _probe_cache_dir(cache_dir: str) -> None:
    """Fail fast on an unusable cache dir: jax itself degrades cache-WRITE
    errors to warnings, so without this probe the tool would burn the
    full compile budget and then claim success while having warmed
    nothing.  (A permissions probe via os.access lies under root, so
    actually write.)"""
    probe = os.path.join(cache_dir, f".tdx_warm_probe_{os.getpid()}")
    try:
        os.makedirs(cache_dir, exist_ok=True)
        with open(probe, "w") as f:
            f.write("probe")
        os.remove(probe)
    except OSError as e:
        raise OSError(
            f"cache dir {cache_dir!r} is not writable ({e}); nothing warmed"
        ) from e


def warm(factory, cache_dir, *, mesh=None, plan=None, param_dtype=None,
         skip_whole=False, skip_groups=False, registry_dir=None,
         hosts=1, host_id=0, steal_after_s=120.0, poll_s=0.5) -> dict:
    """Compile a module factory's init programs into ``cache_dir`` (and,
    when ``registry_dir`` is set, exchange them through the shared
    artifact registry — sharded across ``hosts`` by
    :func:`torchdistx_tpu.registry.warm_sharded`); returns a summary
    dict with per-program outcome reports.  Importable (the tests drive
    it in-process); ``main`` is the CLI shell around it."""
    from torchdistx_tpu.registry import warm_sharded

    _probe_cache_dir(cache_dir)
    return warm_sharded(
        factory, cache_dir, registry_dir=registry_dir,
        hosts=hosts, host_id=host_id, mesh=mesh, plan=plan,
        param_dtype=param_dtype, skip_whole=skip_whole,
        skip_groups=skip_groups, steal_after_s=steal_after_s,
        poll_s=poll_s,
    )


def warm_decode(model_name, cache_dir, *, registry_dir=None, serve_cfg=None,
                seed=0, param_dtype=None, mesh=None, plan=None) -> dict:
    """Warm the SERVING program set of a model-zoo preset — the
    deferred-init parameter program, every prefill/chunk bucket, the
    cow + decode programs, and every speculative ``verify-<k>`` bucket
    — via :func:`torchdistx_tpu.serve.warm_serving`, so a later
    ``spin_up_replica`` of the same shape is all-hit end to end, with
    speculation on or off (the warm set ignores the host-side
    ``TDX_SPEC_DECODE`` toggle so one registry serves both)."""
    from torchdistx_tpu.models import PRESETS, TransformerConfig
    from torchdistx_tpu.serve import warm_serving
    from torchdistx_tpu.serve.programs import model_family

    cfg = PRESETS.get(model_name)
    if not isinstance(cfg, TransformerConfig) or cfg.moe is not None:
        raise SystemExit(
            f"--decode needs a DENSE decoder-LM zoo preset for --model; "
            f"{model_name!r} is not one (choose from "
            f"{sorted(k for k, v in PRESETS.items() if isinstance(v, TransformerConfig) and v.moe is None)})"
        )
    _probe_cache_dir(cache_dir)
    return warm_serving(
        model_family(model_name), cfg, cache_dir,
        registry_dir=registry_dir, serve_cfg=serve_cfg, seed=seed,
        param_dtype=param_dtype, mesh=mesh, plan=plan,
    )


def _spawn_shards(args, argv) -> None:
    """Parent mode for ``--spawn-shards``: launch every shard of the
    sharded warm as a concurrent child of THIS process, each inheriting
    the parent's trace context plus a per-shard flow id — so one merged
    trace shows the whole rehearsal as a causal tree."""
    import subprocess

    from torchdistx_tpu import observe
    from torchdistx_tpu.observe import tracectx

    if args.hosts < 1:
        raise SystemExit("--spawn-shards requires --hosts >= 1")
    if args.hosts > 1 and not args.registry_dir:
        raise SystemExit("--spawn-shards with --hosts > 1 requires "
                         "--registry-dir (the shards exchange through it)")
    # The children re-run this script with the parent's arguments minus
    # the spawn flag and any explicit --host-id, plus their own id.
    base = []
    skip_next = False
    for tok in argv:
        if skip_next:
            skip_next = False
            continue
        if tok == "--spawn-shards":
            continue
        if tok == "--host-id":
            skip_next = True
            continue
        if tok.startswith("--host-id="):
            continue
        base.append(tok)
    script = os.path.abspath(__file__)
    procs = []
    with observe.span(
        "warm.spawn", category="warm", hosts=args.hosts,
    ):
        for host_id in range(args.hosts):
            flow_id = (tracectx.flow_start("warm.spawn_shard")
                       if observe.enabled() else None)
            env = tracectx.child_env(flow_id)
            # A rehearsal, not a device warm: concurrent children that
            # each took the default backend would contend for one chip.
            env["JAX_PLATFORMS"] = "cpu"
            procs.append(subprocess.Popen(
                [sys.executable, script, *base, "--host-id", str(host_id)],
                env=env,
            ))
        rcs = [p.wait() for p in procs]
    for host_id, rc in enumerate(rcs):
        print(f"warm: shard host_id={host_id} rc={rc}", file=sys.stderr)
    print(json.dumps({"hosts": args.hosts, "shard_rcs": rcs}))
    observe.flush()
    if any(rcs):
        raise SystemExit(1)


def main(argv=None) -> None:
    argv = list(argv if argv is not None else sys.argv[1:])
    args = _parse_args(argv)
    if args.spawn_shards:
        return _spawn_shards(args, argv)
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.host_devices}"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

    mesh = plan = None
    if args.mesh:
        from torchdistx_tpu.parallel import (
            fsdp_plan, gspmd_2d_plan, make_mesh,
        )

        mesh = make_mesh(_parse_mesh(args.mesh))
        plan = fsdp_plan() if args.plan == "fsdp" else gspmd_2d_plan()
    param_dtype = None
    if args.param_dtype:
        import jax.numpy as jnp

        param_dtype = getattr(jnp, args.param_dtype)

    from torchdistx_tpu import config as tdx_config

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if args.cache_dir is None:
        args.cache_dir = tdx_config.compile_cache_dir()
        if not args.cache_dir:
            raise SystemExit('no cache directory: TDX_CACHE_DIR="" disables '
                             "the cache; pass --cache-dir")
    elif placed and os.path.realpath(placed) != os.path.realpath(
            args.cache_dir):
        raise SystemExit(
            f"JAX_COMPILATION_CACHE_DIR={placed!r} places the compile cache "
            f"from outside; warming --cache-dir {args.cache_dir!r} instead "
            f"is not possible (unset the variable or drop --cache-dir)"
        )
    os.makedirs(args.cache_dir, exist_ok=True)
    if args.decode:
        if args.model is None:
            raise SystemExit("--decode requires --model <zoo preset>")
        if args.hosts > 1:
            raise SystemExit(
                "--decode warms a single replica shape; sharded "
                "multi-host warming applies to the init-program sets "
                "(drop --hosts)"
            )
        from torchdistx_tpu.serve import ServeConfig

        buckets = ()
        if args.prefill_buckets:
            buckets = tuple(
                int(b) for b in args.prefill_buckets.split(",") if b.strip()
            )
        from torchdistx_tpu.models import PRESETS

        # A stack with recurrent layers has no verify-<k> and no cow
        # program to warm: ServeConfig.resolve refuses speculation and
        # the prefix cache for it, and says why.
        hybrid = getattr(PRESETS.get(args.model), "mamba", None) is not None
        serve_cfg = ServeConfig(
            max_batch=args.serve_batch, page_size=args.page_size,
            n_pages=args.pages,
            max_pages_per_seq=args.max_pages_per_seq or None,
            prefill_buckets=buckets,
            **({"spec_decode": False, "prefix_cache": False}
               if hybrid else {}),
        )
        summary = warm_decode(
            args.model, args.cache_dir, registry_dir=args.registry_dir,
            serve_cfg=serve_cfg, seed=args.seed, param_dtype=param_dtype,
            mesh=mesh, plan=plan,
        )
    else:
        summary = warm(
            _model_factory(args), args.cache_dir, mesh=mesh, plan=plan,
            param_dtype=param_dtype, skip_whole=args.skip_whole,
            skip_groups=args.skip_groups, registry_dir=args.registry_dir,
            hosts=args.hosts, host_id=args.host_id,
            steal_after_s=args.steal_after, poll_s=args.poll,
        )
    for rep in summary.get("program_reports", []):
        line = (f"warm: program={rep['program']} outputs={rep['outputs']} "
                f"outcome={rep['outcome']}")
        if "cache" in rep:
            line += f" cache={rep['cache']}"
        if "owner" in rep and args.hosts > 1:
            line += f" owner={rep['owner']}"
        line += f" {rep['seconds']:.2f}s"
        if "error" in rep:
            line += f" error={rep['error']}"
        print(line, file=sys.stderr)
    print(json.dumps(summary))
    if summary.get("unwarmed"):
        # Partial warms must FAIL the invocation: a deployment script
        # that gates rollout on this tool needs "every program warmed"
        # to be the zero-exit contract, not a line in the JSON.
        raise SystemExit(1)


if __name__ == "__main__":
    main()
