"""AOT TPU cross-lowering guards for the pallas kernels.

The CPU suite runs the kernels in interpret mode, which skips the
Pallas→Mosaic lowering entirely — that is how round 1 shipped an lse
BlockSpec that real TPUs reject (ADVICE r1).  ``jax.export`` with
``platforms=['tpu']`` runs the full Mosaic module generation (BlockSpec
tiling rules, layout checks, kernel jaxpr lowering) on a CPU-only host,
so every kernel flavor gets its TPU lowering exercised in CI even though
no chip is present.  (The final Mosaic→binary compile still only happens
on hardware; ``chip_smoke.py`` covers that.)
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from torchdistx_tpu.models import LLAMA3_8B
from torchdistx_tpu.ops import flash_attention, paged_attention
from torchdistx_tpu.serve import ServeConfig, serve_program_specs

B, S, H, D = 2, 512, 8, 64


def _export(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def _inputs(kv_heads=H):
    q = jnp.zeros((B, S, H, D), jnp.bfloat16)
    k = jnp.zeros((B, S, kv_heads, D), jnp.bfloat16)
    v = jnp.zeros((B, S, kv_heads, D), jnp.bfloat16)
    return q, k, v


@pytest.mark.parametrize("kv_heads", [H, 2])
def test_flash_fwd_bwd_lowers_for_tpu(kv_heads):
    q, k, v = _inputs(kv_heads)

    def fwd_and_grads(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, block_q=256, block_k=256, interpret=False
        )
        grads = jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=256, block_k=256, interpret=False
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        return out, grads

    assert _export(fwd_and_grads, q, k, v).mlir_module()


def test_bench_shape_lowers_for_tpu():
    # The production bench configuration (B=4, H=16, S=2048, D=64,
    # blocks 1024x1024, bf16, causal) — exactly what phase_flash compiles
    # on the chip.
    q = jnp.zeros((4, 2048, 16, 64), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=1024, block_k=1024, interpret=False
        )

    assert _export(fwd, q, q, q).mlir_module()


def test_graft_entry_shape_lowers_for_tpu():
    # The driver's single-chip compile check runs the flagship TINY
    # Llama THROUGH the flash kernel (__graft_entry__.entry): guard its
    # exact shape class — f32, D=16, S=32, GQA 4/2, blocks clamped to
    # 32x32 — so a tiling assumption valid only at D=64 cannot pass CI
    # and then fail the driver's on-hardware Mosaic compile.
    q = jnp.zeros((2, 32, 4, 16), jnp.float32)
    k = jnp.zeros((2, 32, 2, 16), jnp.float32)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    assert _export(fwd, q, k, k).mlir_module()


@pytest.mark.parametrize("bias_heads", [H, 1])
def test_flash_bias_and_segments_lower_for_tpu(bias_heads):
    # The full operand surface in one program: additive bias (incl. the
    # dbias kernel and its head-broadcast grid) + packed segment ids
    # (incl. the _seg_mask transpose) through fwd and every backward
    # kernel.
    q, k, v = _inputs()
    bias = jnp.zeros((bias_heads, S, S), jnp.float32)
    seg = jnp.zeros((B, S), jnp.int32)

    def fwd_and_grads(q, k, v, bias, seg):
        kw = dict(
            causal=True, segment_ids=seg, block_q=256, block_k=256,
            interpret=False,
        )
        out = flash_attention(q, k, v, bias=bias, **kw)
        grads = jax.grad(
            lambda q, k, v, b: flash_attention(q, k, v, bias=b, **kw)
            .astype(jnp.float32)
            .sum(),
            argnums=(0, 1, 2, 3),
        )(q, k, v, bias)
        return out, grads

    assert _export(fwd_and_grads, q, k, v, bias, seg).mlir_module()


# -- serving decode kernel ----------------------------------------------------
#
# The guard PR 7 lacked: the flash kernels above were cross-lowered from
# the start, the paged decode kernel never was, and its [P, page, KV, D]
# pool put a one-kv-head block on the second-minor dim — refused by the
# Mosaic lowering for every KV > 1, unnoticed while the kernel only ever
# ran interpreted.


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("page", [8, 16, 128])
@pytest.mark.parametrize("H,KV,D", [(32, 8, 128),    # chip_smoke / Llama-3-8B
                                    (12, 12, 64)])   # GPT-2
def test_paged_attention_lowers_for_tpu(H, KV, D, page, dtype):
    B, P, maxp = 4, 64, 8
    q = jax.ShapeDtypeStruct((B, H, D), dtype)
    pool = jax.ShapeDtypeStruct((P, KV, page, D), dtype)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32)
    table = jax.ShapeDtypeStruct((B, maxp), jnp.int32)
    fn = functools.partial(paged_attention, interpret=False)
    assert _export(fn, q, pool, pool, lens, table).mlir_module()


@pytest.mark.parametrize("program",
                         ["decode", "verify-2", "prefill-16", "chunk-16"])
def test_serving_program_lowers_for_tpu(program, monkeypatch):
    # The whole program as the replica compiles it (2-layer Llama-width
    # config, bf16 params): scatter into the flat pool at layer*P + page,
    # kernel, head -- every program kind, since each addresses the pool
    # its own way.  The kernel resolves its own interpret mode inside the
    # program, so the backend it consults is pinned to "tpu" for the
    # trace.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LLAMA3_8B.replace(n_layers=2)
    scfg = ServeConfig(max_batch=4, page_size=16, n_pages=16,
                       max_pages_per_seq=4, prefill_buckets=(16,))
    specs = {s.name: s for s in serve_program_specs(
        "llama", cfg, scfg, param_dtype=jnp.bfloat16, include_init=False)}
    spec = specs[program]
    module = _export(spec.fn, *spec.args).mlir_module()
    # decode carries the compiled kernel; the others attend through the
    # gather-based jnp path and must lower without one.
    assert ("tpu_custom_call" in module) == (program == "decode")


# -- the whole compile, for a chip that is described and not attached ---------
#
# ``jax.export`` above stops at the Mosaic module; what Mosaic itself
# refuses (a slice not aligned to the tiling, more VMEM than a kernel
# may have) shows only when the module is compiled.  The TPU compiler is
# installed beside jax and compiles for a described v5e: the decode
# kernel at the widths the benchmark's cells and the kept configuration
# files have.  The topology is described inside a fixture (only one
# process may load the TPU library, and under several workers only the
# one that is given this file may try).


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,H,KV,D,page,maxp,dtype", [
    (32, 32, 8, 128, 16, 48, jnp.bfloat16),    # mistral7b-chat-backlog
    (32, 32, 8, 128, 16, 260, jnp.bfloat16),   # mistral7b-doc-prefill
    (128, 20, 1, 128, 16, 48, jnp.bfloat16),   # jamba2-3b-chat-backlog
    (32, 16, 4, 128, 16, 48, jnp.bfloat16),    # one shard of a tp=2 mesh
    (4, 32, 8, 128, 16, 8, jnp.float32),       # float32 pools
    (4, 32, 8, 128, 8, 8, jnp.bfloat16),       # a page of half a tile
    (8, 25, 25, 64, 16, 48, jnp.bfloat16),     # gpt2-xl: ``_page_kernel``
    (32, 32, 8, 128, 16, 800, jnp.bfloat16),   # Mistral under mixed-queue
    (128, 6, 1, 128, 16, 800, jnp.bfloat16),   # trinity: the full layer
    (128, 30, 30, 128, 16, 48, jnp.bfloat16),  # olmo-hybrid: MHA, group 1
], ids=["mistral-48", "mistral-260", "jamba", "tp2-shard", "float32",
        "page8-bf16", "gpt2-xl", "mistral-800", "trinity-full",
        "olmo-hybrid-full"])
def test_paged_attention_compiles_for_v5e(one_chip, B, H, KV, D, page, maxp,
                                          dtype):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn = jax.jit(functools.partial(paged_attention, interpret=False))
    compiled = fn.lower(
        arg((B, H, D), dtype), arg((512, KV, page, D), dtype),
        arg((512, KV, page, D), dtype), arg((B,), jnp.int32),
        arg((B, maxp), jnp.int32)).compile()
    assert "tdx_paged_attention_decode" in compiled.as_text()


def test_paged_attention_from_a_first_position_compiles_for_v5e(one_chip):
    """A window layer's call at the trinity cell's shape: 128 lanes, one KV
    head under six query heads, a row of 386 live pages, a first position a
    lane as a third scalar operand, and the values 4 x 33,281 pages behind
    the keys in ONE pool that is passed twice."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows = 8 * 33281
    fn = jax.jit(lambda q, pool, lens, table, starts: paged_attention(
        q, pool, pool, lens, table, starts=starts, v_page_offset=4 * 33281,
        interpret=False))
    compiled = fn.lower(
        arg((128, 6, 128), jnp.bfloat16),
        arg((rows, 1, 16, 128), jnp.bfloat16), arg((128,), jnp.int32),
        arg((128, 386), jnp.int32), arg((128,), jnp.int32)).compile()
    assert "tdx_paged_attention_decode" in compiled.as_text()


@pytest.mark.parametrize("kernel, S", [("decode", 1), ("chunk", 128),
                                       ("chunk", 512)])
def test_the_delta_rule_kernels_compile_for_v5e(one_chip, kernel, S):
    """The two Gated DeltaNet kernels at Olmo-Hybrid's widths (30 heads,
    d_k 96, d_v 192): the decode update on the whole state of 6 linear
    layers and 128 lanes, in place; the chunk on one sequence of a
    bucket's positions.  The state stays one buffer (aliased)."""
    from torchdistx_tpu.ops import gdn

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    if kernel == "decode":
        fn = jax.jit(lambda st, lay, q, k, v, b, g, nv: gdn.gdn_decode_update(
            st, lay, q, k, v, b, g, nv, interpret=False), donate_argnums=0)
        compiled = fn.lower(
            arg((6, 128, 96, 5760), f32), arg((), i32),
            arg((128, 30, 96), bf), arg((128, 30, 96), bf),
            arg((128, 30, 192), bf), arg((128, 30), f32),
            arg((128, 30), f32), arg((128,), i32)).compile()
        assert compiled.memory_analysis().alias_size_in_bytes == (
            6 * 128 * 96 * 5760 * 4)
    else:
        fn = jax.jit(lambda q, k, v, b, g, s0, nv: gdn.gdn_chunk(
            q, k, v, b, g, s0, nv, interpret=False))
        compiled = fn.lower(
            arg((S, 30, 96), bf), arg((S, 30, 96), bf),
            arg((S, 30, 192), bf), arg((S, 30), f32), arg((S, 30), f32),
            arg((30, 96, 192), f32), arg((), i32)).compile()
    assert getattr(gdn, kernel.upper() if kernel == "chunk"
                   else "DECODE_UPDATE") in compiled.as_text()


# -- the serving programs, whole, at the benchmark's cells' widths ------------
#
# Every program that takes the pools consumes them and returns them in
# the same buffers (ROADMAP S1, PR 33).  An argument that is not donated
# and that the layer loop writes is copied once, whole, at the program's
# entry: 2.0 GB each for the Mistral cells' pools, 5.6 ms a copy, two a
# call.  What the chip's compiler makes of the donation shows in the
# compiled module: an ``input_output_alias`` entry for every consumed
# argument, and no ``copy`` of a pool's or a state's shape.

_CELLS = {
    "mistral7b-chat-backlog": ("mistral-7b-v0.3-d12", "chat-backlog"),
    "mistral7b-doc-prefill-busy": ("mistral-7b-v0.3-d12", "doc-prefill-busy"),
    "jamba2-3b-chat-backlog": ("jamba2-3b", "chat-backlog-wide"),
    "trinity-large-mixed-queue": ("trinity-large-preview-tp8-d5",
                                  "mixed-queue"),
    "olmo-hybrid-7b-d8-chat-backlog": ("olmo-hybrid-7b-d8",
                                       "chat-backlog-wide"),
    # no cell of the manifest (PERF.md 7: measured and left out in PR 34);
    # the dense stack at the same traffic's widths, 800 pages a sequence
    "mistral-under-mixed-queue": ("mistral-7b-v0.3-d12", "mixed-queue"),
}


def _cell_specs(cell):
    """The cell's serving programs as its replica compiles them
    (``benchmark/kinds/serve*.py``), by name."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import adapters, configs

    config, mix = _CELLS[cell]
    with open(os.path.join(root, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic", mix + ".json")) as f:
        engine = json.load(f)["engine"]
    if cfg["family"] in ("jamba", "afmoe", "olmo_hybrid"):
        from benchmark import harness

        fam = harness.load_module(root, cfg["family_module"])
        tcfg = fam.transformer_config(cfg, fam.dims(cfg))
    else:
        tcfg = adapters.transformer_config(cfg, configs.dims(cfg))
    return {s.name: s for s in serve_program_specs(
        cfg["family"], tcfg, adapters.serve_config(cfg, engine),
        param_dtype=jnp.bfloat16, include_init=False)}


def _hlo_shape(sds):
    dt = {"bfloat16": "bf16", "float32": "f32", "int32": "s32"}[
        str(jnp.dtype(sds.dtype))]
    return f"{dt}[{','.join(map(str, sds.shape))}]"


_ALIAS_CASES = [
    ("mistral7b-chat-backlog", "decode", ()),
    ("mistral7b-chat-backlog", "prefill-128", ()),
    ("mistral7b-chat-backlog", "chunk-512", ()),
    ("mistral7b-chat-backlog", "cow", ()),
    ("mistral7b-chat-backlog", "verify-4", ()),
    ("mistral7b-doc-prefill-busy", "chunk-2048", ()),
    ("jamba2-3b-chat-backlog", "decode", ()),
    # The one-sequence programs of the hybrid stack still re-lay the conv
    # tail (0.1 GB) for their lane slice, once into the loop's layout and
    # once back: there before donation, and no entry copy (PERF.md §7).
    # ``layout_copies``: the positions in ``args`` this is allowed for.
    ("jamba2-3b-chat-backlog", "prefill-128", (4,)),
    ("jamba2-3b-chat-backlog", "chunk-256", (4,)),
    # The afmoe family carries four arrays: both pools of the full group,
    # the window group's one pool and the held experts' pair counts.
    ("trinity-large-mixed-queue", "decode", ()),
    ("trinity-large-mixed-queue", "prefill-256", ()),
    ("trinity-large-mixed-queue", "chunk-2048", ()),
    # The delta-rule stack: the decode kernel works in place on the whole
    # state; the one-sequence programs re-lay the conv tail (0.05 GB) for
    # their lane slice as jamba's do.
    ("olmo-hybrid-7b-d8-chat-backlog", "decode", ()),
    ("olmo-hybrid-7b-d8-chat-backlog", "prefill-128", (4,)),
    ("olmo-hybrid-7b-d8-chat-backlog", "chunk-512", (4,)),
    ("mistral-under-mixed-queue", "decode", ()),
    ("mistral-under-mixed-queue", "chunk-2048", ()),
]


@pytest.mark.parametrize("cell,program,layout_copies", _ALIAS_CASES,
                         ids=[f"{c}-{p}" for c, p, _ in _ALIAS_CASES])
def test_serving_program_aliases_its_pools_on_v5e(one_chip, monkeypatch, cell,
                                                  program, layout_copies):
    import dataclasses
    import re

    from torchdistx_tpu.observe.costmodel import program_costs
    from torchdistx_tpu.serve.programs import compile_serving_program

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = _cell_specs(cell)[program]
    spec = dataclasses.replace(spec, args=jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        spec.args))
    compiled, _ = compile_serving_program(spec)
    text = compiled.as_text()
    if program == "decode":
        assert "tdx_paged_attention_decode" in text
    if cell.startswith("trinity"):
        # the expert products are the program's own kernel, not XLA's
        assert "tdx_moe_experts_gmm" in text and "ragged-dot" not in text
    # Parameters are numbered over the flattened arguments: the consumed
    # ones follow the parameter tree's leaves.
    first = len(jax.tree.leaves(spec.args[:spec.consumes[0]]))
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}", text.split("input_output_alias={", 1)[1]
        .split("entry_computation_layout", 1)[0])}
    assert aliased == set(range(first, first + len(spec.consumes)))
    carried = [spec.args[i] for i in spec.consumes]
    # The afmoe family's pair counts, int32 [4, 32], are laid out in a
    # whole (8, 128) tile on the chip: 2,048 bytes for their 512.
    tile_pad = 1536 if cell.startswith("trinity") else 0
    assert program_costs(compiled)["alias_bytes"] == tile_pad + sum(
        a.size * jnp.dtype(a.dtype).itemsize for a in carried)
    copies = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= \S+ copy\(", line)]
    for i, a in zip(spec.consumes, carried):
        if i in layout_copies:
            continue
        assert not [c for c in copies if f"= {_hlo_shape(a)}" in c], (
            program, _hlo_shape(a))


# -- the expert layers' grouped product: lowered once a shape ---------------
#
# The trinity cell's programs call the grouped-matmul kernel three times
# in each of four expert layers: 12 call sites a program, of one shape.
# Behind its inner ``jax.jit`` each distinct shape lowers to ONE Mosaic
# module, which every site of that shape calls; lowered once a site it
# would cost the cell's ``setup_s`` the lowering twelve times over (four
# shapes a program, one for each of a few tiers of rows, cost it 5.3 s).


@pytest.mark.parametrize("program", ["prefill-256", "prefill-1024",
                                     "prefill-2048", "chunk-256",
                                     "chunk-1024", "chunk-2048", "decode"])
def test_the_afmoe_programs_lower_the_grouped_kernel_once_a_shape(
        one_chip, monkeypatch, program):
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = _cell_specs("trinity-large-mixed-queue")[program]
    text = jax.jit(spec.fn).lower(*jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        spec.args)).as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "tdx_moe_experts_gmm" in line]
    sites = re.findall(r"call @\w*gmm\w*\(.*?\) : (\(.*?\))", text)
    assert len(kernels) == len(set(sites)) == 1
    assert len(sites) == 3 * 4                  # three products, four layers
    assert "ragged_dot" not in text
