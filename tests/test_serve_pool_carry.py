"""The serving programs carry the KV pools through the layer scan
(ISSUE 27): the structure that keeps a layer from copying a pool slice,
and parity with the formulation it replaced.

* **Structure** — read off the jaxpr, so it holds on every backend: the
  layer scan has both pools in its carry, viewed flat as
  ``[L*P, KV, page, D]``; nothing pool-shaped is a constant, a mapped
  input or a mapped output of it; and no equation of its body produces a
  ``[P, KV, page, D]`` (or ``[1, P, KV, page, D]``) array.
* **Parity** — the parent's formulation (pools as mapped inputs and
  outputs, each layer on its own ``[P, KV, page, D]`` slice) is kept
  HERE as the reference, block bodies included, and every program kind
  of both families must give it back bitwise in float32: logits and
  every page of both pools that a sequence can own, through an idle
  lane, a prompt that ends mid-page, a chunk that starts mid-page and
  ``KV > 1``.  The null page (page 0 of each layer) is held to its
  contract instead — written by padding and idle lanes, never read: the
  parent scattered padded positions' rows into its slot 0, the carried
  formulation writes whole pages and hands the null page back what it
  held, so the two differ there, where nothing may look.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu.models import TransformerConfig
from torchdistx_tpu.models.layers import (apply_rope, default_attention,
                                          make_norm)
from torchdistx_tpu.ops import paged_attention, paged_prefill_attention
from torchdistx_tpu.serve import ServeConfig, programs, serve_program_specs

CFGS = {
    "llama": TransformerConfig(
        vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32,
    ),
    "gpt2": TransformerConfig(
        vocab_size=128, d_model=32, n_layers=3, n_heads=4, d_ff=64,
        max_seq_len=64, use_bias=True, activation="gelu", norm="layernorm",
        positions="learned", tie_embeddings=True, dtype=jnp.float32,
    ),
}
# Sizes chosen so that no shape is another's by accident: L=3, P=11,
# maxp=4, page=8, B=2.
SCFG = ServeConfig(max_batch=2, page_size=8, n_pages=11,
                   max_pages_per_seq=4, prefill_buckets=(16,),
                   spec_buckets=(2,))
PROGRAMS = ["decode", "prefill-16", "chunk-16", "verify-2"]


def _specs(family, mesh=None, plan=None):
    return {s.name: s for s in serve_program_specs(
        family, CFGS[family], SCFG, include_init=False, mesh=mesh, plan=plan)}


# -- structure ----------------------------------------------------------------


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_layer_scan_carries_the_pools(family, program):
    cfg = CFGS[family]
    spec = _specs(family)[program]
    L, P, KV, page, D = spec.args[1].shape
    flat, tail = (L * P, KV, page, D), (P, KV, page, D)
    jaxpr = jax.make_jaxpr(spec.fn)(*spec.args).jaxpr
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == cfg.n_layers]
    assert len(scans) == 1, [e.params["length"] for e in scans]
    scan = scans[0]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = [v.aval.shape for v in scan.invars]
    consts, carry, xs = (shapes[:n_consts],
                         shapes[n_consts:n_consts + n_carry],
                         shapes[n_consts + n_carry:])
    ys = [v.aval.shape for v in scan.outvars[n_carry:]]
    assert carry.count(flat) == 2, carry
    for where, group in (("const", consts), ("xs", xs), ("ys", ys)):
        pool_like = [s for s in group if s[-3:] == (KV, page, D)
                     and int(np.prod(s)) >= int(np.prod(tail))]
        assert not pool_like, (where, pool_like)
    body = scan.params["jaxpr"].jaxpr
    sliced = [(e.primitive.name, v.aval.shape) for e in _eqns(body)
              for v in e.outvars
              if getattr(v.aval, "shape", None) in (tail, (1,) + tail)]
    assert not sliced, sliced


# -- parity with the parent's formulation -------------------------------------
#
# What programs.py held at PR 26, kept as the reference: the pools ride
# the scan as xs -> ys, and each block works on its own layer's slice.


def _ref_scan_blocks(decomp, p, x, k_pages, v_pages, block_step):
    blocks = decomp.block_params(p)

    def body(carry, inp):
        blk, kp, vp = inp
        y, kp, vp = block_step(blk, carry, kp, vp, None)
        return y, (kp, vp)

    x, (k_pages, v_pages) = jax.lax.scan(body, x, (blocks, k_pages, v_pages))
    return x, k_pages, v_pages


def _ref_decode_block(cfg, blk, x, kp, vp, base, *, angles, positions,
                      lengths, page_table, attend):
    n0, n1 = programs._norm_keys(cfg)
    page_size = kp.shape[2]
    B = x.shape[0]
    h = make_norm(cfg).apply({"params": blk[n0]}, x)
    q, k, v = programs._qkv(cfg, blk["attn"], h)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    page = page_table[jnp.arange(B), positions // page_size]
    slot = positions % page_size
    kp = kp.at[page, :, slot].set(k[:, 0])
    vp = vp.at[page, :, slot].set(v[:, 0])
    attn = paged_attention(q[:, 0], kp, vp, lengths, page_table)
    x = x + programs._attn_out(cfg, blk["attn"], attn[:, None])
    h2 = make_norm(cfg).apply({"params": blk[n1]}, x)
    x = x + programs._mlp(cfg, blk, h2)
    return x, kp, vp


def _ref_scatter(cfg, blk, x, kp, vp, angles, positions, end, page_table):
    n0, _ = programs._norm_keys(cfg)
    page_size = kp.shape[2]
    maxp = page_table.shape[1]
    h = make_norm(cfg).apply({"params": blk[n0]}, x)
    q, k, v = programs._qkv(cfg, blk["attn"], h)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    valid = positions < end[:, None]
    pidx = jnp.minimum(positions // page_size, maxp - 1)
    page = jnp.where(valid, jnp.take_along_axis(page_table, pidx, axis=1), 0)
    slot = jnp.where(valid, positions % page_size, 0)
    kp = kp.at[page, :, slot].set(k)
    vp = vp.at[page, :, slot].set(v)
    return q, k, v, valid, kp, vp


def _ref_finish(cfg, blk, x, attn):
    _, n1 = programs._norm_keys(cfg)
    x = x + programs._attn_out(cfg, blk["attn"], attn)
    h2 = make_norm(cfg).apply({"params": blk[n1]}, x)
    return x + programs._mlp(cfg, blk, h2)


def _ref_prefill_block(cfg, blk, x, kp, vp, base, *, angles, positions,
                       length, page_table):
    q, k, v, valid, kp, vp = _ref_scatter(
        cfg, blk, x, kp, vp, angles, positions, length, page_table)
    attn = default_attention(q, k, v, causal=True,
                             segment_ids=valid.astype(jnp.int32))
    return _ref_finish(cfg, blk, x, attn), kp, vp


def _ref_chunk_block(cfg, blk, x, kp, vp, base, *, angles, positions, end,
                     page_table):
    q, _, _, _, kp, vp = _ref_scatter(
        cfg, blk, x, kp, vp, angles, positions, end, page_table)
    attn = paged_prefill_attention(q, kp, vp, positions, end, page_table)
    return _ref_finish(cfg, blk, x, attn), kp, vp


def _use_parent_formulation(monkeypatch):
    monkeypatch.setattr(programs, "_scan_blocks", _ref_scan_blocks)
    monkeypatch.setattr(programs, "_decode_block", _ref_decode_block)
    monkeypatch.setattr(programs, "_prefill_block", _ref_prefill_block)
    monkeypatch.setattr(programs, "_chunk_block", _ref_chunk_block)


def _random_like(tree, seed):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        0.3 * jax.random.normal(k, a.shape, a.dtype)
        for k, a in zip(keys, leaves)])


def _inputs(program):
    """Arguments after (params, k_pages, v_pages): two sequences' worth
    of pages from a pool whose every row holds something, lane 1 idle
    where the program has lanes."""
    i32 = jnp.int32
    table = jnp.asarray([[7, 2, 9, 0], [0, 0, 0, 0]], i32)
    if program == "decode":
        # lane 0 writes slot 3 of its second page; lane 1 is idle
        # (position 0, null table: its row lands in the null page).
        return (jnp.asarray([5, 0], i32), jnp.asarray([11, 0], i32), table)
    if program == "prefill-16":
        # 11 valid tokens: the prompt ends mid-page, five padded
        # positions write the null page.
        toks = jnp.arange(16, dtype=i32)[None] % 128
        return (toks, jnp.asarray([11], i32), table[:1])
    if program == "chunk-16":
        # positions [5, 18): starts mid-page behind a written prefix,
        # ends mid-page two pages on.
        toks = (3 * jnp.arange(16, dtype=i32)[None] + 1) % 128
        return (toks, jnp.asarray([5], i32), jnp.asarray([18], i32),
                table[:1])
    assert program == "verify-2"
    # lane 0 scores positions [14, 17) across a page boundary; lane 1
    # idle (start == end == 0).
    toks = jnp.asarray([[4, 8, 15], [0, 0, 0]], i32)
    return (toks, jnp.asarray([14, 0], i32), jnp.asarray([17, 0], i32),
            table)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_equals_parent_formulation(family, program, monkeypatch):
    spec = _specs(family)[program]
    params = _random_like(spec.args[0], 1)
    k_pages, v_pages = _random_like((spec.args[1], spec.args[2]), 2)
    rest = _inputs(program)
    got = jax.jit(spec.fn)(params, k_pages, v_pages, *rest)

    _use_parent_formulation(monkeypatch)
    ref_spec = _specs(family)[program]
    want = jax.jit(ref_spec.fn)(params, k_pages, v_pages, *rest)

    assert got[1].shape == k_pages.shape and got[2].shape == v_pages.shape
    if program in ("decode", "verify-2"):
        # idle lanes' logits are ignored by contract (the kernel writes
        # zeros where the gather-based reference softmaxes masked rows)
        got, want = (got[0][:1],) + got[1:], (want[0][:1],) + want[1:]
    got, want = [(g[0], g[1][:, 1:], g[2][:, 1:]) for g in (got, want)]
    for name, a, b in zip(("logits", "k_pages", "v_pages"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{family} {program} {name}")
    # ... and the program did write: the pools are not the inputs.
    assert not np.array_equal(np.asarray(got[1]),
                              np.asarray(k_pages[:, 1:]))


# -- the mesh path ------------------------------------------------------------


def test_decode_on_tp2_mesh_equals_one_device():
    """``pool_sharding`` splits the kv heads over ``tp`` and
    ``_decode_attention`` runs the kernel under ``shard_map``; the flat
    view merges two unsharded dims, so the specs are the layer slice's.
    The interpreted kernel runs under ``shard_map`` on the virtual CPU
    devices, which is what lets this be a tier-1 case."""
    from torchdistx_tpu.models import decoder_lm_plan
    from torchdistx_tpu.parallel import make_mesh

    family = "llama"
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    one = _specs(family)["decode"]
    spec = _specs(family, mesh=mesh,
                  plan=decoder_lm_plan(fsdp=None, ep=None))["decode"]
    pool_sh = spec.args[1].sharding
    assert pool_sh.spec[2] == "tp", pool_sh

    params = _random_like(one.args[0], 1)
    k_pages, v_pages = _random_like((one.args[1], one.args[2]), 2)
    rest = _inputs("decode")
    want = jax.jit(one.fn)(params, k_pages, v_pages, *rest)

    placed = jax.tree.map(lambda a, s: jax.device_put(a, s.sharding),
                          (params, k_pages, v_pages), tuple(spec.args[:3]))
    got = jax.jit(spec.fn, out_shardings=spec.out_shardings)(*placed, *rest)
    assert got[1].sharding.is_equivalent_to(pool_sh, got[1].ndim)
    np.testing.assert_allclose(np.asarray(got[0][:1]),
                               np.asarray(want[0][:1]), atol=1e-5)
    # Not bitwise: the row-sharded output projection sums over tp in
    # another order, so later layers' K/V differ in the last place.
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# -- the walking kernel under the programs (PR 29) ----------------------------
#
# The configurations above have a head dim of 8, which the decode kernel
# walks a page a block.  Here the head dim is 128, as every configuration
# the benchmark serves has it, so a block is 64 pages of 8 tokens, and
# lane 0 attends 530 tokens: two blocks, the second one three pages long,
# at each layer's own base of the flat pool.

WIDE = TransformerConfig(
    vocab_size=128, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
    d_ff=64, max_seq_len=640, dtype=jnp.float32,
)
WIDE_SCFG = ServeConfig(max_batch=3, page_size=8, n_pages=150,
                        max_pages_per_seq=70, prefill_buckets=(16,),
                        spec_decode=False)


def _wide_decode_spec(mesh=None, plan=None):
    return {s.name: s for s in serve_program_specs(
        "llama", WIDE, WIDE_SCFG, include_init=False, mesh=mesh,
        plan=plan)}["decode"]


@pytest.mark.parametrize("tp", [1, 2])
def test_decode_walks_blocks_of_pages_at_each_layers_base(tp, monkeypatch):
    from torchdistx_tpu.models import decoder_lm_plan
    from torchdistx_tpu.ops import (kv_blocks_walked, pages_per_block,
                                    paged_attention_reference)
    from torchdistx_tpu.parallel import make_mesh

    one = _wide_decode_spec()
    params = _random_like(one.args[0], 3)
    k_pages, v_pages = _random_like((one.args[1], one.args[2]), 4)
    assert pages_per_block(2 // tp, 8, 128, jnp.float32) == 64
    positions = [529, 0, 7]  # lane 1 idle, lane 2 inside its first page
    assert kv_blocks_walked([530, 0, 8], 8, 2 // tp, 128, jnp.float32) == 3
    table = np.zeros((3, 70), np.int32)
    table[0, :67] = 1 + np.random.RandomState(0).permutation(140)[:67]
    table[2, 0] = 149
    rest = (jnp.asarray([5, 0, 9], jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(table))

    if tp == 1:
        got = jax.jit(one.fn)(params, k_pages, v_pages, *rest)
    else:
        mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
        spec = _wide_decode_spec(mesh, decoder_lm_plan(fsdp=None, ep=None))
        placed = jax.tree.map(lambda a, s: jax.device_put(a, s.sharding),
                              (params, k_pages, v_pages),
                              tuple(spec.args[:3]))
        got = jax.jit(spec.fn, out_shardings=spec.out_shardings)(
            *placed, *rest)

    monkeypatch.setattr(programs, "paged_attention",
                        paged_attention_reference)
    want = jax.jit(_wide_decode_spec().fn)(params, k_pages, v_pages, *rest)
    live = np.asarray([0, 2])
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(want[0])[live], atol=2e-5)
    # Pages a sequence can own; the null page takes the idle lane's rows,
    # which follow its attention output (zeros from the kernel, a uniform
    # softmax from the reference) from the second layer on.
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a)[:, 1:],
                                   np.asarray(b)[:, 1:], atol=2e-5)
