"""Latent attention, MLA (DeepSeek-V2's; openPangu-Ultra-MoE's): the
attention block of a layer, as pure functions of its parameters, in the
two forms that compute the same attention.

With ``x`` the block's normed input, ``H`` heads, ``RMS`` an RMSNorm
with its own gain, and widths ``q_rank``, ``kv_rank``, ``nope_dim``,
``rope_dim`` and ``v_dim`` (:class:`..configs.MLAConfig`)::

    c_q            = RMS_q(x W_qa)                        [q_rank]
    [q_nope; q_rope]_h = c_q W_qb                         [nope + rope] a head
    [c_kv'; k_rope']   = x W_kva                          [kv_rank + rope]
    c_kv   = RMS_kv(c_kv'),  k_rope = RoPE(k_rope')       one key, all heads
    q_rope = RoPE(q_rope)     (rotate-half over rope_dim, cfg.rope_theta)
    [k_nope; v]_h  = c_kv W_kvb                           [nope + v] a head
    score_h(t, s)  = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s))
                     / sqrt(nope + rope),  causal
    out    = [o_1 .. o_H] W_o,  o_h = sum_s softmax_s(score_h) v_h(s)

A token's cache is ``[c_kv ; k_rope]`` (:func:`cache_rows`), written after
``RMS_kv`` and after RoPE, in the activation dtype, padded with zeros to a
multiple of the 128 lanes (``ops/mla.py`` says why).

* **Expanded** (prefill and chunk; :func:`expand`): ``k_nope`` and ``v``
  rebuilt from the latents, attention at a query-key width of ``nope +
  rope`` and a value width of ``v_dim``.
* **Absorbed** (decode; :func:`decode_query`, :func:`unabsorb`): with
  ``W_uk_h`` / ``W_uv_h`` head ``h``'s k_nope / v columns of ``W_kvb``,
  ``q~_h = q_nope_h W_uk_h^T`` is ``kv_rank`` wide and scores the cached
  latent directly, ``o~_h = sum_s p_s c_kv(s)``, and ``o_h = o~_h W_uv_h``:
  the same attention, reading one row a token instead of ``H`` keys and
  values.

The parameters of a layer (:func:`layer_shapes`) are ``wq_a``,
``q_a_norm``, ``wq_b``, ``wkv_a``, ``kv_a_norm``, ``wkv_b`` and ``wo``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .afmoe import rms_norm
from .layers import apply_rope

F32 = jnp.float32
_NEG = -1e30
_LANES = 128


def cache_width(cfg) -> int:
    """One cached row: ``kv_rank + rope_dim`` padded to the lanes."""
    m = cfg.mla
    return -(-m.cache_dim // _LANES) * _LANES


def scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.mla.qk_dim)


def layer_shapes(cfg) -> dict:
    """name -> (shape, kind) of the attention block's parameters, as
    ``models/afmoe.py`` ``layer_shapes`` names kinds."""
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    return {
        "wq_a": ((d, m.q_rank), "w"), "q_a_norm": ((m.q_rank,), "scale"),
        "wq_b": ((m.q_rank, H, m.qk_dim), "w"),
        "wkv_a": ((d, m.kv_rank + m.rope_dim), "w"),
        "kv_a_norm": ((m.kv_rank,), "scale"),
        "wkv_b": ((m.kv_rank, H, m.nope_dim + m.v_dim), "w"),
        "wo": ((H, m.v_dim, d), "w"),
    }


def rope_angles(cfg, positions):
    """positions [B, S] -> [B, S, rope_dim / 2]: rotate-half, no scaling."""
    dr = cfg.mla.rope_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr))
    return positions.astype(F32)[..., None] * inv


def project(cfg, lp, h, angles):
    """The block's normed input h [B, S, d] -> (q_nope [B, S, H, nope],
    q_rope [B, S, H, rope] rotated, c_kv [B, S, kv_rank] normed, k_rope
    [B, S, rope] rotated), all in the activation dtype."""
    m, dt = cfg.mla, cfg.dtype
    mm = lambda eq, a, w: jnp.einsum(eq, a.astype(dt), w.astype(dt))
    c_q = rms_norm(mm("bsd,dr->bsr", h, lp["wq_a"]), lp["q_a_norm"],
                   cfg.norm_eps, dt)
    q = mm("bsr,rhk->bshk", c_q, lp["wq_b"])
    kv = mm("bsd,dr->bsr", h, lp["wkv_a"])
    c_kv = rms_norm(kv[..., :m.kv_rank], lp["kv_a_norm"], cfg.norm_eps, dt)
    k_rope = apply_rope(kv[..., None, m.kv_rank:], angles)[:, :, 0]
    return (q[..., :m.nope_dim], apply_rope(q[..., m.nope_dim:], angles),
            c_kv, k_rope)


def cache_rows(cfg, c_kv, k_rope):
    """[B, S, 1, cache_width]: what a token leaves in the cache."""
    pad = cache_width(cfg) - cfg.mla.cache_dim
    row = jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype)], -1)
    return jnp.pad(row, ((0, 0), (0, 0), (0, pad)))[:, :, None]


def split_rows(cfg, rows):
    """Cached rows [..., cache_width] -> (c_kv, k_rope)."""
    m = cfg.mla
    return rows[..., :m.kv_rank], rows[..., m.kv_rank:m.cache_dim]


def expand(cfg, lp, c_kv, heads_major: bool = False):
    """Latents [..., T, kv_rank] -> (k_nope, v) [..., T, H, width], or
    [H, T, width] from [T, kv_rank] with ``heads_major``."""
    m, dt = cfg.mla, cfg.dtype
    w = lp["wkv_b"].astype(dt)
    eq = "tr,rhk->htk" if heads_major else "...tr,rhk->...thk"
    return (jnp.einsum(eq, c_kv.astype(dt), w[..., :m.nope_dim]),
            jnp.einsum(eq, c_kv.astype(dt), w[..., m.nope_dim:]))


def decode_query(cfg, lp, q_nope, q_rope):
    """The absorbed query of one token a lane: q_nope [B, H, nope], q_rope
    [B, H, rope] -> [B, H, cache_width], ``[q_nope W_uk^T ; q_rope ; 0]``:
    it scores a cached row as the expanded form scores that token."""
    m, dt = cfg.mla, cfg.dtype
    w_uk = lp["wkv_b"][..., :m.nope_dim].astype(dt)
    q = jnp.einsum("bhn,rhn->bhr", q_nope.astype(dt), w_uk)
    pad = cache_width(cfg) - m.cache_dim
    return jnp.pad(jnp.concatenate([q, q_rope.astype(dt)], -1),
                   ((0, 0), (0, 0), (0, pad)))


def unabsorb(cfg, lp, o_lat):
    """[B, H, kv_rank] latent-weighted sums -> [B, H, v_dim]: o~ W_uv."""
    m, dt = cfg.mla, cfg.dtype
    return jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt),
                      lp["wkv_b"][..., m.nope_dim:].astype(dt))


def out(cfg, lp, o):
    """[B, S, H, v_dim] -> [B, S, d]."""
    return jnp.einsum("bshv,hvd->bsd", o.astype(cfg.dtype),
                      lp["wo"].astype(cfg.dtype))


def dense_attention(cfg, q_nope, q_rope, k_nope, k_rope, v, positions,
                    length):
    """The expanded form over a sequence's own keys, in jnp: q_* [B, S,
    H, *], k_nope / v [B, S, H, *], k_rope [B, S, rope]; causal and below
    ``length`` [B].  Float32 softmax; [B, S, H, v_dim] in q's dtype."""
    f = lambda x: x.astype(F32)
    s = (jnp.einsum("bshd,bthd->bhst", f(q_nope), f(k_nope))
         + jnp.einsum("bshd,btd->bhst", f(q_rope), f(k_rope))) * scale(cfg)
    i, j = positions[:, :, None], positions[:, None, :]
    mask = (j <= i) & (j < length[:, None, None])
    p = jax.nn.softmax(jnp.where(mask[:, None], s, _NEG), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, f(v)).astype(q_nope.dtype)
