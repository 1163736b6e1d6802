"""The plain reference for the ``pangu_ultra_moe`` family
(openPangu-Ultra-MoE): the published equations in straightforward
``jax.numpy``, float32 with ``highest`` matmul precision, no kernel, no
cache, no batching, no latent absorbed into a query, no sorting of tokens
by expert.  It imports nothing of the program and takes only the
benchmark's own weights (``benchmark/families/pangu_ultra_moe.py``); the
expert layer's functions are ``reference/afmoe.py``'s, which compute the
same routing.

With ``x`` of one sequence ``[T, d]`` and ``RMS_w(v) = v * rsqrt(mean(v^2)
+ eps) * w``::

    h0     = E[token]                                 (no scale)
    h      = h + RMS_post_attn(MLA(RMS_in(h)))        (sandwich norms)
    h      = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))
    logits = W_head RMS_final(h)                      (untied)

    MLA(v): c_q = RMS_qa(v W_qa);  [q_nope ; q_rope]_h = c_q W_qb
        [c_kv' ; k_rope'] = v W_kva;  c_kv = RMS_kva(c_kv')
        q_rope, k_rope <- RoPE (rotate-half over the 64 rope dims, theta,
          no scaling); k_rope is one key shared by all heads
        [k_nope ; v']_h = c_kv W_kvb
        s_h = (q_nope_h k_nope_h + q_rope_h k_rope) / sqrt(nope + rope),
          causal, softmax in float32; out = [o_1 .. o_H] W_o
    FFN, the leading first_k_dense_replace layers: (silu(v W_gate) *
        (v W_up)) W_down
    FFN, the others: s = sigmoid(v W_r); S = top-k of (s + b) (b: a stored
        selection bias, choice only); w_e = routed_scaling_factor * s_e /
        (sum_{e in S} s_e + 1e-20); y = Shared(v) + sum_{e in S} w_e
        Expert_e(v); Shared and each Expert_e the gated MLP at
        moe_intermediate_size

Departures from the published code, all of them: (1) weights are the
benchmark's (bfloat16 values from the seed, upcast where used), not a
checkpoint's; (2) the configuration is ONE CHIP'S SHARE of a 16-chip
expert-parallel group (``c``: the experts from ``first_expert`` on and
the vocabulary rows the chip holds): the router scores all
``router_outputs`` experts and chooses among all of them, and the sum
over ``S`` runs over the chosen experts that are HELD; a chosen expert
held elsewhere adds nothing here, as on the chip of the deployment before
the exchange; attention, ``Shared``, the dense MLP and the norms are
whole; with every expert held the functions below are the uncut layer
(``tests/test_serve_pangu_ultra_moe.py`` adds the sixteen shares up to
it); (3) no multi-token prediction module (it reads the last published
layer's output); (4) attention runs a block of heads and a block of
queries at a time, and the feed-forward a block of positions at a time,
so that a 17 k-token sequence fits the chip beside the weights; the
routed sum is computed densely, every held expert over every token with
the weight 0 where it was not chosen, an expert at a time.

``quant`` is the control's hook, as in ``reference/afmoe.py``: a function
applied to both operands of every weight matmul as the product takes them
(a head block's slice of ``W_qb``, ``W_kvb`` and ``W_o``, a block of
positions), the router's included.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import (  # noqa: F401
    F32, HIGHEST, QUANT, _gated_mlp, _mm, _rms, _rope, route, routed_part,
    shared_part)

H_BLOCK = 16       # heads a step of the attention
Q_BLOCK = 256      # queries a step: scores [H_BLOCK, Q_BLOCK, T]
ROW_BLOCK = 2048   # positions a step of a feed-forward


def _divisor(n: int, most: int) -> int:
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _blocks(f, x, block: int):
    """``f`` over ``x`` [T, ...] a block of rows at a time (T a multiple
    of the block or under it)."""
    T = x.shape[0]
    b = _divisor(T, block)
    out = jax.lax.map(f, x.reshape((T // b, b) + x.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


def attention_part(c, quant, x, lw):
    """MLA's output [T, d] on the block's normed input x [T, d]."""
    eps, theta = c["norm_eps"], c["rope_theta"]
    R, dn = c["kv_rank"], c["nope_dim"]
    T, H = x.shape[0], c["n_heads"]
    scale = 1.0 / math.sqrt(c["nope_dim"] + c["rope_dim"])
    c_q = _rms(_mm("td,dr->tr", x, lw["wq_a"], quant), lw["q_a_norm.scale"],
               eps)
    kv = _mm("td,dr->tr", x, lw["wkv_a"], quant)
    c_kv = _rms(kv[:, :R], lw["kv_a_norm.scale"], eps)
    k_rope = _rope(kv[:, None, R:], theta)[:, 0]
    hb = _divisor(H, H_BLOCK)
    G = H // hb
    n = -(-T // Q_BLOCK)
    j = jnp.arange(T)[None, :]
    heads = lambda w: jnp.moveaxis(w.reshape(
        w.shape[:1] + (G, hb) + w.shape[2:]), 1, 0)
    w_o = lw["wo"].reshape((G, hb) + lw["wo"].shape[1:])

    def group(acc, g):
        wq, wkv, wo = g
        q = _mm("tr,rhk->thk", c_q, wq, quant)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], theta)
        k_nope = _mm("tr,rhk->thk", c_kv, wkv[..., :dn], quant)
        v = _mm("tr,rhk->thk", c_kv, wkv[..., dn:], quant)
        pad = lambda a: jnp.pad(a, ((0, n * Q_BLOCK - T), (0, 0), (0, 0)))

        def block(args):
            qn, qr, b = args
            i = (b * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
            s = (jnp.einsum("qhd,thd->hqt", qn, k_nope, precision=HIGHEST)
                 + jnp.einsum("qhd,td->hqt", qr, k_rope, precision=HIGHEST))
            p = jax.nn.softmax(jnp.where(j <= i, s * scale, -jnp.inf), -1)
            return jnp.einsum("hqt,thd->qhd", p, v, precision=HIGHEST)

        o = jax.lax.map(block, (
            pad(q_nope).reshape(n, Q_BLOCK, hb, dn),
            pad(q_rope).reshape(n, Q_BLOCK, hb, -1), jnp.arange(n)))
        o = o.reshape(n * Q_BLOCK, hb, -1)[:T]
        return acc + _mm("thv,hvd->td", o, wo, quant), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(x), (
        heads(lw["wq_b"]), heads(lw["wkv_b"]), w_o))
    return out


def layer(c, quant, dense, x, lw):
    """One block on one sequence: (h [T, d], its router's near ties [T]).
    Layers of a kind (dense or expert) share one program."""
    eps = c["norm_eps"]
    a = attention_part(c, quant, _rms(x, lw["norm_in.scale"], eps), lw)
    x = x + _rms(a, lw["norm_post_attn.scale"], eps)
    h = _rms(x, lw["norm_pre_mlp.scale"], eps)
    near = jnp.zeros(x.shape[:1], bool)
    if dense:
        y = _blocks(lambda v: _gated_mlp(v, lw["w_gate"], lw["w_up"],
                                         lw["w_down"], quant), h, ROW_BLOCK)
    else:
        weights, near = route(c, quant, h, lw)
        y = shared_part(quant, h, lw) + routed_part(c, quant, weights, h, lw)
    return x + _rms(y, lw["norm_post_mlp.scale"], eps), near


def head(c, quant, w, x):
    h = _rms(x, w["final_norm.scale"], c["norm_eps"])
    return _mm("td,dv->tv", h, w["lm_head"], quant)


class Forward:
    """Logits of one sequence at chosen positions, layer by layer.
    Sequences are padded to a multiple of ``pad``: padding lies after
    every real position, which causal attention never looks at.
    ``near_ties`` / ``choices``: the routing choices made so far within
    ``reference/afmoe.py``'s ``TIE`` of the next score, and all of them;
    ``undecided``: of the last call's positions, those with a near tie in
    any expert layer."""

    def __init__(self, c, quant=None, pad=256):
        self.c, self.pad = c, pad
        self.faults = False
        kinds = [i < c["n_dense_layers"] for i in range(c["n_layers"])]
        by_kind = {k: jax.jit(functools.partial(layer, c, quant, k))
                   for k in set(kinds)}
        self._layer = [by_kind[k] for k in kinds]
        self._head = jax.jit(functools.partial(head, c, quant))
        self.near_ties = 0
        self.choices = 0
        self.undecided = None

    def logits(self, w, tokens, first, n):
        """float32 [n, vocab]: logits at positions first..first+n-1."""
        import numpy as np

        c = self.c
        S = len(tokens)
        P = -(-S // self.pad) * self.pad
        toks = np.zeros((P,), np.int32)
        toks[:S] = tokens
        x = w["embed"][jnp.asarray(toks)].astype(F32)
        undecided = np.zeros((P,), bool)
        for i in range(c["n_layers"]):
            pre = f"layers.{i}."
            lw = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
            x, near = self._layer[i](x, lw)
            undecided |= np.asarray(near)
            self.near_ties += int(near.sum())
        self.choices += P * c["n_expert_layers"]
        npos = -(-n // 256) * 256
        idx = np.minimum(first + np.arange(npos), P - 1)
        self.undecided = undecided[idx][:n]
        top = {k: w[k] for k in ("final_norm.scale", "lm_head")}
        return np.asarray(self._head(top, x[jnp.asarray(idx)])[:n])
