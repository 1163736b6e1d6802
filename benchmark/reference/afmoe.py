"""The plain reference for the ``afmoe`` family (arcee-ai Trinity): the
published equations in straightforward ``jax.numpy``, float32 with
``highest`` matmul precision, no kernel, no cache, no batching, no sorting
of tokens by expert.  It imports nothing of the program and takes only the
benchmark's own weights (``benchmark/families/afmoe.py``).

With ``x`` of one sequence ``[T, d]``, ``d`` the model width, and
``RMS_w(v) = v * rsqrt(mean(v^2) + eps) * w``::

    h0     = E[token] * sqrt(d)                       (mup_enabled)
    h      = h + RMS_post_attn(Attn(RMS_in(h)))       (sandwich norms)
    h      = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))
    logits = W_head RMS_final(h)                      (untied)

    Attn(v): q = v W_q, k = v W_k, v' = v W_v, g = v W_g  (g as wide as q)
        q, k <- RMS over the head width, one gain vector each, all heads
        sliding_attention layers: rotary on q and k (rotate-half over the
          whole head width, theta, no scaling), keys 0 <= i - j < window
        full_attention layers: no positional term, causal
        softmax(q k / sqrt(hd)) in float32; out = (o * sigmoid(g)) W_o
    FFN, the leading num_dense_layers: (silu(v W_gate) * (v W_up)) W_down
    FFN, the others: s = sigmoid(v W_r)              (no router bias)
        S = top-k of (s + b)      (b: a stored selection bias, choice only)
        w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
        y = Shared(v) + sum_{e in S} w_e Expert_e(v)
        Shared and each Expert_e: the gated MLP at moe_intermediate_size

Departures from the published code, all of them: (1) weights are the
benchmark's (bfloat16 values from the seed, upcast where used), not a
checkpoint's; (2) the configuration is ONE CHIP'S SHARE of a layer group
(``c``: the heads, the experts from ``first_expert`` on and the
vocabulary rows the chip holds): the router scores all
``router_outputs`` experts and chooses among all of them, and the sum
over ``S`` runs over the chosen experts that are HELD; a chosen expert
held elsewhere adds nothing here, as on the chip of the deployment before
the exchange; ``Shared``, the dense MLP and the norms are whole; with all
heads and all experts held the functions below are the uncut layer
(``tests/test_serve_afmoe.py`` adds the eight shares up to it); (3)
``n_group`` and ``topk_group`` are 1: no grouped routing; (4) the
routed sum is computed densely, every held expert over every token with
the weight 0 where it was not chosen, an expert at a time.

``quant`` is the control's hook, as in ``reference/decoder.py``: a
function applied to both operands of every weight matmul, the router's
included.  Three faults the reference can plant in itself, for the
builder's readings and the tests (``families/afmoe.py`` ``FAULTS``):
``router_quant`` (the router's product alone in a lower precision),
``window`` (None: a sliding layer attends the whole context),
``drop_expert`` (a held expert's output left out).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 256        # queries a step of the attention: scores [G, 256, T]
TIE = 1e-3           # a choice this close to the next score is a near tie
AS_CONFIGURED = object()


def fp8(x):
    """Fake-quantise to float8 e4m3 with a per-tensor scale."""
    xf = x.astype(F32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30)
    return (xf * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


QUANT = {"fp8": fp8, "bf16": bf16, None: None, "": None}


def _mm(eq, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(F32))


def _rope(x, theta):
    """x [T, H, D], positions 0..T-1, rotate-half."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window):
    """q [T, H, D], k / v [T, KV, D] -> [T, H, D]; causal, and with
    ``window`` only keys ``0 <= i - j < window``; a block of queries a
    step, so that the scores are [KV, G, Q_BLOCK, T]."""
    T, H, D = q.shape
    KV = k.shape[1]
    n = -(-T // Q_BLOCK)
    qg = jnp.pad(q, ((0, n * Q_BLOCK - T), (0, 0), (0, 0))).reshape(
        n, Q_BLOCK, KV, H // KV, D)
    j = jnp.arange(T)[None, :]

    def block(args):
        qb, b = args
        i = (b * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        s = jnp.einsum("tkgd,skd->kgts", qb, k,
                       precision=HIGHEST) / math.sqrt(D)
        mask = j <= i
        if window is not None:
            mask &= i - j < window
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (qg, jnp.arange(n)))
    return o.reshape(n * Q_BLOCK, H, D)[:T]


def attention_part(c, quant, window, sliding, x, lw):
    """The share's attention output ``(o * sigmoid(g)) W_o`` [T, d] on
    the block's normed input: the sum over the shares is the layer's."""
    eps = c["norm_eps"]
    q, k, v, g = (_mm("td,dhk->thk", x, lw[n], quant)
                  for n in ("wq", "wk", "wv", "wg"))
    q, k = _rms(q, lw["q_norm.scale"], eps), _rms(k, lw["k_norm.scale"], eps)
    if sliding:
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    o = _attention(q, k, v, window if sliding else None)
    return _mm("thk,hkd->td", o * jax.nn.sigmoid(g), lw["wo"], quant)


def _gated_mlp(x, w_gate, w_up, w_down, quant):
    gate = jax.nn.silu(_mm("td,df->tf", x, w_gate, quant))
    return _mm("tf,fd->td", gate * _mm("td,df->tf", x, w_up, quant),
               w_down, quant)


def route(c, quant, x, lw):
    """x [T, d] -> (weights [T, router_outputs], 0 where not chosen;
    bool [T]: the position's last choice lies within ``TIE`` of the next
    score, a near tie that rounding upstream can turn)."""
    s = jax.nn.sigmoid(_mm("td,de->te", x, lw["router"], quant))
    k = c["top_k"]
    best, idx = jax.lax.top_k(s + lw["router_bias"].astype(F32), k + 1)
    near = best[:, k - 1] - best[:, k] < TIE
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(1.0)
    picked = s * chosen
    return (c["route_scale"] * picked
            / (picked.sum(-1, keepdims=True) + 1e-20)), near


def routed_part(c, quant, weights, x, lw, drop_expert=None):
    """The held experts' part of ``sum_{e in S} w_e Expert_e(x)``, an
    expert at a time over every token (weight 0 where not chosen)."""
    first, n = c["first_expert"], c["held_experts"]
    cols = weights[:, first:first + n].T                     # [held, T]
    if drop_expert is not None:
        cols = cols.at[drop_expert].set(0.0)

    def one(acc, e):
        wg, wu, wd, col = e
        return acc + col[:, None] * _gated_mlp(x, wg, wu, wd, quant), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lw["experts.w_gate"], lw["experts.w_up"], lw["experts.w_down"], cols))
    return acc


def shared_part(quant, x, lw):
    return _gated_mlp(x, lw["shared.w_gate"], lw["shared.w_up"],
                      lw["shared.w_down"], quant)


def layer(c, quant, faults, sliding, dense, x, lw):
    """One block on one sequence: (h [T, d], its router's near ties [T]).
    A layer is its two kinds (window or full attention, dense or expert
    feed-forward) and its weights: layers of a kind share one program."""
    router_quant, window, drop_expert = faults
    eps = c["norm_eps"]
    a = attention_part(c, quant, window, sliding,
                       _rms(x, lw["norm_in.scale"], eps), lw)
    x = x + _rms(a, lw["norm_post_attn.scale"], eps)
    h = _rms(x, lw["norm_pre_mlp.scale"], eps)
    near = jnp.zeros(x.shape[:1], bool)
    if dense:
        y = _gated_mlp(h, lw["w_gate"], lw["w_up"], lw["w_down"], quant)
    else:
        weights, near = route(c, router_quant or quant, h, lw)
        y = shared_part(quant, h, lw) + routed_part(
            c, quant, weights, h, lw, drop_expert)
    return x + _rms(y, lw["norm_post_mlp.scale"], eps), near


def head(c, quant, w, x):
    h = _rms(x, w["final_norm.scale"], c["norm_eps"])
    return _mm("td,dv->tv", h, w["lm_head"], quant)


class Forward:
    """Logits of one sequence at chosen positions, layer by layer.
    Sequences are padded to a multiple of ``pad``: padding lies after
    every real position, which causal attention never looks at.
    ``near_ties`` / ``choices``: the routing choices made so far that
    lie within ``TIE`` of the next score, and all of them, over the
    padded lengths; ``undecided``: of the last call's positions, those
    with a near tie in any expert layer."""

    def __init__(self, c, quant=None, pad=256, router_quant=None,
                 window=AS_CONFIGURED, drop_expert=None):
        self.c, self.pad = c, pad
        self.faults = (router_quant is not None or drop_expert is not None
                       or window is not AS_CONFIGURED)
        window = c["window"] if window is AS_CONFIGURED else window
        planted = (QUANT.get(router_quant, router_quant), window, drop_expert)
        kinds = [(t == "sliding", i < c["n_dense_layers"])
                 for i, t in enumerate(c["layer_types"])]
        by_kind = {k: jax.jit(functools.partial(layer, c, quant, planted, *k))
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
        x = w["embed"][jnp.asarray(toks)].astype(F32) * math.sqrt(c["d_model"])
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
