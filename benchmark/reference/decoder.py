"""The plain reference: a decoder-only transformer in straightforward
``jax.numpy``, float32 with ``highest`` matmul precision, no kernels, no
cache, no batching.  It imports nothing of the program and takes only the
benchmark's own weights (``benchmark/weights.py``).

Two families, written from their published descriptions:

* ``llama`` (Mistral-7B-v0.3's equations): RMSNorm, rotary positions in
  the rotate-half convention, grouped-query causal attention, SwiGLU,
  untied head, no biases, no sliding window (``sliding_window: null``).
* ``gpt2``: learned positions, LayerNorm with bias, multi-head causal
  attention, GELU (tanh form, ``gelu_new``), biases everywhere, tied head.

``quant`` is the control's hook: a function applied to both operands of
every weight matmul (see ``fp8``); ``None`` is the reference itself.

Serving: ``Forward`` runs one sequence layer by layer (weights upcast a
layer at a time) and returns logits at the requested positions.
Training: ``loss_and_grad`` runs row by row with every layer
rematerialised, so that it fits beside nothing else on one chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def fp8(x):
    """Fake-quantise to float8 e4m3 with a per-tensor scale (what an fp8
    path would feed the MXU), straight-through for gradients."""
    xf = x.astype(F32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30)
    q = (xf * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return xf + jax.lax.stop_gradient(q - xf)


def bf16(x):
    xf = x.astype(F32)
    return xf + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(F32) - xf)


QUANT = {"fp8": fp8, "bf16": bf16, None: None, "": None}


def _mm(eq, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, theta):
    """x [S, H, D], positions 0..S-1, rotate-half."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """q [S, H, D], k/v [S, KV, D] -> [S, H, D]; causal; one kv group at
    a time so that the score matrix is [G, S, S]."""
    S, H, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(S, KV, H // KV, D).transpose(1, 2, 0, 3)  # [KV,G,S,D]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [KV,S,D]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def group(args):
        qq, kk, vv = args
        s = jnp.einsum("gsd,td->gst", qq, kk, precision=HIGHEST)
        s = jnp.where(mask, s / math.sqrt(D), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gst,td->gsd", p, vv, precision=HIGHEST)

    o = jax.lax.map(group, (qg, kg, vg))                     # [KV,G,S,D]
    return o.transpose(2, 0, 1, 3).reshape(S, H, D)


def layer(family, c, quant, x, lw):
    """One block on one sequence: x [S, d] float32."""
    lw = {k: v.astype(F32) for k, v in lw.items()}
    eps = c["norm_eps"]
    g = lambda n: lw.get(n, 0.0)
    if family == "llama":
        h = _rms(x, lw["norm0.scale"], eps)
    else:
        h = _ln(x, lw["norm0.scale"], lw["norm0.bias"], eps)
    q = _mm("sd,dhk->shk", h, lw["wq"], quant) + g("wq.bias")
    k = _mm("sd,dhk->shk", h, lw["wk"], quant) + g("wk.bias")
    v = _mm("sd,dhk->shk", h, lw["wv"], quant) + g("wv.bias")
    if family == "llama":
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    o = _attention(q, k, v)
    x = x + _mm("shk,hkd->sd", o, lw["wo"], quant) + g("wo.bias")
    if family == "llama":
        h = _rms(x, lw["norm1.scale"], eps)
        gate = jax.nn.silu(_mm("sd,df->sf", h, lw["w_gate"], quant))
        up = _mm("sd,df->sf", h, lw["w_up"], quant)
        return x + _mm("sf,fd->sd", gate * up, lw["w_down"], quant)
    h = _ln(x, lw["norm1.scale"], lw["norm1.bias"], eps)
    up = _mm("sd,df->sf", h, lw["w_up"], quant) + lw["w_up.bias"]
    up = jax.nn.gelu(up, approximate=True)
    return x + _mm("sf,fd->sd", up, lw["w_down"], quant) + lw["w_down.bias"]


def embed(family, w, tokens):
    if family == "llama":
        return w["embed"].astype(F32)[tokens]
    pos = jnp.arange(tokens.shape[0])  # padding past the table is clipped
    return (w["wte"].astype(F32)[tokens]
            + jnp.take(w["wpe"].astype(F32), pos, axis=0, mode="clip"))


def head(family, c, quant, w, x):
    eps = c["norm_eps"]
    if family == "llama":
        h = _rms(x, w["final_norm.scale"].astype(F32), eps)
        return _mm("sd,dv->sv", h, w["lm_head"], quant)
    h = _ln(x, w["final_norm.scale"].astype(F32),
            w["final_norm.bias"].astype(F32), eps)
    return _mm("sd,vd->sv", h, w["wte"], quant)


def layer_weights(w, i=None):
    """The ``layers.*`` tensors, layer ``i`` of them (or all, stacked)."""
    out = {k[len("layers."):]: v for k, v in w.items()
           if k.startswith("layers.")}
    return out if i is None else {k: v[i] for k, v in out.items()}


class Forward:
    """Serving reference for one configuration: logits of one sequence at
    chosen positions.  Sequences are padded to a multiple of ``pad`` so
    that a handful of programs serve every length (padding lies after
    every real position, which causal attention never looks at)."""

    def __init__(self, family, c, quant=None, pad=256):
        self.family, self.c, self.pad = family, c, pad
        self._layer = jax.jit(functools.partial(layer, family, c, quant))
        self._embed = jax.jit(functools.partial(embed, family))
        self._head = jax.jit(functools.partial(head, family, c, quant))

    def logits(self, w, tokens, first, n):
        """float32 [n, vocab]: the logits at positions first..first+n-1
        of ``tokens`` (a list of ints)."""
        import numpy as np

        S = len(tokens)
        P = -(-S // self.pad) * self.pad  # pad is as a rule >= S: one size
        toks = np.zeros((P,), np.int32)
        toks[:S] = tokens
        x = self._embed({k: v for k, v in w.items()
                         if not k.startswith("layers.")}, jnp.asarray(toks))
        for i in range(self.c["n_layers"]):
            x = self._layer(x, layer_weights(w, i))
        npos = -(-n // 256) * 256
        idx = np.minimum(first + np.arange(npos), P - 1)
        out = self._head({k: v for k, v in w.items()
                          if not k.startswith("layers.")}, x[jnp.asarray(idx)])
        return np.asarray(out[:n])


# -- training ---------------------------------------------------------------


def row_loss(family, c, quant, w, row):
    """Mean next-token cross-entropy of one row [S]."""
    x = embed(family, w, row)
    block = jax.checkpoint(functools.partial(layer, family, c, quant))

    def body(x, lw):
        return block(x, lw), None

    x, _ = jax.lax.scan(body, x, layer_weights(w))
    logits = head(family, c, quant, w, x)
    logp = jax.nn.log_softmax(logits[:-1])
    return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], -1))


def make_loss_and_grad(family, c, quant=None):
    """(w, tokens [B, S]) -> (mean loss, grads): row by row, the mean
    over rows (every row has the same number of targets)."""
    one = jax.jit(jax.value_and_grad(
        functools.partial(row_loss, family, c, quant)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a))

    def loss_and_grad(w, tokens):
        total, grads = 0.0, None
        for r in range(tokens.shape[0]):
            l, g = one(w, tokens[r])
            total = total + l
            grads = g if grads is None else add(grads, g)
        n = tokens.shape[0]
        return total / n, scale(grads, 1.0 / n)

    return loss_and_grad


def adamw_init(w):
    z = jax.tree.map(jnp.zeros_like, w)
    return {"m": z, "v": jax.tree.map(jnp.zeros_like, w), "t": 0}


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"))
def _adamw(w, g, m, v, t, *, lr, b1, b2, eps, wd):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), w, m, v)
    return w, m, v


def adamw_step(w, g, st, o):
    """Decoupled weight decay on every tensor, bias correction, as the
    training settings in the configuration's file state them."""
    t = st["t"] + 1
    w, m, v = _adamw(w, g, st["m"], st["v"], jnp.float32(t), lr=o["lr"],
                     b1=o["b1"], b2=o["b2"], eps=o["eps"],
                     wd=o["weight_decay"])
    return w, {"m": m, "v": v, "t": t}


def leaf_norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))))
            for k, v in tree.items()}
