"""The plain reference for the ``olmo_hybrid`` family (allenai
Olmo-Hybrid): the published equations in straightforward ``jax.numpy``,
float32 with ``highest`` matmul precision, the delta rule a position at a
time (a plain ``lax.scan`` over time), no kernel, no cache, no batching.
It imports nothing of the program and takes only the benchmark's own
weights (``benchmark/families/olmo_hybrid.py``), in the shapes the
published modelling code keeps them: the conv's weight is ``[channels,
d_conv]`` and a head's state ``[d_k, d_v]`` (the program lays the state
of all heads side by side and the taps the other way round).

With ``x`` of one sequence ``[T, d]`` and eps ``rms_norm_eps``
everywhere, layer ``i``::

    h = x + RMSNorm(Mixer_i(x))          y = h + RMSNorm(MLP(h))
    MLP(v) = (silu(v W_gate) * (v W_up)) W_down
    full attention (layer_types[i] == "full_attention"), MHA:
        q = RMSNorm(x W_q), k = RMSNorm(x W_k)  (over all heads at once)
        v = x W_v ; causal softmax(q k^T / sqrt(hd)) v, W_o ; no position
        term of any kind
    linear attention (Gated DeltaNet, H heads, d_k, d_v):
        [q, k, v] = silu(conv1d_causal([x W_q, x W_k, x W_v]; depthwise,
                         kernel d_conv, no bias))        zeros before 0
        q, k = l2norm(q), l2norm(k) a head ; q = q / sqrt(d_k)
        beta = 2 sigmoid(x W_b) ;  g = -exp(A_log) softplus(x W_a + dt_bias)
        S_t = exp(g_t) S_{t-1} ; u_t = beta_t (v_t - S_t^T k_t)
        S_t = S_t + k_t u_t^T ; o_t = S_t^T q_t ,  S_0 = 0
        out = (RMSNorm_head(o) * silu(x W_g)) W_o
    logits = RMSNorm(y_L) W_head   (untied)

Departures from the published description, all of them: (1) the
modelling code runs the rule through a chunked kernel; this is the rule
itself, a position at a time; (2) weights are the benchmark's (bfloat16
values from the seed, upcast a layer at a time), not a checkpoint's;
(3) the sizes the config lacks are the configuration file's ``assumed``.

``quant`` is the control's hook, as in ``reference/decoder.py``: a
function applied to both operands of every weight matmul.  The conv and
the rule are no matmuls and stay float32 under it.  ``state_round`` is a
planted fault's hook: applied to the state after every position
(``bf16``: a rule carried in bfloat16).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


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
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _l2norm(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _mlp(c, quant, x, lw):
    gate = jax.nn.silu(_mm("td,df->tf", x, lw["w_gate"], quant))
    up = _mm("td,df->tf", x, lw["w_up"], quant)
    return _mm("tf,fd->td", gate * up, lw["w_down"], quant)


def _block(c, quant, x, lw, mixer_out):
    eps = c["norm_eps"]
    h = x + _rms(mixer_out, lw["post_mixer_norm.scale"], eps)
    return h + _rms(_mlp(c, quant, h, lw), lw["post_ffn_norm.scale"], eps)


def full_layer(c, quant, x, lw):
    lw = {k: v.astype(F32) for k, v in lw.items()}
    T, eps = x.shape[0], c["norm_eps"]
    H, KV, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    q = _rms(_mm("td,dhk->thk", x, lw["wq"], quant).reshape(T, H * hd),
             lw["q_norm.scale"], eps).reshape(T, H, hd)
    k = _rms(_mm("td,dhk->thk", x, lw["wk"], quant).reshape(T, KV * hd),
             lw["k_norm.scale"], eps).reshape(T, KV, hd)
    v = _mm("td,dhk->thk", x, lw["wv"], quant)
    qg = q.reshape(T, KV, H // KV, hd)
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST).reshape(T, H, hd)
    return _block(c, quant, x, lw, _mm("thk,hkd->td", o, lw["wo"], quant))


def delta_rule(q, k, v, beta, g, state_round=None):
    """q, k [T, H, d_k], v [T, H, d_v], beta, g [T, H] -> o [T, H, d_v]."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, inp):
        q_t, k_t, v_t, b_t, g_t = inp
        S = jnp.exp(g_t)[:, None, None] * S
        kS = jnp.einsum("hk,hkv->hv", k_t, S, precision=HIGHEST)
        u = b_t[:, None] * (v_t - kS)
        S = S + k_t[:, :, None] * u[:, None, :]
        if state_round is not None:
            S = state_round(S)
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), F32), (q, k, v, beta, g))
    return o


def linear_layer(c, quant, state_round, x, lw):
    lw = {k: v.astype(F32) for k, v in lw.items()}
    T, eps = x.shape[0], c["norm_eps"]
    H, dk, dv, K = c["lin_heads"], c["d_k"], c["d_v"], c["d_conv"]
    proj = jnp.concatenate([_mm("td,de->te", x, lw[n], quant)
                            for n in ("wq", "wk", "wv")], -1)   # [T, C]
    padded = jnp.concatenate([jnp.zeros((K - 1, proj.shape[1]), F32), proj])
    conv = jax.nn.silu(sum(padded[i:i + T] * lw["conv_w"][:, i]
                           for i in range(K)))
    q = _l2norm(conv[:, :H * dk].reshape(T, H, dk), eps) / math.sqrt(dk)
    k = _l2norm(conv[:, H * dk:2 * H * dk].reshape(T, H, dk), eps)
    v = conv[:, 2 * H * dk:].reshape(T, H, dv)
    beta = jax.nn.sigmoid(_mm("td,dh->th", x, lw["wb"], quant))
    if c["allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(lw["A_log"]) * jax.nn.softplus(
        _mm("td,dh->th", x, lw["wa"], quant) + lw["dt_bias"])
    o = delta_rule(q, k, v, beta, g, state_round)
    o = _rms(o, lw["o_norm.scale"], eps).reshape(T, H * dv)
    y = o * jax.nn.silu(_mm("td,de->te", x, lw["wg"], quant))
    return _block(c, quant, x, lw, _mm("te,ed->td", y, lw["wo"], quant))


def head(c, quant, w, x):
    h = _rms(x, w["final_norm.scale"].astype(F32), c["norm_eps"])
    return _mm("td,dv->tv", h, w["lm_head"], quant)


def is_full(c, i: int) -> bool:
    return c["layer_types"][i] == "full"


def layer_weights(c, w, i: int) -> dict:
    """Layer ``i``'s tensors: its row of ``ffn.*`` and its row of
    ``attn.*`` or ``gdn.*`` (the stacks hold one kind each, in layer
    order)."""
    full = is_full(c, i)
    n_full_before = sum(is_full(c, j) for j in range(i))
    grp, row = ("attn.", n_full_before) if full else ("gdn.", i - n_full_before)
    out = {k[len("ffn."):]: v[i] for k, v in w.items() if k.startswith("ffn.")}
    out.update({k[len(grp):]: v[row] for k, v in w.items()
                if k.startswith(grp)})
    return out


class Forward:
    """Logits of one sequence at chosen positions, layer by layer (weights
    upcast a layer at a time).  Sequences are padded to a multiple of
    ``pad``: padding lies after every real position, which neither causal
    attention nor a causal recurrence ever looks at."""

    def __init__(self, c, quant=None, pad=256, state_round=None):
        self.c, self.pad = c, pad
        self._linear = jax.jit(functools.partial(
            linear_layer, c, quant, state_round))
        self._full = jax.jit(functools.partial(full_layer, c, quant))
        self._head = jax.jit(functools.partial(head, c, quant))

    def logits(self, w, tokens, first, n):
        """float32 [n, vocab]: logits at positions first..first+n-1."""
        import numpy as np

        c = self.c
        S = len(tokens)
        P = -(-S // self.pad) * self.pad
        toks = np.zeros((P,), np.int32)
        toks[:S] = tokens
        x = w["embed"][jnp.asarray(toks)].astype(F32)
        for i in range(c["n_layers"]):
            lw = layer_weights(c, w, i)
            x = (self._full if is_full(c, i) else self._linear)(x, lw)
        npos = -(-n // 256) * 256
        idx = np.minimum(first + np.arange(npos), P - 1)
        top = {k: v for k, v in w.items() if "." not in k
               or k.startswith("final_norm")}
        return np.asarray(self._head(top, x[jnp.asarray(idx)])[:n])
