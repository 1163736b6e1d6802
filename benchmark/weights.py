"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark, not the program, makes the weights that are served and
trained: the plain reference then needs nothing that the program made.
Names and shapes are the benchmark's own (one flat dict; per-layer
tensors stacked on a leading layer axis); ``benchmark/adapters.py`` maps
them into the program's parameter tree.

Matrices are N(0, 0.02), norm scales 1 + N(0, 0.02) and biases
N(0, 0.02), so that every tensor moves the output (a scale of exactly 1
or a bias of exactly 0 would let a wrong wiring go unseen).  Layers are
drawn inside ``lax.map`` so that the float32 temporaries are one layer
wide.
"""

from __future__ import annotations

STD = 0.02


def shapes(family: str, c: dict) -> dict:
    """name -> (shape, kind); per-layer tensors WITHOUT the layer axis
    come under ``layers.<name>``.  ``c`` is the configuration's ``model``
    group (the benchmark's own key names)."""
    d, h, kv, hd, ff, v = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                           c["head_dim"], c["d_ff"], c["vocab_size"])
    if family == "llama":
        return {
            "embed": ((v, d), "w"),
            "lm_head": ((d, v), "w"),
            "final_norm.scale": ((d,), "scale"),
            "layers.norm0.scale": ((d,), "scale"),
            "layers.norm1.scale": ((d,), "scale"),
            "layers.wq": ((d, h, hd), "w"),
            "layers.wk": ((d, kv, hd), "w"),
            "layers.wv": ((d, kv, hd), "w"),
            "layers.wo": ((h, hd, d), "w"),
            "layers.w_gate": ((d, ff), "w"),
            "layers.w_up": ((d, ff), "w"),
            "layers.w_down": ((ff, d), "w"),
        }
    if family == "gpt2":
        return {
            "wte": ((v, d), "w"),
            "wpe": ((c["max_seq_len"], d), "w"),
            "final_norm.scale": ((d,), "scale"),
            "final_norm.bias": ((d,), "w"),
            "layers.norm0.scale": ((d,), "scale"),
            "layers.norm0.bias": ((d,), "w"),
            "layers.norm1.scale": ((d,), "scale"),
            "layers.norm1.bias": ((d,), "w"),
            "layers.wq": ((d, h, hd), "w"),
            "layers.wq.bias": ((h, hd), "w"),
            "layers.wk": ((d, kv, hd), "w"),
            "layers.wk.bias": ((kv, hd), "w"),
            "layers.wv": ((d, kv, hd), "w"),
            "layers.wv.bias": ((kv, hd), "w"),
            "layers.wo": ((h, hd, d), "w"),
            "layers.wo.bias": ((d,), "w"),
            "layers.w_up": ((d, ff), "w"),
            "layers.w_up.bias": ((ff,), "w"),
            "layers.w_down": ((ff, d), "w"),
            "layers.w_down.bias": ((d,), "w"),
        }
    raise ValueError(f"unknown family {family!r}")


def n_params(family: str, c: dict) -> int:
    import math

    total = 0
    for name, (shape, _) in shapes(family, c).items():
        n = math.prod(shape)
        total += n * (c["n_layers"] if name.startswith("layers.") else 1)
    return total


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make(family: str, c: dict, seed: int, dtype) -> dict:
    """The flat dict of weights, on the default device, in ``dtype``."""
    import jax
    import jax.numpy as jnp

    spec = shapes(family, c)
    names = sorted(spec)
    n_layers = c["n_layers"]

    def draw(key, shape, kind):
        x = STD * jax.random.normal(key, shape, jnp.float32)
        if kind == "scale":
            x = 1.0 + x
        return x.astype(dtype)

    @jax.jit
    def build(key):
        out = {}
        layer_names = [n for n in names if n.startswith("layers.")]
        for i, n in enumerate(names):
            if n in layer_names:
                continue
            shape, kind = spec[n]
            out[n] = draw(jax.random.fold_in(key, i), shape, kind)

        def one_layer(lkey):
            return {
                n: draw(jax.random.fold_in(lkey, j), *spec[n])
                for j, n in enumerate(layer_names)
            }

        lkeys = jax.random.split(jax.random.fold_in(key, 10_000), n_layers)
        out.update(jax.lax.map(one_layer, lkeys))
        return out

    return build(seed_key(seed))
