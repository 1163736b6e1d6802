"""``correct`` for a training cell: the program's first three steps
against the plain reference's (float32, ``highest``), after the window has
closed and the program's state is freed.

Numbers compared (limits in ``benchmark/limits/<workload>.json``):

* ``loss_gap``: the widest |program - reference| over the three losses.
* ``grad_norm_gap``: the first gradient as the optimizer got it (Adam's
  first moment after one step over 1 - b1), by the worst tensor: the gap
  between the program's norm and the reference's over the larger of the
  reference's norm of that tensor and of the median tensor.
* ``grad_sample_gap``: the first gradient itself on a fixed sample, the
  first 4,096 entries of each tensor: the norm of the difference between the
  program's and the reference's over the reference's, by the worst tensor
  (tensors whose gradient is nought to rounding left out, as below).  The
  three norms and mean losses above average unbiased rounding away -- their
  gap is of second order in it -- so fp8 reads within three times bf16 on
  all of them (PERF.md 2); this one is of first order and separates them.
* ``update_norm_gap``: the same measure on the norm of each tensor's
  change over the three steps, leaving out tensors whose reference
  gradient is under a thousandth of the median tensor's (they move under
  Adam by round-off alone).
"""

from __future__ import annotations

import statistics

SAMPLE = 4096


def reference(env, c, *, quant=None, rows_used=None, steps=3):
    """The reference's three steps: {'loss', 'grad_norm', 'update_norm'}."""
    import jax
    import jax.numpy as jnp

    from benchmark import traffic, weights
    from benchmark.reference import decoder

    cfg, mix, seed = env["cfg"], env["mix"], env["seed"]
    opt = cfg["train_config"]["optimizer"]
    w = weights.make(cfg["family"], c, seed, jnp.float32)
    w0 = w
    lg = decoder.make_loss_and_grad(cfg["family"], c, decoder.QUANT[quant])
    st = decoder.adamw_init(w)
    out = {"loss": []}
    for i in range(steps):
        rows = traffic.training_rows(mix, seed, i, c["vocab_size"])
        if rows_used is not None:
            rows = rows[:rows_used]
        loss, g = lg(w, jnp.asarray(rows))
        out["loss"].append(float(loss))
        if i == 0:
            out["grad_norm"] = decoder.leaf_norms(g)
            out["grad_sample"] = jax.device_get(
                {k: v.reshape(-1)[:SAMPLE] for k, v in g.items()})
        w, st = decoder.adamw_step(w, g, st, opt)
    delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(w, w0)
    out["update_norm"] = decoder.leaf_norms(delta)
    return out


def gaps(observed: dict, ref: dict) -> dict:
    """The three numbers, and which tensor was the worst."""
    loss = max(abs(a - b) for a, b in zip(observed["loss"], ref["loss"]))
    gmed = statistics.median(ref["grad_norm"].values())
    g_gap, g_leaf = max(
        (abs(observed["grad_norm"][k] - r) / max(r, gmed), k)
        for k, r in ref["grad_norm"].items())
    moved = [k for k, r in ref["grad_norm"].items() if r >= 1e-3 * gmed]
    umed = statistics.median(ref["update_norm"][k] for k in moved)
    u_gap, u_leaf = max(
        (abs(observed["update_norm"][k] - ref["update_norm"][k])
         / max(ref["update_norm"][k], umed), k) for k in moved)
    import numpy as np

    norm = lambda x: float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
    s_gap, s_leaf = max(
        (norm(observed["grad_sample"][k] - ref["grad_sample"][k])
         / max(norm(ref["grad_sample"][k]), 1e-30), k) for k in moved)
    return {"loss_gap": loss, "grad_norm_gap": g_gap,
            "grad_sample_gap": s_gap, "update_norm_gap": u_gap,
            "_worst": {"grad": g_leaf, "sample": s_leaf, "update": u_leaf}}


def judge(env, numbers: dict) -> dict:
    from benchmark import harness

    limits = harness.load_json(
        env["root"], f"benchmark/limits/{env['cell']['name']}.json")
    key = "rehearsal" if env["rehearse"] else "limits"
    return {k: {"value": numbers[k], "limit": lim, "ok": numbers[k] <= lim}
            for k, lim in limits[key].items()}


def check(env, c, observed: dict) -> dict:
    ref = reference(env, c)
    numbers = gaps(observed, ref)
    env["extra_notes"]["check"] = {
        "worst_tensor": numbers["_worst"], "reference_losses": ref["loss"]}
    if env.get("control"):
        half = env["mix"]["batch"] // 2
        env["extra_notes"]["control"] = {
            "precision": env["control"],
            **gaps(reference(env, c, quant=env["control"]), ref)}
        env["extra_notes"]["fault_half_batch"] = gaps(
            reference(env, c, rows_used=half), ref)
    return judge(env, numbers)
