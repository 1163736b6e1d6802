"""What the readers of the ``afmoe`` cell's device metrics share: the
time of device events by name in the reduced trace (``ctx["trace"]["ops"]``,
``benchmark/xplane.py``: self time by ``op_key``, which begins with the
HLO instruction's name), and the sums of ``serve.program`` span arguments
over the traced slice's steps.

The expert products are ``jax.lax.ragged_dot`` under the scope
``tdx_moe_experts``; XLA names the grouped-matmul kernel it lowers them
to ``ragged-dot*`` (and its tile bookkeeping ``ragged-dot-metadata``),
whatever the scope, so both names are taken: a later kernel of the
program's own is to be named ``tdx_moe_experts_*``.  A trace without such
events (a parent commit, another cell) gives None."""

from __future__ import annotations

EXPERTS = ("tdx_moe_experts", "ragged-dot", "ragged_dot")


def named_seconds(ctx, *prefixes):
    """Seconds of the traced slice's device events whose instruction name
    begins with one of ``prefixes``; None where there is none."""
    tr = ctx.get("trace")
    if not tr:
        return None
    secs = sum(v for k, v in tr.get("ops", {}).items()
               if k.startswith(prefixes))
    return secs or None


def experts_seconds(ctx):
    return named_seconds(ctx, *EXPERTS)


def slice_args(ctx, *names, decode_only=False):
    """Sums of ``serve.program`` span arguments over the spans that began
    inside the traced slice's steps (zeros where the program records no
    such argument)."""
    from benchmark import spanlog
    from torchdistx_tpu.observe import spans as tracer_spans

    steps = ctx.get("traced_steps") or []
    to_us = getattr(tracer_spans, "from_perf_counter", None)
    if not steps or to_us is None:
        return (0,) * len(names)
    lo, hi = to_us(steps[0]["t0"]), to_us(steps[-1]["t1"])
    events = spanlog.window_spans(ctx).get("serve.program", [])
    if decode_only:
        events = spanlog.decodes(events)
    inside = [e for e in events if lo <= e["ts"] <= hi]
    return tuple(sum(e["args"].get(n, 0) for e in inside) for n in names)
