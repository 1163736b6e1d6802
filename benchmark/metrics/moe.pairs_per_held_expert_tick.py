"""Pairs a held expert gets in a decode tick, mean over the window's
ticks, the expert layers and the held experts: ``routed_pairs`` of the
decode ticks' ``serve.program`` spans over held experts x expert layers x
ticks.  Every chip of the deployment's group sees every token, so the
deployment's figure is lanes x top_k / experts (2.0 with every lane
decoding); it says how many rows each expert's matrices are read for."""
from benchmark import spanlog


def read(ctx):
    ticks = [e for e in spanlog.decodes(
        spanlog.window_spans(ctx).get("serve.program", []))
        if "routed_pairs" in e["args"]]
    c = ctx["c"]
    if not ticks or not c.get("held_experts"):
        return None
    return sum(e["args"]["routed_pairs"] for e in ticks) / (
        len(ticks) * c["held_experts"] * c["n_expert_layers"])
