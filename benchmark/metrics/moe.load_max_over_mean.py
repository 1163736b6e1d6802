"""How unevenly the router loads the held experts: the fullest held
expert's pairs, summed over the calls (``tdx.serve.moe_pairs_max_expert``),
over the mean pairs a held expert and layer got
(``tdx.serve.moe_routed_pairs`` / (held experts x expert layers)); 1 is an
even load.  The counters run from the process's start: the warm-up's few
calls are in both."""


def read(ctx):
    from torchdistx_tpu import observe

    c = ctx["c"]
    pairs = observe.counter("tdx.serve.moe_routed_pairs").value
    if not pairs or not c.get("held_experts"):
        return None
    mean = pairs / (c["held_experts"] * c["n_expert_layers"])
    return observe.counter("tdx.serve.moe_pairs_max_expert").value / mean
