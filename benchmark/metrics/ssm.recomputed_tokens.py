"""Prompt tokens prefilled a second time because a preempted request kept
neither its pages nor its recurrent state
(``tdx.serve.recomputed_tokens``); read only where the cache has a state
group."""


def read(ctx):
    e = ctx["engine"]
    if not e.get("state_lanes"):
        return None
    return e.get("recomputed_tokens")
