"""Share of decode ticks that were verify ticks, in percent
(``engine.program_calls``)."""


def read(ctx):
    calls = ctx["engine"]["program_calls"]
    v = sum(n for k, n in calls.items() if k.startswith("verify"))
    d = calls.get("decode", 0)
    return 100.0 * v / (v + d) if v + d else None
