"""Seconds of set-up booked on the ``materialize`` phase by the benchmark's own
clock (``harness.Clock``); the six phases sum to ``setup_s``."""


def read(ctx):
    return ctx["clock"].phases["materialize"]
