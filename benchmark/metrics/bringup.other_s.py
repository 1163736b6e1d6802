"""Seconds of set-up booked on the ``other`` phase by the benchmark's own
clock (``harness.Clock``); the six phases sum to ``setup_s``."""


def read(ctx):
    return ctx["clock"].phases["other"]
