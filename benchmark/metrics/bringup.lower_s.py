"""Seconds spent tracing functions and lowering them to MLIR before the
window opened (``observe.compilelog``): paid on every run, since a
program's cache entry is only found once it has been traced and lowered."""
from benchmark import spanlog


def read(ctx):
    return spanlog.setup_seconds(ctx, ("trace", "lower"))
