"""Share of the traced slice in which the host was inside a program call
and the device ran nothing, in percent: 100 x (seconds of
``serve.program`` inside the slice - the device's busy seconds) / the
slice.  While traced a call waits for its result, so what of it the
device did not fill is launch and wake-up.  Device work outside any call
(a retiring lane's row) makes it read low by its time."""
from benchmark import spanslice


def read(ctx):
    tr = ctx.get("trace")
    cut = spanslice.traced_slice(ctx)
    if not tr or not tr["window_s"] or cut is None:
        return None
    spans = spanslice.window(ctx)
    if not spans or not spans.get("serve.program.launch"):
        return None
    inside = spanslice.seconds_inside(spans.get("serve.program", []), *cut)
    return 100.0 * (inside - tr["busy_s"]) / tr["window_s"]
