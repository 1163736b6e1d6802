"""Share of the traced slice in which no operation ran on the device, in
percent (union of the device's op intervals, ``benchmark/xplane.py``)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
