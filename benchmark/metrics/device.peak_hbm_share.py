"""Peak device memory of the process over the chip's HBM, in percent
(``memory_stats()['peak_bytes_in_use']`` read when the window closed)."""


def read(ctx):
    if not ctx.get("peaks") or not ctx["memory_peak_bytes"]:
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]
