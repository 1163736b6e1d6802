"""The prefill scan's share of its roofline over the traced slice: the
least time the chip could take for the recurrence over the REAL prompt
positions that the slice's prefill and chunk calls scanned
(``rooflines/ssm.py`` ``chunk_scan_needs``; padding to a bucket is no
needed work) over the time of the events under ``tdx_ssm_chunk_scan``
(``benchmark/ssm_trace.py``)."""
from benchmark.rooflines import ssm


def read(ctx):
    tr = (ctx.get("ssm_trace") or {}).get("tdx_ssm_chunk_scan")
    if not tr or not tr["seconds"] or not ctx.get("peaks"):
        return None
    steps = ctx.get("traced_steps", [])
    positions = sum(s.get("prefill_tokens", 0) for s in steps)
    calls = sum(n for s in steps for k, n in s["calls"].items()
                if k.startswith(("prefill-", "chunk-")))
    if not positions:
        return None
    least, _bound = ssm.least_seconds(
        ssm.chunk_scan_needs(ctx["c"], positions, calls), ctx["peaks"])
    return 100.0 * least / tr["seconds"]
