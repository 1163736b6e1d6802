"""Share of the device's busy time in the traced slice that the two
recurrences take, in percent: the events under ``tdx_ssm_decode_update``
and ``tdx_ssm_chunk_scan`` (``benchmark/ssm_trace.py``) over the union of
all op intervals (``benchmark/xplane.py``)."""


def read(ctx):
    tr, ssm = ctx.get("trace"), ctx.get("ssm_trace")
    if not tr or not tr["busy_s"] or not ssm:
        return None
    named = sum(v["seconds"] for v in ssm.values() if isinstance(v, dict))
    return 100.0 * named / tr["busy_s"]
