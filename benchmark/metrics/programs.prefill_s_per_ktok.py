"""Seconds per 1,000 prompt positions computed: over the steps that ran a
prefill or chunk program, their wall time less the median decode-only step
(where they also decoded), over the bucket sizes they ran."""
from benchmark import harness


def _bucket(name):
    return int(name.split("-")[1])


def read(ctx):
    dec = [s["t1"] - s["t0"] for s in ctx["steps"] if s["calls"] and all(
        k == "decode" or k.startswith("verify") for k in s["calls"])]
    base = harness.quantile(dec, 0.5) or 0.0
    secs = toks = 0.0
    for s in ctx["steps"]:
        pre = {k: n for k, n in s["calls"].items()
               if k.startswith(("prefill-", "chunk-"))}
        if not pre:
            continue
        also_decoded = len(pre) < len(s["calls"])
        secs += max((s["t1"] - s["t0"]) - (base if also_decoded else 0.0), 0.0)
        toks += sum(_bucket(k) * n for k, n in pre.items())
    return 1000.0 * secs / toks if toks else None
