#!/usr/bin/env python3
"""On-chip parity and timing sweep of the paged-attention decode kernel.

No benchmark cell runs this; it is the kernel alone, at the shapes the
cells and the kept configuration files use, against
``paged_attention_reference``:

* parity: Mistral 32/8/128 at ``max_pages`` 48 and 260 with 32 lanes,
  Jamba 20/1/128 with 128 lanes, gpt2-xl 25/25/64, each over a ragged
  batch that holds an idle lane, a one-token lane and a full-table lane;
* time: the same shapes at the context the cells' decode ticks carry,
  the kernel's own device events from a profiler trace, as a share of
  the bytes' least time on the chip, and one pair at ``max_pages`` 260
  that shows the time follows the lengths (every length 64 against
  every length 4,096).

    chiprun -- python3 tools/paged_attention_chip.py

``--tiny`` rehearses the control flow on the CPU (interpret mode, toy
shapes); its times mean nothing and it says so.  ``--ppb 8,16,32`` also
times those block sizes beside the derived one.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from torchdistx_tpu.utils.profiling import trace

pa = importlib.import_module("torchdistx_tpu.ops.paged_attention")
KERNEL = "tdx_paged_attention_decode"

CALLS = 12  # kernel calls chained in one program, as a 12-layer tick has

# name -> lanes, heads, kv heads, head dim, page, max_pages, and what a
# decode tick of the cell holds (ledger, PR 28): mean context of a live
# lane, live lanes.  doc-prefill decodes 7 prompts of 1,024-4,096 at once
# (15,449 attended tokens a tick), the other lanes idle.
SHAPES = {
    "mistral-chat": (32, 32, 8, 128, 16, 48, 245, 32),
    "mistral-doc": (32, 32, 8, 128, 16, 260, 2200, 7),
    "jamba-chat": (128, 20, 1, 128, 16, 48, 228, 128),
    "gpt2-xl": (32, 25, 25, 64, 16, 48, 245, 32),
}
TINY = {
    "tiny-gqa": (4, 4, 2, 16, 8, 6, 20, 4),
    "tiny-mha64": (3, 2, 2, 64, 8, 5, 17, 2),
}


def make_case(rng, shape, lengths, dtype=jnp.bfloat16):
    B, H, KV, D, page, maxp = shape[:6]
    n_pages = B * maxp + 1
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((n_pages, KV, page, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_pages, KV, page, D)), dtype)
    table = (rng.permutation(n_pages - 1)[: B * maxp] + 1).reshape(B, maxp)
    return (q, kp, vp, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(table, jnp.int32))


def ragged_lengths(rng, shape, planted=True):
    """Lengths around the shape's mean context; ``planted``: an idle
    lane, a one-token lane and a full-table lane among them (a cell's
    tick holds none, so the timed batches leave them out)."""
    B, _, _, _, page, maxp, mean, live = shape
    lens = np.clip(rng.normal(mean, mean / 3, B).astype(int), 1, maxp * page)
    lens[rng.permutation(B)[live:]] = 0
    if planted:
        lens[:3] = (0, 1, maxp * page)
    return lens


def parity(name, shape, rng):
    lens = ragged_lengths(rng, shape)
    q, kp, vp, lengths, table = make_case(rng, shape, lens)
    out = np.asarray(jax.jit(pa.paged_attention)(q, kp, vp, lengths, table),
                     np.float32)
    ref = np.asarray(
        jax.jit(pa.paged_attention_reference)(q, kp, vp, lengths, table),
        np.float32)
    live = lens > 0
    gap = float(np.abs(out[live] - ref[live]).max())
    return {"shape": name, "max_abs_gap": gap, "finite": bool(
        np.isfinite(out).all()), "idle_row_zero": bool((out[~live] == 0).all())}


def seconds_a_call(attend, case, runs=3):
    """(kernel, chain) seconds of one call, and the kernel's trace events
    a call.  ``CALLS`` calls run in one
    jitted program, each fed the one before, so that no dispatch lies
    between them.  ``kernel``: the kernel's own events on the device,
    summed over a profiler trace of ``runs`` such programs and divided
    by the calls made (a call may show as more than one event);
    ``chain``: the host's clock over them, a call's share,
    which also holds the pad, the slices and the loop around the kernel
    (about 55 us a call).  A backend that writes no device plane (the
    CPU rehearsal) gives the chain's time for both."""
    q, kp, vp, lengths, table = case

    @jax.jit
    def chain(q, kp, vp, lengths, table):
        def layer(x, _):
            return attend(x, kp, vp, lengths, table), None
        return jax.lax.scan(layer, q, None, length=CALLS)[0]

    chain(q, kp, vp, lengths, table).block_until_ready()
    with tempfile.TemporaryDirectory() as logdir:
        with trace(logdir):
            t0 = time.perf_counter()
            for _ in range(runs):
                chain(q, kp, vp, lengths, table).block_until_ready()
            wall = (time.perf_counter() - t0) / (runs * CALLS)
        durations = [
            e.duration_ns
            for path in glob.glob(os.path.join(
                logdir, "plugins", "profile", "*", "*.xplane.pb"))
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/device:TPU")
            for line in plane.lines if line.name == "XLA Ops"
            for e in line.events if KERNEL in e.name]
    kernel = sum(durations) * 1e-9 / (runs * CALLS) if durations else wall
    return kernel, wall, len(durations) / (runs * CALLS)


def timing(name, shape, lens, rng, peak, ppbs=()):
    B, H, KV, D, page, maxp = shape[:6]
    case = make_case(rng, shape, lens)
    kv_bytes = 2 * int(np.sum(lens)) * KV * D * case[1].dtype.itemsize
    row = {"shape": name, "attended_tokens": int(np.sum(lens)),
           "kv_bytes": kv_bytes}
    derived = getattr(pa, "pages_per_block", None)
    if derived is not None:
        row["pages_per_block"] = derived(KV, page, D, case[1].dtype)
        row["kv_blocks"] = pa.kv_blocks_walked(lens, page, KV, D,
                                               case[1].dtype)
    row["kernel_s"], row["chain_s_a_call"], row["events_a_call"] = (
        seconds_a_call(pa.paged_attention, case))
    if peak:
        row["roofline_share_pct"] = 100.0 * kv_bytes / peak / row["kernel_s"]
    for ppb in ppbs:
        row[f"kernel_s.ppb{ppb}"] = seconds_a_call(
            lambda *a, ppb=ppb: pa._paged_attention(
                *a, ppb, jax.default_backend() != "tpu"), case)[0]
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ppb", default="")
    ap.add_argument("--shapes", default="")
    args = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if not args.tiny and dev.platform != "tpu":
        print(f"paged_attention_chip: backend is {dev.platform!r}, not a "
              f"TPU; pass --tiny for a rehearsal.", file=sys.stderr)
        return 2
    shapes = TINY if args.tiny else SHAPES
    if args.shapes:
        shapes = {k: shapes[k] for k in args.shapes.split(",")}
    # The peaks are the benchmark's table, one for the repo.
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f).get(dev.device_kind, {}).get("hbm_bytes_per_s")
    ppbs = [int(x) for x in args.ppb.split(",") if x]
    rng = np.random.default_rng(0)
    ok = True
    for name, shape in shapes.items():
        row = parity(name, shape, rng)
        ok &= row["finite"] and row["idle_row_zero"] and (
            row["max_abs_gap"] < 3e-2)
        print("parity " + json.dumps(row), flush=True)
    for name, shape in shapes.items():
        lens = ragged_lengths(rng, shape, planted=False)
        print("time " + json.dumps(
            timing(name, shape, lens, rng, peak, ppbs)), flush=True)
    # The time follows the lengths, not the table's width.
    wide = next(iter(shapes.values()))[:5] + ((6,) if args.tiny else (260,))
    span = wide[4] * wide[5]
    pair = {}
    for label, n in (("short", span // 65 or 1), ("long", span * 64 // 65)):
        lens = np.full(wide[0], n)
        pair[label] = timing(f"lengths-{n}", wide, lens, rng, peak, ppbs)
        print("time " + json.dumps(pair[label]), flush=True)
    ratio = pair["long"]["kernel_s"] / pair["short"]["kernel_s"]
    print("pair " + json.dumps({"long_over_short": ratio}), flush=True)
    if not args.tiny:
        ok &= ratio >= 10.0
    print(json.dumps({"ok": bool(ok), "device": device,
                      "times_are_device_times": not args.tiny}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
