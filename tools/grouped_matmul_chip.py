#!/usr/bin/env python3
"""On-chip parity and timing of the expert layers' grouped product.

No benchmark cell runs this; it is one product alone, at the trinity
cell's widths (32 held experts, ``[3072, 3072]`` bfloat16 matrices), at
the row counts a call gives (a decode tick's 512, a chunk's 8,192) and
those of the tiers that cut them for ``ragged_dot`` before, against
``jax.lax.ragged_dot`` on the same operands:

* parity: the rows that belong to a group, from the kernel and from
  ``ragged_dot``, against each group's rows through its matrix in
  float32 at full precision rounded once, in bfloat16 ulps;
* time: ``CALLS`` products chained in one program, each fed the one
  before; the device events of each implementation from a profiler trace
  (``tdx_moe_experts_gmm``, or XLA's ``ragged-dot*``), a call's share,
  and the host's clock over the chain, which also holds the visits'
  bookkeeping; and the share of the least time the held experts' bytes
  and the pairs' FLOPs need on the chip.

    python3 tools/grouped_matmul_chip.py     # on a host with a TPU

``--tiny`` rehearses the control flow on the CPU (interpret mode, toy
widths); its times mean nothing and it says so.  ``--tiles 32x1024,...``
also times those (row tile x k tile) choices beside the derived one.
"""

from __future__ import annotations

import argparse
import glob
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

from torchdistx_tpu.ops import grouped_matmul as gm
from torchdistx_tpu.utils.profiling import trace

CALLS = 12  # products chained in one program, as an expert call has

# name -> rows given, pairs in the groups, Dirichlet concentration of the
# pairs over the experts (None: uniform, as a decode tick's 128 lanes
# make it; small: one sequence's chunk, whose tokens favour a few
# experts, PERF.md §5).  The expert layer gives the products all its
# rows: 512 in a decode tick, 8,192 in a chunk of 2,048 (128 lanes or
# tokens x 4 choices); the smaller counts are what a layer that cuts the
# rows to the share that holds its pairs (1.5 times the expected share,
# doubling) would give ``ragged_dot``, whose time follows the rows given.
CASES = {
    "rows96-pairs64": (96, 64, None),
    "rows512-pairs64": (512, 64, None),
    "rows512-pairs400": (512, 400, None),
    "rows768-pairs512": (768, 512, 0.3),
    "rows1536-pairs1024": (1536, 1024, 0.3),
    "rows1536-pairs1024-even": (1536, 1024, None),
    "rows3072-pairs2048": (3072, 2048, 0.3),
    "rows4096-pairs512": (4096, 512, 0.3),
    "rows8192-pairs1024": (8192, 1024, 0.3),
    "rows8192-pairs7000": (8192, 7000, 0.3),
}
WIDE = (32, 3072, 3072)   # held experts, k, n
TINY = {"tiny-40": (40, 24, None), "tiny-1100": (1100, 700, 0.3)}
TINY_WIDE = (8, 256, 256)


def group_sizes(rng, g, pairs, alpha):
    p = np.full(g, 1.0 / g) if alpha is None else rng.dirichlet([alpha] * g)
    return rng.multinomial(pairs, p).astype(np.int32)


def ragged(x, w, s):
    return jax.lax.ragged_dot(x, w, s)


def kernel(tiles=None):
    """The program's kernel, at ``tiles`` (row tile, k tile) or the
    derived tiling."""
    if tiles is None:
        return gm.grouped_matmul
    return lambda x, w, s: gm._gmm(x, w, s, tiles=tiles, interpret=(
        jax.default_backend() != "tpu"))


def _ulps(got, want):
    """|got - want| in bfloat16 ulps of each reference value, at least
    1/16's: the outputs are of order 1, and a sum that nearly cancels
    keeps the float32 sums' absolute difference."""
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1 / 16))) - 7)
    return np.abs(got - want) / ulp


def parity(x, w, sizes):
    """Both implementations against each group's rows through its matrix
    in float32 at full precision, rounded once to bfloat16, in ulps."""
    rows = int(sizes.sum())
    ends = np.cumsum(np.asarray(sizes))
    exact = np.concatenate([np.zeros((0, w.shape[2]), np.float32)] + [
        np.asarray(jnp.dot(x[a:b].astype(jnp.float32),
                           w[e].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST
                           ).astype(x.dtype), np.float32)
        for e, (a, b) in enumerate(zip(ends - np.asarray(sizes), ends))
        if b > a])
    out = {"rows": rows}
    for label, product in (("kernel", kernel()), ("ragged_dot", ragged)):
        got = np.asarray(jax.jit(product)(x, w, sizes)[:rows], np.float32)
        ulps = _ulps(got, exact)
        out[label + "_finite"] = bool(np.isfinite(got).all())
        out[label + "_max_ulps"] = float(ulps.max()) if rows else 0.0
        out[label + "_rows_over_1_ulp"] = int((ulps > 1).any(1).sum())
    return out


def seconds_a_call(product, x, w, sizes, names, runs=3):
    """(device events, host chain) seconds of one product: ``CALLS``
    products in one jitted program, each fed the one before."""
    @jax.jit
    def chain(x, w, sizes):
        def layer(h, _):
            return product(h, w, sizes), None
        return jax.lax.scan(layer, x, None, length=CALLS)[0]

    chain(x, w, sizes).block_until_ready()
    with tempfile.TemporaryDirectory() as logdir:
        with trace(logdir):
            t0 = time.perf_counter()
            for _ in range(runs):
                chain(x, w, sizes).block_until_ready()
            wall = (time.perf_counter() - t0) / (runs * CALLS)
        durations = [
            e.duration_ns
            for path in glob.glob(os.path.join(
                logdir, "plugins", "profile", "*", "*.xplane.pb"))
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/device:TPU")
            for line in plane.lines if line.name == "XLA Ops"
            for e in line.events if e.name.lstrip("%").startswith(names)]
    device = sum(durations) * 1e-9 / (runs * CALLS) if durations else wall
    return device, wall


def least_seconds(sizes, k, n, peaks):
    """The larger of the hit experts' matrices and the pairs' rows over
    the bandwidth, and the pairs' FLOPs over the peak."""
    if not peaks:
        return None
    rows = int(sizes.sum())
    byte = 2 * (int((sizes > 0).sum()) * k * n + rows * (k + n))
    return max(byte / peaks["hbm_bytes_per_s"],
               2.0 * rows * k * n / peaks["bf16_flops_per_s"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--cases", default="")
    args = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if not args.tiny and dev.platform != "tpu":
        print(f"grouped_matmul_chip: backend is {dev.platform!r}, not a TPU; "
              f"pass --tiny for a rehearsal.", file=sys.stderr)
        return 2
    cases, (g, k, n) = (TINY, TINY_WIDE) if args.tiny else (CASES, WIDE)
    if args.cases:
        cases = {c: cases[c] for c in args.cases.split(",")}
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f).get(dev.device_kind)
    others = [tuple(int(v) for v in t.split("x")) for t in args.tiles.split(",")
              if t]
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((g, k, n), np.float32) * 0.02,
                    jnp.bfloat16)
    ok = True
    for name, (m, pairs, alpha) in cases.items():
        sizes = jnp.asarray(group_sizes(rng, g, pairs, alpha))
        x = jnp.asarray(rng.standard_normal((m, k), np.float32), jnp.bfloat16)
        row = {"case": name, "m": m, "experts_hit": int((sizes > 0).sum()),
               "tiles": list(gm.tiling(m, k)), **parity(x, w, sizes)}
        ok &= row["kernel_finite"] and row["kernel_max_ulps"] <= 1.0
        least = least_seconds(np.asarray(sizes), k, n, peaks)
        for label, product, names in (
                ("ragged_dot", ragged, ("ragged-dot", "ragged_dot")),
                ("kernel", kernel(), (gm.GMM,)),
                *((f"kernel.{t[0]}x{t[1]}", kernel(t), (gm.GMM,))
                  for t in others)):
            row[label + "_s"], row[label + "_chain_s"] = seconds_a_call(
                product, x, w, sizes, names)
            if least:
                row[label + "_roofline_pct"] = 100.0 * least / row[label + "_s"]
        print("case " + json.dumps(row), flush=True)
    print(json.dumps({"ok": bool(ok), "device": device,
                      "times_are_device_times": not args.tiny}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
