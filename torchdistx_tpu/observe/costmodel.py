"""XLA device accounting: compiler-reported FLOPs/bytes/HBM per program,
and a measured host→device link-bandwidth baseline.

Every headline number must be measured, not estimated (ROADMAP): the
compiler already knows each program's FLOPs, bytes accessed, and device
memory footprint — ``compiled.cost_analysis()`` /
``compiled.memory_analysis()`` — so MFU and HBM figures should come from
there, not from a 6·N·D guess.  This module wraps both probes behind
version-tolerant extractors (jax has changed their return shapes across
releases; any failure degrades to "no costs", never an error), keeps a
process-wide HBM high-water gauge, and measures the actually-attainable
host→device bandwidth so ``materialize_gbps`` can be reported as a
utilization fraction (``tdx.jax.link_utilization``) instead of a number
with no denominator.

Consumers: ``compile_service.compile_program`` attaches
:func:`program_costs` to every ``jax.compile`` span and to the artifact
registry manifest; ``parallel.train._instrument_step`` feeds
:class:`~.step.StepMeter` compiler FLOPs so the training loop publishes
``tdx.train.mfu`` (compiler-derived) instead of ``mfu_est``; ``bench.py``
reports ``materialize_link_utilization`` as a tracked headline.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

__all__ = [
    "link_bandwidth_gbps",
    "link_probe_size_mb",
    "note_program_memory",
    "program_costs",
    "reset_link_probe",
]


def _first_analysis(obj):
    """cost_analysis() has returned a dict, a list of dicts (one per
    partition/computation), and None across jax versions — normalize to
    one dict or None."""
    if isinstance(obj, (list, tuple)):
        obj = obj[0] if obj else None
    return obj if isinstance(obj, dict) else None


def program_costs(compiled) -> Optional[Dict[str, float]]:
    """Compiler-reported accounting for one compiled program, or None
    when this jax/backend exposes neither probe.

    Keys (all floats, bytes unless named otherwise; absent keys mean the
    probe did not report them):

    * ``flops`` — XLA's model FLOP count for one execution;
    * ``bytes_accessed`` — modeled HBM traffic;
    * ``argument_bytes`` / ``output_bytes`` / ``temp_bytes`` /
      ``generated_code_bytes`` — the memory_analysis footprint split;
    * ``alias_bytes`` — the bytes of outputs that live in the buffer of
      a donated argument (0 for a program that donates nothing): the
      record that a serving program returns its pools in place;
    * ``peak_bytes`` — the device high-water estimate: XLA's own
      ``peak_memory_in_bytes`` where available, else the
      arguments+outputs+temps sum less the aliased bytes, which that sum
      counts twice (an upper bound on live buffers).
    """
    out: Dict[str, float] = {}
    try:
        ca = _first_analysis(compiled.cost_analysis())
    except Exception:  # noqa: BLE001 — version drift, unsupported backend
        ca = None
    if ca:
        for key, name in (("flops", "flops"),
                          ("bytes accessed", "bytes_accessed")):
            v = ca.get(key)
            if isinstance(v, (int, float)) and v >= 0:
                out[name] = float(v)
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        ma = None
    if ma is not None:
        for attr, name in (
            ("argument_size_in_bytes", "argument_bytes"),
            ("output_size_in_bytes", "output_bytes"),
            ("temp_size_in_bytes", "temp_bytes"),
            ("generated_code_size_in_bytes", "generated_code_bytes"),
            ("alias_size_in_bytes", "alias_bytes"),
        ):
            v = getattr(ma, attr, None)
            if isinstance(v, (int, float)) and v >= 0:
                out[name] = float(v)
        peak = getattr(ma, "peak_memory_in_bytes", None)
        if not isinstance(peak, (int, float)) or peak <= 0:
            parts = [out.get(k) for k in
                     ("argument_bytes", "output_bytes", "temp_bytes")]
            peak = (sum(p for p in parts if p) - out.get("alias_bytes", 0.0)
                    if any(parts) else None)
        if peak:
            out["peak_bytes"] = float(peak)
    return out or None


# -- HBM high-water ----------------------------------------------------------

_hbm_lock = threading.Lock()
_hbm_high_water = 0.0


def note_program_memory(costs: Optional[Dict[str, float]]) -> None:
    """Fold one program's ``peak_bytes`` into the process-wide
    ``tdx.jax.hbm_high_water_bytes`` gauge (monotone max — the largest
    single-program device footprint seen, the number an operator sizes
    replicas by)."""
    global _hbm_high_water
    if not costs or not costs.get("peak_bytes"):
        return
    peak = costs["peak_bytes"]
    with _hbm_lock:
        if peak <= _hbm_high_water:
            return
        _hbm_high_water = peak
    from . import enabled, gauge

    if enabled():
        gauge("tdx.jax.hbm_high_water_bytes").set(peak)


# -- link-bandwidth probe ----------------------------------------------------
#
# The ROADMAP's bandwidth gap headline needs a denominator: 0.19 GB/s is
# meaningless until it is divided by what THIS host→device link can
# actually move.  The probe device_puts buffers of a few SIZES a few
# times each and takes the best rate (max, not min: we want attainable
# bandwidth, and any interference only lowers a sample; the size sweep
# keeps a single too-small buffer from under-measuring a fast link
# whose fixed dispatch cost dominates small transfers — exactly the
# skew that would inflate the utilization headline's denominator...
# or deflate its numerator).  Measured once per process and cached —
# the link does not change under us, and the sweep costs well under a
# second.

_link_lock = threading.Lock()
_link_gbps: Optional[float] = None
_link_probe_mb: Optional[int] = None
_LINK_PROBE_SWEEP_MB = (8, 32, 128)
_LINK_PROBE_REPEATS = 3


def _probe_sizes_mb(probe_mb: Optional[int]) -> tuple:
    """The probe sizes to sweep: an explicit argument pins one size;
    ``TDX_LINK_PROBE_MB`` accepts one size or a comma list; default is
    the built-in 8/32/128 MB sweep."""
    import os

    if probe_mb:
        return (int(probe_mb),)
    env = os.environ.get("TDX_LINK_PROBE_MB", "")
    if env:
        return tuple(int(p) for p in env.split(",") if p.strip())
    return _LINK_PROBE_SWEEP_MB


def link_bandwidth_gbps(probe_mb: Optional[int] = None, *,
                        cached_only: bool = False) -> Optional[float]:
    """Measured host→device transfer bandwidth (GB/s), cached per
    process; None when the probe failed (no usable device).  Sweeps the
    ``TDX_LINK_PROBE_MB`` sizes (default 8,32,128 MB) and keeps the best
    size's best rate; the chosen size is exported as a ``probe_mb``
    label on the gauge and via :func:`link_probe_size_mb`.

    ``cached_only`` returns the cached value or None WITHOUT probing —
    for callers inside a timed region or an open span, where the
    first-call probe (tens of ms of device_puts) would skew the very
    numbers it contextualizes."""
    global _link_gbps, _link_probe_mb
    with _link_lock:
        if _link_gbps is not None:
            return _link_gbps if _link_gbps > 0 else None
        if cached_only:
            return None
        import numpy as np

        try:
            import jax

            dev = jax.devices()[0]
            best = 0.0
            best_mb = None
            for mb in _probe_sizes_mb(probe_mb):
                n_bytes = mb * (1 << 20)
                # Deliberately UNALIGNED view: an aligned host buffer
                # can take a zero-copy/alias fast path on the CPU
                # backend (observed: 8 MB "transferring" at 159 GB/s),
                # which would put a fantasy denominator under the
                # utilization headline.  Real accelerator links always
                # copy; forcing the copy here keeps the CPU harness's
                # number meaning the same thing.
                buf = np.empty(n_bytes + 64, dtype=np.uint8)
                host = buf[1:n_bytes + 1]
                for _ in range(_LINK_PROBE_REPEATS):
                    t0 = time.perf_counter()
                    arr = jax.device_put(host, dev)
                    arr.block_until_ready()
                    dt = time.perf_counter() - t0
                    if dt > 0 and n_bytes / dt / 1e9 > best:
                        best = n_bytes / dt / 1e9
                        best_mb = mb
                    del arr
                del host, buf
            _link_gbps = best if best > 0 else -1.0
            _link_probe_mb = best_mb
        except Exception:  # noqa: BLE001 — no device, OOM on the probe, ...
            _link_gbps = -1.0
        if _link_gbps > 0:
            from . import enabled, gauge

            if enabled():
                gauge("tdx.jax.link_bandwidth_gbps").set(round(_link_gbps, 3))
                # The labeled twin records WHICH buffer size won the
                # sweep — the provenance a reader needs to trust the
                # utilization denominator (a 8 MB winner on a fast link
                # hints the sweep should be extended).
                gauge(
                    "tdx.jax.link_bandwidth_gbps",
                    probe_mb=_link_probe_mb,
                ).set(round(_link_gbps, 3))
            return _link_gbps
        return None


def link_probe_size_mb() -> Optional[int]:
    """The buffer size (MB) that won the link-probe sweep, or None when
    the probe has not run (or failed)."""
    with _link_lock:
        return _link_probe_mb


def reset_link_probe() -> None:
    """Forget the cached probe (tests, backend switches)."""
    global _link_gbps, _hbm_high_water, _link_probe_mb
    with _link_lock:
        _link_gbps = None
        _link_probe_mb = None
    with _hbm_lock:
        _hbm_high_water = 0.0


def mfu(flops: float, seconds: float, peak_tflops: Optional[float]
        ) -> Optional[float]:
    """Achieved / peak for compiler-reported FLOPs over a measured wall
    time; None when either side is unusable (callers omit MFU rather
    than guess — same contract as :func:`~.step.peak_tflops_for`)."""
    if not flops or not seconds or seconds <= 0 or not peak_tflops:
        return None
    return flops / seconds / 1e12 / peak_tflops
