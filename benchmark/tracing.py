"""Starting the profiler for the traced slice of a run, and reducing what
it wrote.  The trace goes under ``benchmark/.work/`` (git-ignored, scanned
by no start-up) and is removed once reduced."""

from __future__ import annotations

import glob
import os
import shutil


def start(trace_dir: str) -> None:
    """``jax.profiler`` as ``utils/profiling.trace`` starts it, but with
    the Python call tracer off: it writes an event for every Python call,
    which makes a three-second trace tens of MB and slows the host that
    the idle gaps are about."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def reduce_dir(trace_dir: str, labels: dict):
    """(reduction, breakdown) of the newest ``.xplane.pb`` under
    ``trace_dir``; the directory is removed afterwards."""
    from benchmark import xplane

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None, None
    red = xplane.reduce(xplane.load(files[-1]), labels)
    red["trace_bytes"] = os.path.getsize(files[-1])
    shutil.rmtree(trace_dir, ignore_errors=True)
    breakdown = {"device_ops": xplane.top(red["ops"]),
                 "idle_gaps": xplane.top(red["gaps"])}
    return red, breakdown
