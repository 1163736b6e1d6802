"""Hang-proof child processes (stdlib only — importable by bench.py
without pulling in torch/jax).

A chip belongs to one process at a time, so the bench parent runs each
phase as a child and must get the chip back whatever the child does: a
phase that hangs, or that leaves helpers behind holding its stdio, would
starve every phase after it.  The recipe: run the child in its own
session, exchange output through FILES rather than pipes (a grandchild
can hold a pipe open past the child's exit, deadlocking the read even on
success), and kill the whole process group on timeout and on success
alike (``start_new_session`` + ``killpg``).
"""

from __future__ import annotations

import os
import signal
import subprocess
import time


def run_in_killable_group(argv, timeout: float, stdout=None, stderr=None,
                          cwd: "str | None" = None,
                          env: "dict | None" = None,
                          reap_grace: float = 10.0) -> "int | None":
    """THE hang-proof subprocess recipe (bench._run_phase): spawn
    ``argv`` in its OWN session, wait at most ``timeout``, and
    process-group-kill on timeout — AND after a successful exit, because
    a helper the child started can outlive it, holding inherited fds
    and the device.

    The child's exit is observed with ``os.waitid(..., WNOWAIT)`` — the
    zombie is left unreaped until AFTER the killpg, so the pid (and with
    it the process-group id) stays pinned and the SIGKILL cannot land on
    a recycled pid/pgid from an unrelated process (a ``Popen.wait``
    first would reap, and the kill would then go by bare number).

    The final reap is bounded by ``reap_grace`` seconds: a hang-proof
    wrapper must not itself hang, so if the child cannot be reaped after
    the group kill (e.g. wedged in an uninterruptible state) we give up
    and report None rather than block forever.

    ``stdout``/``stderr`` accept real file objects (no EOF needed to
    read back — pipes would deadlock on a helper that keeps the write
    end open) or None for DEVNULL.  ``env`` passes through to ``Popen``
    (None = inherit) — bench phases use it to hand the child its
    ``TDX_TRACE_PARENT`` causal context.  Returns the child's returncode, or
    None on timeout or failed reap.  Spawn failures propagate (OSError /
    SubprocessError) — what they mean is caller-specific."""
    proc = subprocess.Popen(
        argv,
        stdout=stdout if stdout is not None else subprocess.DEVNULL,
        stderr=stderr if stderr is not None else subprocess.DEVNULL,
        start_new_session=True,
        cwd=cwd,
        env=env,
    )
    timed_out = not _wait_exited_unreaped(proc.pid, timeout)
    # Whether the child exited (now a zombie — still pinning the pgid) or
    # is still running, the group id is valid: kill every helper in it.
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (OSError, ProcessLookupError):
        try:
            proc.kill()
        except (OSError, ProcessLookupError):
            pass
    try:
        proc.wait(timeout=reap_grace)
    except subprocess.TimeoutExpired:
        return None  # unreapable child: report failure, do not hang
    return None if timed_out else proc.returncode


def _wait_exited_unreaped(pid: int, timeout: float) -> bool:
    """Block until ``pid`` exits or ``timeout`` expires, WITHOUT reaping:
    ``WNOWAIT`` leaves the zombie in place, so the pid/pgid cannot be
    recycled before the caller's ``killpg``.  Returns True if the exit
    was observed.  Polling (WNOHANG) rather than a blocking waitid keeps
    the timeout exact without signals/threads."""
    deadline = time.monotonic() + timeout
    delay = 0.005
    while True:
        try:
            res = os.waitid(
                os.P_PID, pid, os.WEXITED | os.WNOWAIT | os.WNOHANG
            )
        except ChildProcessError:
            return True  # already reaped elsewhere; nothing left to pin
        if res is not None:
            return True
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return False
        time.sleep(min(delay, remaining))
        delay = min(delay * 2, 0.25)
