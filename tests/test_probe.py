"""Hang-proof child processes (torchdistx_tpu/_probe.py): the recipe
bench._run_phase relies on to get the chip back from a phase that hangs
or leaves helpers behind."""

from __future__ import annotations

import sys
import time

from torchdistx_tpu._probe import run_in_killable_group


class TestRunInKillableGroup:
    def test_returncode_passthrough(self):
        rc = run_in_killable_group([sys.executable, "-c", "raise SystemExit(7)"],
                                   timeout=60.0)
        assert rc == 7

    def test_timeout_returns_none_promptly(self):
        t0 = time.monotonic()
        rc = run_in_killable_group(
            [sys.executable, "-c", "import time; time.sleep(600)"],
            timeout=1.5,
        )
        # None on timeout, and the bounded reap means the wrapper itself
        # returns promptly (well under the child's sleep).
        assert rc is None
        assert time.monotonic() - t0 < 30.0

    def test_group_kill_takes_helpers(self, tmp_path):
        # A child that spawns a long-lived helper in its session: the
        # group kill must take the helper down too, and the wrapper must
        # return the CHILD's code (exit observed unreaped via WNOWAIT
        # before the killpg — not a recycled-pid kill).  The CHILD writes
        # the helper's pid before exiting, so the assertion is about the
        # helper process actually being gone — not about a marker it
        # would only have written minutes later.
        pidfile = tmp_path / "helper_pid"
        code = (
            f"import subprocess, sys; "
            f"p = subprocess.Popen([sys.executable, '-c', "
            f"'import time; time.sleep(300)']); "
            f"open({str(pidfile)!r}, 'w').write(str(p.pid)); "
            f"raise SystemExit(3)"
        )
        rc = run_in_killable_group([sys.executable, "-c", code], timeout=60.0)
        assert rc == 3
        helper_pid = int(pidfile.read_text())
        assert self._gone(helper_pid), "helper survived the group kill"

    @staticmethod
    def _gone(pid: int, deadline_s: float = 10.0) -> bool:
        """Whether ``pid`` is dead (missing, or an unreaped zombie —
        after the group kill the reparented helper may wait briefly on
        init's reap, so poll /proc state rather than os.kill)."""
        end = time.monotonic() + deadline_s
        proc_stat = f"/proc/{pid}/stat"
        while time.monotonic() < end:
            try:
                with open(proc_stat) as f:
                    state = f.read().rsplit(")", 1)[1].split()[0]
            except OSError:
                return True  # no such process
            if state == "Z":
                return True  # killed, awaiting reap
            time.sleep(0.05)
        return False
