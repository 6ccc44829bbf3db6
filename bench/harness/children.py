"""Server children whose logs the parent can read back.

Copied from ``production_stack_tpu/testing/procs.py`` (which ran on the chip
in PR 21) so that the yardstick does not change when the program does.
Standard library only: the parent stays off JAX while its children hold the
chip.
"""

from __future__ import annotations

import os
import re
import signal
import socket
import subprocess
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ChildFailed(RuntimeError):
    """A child exited early or never became ready; the message ends with
    the tail of its log."""


# The engine logs this line right after it has built its mesh, before any
# weight is made (engine.py _report_device_and_kernels).
DEVICE_LINE = re.compile(r"Device: platform=(\S+) kind=(.+?) count=(\d+) mesh=")


class Child:
    """One server process with stdout+stderr in ``<log_dir>/<name>.log``."""

    def __init__(self, name: str, cmd: List[str], log_dir: str,
                 env: Optional[Dict[str, str]] = None,
                 cwd: Optional[str] = None):
        self.name = name
        self.cmd = cmd
        self.env = env
        self.cwd = cwd
        os.makedirs(log_dir, exist_ok=True)
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self.proc: Optional[subprocess.Popen] = None

    def start(self) -> "Child":
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                self.cmd, stdout=log, stderr=subprocess.STDOUT, env=self.env,
                cwd=self.cwd,
            )
        return self

    def tail(self, max_bytes: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - max_bytes))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"<no log: {e}>"

    def fail(self, what: str) -> ChildFailed:
        return ChildFailed(
            f"{self.name}: {what}\n--- tail of {self.log_path} ---\n"
            f"{self.tail()}"
        )

    def _alive_or_raise(self, waiting_for: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise self.fail(f"exited with code {rc} before {waiting_for}")

    def wait_device_line(self, timeout_s: float) -> Dict:
        """The device the engine says it holds, read from its log seconds
        after start: a run on the wrong platform is stopped before a 7B
        model is made on it."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            with open(self.log_path, "rb") as f:
                m = DEVICE_LINE.search(f.read().decode(errors="replace"))
            if m:
                return {"platform": m.group(1), "kind": m.group(2),
                        "count": int(m.group(3))}
            self._alive_or_raise("it reported its device")
            time.sleep(0.2)
        raise self.fail(f"no device report in {timeout_s:.0f}s")

    def wait_http_ok(self, url: str, timeout_s: float) -> float:
        """Poll ``url`` until it answers 200; returns the seconds waited."""
        t0 = time.monotonic()
        last = "never reached"
        while time.monotonic() - t0 < timeout_s:
            self._alive_or_raise(f"{url} answered")
            try:
                with urllib.request.urlopen(url, timeout=2) as resp:
                    if resp.status == 200:
                        return time.monotonic() - t0
                    last = f"status {resp.status}"
            except (urllib.error.URLError, OSError) as e:
                last = str(e)
            time.sleep(0.25)
        raise self.fail(f"{url} not ready in {timeout_s:.0f}s ({last})")

    def cpu_seconds(self) -> Dict:
        """CPU time the child has used so far (user + system, from /proc):
        the whole process and its busiest thread.  Set-up is mostly one
        thread tracing and loading programs, so the same work at more CPU
        seconds is a slower core, not more work; None where /proc has no
        such file."""
        tick = os.sysconf("SC_CLK_TCK")

        def used(path: str) -> Optional[float]:
            try:
                with open(path) as f:
                    fields = f.read().rsplit(")", 1)[1].split()
                return (int(fields[11]) + int(fields[12])) / tick
            except (OSError, IndexError, ValueError):
                return None

        pid = self.proc.pid
        try:
            threads = [used(f"/proc/{pid}/task/{tid}/stat")
                       for tid in os.listdir(f"/proc/{pid}/task")]
        except OSError:
            threads = []
        return {"process": used(f"/proc/{pid}/stat"),
                "busiest_thread": max((t for t in threads if t is not None),
                                      default=None)}

    def stop(self, grace_s: float = 60.0) -> Optional[int]:
        """SIGTERM (the servers' graceful drain), then SIGKILL past the
        grace; waits for the exit and returns its code."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        return self.proc.returncode
