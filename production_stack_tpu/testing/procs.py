"""Server child processes whose output the parent can read back.

The engine and the router are started as real OS processes by the serving
bench and by ``chip_smoke.py``.  A child that dies at boot used to show
only as "/health not ready": its stdout and stderr went to DEVNULL.  Here
they go to a log file, and every failure raised from this module carries
the end of that file.  Standard library only (no jax, no aiohttp): a
parent that must leave the chip to its children imports nothing heavier.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def probe_devices(timeout_s: float = 300.0) -> Dict:
    """``jax.devices()`` as a throwaway child sees it: {"platform", "kind",
    "count"}.  For a parent that must not touch JAX while its children
    need the chip, and wants to fail in seconds where no chip is found,
    not after a 7B model has been made on whatever JAX fell back to."""
    code = (
        "import json, jax; d = jax.devices(); print(json.dumps({"
        "'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d)}))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout_s,
    )
    if out.returncode != 0:
        raise RuntimeError(f"device probe failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


class ChildFailed(RuntimeError):
    """A child exited early or never became ready; the message ends with
    the tail of its log."""


class Child:
    """One server process with stdout+stderr in ``<log_dir>/<name>.log``."""

    def __init__(
        self, name: str, cmd: List[str], log_dir: str,
        env: Optional[Dict[str, str]] = None, cwd: Optional[str] = None,
    ):
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

    def _fail(self, what: str) -> ChildFailed:
        return ChildFailed(
            f"{self.name}: {what}\n--- tail of {self.log_path} ---\n"
            f"{self.tail()}"
        )

    def wait_http_ok(self, url: str, timeout_s: float) -> float:
        """Poll ``url`` until it answers 200; returns the seconds waited.
        Blocking: an asyncio caller runs it in a thread."""
        t0 = time.monotonic()
        last = "never reached"
        while time.monotonic() - t0 < timeout_s:
            rc = self.proc.poll()
            if rc is not None:
                raise self._fail(f"exited with code {rc} before {url} answered")
            try:
                with urllib.request.urlopen(url, timeout=2) as resp:
                    if resp.status == 200:
                        return time.monotonic() - t0
                    last = f"status {resp.status}"
            except (urllib.error.URLError, OSError) as e:
                last = str(e)
            time.sleep(0.5)
        raise self._fail(f"{url} not ready in {timeout_s:.0f}s ({last})")

    def stop(self, grace_s: float = 60.0) -> Optional[int]:
        """SIGTERM (the servers' graceful drain), then SIGKILL past the
        grace; returns the exit code (None if it was never started)."""
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
