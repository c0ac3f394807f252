"""The server child and the proof that a run leaves nothing behind.

The child is the one process that owns the chip: `serve_child.py`, which
calls `minio_tpu.cli.main(["server", ...])` unchanged. It gets its own
session and an environment marker, `MTPU_BENCH_RUN=<uuid4>`, that every
descendant inherits. `Child.stop()` ends the group (TERM, wait, KILL),
then scans `/proc/*/environ` for the marker, kills what still carries it
and says what it was, and scans again; as the parent is the subreaper of
its descendants it also reaps what was orphaned, so no zombie stays.
"""

from __future__ import annotations

import ctypes
import os
import signal
import socket
import subprocess
import sys
import time
import uuid

from .spec import CHECKOUT, HARNESS_DIR

MARKER = "MTPU_BENCH_RUN"
PR_SET_CHILD_SUBREAPER = 36


class LeftBehind(Exception):
    """Something of the run could not be shown to be gone."""


def become_subreaper() -> None:
    """Orphaned descendants are handed to this process, not to init, so
    that it can wait for them."""
    try:
        ctypes.CDLL(None, use_errno=True).prctl(PR_SET_CHILD_SUBREAPER, 1,
                                                0, 0, 0)
    except (OSError, AttributeError):
        pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def port_open(port: int) -> bool:
    with socket.socket() as s:
        s.settimeout(1.0)
        return s.connect_ex(("127.0.0.1", port)) == 0


def carriers(marker_value: str) -> dict[int, str]:
    """pid -> command line of every live process whose environment holds
    this run's marker (zombies have no environment and are not listed)."""
    needle = f"{MARKER}={marker_value}".encode()
    found = {}
    me = os.getpid()
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == me:
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                env = f.read()
            if needle not in env.split(b"\0"):
                continue
            with open(f"/proc/{name}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue                      # gone meanwhile, or not ours
        found[int(name)] = cmd.strip()
    return found


def reap() -> None:
    """Wait for every child that has ended, adopted ones included."""
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return
        if pid == 0:
            return


def sweep(marker_value: str, say=None, tries: int = 50) -> None:
    """Kill whatever still carries the marker; raise unless a scan comes
    back empty."""
    for _ in range(tries):
        left = carriers(marker_value)
        if not left:
            reap()
            return
        for pid, cmd in left.items():
            if say:
                say(f"still running after the group was killed: pid {pid} "
                    f"({cmd[:120]}); killing it")
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        time.sleep(0.1)
        reap()
    raise LeftBehind(f"processes still carry {MARKER}={marker_value}: "
                     f"{carriers(marker_value)}")


class Child:
    """One server child with `drives` directories under `root`."""

    def __init__(self, root: str, drives: int, env: dict,
                 fault: str | None = None):
        self.marker = str(uuid.uuid4())
        self.port = free_port()
        self.host = f"127.0.0.1:{self.port}"
        self.log_path = os.path.join(root, "server.log")
        self.ctl_path = os.path.join(root, "ctl.fifo")
        os.mkfifo(self.ctl_path)
        env = dict(env)
        env[MARKER] = self.marker
        env["MTPU_BENCH_CTL"] = self.ctl_path
        env["MTPU_BENCH_PARENT"] = str(os.getpid())
        env["PYTHONPATH"] = os.pathsep.join(
            [CHECKOUT] + [p for p in env.get("PYTHONPATH", "").split(
                os.pathsep) if p])
        if fault:
            env["MTPU_BENCH_FAULT"] = fault
        else:
            env.pop("MTPU_BENCH_FAULT", None)
        self._log = open(self.log_path, "wb")
        # Started from the caller's thread, which has to outlive the
        # child: PR_SET_PDEATHSIG fires when the *thread* that forked
        # ends. The runner calls this from its main thread.
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HARNESS_DIR, "serve_child.py"),
             "server", f"{root}/d{{1...{drives}}}", "--port", str(self.port)],
            cwd=CHECKOUT, env=env, stdin=subprocess.DEVNULL,
            stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        # The write end of the cue pipe; opened read-write so that the
        # open never blocks and the child's reader never sees EOF while
        # the run lasts.
        self._ctl = os.open(self.ctl_path, os.O_RDWR)

    def cue(self, line: str) -> None:
        os.write(self._ctl, (line + "\n").encode())

    def log_tail(self, n: int = 6000) -> str:
        try:
            if not self._log.closed:
                self._log.flush()
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode(errors="replace")
        except (OSError, ValueError):
            return ""

    def stop(self, say=None) -> None:
        """End the child's group, then everything that carries the
        marker; raises LeftBehind unless both are shown to be gone."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
                self.proc.wait(timeout=15)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        if self._ctl is not None:
            os.close(self._ctl)
            self._ctl = None
        if not self._log.closed:
            self._log.close()
        sweep(self.marker, say)
        for _ in range(50):
            if not port_open(self.port):
                return
            time.sleep(0.1)
        raise LeftBehind(f"port {self.port} still accepts connections")
