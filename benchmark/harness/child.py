"""The server children and the proof that a run leaves nothing behind.

A deployment of N nodes (`deployment.nodes`) is N children, one cluster:
each is `serve_child.py`, which calls `minio_tpu.cli.main(["server", ...])`
unchanged, in a session of its own, with its own log, cue pipe and S3
port, and each is the one process that owns its share of the chips. All
carry one environment marker, `MTPU_BENCH_RUN=<uuid4>`, that every
descendant inherits. `Cluster.stop()` ends every group (TERM, wait,
KILL), then scans `/proc/*/environ` for the marker, kills what still
carries it and says what it was, and scans again; as the parent is the
subreaper of its descendants it also reaps what was orphaned, so no
zombie stays. Then every port a node bound has to refuse connections.

One node is started as `server {root}/d{1...D} --port P`. N nodes get one
endpoint list, the same on each as the reference demands: a URL a drive,
`http://127.0.0.1:<sp_i>{root}/d<j>`, node i holding drives
`(i-1)*D/N+1 .. i*D/N` of the flat `d1 .. dD` under the run's root, then
`--port <s3_i> --storage-address 127.0.0.1:<sp_i>`. (Plain URLs and no
`{a...b}`: the program, like the reference, makes a pool of every
argument with an ellipsis, and the deployment is one set over all nodes.)
A node binds its storage, peer and lock planes at `sp`, `sp+1`, `sp+2`.

Which chips a node's process may open is a fact of the machine and not of
the deployment: `chip_share.json`, beside `peaks.json`, holds the
environment that gives process i its share of a host's chips.
"""

from __future__ import annotations

import ctypes
import glob
import json
import os
import shutil
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


def _bindable(port: int) -> bool:
    with socket.socket() as s:
        try:
            s.bind(("127.0.0.1", port))
        except OSError:
            return False
        return True


def free_triples(n: int, taken: set[int]) -> list[int]:
    """n ports whose own, next and next-but-one are free now, no two
    triples sharing a port and none holding a port of `taken`. Free now
    is not bound later: a cluster whose bind races starts again."""
    picked: list[int] = []
    while len(picked) < n:
        p = free_port()
        triple = {p, p + 1, p + 2}
        if (triple & taken or any(abs(p - q) < 3 for q in picked)
                or not all(_bindable(q) for q in triple)):
            continue
        picked.append(p)
    return picked


def chip_share_env(node: int, nodes: int, chips: int) -> dict:
    """What restricts node `node` (0-based) of `nodes` to its share of
    the cell's `chips` on a TPU host; nothing for one node, which takes
    the host as it finds it. The settings are libtpu's and mean nothing
    to another backend."""
    if nodes == 1:
        return {}
    per = chips // nodes
    with open(os.path.join(HARNESS_DIR, "chip_share.json")) as f:
        table = json.load(f)
    if str(per) not in table["chips_per_process"]:
        raise KeyError(f"chip_share.json has no entry for {per} chip(s) a "
                       "process")
    mine = ",".join(str(node * per + c) for c in range(per))
    env = {**table["every_process"], **table["chips_per_process"][str(per)]}
    return {k: str(v).format(chips=mine) for k, v in env.items()}


class Child:
    """One server child: one node of the deployment."""

    def __init__(self, root: str, tag: str, args: list[str], port: int,
                 planes: tuple[int, ...], env: dict, share: dict,
                 marker: str, fault: str | None):
        """`share` is what gives this node its chips and no other's, laid
        over `env`."""
        self.tag, self.port, self.planes = tag, port, planes
        self.share = share
        self.host = f"127.0.0.1:{port}"
        self.log_path = os.path.join(root, f"server{tag}.log")
        self.ctl_path = os.path.join(root, f"ctl{tag}.fifo")
        os.mkfifo(self.ctl_path)
        env = {**env, **share}
        env[MARKER] = marker
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
        self.argv = [sys.executable,
                     os.path.join(HARNESS_DIR, "serve_child.py"), "server",
                     *args, "--port", str(port)]
        # Started from the caller's thread, which has to outlive the
        # child: PR_SET_PDEATHSIG fires when the *thread* that forked
        # ends. The runner calls this from its main thread.
        self.proc = subprocess.Popen(
            self.argv, cwd=CHECKOUT, env=env, stdin=subprocess.DEVNULL,
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

    def signal(self, sig: int) -> None:
        try:
            os.killpg(self.proc.pid, sig)
        except ProcessLookupError:
            pass

    def close(self) -> None:
        """Once the group has been killed: wait, and give up the pipe, the
        log and the pipe's name (a cluster that starts again makes it
        anew)."""
        self.proc.wait()
        if self._ctl is not None:
            os.close(self._ctl)
            self._ctl = None
        if not self._log.closed:
            self._log.close()
        try:
            os.unlink(self.ctl_path)
        except OSError:
            pass


class Cluster:
    """The deployment's server children, `nodes` of them, with `drives`
    directories `d1 .. dD` under `root` between them and `chips` chips."""

    ATTEMPTS = 3

    def __init__(self, root: str, drives: int, nodes: int, chips: int,
                 env: dict, fault: str | None = None):
        self.root, self.drives, self.nodes = root, drives, nodes
        self.chips, self.env, self.fault = chips, env, fault
        self.marker = str(uuid.uuid4())
        self.starts = 0
        self.children: list[Child] = []
        self._start()

    def _start(self) -> None:
        """Every node at once: each needs the others' storage plane to
        agree on the format."""
        self.starts += 1
        n, root = self.nodes, self.root
        s3 = []
        while len(s3) < n:
            p = free_port()
            if p not in s3:
                s3.append(p)
        if n == 1:
            self.children = [Child(root, "", [f"{root}/d{{1...{self.drives}}}"],
                                   s3[0], (), self.env, {}, self.marker,
                                   self.fault)]
            return
        sp = free_triples(n, set(s3))
        per = self.drives // n
        endpoints = [f"http://127.0.0.1:{sp[i]}{root}/d{i * per + j + 1}"
                     for i in range(n) for j in range(per)]
        for i in range(n):
            self.children.append(Child(
                root, f".n{i + 1}",
                [*endpoints, "--storage-address", f"127.0.0.1:{sp[i]}"],
                s3[i], (sp[i], sp[i] + 1, sp[i] + 2), self.env,
                chip_share_env(i, n, self.chips), self.marker, self.fault))

    @property
    def hosts(self) -> list[str]:
        return [c.host for c in self.children]

    @property
    def ports(self) -> list[int]:
        return [p for c in self.children for p in (c.port, *c.planes)]

    def exited(self) -> str:
        """Which node's server has exited, '' while all run."""
        for i, c in enumerate(self.children):
            if c.proc.poll() is not None:
                return (f"the server{'' if self.nodes == 1 else f' of node {i + 1}'}"
                        f" exited with {c.proc.returncode}")
        return ""

    def lost_a_bind(self) -> bool:
        """Whether a node went down because a port it was given was taken
        between the draw and its bind."""
        return any(c.proc.poll() is not None
                   and "Address already in use" in c.log_tail()
                   for c in self.children)

    def start_again(self, say=None) -> bool:
        """After a lost bind: every node down, the drives emptied (a
        format may have been written), new ports. False once the attempts
        are spent."""
        if self.starts >= self.ATTEMPTS:
            return False
        if say:
            say(f"a node lost the race for a port (attempt {self.starts} of "
                f"{self.ATTEMPTS}); the cluster starts again on fresh "
                f"ports\n{self.log_tails(1500)}")
        self._end_children(say)
        for d in glob.glob(os.path.join(self.root, "d[0-9]*")):
            shutil.rmtree(d, ignore_errors=True)
        self.children = []
        self._start()
        return True

    def cue(self, line_for) -> None:
        """`line_for(i)` -> the cue of node i (0-based)."""
        for i, c in enumerate(self.children):
            c.cue(line_for(i))

    def log_tails(self, n: int = 6000) -> str:
        """The end of every node's log, each under its name."""
        if len(self.children) == 1:
            return self.children[0].log_tail(n)
        return "\n".join(f"--- node {i + 1} (port {c.port}) ---\n"
                         + c.log_tail(n)
                         for i, c in enumerate(self.children))

    def _end_children(self, say=None) -> None:
        for c in self.children:
            if c.proc.poll() is None:
                c.signal(signal.SIGTERM)
        t_end = time.monotonic() + 15
        for c in self.children:
            try:
                c.proc.wait(timeout=max(0.0, t_end - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
        for c in self.children:
            c.signal(signal.SIGKILL)
            c.close()
        sweep(self.marker, say)

    def stop(self, say=None) -> None:
        """End every child's group, then everything that carries the
        marker; raises LeftBehind unless both are shown to be gone and
        every port of every node refuses connections."""
        self._end_children(say)
        for _ in range(50):
            still = [p for p in self.ports if port_open(p)]
            if not still:
                return
            time.sleep(0.1)
        raise LeftBehind(f"port(s) {still} still accept connections")
