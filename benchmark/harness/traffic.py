"""The one general traffic generator. A mix is a data file
(`traffic/<name>.json`); this module reads it and drives the server with
it, from the run's seed.

Loop kinds (`kind`):

- `closed_loop`: `clients` clients, each sends its next request when the
  last one was answered (as MinIO's `warp` drives a server);
- `open_loop`: requests are due at `rate_per_s`, with gaps drawn from the
  seed; at most `clients` are in flight, and a request's latency counts
  from when it was due, so a stall shows in the requests behind it; how
  late the generator ran is reported;
- `heal`: set-up preloads objects, wipes the bucket on `wipe_drives`
  drives under the running server and heals `warmup_objects` objects of
  their own (`warm/`), so that what a failure pattern costs once is
  set-up; the window starts one heal sequence over the rest through the
  admin API and polls its status.

Against a deployment of N nodes the load is dealt over them as `warp
--host a,b,c,d` deals its clients (assumed, from memory): client c of a
window and job j of a set-up fan-out talk to node `c mod N`, `j mod N`;
the admin calls of a heal go to node 1. With one host every request is
what it is with no list at all.

Any mix may name `trace_cue_share`, the share of the window at which a
traced run's slice is cued (`runner.trace_cue_at`; the middle otherwise).

Op kinds (`ops[].op`): PUT (fresh keys, bodies from a pool of seed-made
payloads), GET and STAT (of preloaded objects; with `wipe_drives` a GET is
a degraded one; `"keys": "each_once"` reads every object at most once),
DELETE (of this client's own earlier PUTs), LIST. Every seed draws the same sizes and the same
number of each op's chances, in another order.
"""

from __future__ import annotations

import hashlib
import http.client
import os
import shutil
import threading
import time
import xml.etree.ElementTree as ET
from dataclasses import dataclass, field

import numpy as np

from minio_tpu.madmin import AdminClient, AdminError

from .client import ACCESS, S3, SECRET
from .reference import payload, rng_for

NS = "{http://s3.amazonaws.com/doc/2006-03-01/}"
BUCKET = "bench"
NO_ANSWER = (OSError, http.client.HTTPException)


def warm_key(i: int) -> str:
    """Key of the i-th object the warm-up alone touches."""
    return f"warm/{i:03d}"


def preload_key(i: int) -> str:
    """Key of the i-th preloaded object (a heal mix's backlog, in the
    order the sequence walks it)."""
    return f"obj/{i:05d}"


class TrafficError(Exception):
    """Set-up of the traffic failed; the run cannot be measured."""


@dataclass
class Body:
    data: bytes
    md5: str
    sha256: str


@dataclass
class Op:
    kind: str
    key: str
    size: int                    # user bytes the op moves
    client: int
    due: float                   # when it was due (closed loop: sent)
    sent: float = 0.0
    done: float = 0.0
    ok: bool = False
    wrong: str = ""              # the answer came and said the wrong thing
    error: str = ""              # refused, failed, or never answered
    body: int = -1               # index into the pool of its size
    node: int = 0                # which node of the deployment answered

    @property
    def latency(self) -> float:
        return self.done - self.due


@dataclass
class Window:
    """What the measured window did."""

    t0: float = 0.0
    seconds: float = 0.0
    end: float = 0.0             # t0 + seconds, or the sequence's end
    ops: list[Op] = field(default_factory=list)
    # heal: (time, NumHealed, NumFailed) at every poll, names as reported
    heal_polls: list[tuple[float, int, int]] = field(default_factory=list)
    healed_keys: list[str] = field(default_factory=list)
    heal_object_size: int = 0
    generator_late_s: list[float] = field(default_factory=list)

    def in_window(self) -> list[Op]:
        return [o for o in self.ops if o.ok and o.done <= self.end]


class Load:
    """One cell's traffic against one deployment: `hosts` are its nodes'
    S3 endpoints, node 1 first."""

    def __init__(self, traffic: dict, seed: int, hosts: list[str], root: str,
                 drives: int, say):
        self.t, self.seed, self.hosts, self.root = traffic, seed, hosts, root
        self.drives, self.say = drives, say
        self.kind = traffic["kind"]
        if self.kind not in ("closed_loop", "open_loop", "heal"):
            raise TrafficError(f"unknown loop kind {self.kind!r}")
        self.clients = int(traffic.get("clients", 1))
        self.pools: dict[int, list[Body]] = {}
        self.preloaded: list[tuple[str, int, int]] = []   # key, size, body
        self.wiped: list[int] = []
        self.before_wipe: dict[int, dict[str, str]] = {}
        self.rng = rng_for(seed, 1)

    # --- set-up -----------------------------------------------------------

    def pool(self, size: int) -> list[Body]:
        if size not in self.pools:
            n = int(self.t.get("payload_pool", 16))
            bodies = []
            for i in range(n):
                data = payload(self.seed, f"pool/{size}/{i}", size)
                bodies.append(Body(data, hashlib.md5(data).hexdigest(),
                                   hashlib.sha256(data).hexdigest()))
            self.pools[size] = bodies
        return self.pools[size]

    def make_payloads(self) -> None:
        for op in self.t.get("ops", []):
            if op["op"] == "PUT":
                self.pool(int(op["size"]))
        if self.t.get("preload"):
            self.pool(int(self.t["preload"]["size"]))

    def _put(self, s3: S3, key: str, body: Body) -> None:
        st, hdrs, data = s3.request(
            "PUT", f"/{BUCKET}/{key}", body=body.data,
            headers={"x-amz-storage-class": "STANDARD"},
            payload_hash=body.sha256)
        if st != 200 or hdrs.get("ETag", "").strip('"') != body.md5:
            raise TrafficError(f"set-up PUT {key}: {st} {data[:200]!r}")

    def _fan(self, jobs: list, fn, clients: int) -> None:
        """Run fn(s3, job) over jobs from `clients` threads, job j on a
        connection to node `j mod N`; the first error ends set-up."""
        errors: list[BaseException] = []
        it = enumerate(jobs)
        lock = threading.Lock()

        def work():
            conns = [S3(h) for h in self.hosts]    # opened on first use
            try:
                while not errors:
                    with lock:
                        j, job = next(it, (0, None))
                    if job is None:
                        return
                    fn(conns[j % len(conns)], job)
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                errors.append(exc)
            finally:
                for s3 in conns:
                    s3.close()

        threads = [threading.Thread(target=work, daemon=True)
                   for _ in range(max(1, min(clients, len(jobs))))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]

    def setup(self) -> None:
        """Bucket, warm-up of this cell's own shapes, preload, and what
        the mix injects. All of it is set-up time."""
        s3 = S3(self.hosts[0])
        st, _, data = s3.request("PUT", f"/{BUCKET}")
        if st != 200:
            raise TrafficError(f"make bucket: {st} {data[:200]!r}")
        pre = self.t.get("preload")
        warm_heal = int(self.t.get("warmup_objects", 0))
        if pre:
            size = int(pre["size"])
            bodies = self.pool(size)
            self.preloaded = [(preload_key(i), size, i % len(bodies))
                              for i in range(int(pre["objects"]))]
            jobs = [(warm_key(i), size, i % len(bodies))
                    for i in range(warm_heal)] + self.preloaded
            # one PUT alone first: what compiles, compiles once
            self._put(s3, jobs[0][0], bodies[jobs[0][2]])
            self._fan(jobs[1:],
                      lambda c, j: self._put(c, j[0], bodies[j[2]]),
                      int(pre.get("clients", 8)))
        # warm every op the window will send, once alone (on every node
        # in turn: each process compiles, or loads, its own programs) and
        # then from all clients at once
        rounds = int(self.t.get("warmup_ops_per_client", 0))
        puts = [op for op in self.t.get("ops", []) if op["op"] == "PUT"]
        if rounds and puts:
            for n, host in enumerate(self.hosts, 1):
                conn = s3 if n == 1 else S3(host)
                for op in puts:
                    self._put(conn, f"warm/first-{op['size']}"
                              + ("" if n == 1 else f"-n{n}"),
                              self.pool(int(op["size"]))[0])
                if n > 1:
                    conn.close()
            jobs = [(f"warm/c{c:02d}-{r}-{op['size']}", int(op["size"]))
                    for r in range(rounds) for c in range(self.clients)
                    for op in puts]
            self._fan(jobs,
                      lambda c, j: self._put(c, j[0], self.pool(j[1])[0]),
                      self.clients)
        s3.close()
        n_wipe = int(self.t.get("wipe_drives", 0))
        if n_wipe:
            self.wiped = draw_wiped(self.rng, self.drives, n_wipe)
            for d in self.wiped:
                self.before_wipe[d] = shard_file_hashes(self.drive(d))
                base = os.path.join(self.drive(d), BUCKET)
                for e in os.listdir(base):
                    shutil.rmtree(os.path.join(base, e))
            self.say(f"wiped the bucket on drives {self.wiped} under the "
                     "running server")
        if self.kind == "heal" and warm_heal:
            st = self._heal_prefix("warm/", deadline_s=600)
            if st["Summary"] != "finished" or st["NumFailed"]:
                raise TrafficError(f"warm-up heal: {st['Summary']} "
                                   f"failed={st['NumFailed']}")

    def drive(self, d: int) -> str:
        return os.path.join(self.root, f"d{d}")

    def _admin(self) -> AdminClient:
        return AdminClient(self.hosts[0], ACCESS, SECRET, timeout=120.0)

    def _heal_prefix(self, prefix: str, deadline_s: float) -> dict:
        adm = self._admin()
        token = adm.heal(BUCKET, prefix)["clientToken"]
        t0 = time.monotonic()
        while True:
            st = adm.heal_status(BUCKET, prefix, token)
            if st["Summary"] != "running":
                return st
            if time.monotonic() - t0 > deadline_s:
                raise TrafficError(f"heal of {prefix} not done after "
                                   f"{deadline_s:.0f}s")
            time.sleep(0.2)

    # --- the window -------------------------------------------------------

    def run_window(self, seconds: float, on_start=None) -> Window:
        if self.kind == "heal":
            return self._heal_window(seconds, on_start)
        return self._request_window(seconds, on_start)

    def _heal_window(self, seconds: float, on_start) -> Window:
        adm = self._admin()
        poll_s = float(self.t.get("poll_s", 0.5))
        size = int(self.t["preload"]["size"])
        win = Window(seconds=seconds, heal_object_size=size)
        prefix = "obj/"

        def read_status() -> dict:
            # a reading is stamped when it was asked for: the count is
            # what had been healed by then, or an instant later
            t = time.monotonic()
            st = adm.heal_status(BUCKET, prefix, token)
            win.heal_polls.append((t, int(st["NumHealed"]),
                                   int(st["NumFailed"])))
            win.healed_keys += [i["object"] for i in st.get("Items", [])
                                if i.get("detail") == "healed"]
            return st

        win.t0 = time.monotonic()
        if on_start:
            on_start(win.t0)
        token = adm.heal(BUCKET, prefix)["clientToken"]
        close = win.t0 + seconds
        win.heal_polls.append((win.t0, 0, 0))
        while True:
            wait = min(poll_s, close - time.monotonic())
            if wait > 0:
                time.sleep(wait)
            status = read_status()
            if (status["Summary"] != "running"
                    or win.heal_polls[-1][0] >= close - 1e-4):
                break
        # the rate's seconds end with the last reading: at the close, or
        # where the backlog ran dry before it
        win.end = win.heal_polls[-1][0]
        # Readings after the close are not the rate's seconds. They say
        # when the object in work at the close was done, which is waited
        # for, a minute if need be (the rate credits the share of its time
        # that lay in the window), and the check compares what they report
        # healed too.
        at_close = win.heal_polls[-1][1]
        st = status
        while (st["Summary"] == "running"
               and win.heal_polls[-1][1] <= at_close
               and time.monotonic() - close < 60):
            time.sleep(poll_s)
            st = read_status()
        # then the sequence is stopped and waited for until it is still,
        # so that the check reads files nobody is writing
        if st["Summary"] == "running":
            try:
                adm.heal_stop(BUCKET, prefix)
            except AdminError:
                pass
            t_stop = time.monotonic()
            while time.monotonic() - t_stop < 60:
                try:
                    st = read_status()
                except AdminError:
                    break
                if st["Summary"] != "running":
                    break
                time.sleep(0.2)
        if status["Summary"] == "failed":
            raise TrafficError(f"the heal sequence failed: "
                               f"{status.get('FailureDetail')}")
        return win

    def _plan(self, client: int, n: int) -> list[dict]:
        """The client's first n draws from the op mix. Each seed permutes
        one fixed multiset, so every seed sends the same mix."""
        ops = self.t["ops"]
        weights = np.array([float(o.get("weight", 1)) for o in ops])
        counts = np.floor(weights / weights.sum() * n).astype(int)
        counts[0] += n - counts.sum()
        idx = np.repeat(np.arange(len(ops)), counts)
        rng = rng_for(self.seed, 2, client)
        rng.shuffle(idx)
        return [ops[i] for i in idx]

    def _request_window(self, seconds: float, on_start) -> Window:
        win = Window(seconds=seconds)
        lock = threading.Lock()
        start = threading.Barrier(self.clients + 1)
        open_loop = self.kind == "open_loop"
        due: list[float] = []
        next_due = [0]
        if open_loop:
            rate = float(self.t["rate_per_s"])
            n = int(rate * seconds)
            gaps = rng_for(self.seed, 3).exponential(1.0 / rate, size=n)
            due = list(np.cumsum(gaps) * (seconds / max(gaps.sum(), 1e-9))
                       * (n / (n + 1)))
        plans = [self._plan(c, 4096) for c in range(self.clients)]
        stop = threading.Event()

        def client(c: int):
            s3 = S3(self.hosts[c % len(self.hosts)])
            rng = rng_for(self.seed, 4, c)
            own: list[tuple[str, int, int]] = []       # its PUTs, to DELETE
            once = self.preloaded[c::self.clients]     # its share, each once
            rng.shuffle(once)
            mine: list[Op] = []
            n = 0
            start.wait()
            try:
                while not stop.is_set():
                    now = time.monotonic()
                    if open_loop:
                        with lock:
                            i = next_due[0]
                            next_due[0] += 1
                        if i >= len(due):
                            break
                        t_due = win.t0 + due[i]
                        if t_due > now:
                            if stop.wait(t_due - now):
                                break
                        spec = plans[c][n % len(plans[c])]
                    else:
                        if now >= win.t0 + seconds:
                            break
                        t_due = None
                        spec = plans[c][n % len(plans[c])]
                    op = self._one(s3, spec, c, n, rng, own, once, t_due)
                    n += 1
                    if op is not None:
                        mine.append(op)
            finally:
                s3.close()
                with lock:
                    win.ops += mine

        threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                    name=f"client-{c}")
                   for c in range(self.clients)]
        for th in threads:
            th.start()
        win.t0 = time.monotonic() + 0.05
        win.end = win.t0 + seconds
        if on_start:
            on_start(win.t0)
        time.sleep(max(0.0, win.t0 - time.monotonic()))
        start.wait()
        # requests in flight when the window closes are waited for: an
        # answer that comes late is late, not wrong
        for th in threads:
            th.join(timeout=seconds + 120)
        stop.set()
        for th in threads:
            th.join(timeout=60)
            if th.is_alive():
                raise TrafficError(f"{th.name} never came back")
        win.generator_late_s = [o.sent - o.due for o in win.ops] \
            if open_loop else []
        return win

    def _one(self, s3: S3, spec: dict, c: int, n: int, rng, own, once,
             t_due) -> Op | None:
        kind = spec["op"]
        body = None
        if kind == "PUT":
            size = int(spec["size"])
            pool = self.pool(size)
            bi = int(rng.integers(len(pool)))
            body = pool[bi]
            op = Op(kind, f"w/c{c:02d}/{n:06d}", size, c, 0.0, body=bi)
        elif kind in ("GET", "STAT"):
            if spec.get("keys") == "each_once":
                if not once:
                    # its share is read: the chance passes, and with a
                    # pause, so that a client left with nothing but such
                    # chances does not spin (no accepted cell comes here:
                    # their preloads outlast their windows)
                    time.sleep(0.05)
                    return None
                key, size, bi = once.pop()
            else:
                src = self.preloaded
                if not src:
                    return None
                key, size, bi = src[int(rng.integers(len(src)))]
            op = Op(kind, key, size if kind == "GET" else 0, c, 0.0, body=bi)
        elif kind == "DELETE":
            if own:
                key, size, bi = own.pop()
            else:
                return None
            op = Op(kind, key, 0, c, 0.0, body=bi)
        elif kind == "LIST":
            op = Op(kind, "obj/", 0, c, 0.0)
        else:
            raise TrafficError(f"unknown op kind {kind!r}")
        op.node = c % len(self.hosts)
        op.sent = time.monotonic()
        op.due = op.sent if t_due is None else t_due
        try:
            if kind == "PUT":
                st, hdrs, data = s3.request(
                    "PUT", f"/{BUCKET}/{op.key}", body=body.data,
                    headers={"x-amz-storage-class": "STANDARD"},
                    payload_hash=body.sha256)
                op.done = time.monotonic()
                if st != 200:
                    op.error = f"{st} {data[:120]!r}"
                elif hdrs.get("ETag", "").strip('"') != body.md5:
                    op.wrong = f"ETag {hdrs.get('ETag')} is not the md5"
                else:
                    own.append((op.key, op.size, op.body))
            elif kind == "GET":
                st, _, data = s3.request("GET", f"/{BUCKET}/{op.key}")
                op.done = time.monotonic()
                want = self.pool(op.size)[op.body]
                if st != 200:
                    op.error = f"{st} {data[:120]!r}"
                elif (len(data) != op.size
                      or hashlib.md5(data).hexdigest() != want.md5):
                    op.wrong = "the body differs from what was PUT"
            elif kind == "STAT":
                st, hdrs, _ = s3.request("HEAD", f"/{BUCKET}/{op.key}")
                op.done = time.monotonic()
                if st != 200:
                    op.error = f"{st}"
                elif hdrs.get("ETag", "").strip('"') != \
                        self.pool(size)[op.body].md5:
                    op.wrong = "HEAD's ETag is not the md5"
            elif kind == "DELETE":
                st, _, data = s3.request("DELETE", f"/{BUCKET}/{op.key}")
                op.done = time.monotonic()
                if st != 204:
                    op.error = f"{st} {data[:120]!r}"
            elif kind == "LIST":
                st, _, data = s3.request("GET", f"/{BUCKET}", query=[
                    ("list-type", "2"), ("prefix", op.key),
                    ("max-keys", "1000")])
                op.done = time.monotonic()
                if st != 200:
                    op.error = f"{st} {data[:120]!r}"
                else:
                    keys = [e.text for e in ET.fromstring(data).iter(
                        f"{NS}Key")]
                    want = [p[0] for p in self.preloaded][:1000]
                    if keys != want:
                        op.wrong = (f"LIST gave {len(keys)} keys, "
                                    f"{len(want)} are there")
        except NO_ANSWER as exc:
            op.done = time.monotonic()
            op.error = f"{type(exc).__name__}: {exc}"
        op.ok = not op.error and not op.wrong
        if op.error:
            time.sleep(0.05)        # a server that refuses is not hammered
        return op


def draw_wiped(rng, drives: int, n: int) -> list[int]:
    """n of the drives (1-based), drawn from the seed. A set that a turn
    of the ring of drives by fewer than all maps onto itself (two drives
    half the ring apart) is drawn again: objects' shards are placed by
    turns of that ring, so such a set has fewer distinct failure
    patterns than the others (8 for 16), and a seed that drew it would
    pay half the warm-up. Every seed does the same work."""
    while True:
        picked = {int(d) for d in rng.choice(drives, size=n, replace=False)}
        if all({(d + turn) % drives for d in picked} != picked
               for turn in range(1, drives)):
            return sorted(d + 1 for d in picked)


def shard_file_hashes(drive_dir: str) -> dict[str, str]:
    """sha256 of every shard file (part.N) under the bucket on a drive,
    keyed by path relative to the bucket."""
    base = os.path.join(drive_dir, BUCKET)
    out = {}
    for dirpath, _, files in os.walk(base):
        for name in files:
            if name.startswith("part."):
                p = os.path.join(dirpath, name)
                with open(p, "rb") as f:
                    out[os.path.relpath(p, base)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out
