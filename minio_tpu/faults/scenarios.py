"""Composable mixed-workload chaos scenarios with invariant verification
— the production scenario gate (ROADMAP item 5).

Every subsystem is proven in isolation; nothing before this exercised
the COMBINATION a deployment sees: concurrent PUT / GET / degraded-GET /
heal / list / parallel-multipart / lifecycle-expiry / versioned-delete
clients driving the real S3 handlers while drive faults, process faults
and network faults fire underneath. Three composable planes:

- **workload** — `scenario_plan()` derives per-client op streams purely
  from the seed: op kinds, keys, payload sizes and multipart shapes are
  a deterministic function of (seed, client). Clients execute their
  stream concurrently over signed HTTP against a real `S3Server`.
- **faults** — the same plan composes (a) seeded `FaultSchedule` drive
  faults (latency / error / hang / bitrot) armed on a subset of drives,
  (b) process faults: encode-worker kill -9 (the pool must fall back
  byte-identically and respawn) and, via `crash_restart_put`, a whole-
  server SIGKILL mid-PUT with restart recovery verification, and
  (c) network faults: a storage-REST peer blackout (the peer's RPC
  plane stops for a blip and comes back; the rest-layer retry plus
  probe re-admission must ride it out).
- **invariants** — a library of named checks run continuously during
  the soak and strictly at drain: no data loss at quorum, MRF drains
  dry, every shared buffer/shm pool settles to in_use == 0, zero
  lock-order cycles (when the lockgraph checker is armed), no orphaned
  worker processes, admission conservation (grants + rejections ==
  arrivals), and byte-flow ledger reconciliation (put writes ==
  (k+m)/k x payload within framing tolerance; heal read/healed within
  [k/m, k] — the dense-RS bounds of arXiv 1412.3022) that must hold
  even when ops fail mid-stream.

Determinism contract: same seed => same plan => same composed fault
sequence (drive schedules + ordered process/network events) and same
client op streams. Thread interleaving stays the OS's; the REPLAY unit
is the plan, embedded verbatim in every result artifact (docs/SOAK.md).

`pytest -m soak` is the tier-2 gate built on this engine
(tests/test_chaos_soak.py); tests/test_scenarios.py holds the tier-1
determinism/invariant proofs.
"""

from __future__ import annotations

import http.client
import json
import os
import random
import signal
import sys
import threading
import time
import urllib.parse

MIB = 1 << 20

# Workload op classes (the scenario grammar's vocabulary).
OP_PUT = "put"
OP_GET = "get"
OP_GET_DEGRADED = "get-degraded"
OP_HEAL = "heal"
OP_LIST = "list"
OP_MULTIPART = "multipart"
OP_LIFECYCLE = "lifecycle"
OP_VERSIONED = "versioned-delete"

ALL_OPS = (OP_PUT, OP_GET, OP_GET_DEGRADED, OP_HEAL, OP_LIST,
           OP_MULTIPART, OP_LIFECYCLE, OP_VERSIONED)

DEFAULT_WEIGHTS = {
    OP_PUT: 4, OP_GET: 3, OP_GET_DEGRADED: 1, OP_HEAL: 1, OP_LIST: 1,
    OP_MULTIPART: 1, OP_LIFECYCLE: 1, OP_VERSIONED: 1,
}

# Buckets the harness provisions: plain, versioned, lifecycle-expiry.
BUCKET = "soak"
BUCKET_VER = "soak-ver"
BUCKET_EXP = "soak-exp"


def _soak_codecs() -> tuple:
    """Registered codec ids, registration order (stable). Every
    PUT-like op draws one deterministically, so a single soak bucket
    interleaves objects written under every codec and the drain
    invariants are verified ACROSS codec boundaries (ISSUE 16), not
    once per homogeneous bucket."""
    from ..erasure import registry

    return registry.codec_ids()


def _codec_headers(op: dict) -> dict | None:
    """x-mtpu-codec header for the op's planned codec (None for plans
    recorded before codecs existed — replay compatibility)."""
    cid = op.get("codec")
    return {"x-mtpu-codec": cid} if cid else None

ACCESS, SECRET = "soakadmin", "soakadmin-secret-key"

# Per-op stall bound: deadline + straggler grace + generous compute
# slack on a loaded CI host (the hung-drive tolerance bound, never the
# fault duration — injected hangs cap at MAX_HANG_S=120) — same
# contract as the original chaos soak. The slack absorbs CPU
# starvation on oversubscribed 1-core CI hosts, which is weather, not
# a wedge; a real deadlock still blows through it by an order of
# magnitude.
STALL_SLACK_S = 20.0


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


class ScenarioSpec:
    """One scenario's full configuration. Everything that shapes the
    run is HERE (and therefore in the plan/artifact) — reconstructing
    the spec from a failure artifact reproduces the scenario."""

    def __init__(self, seed: int | None = None, clients: int | None = None,
                 ops_per_client: int | None = None, disks: int = 8,
                 parity: int | None = None,
                 payload_sizes: tuple = (64 << 10, 256 << 10, MIB),
                 op_weights: dict | None = None,
                 fault_drives: int = 2,
                 worker_kills: int = 1,
                 peer_blackouts: int = 0,
                 remote_disks: int = 0,
                 blip_s: float = 1.0,
                 admission_slots: int = 0,
                 lock_check: bool = True,
                 op_deadline_s: float = 2.0,
                 straggler_grace_s: float = 0.2,
                 hot_keys: int = 16,
                 zipf_s: float | None = None,
                 hot_gets: float = 0.5,
                 hang_drives: int = 1,
                 hang_hold_s: float | None = None):
        # Env-tunable so operators replay a failing seed without
        # editing tests (docs/SOAK.md seed-replay workflow).
        self.seed = seed if seed is not None else _env_int(
            "MTPU_SOAK_SEED", 1337)
        self.clients = clients if clients is not None else _env_int(
            "MTPU_SOAK_CLIENTS", 8)
        self.ops_per_client = (ops_per_client if ops_per_client is not None
                               else _env_int("MTPU_SOAK_OPS", 10))
        self.disks = disks
        self.parity = parity if parity is not None else disks // 2
        self.payload_sizes = tuple(payload_sizes)
        self.op_weights = dict(op_weights or DEFAULT_WEIGHTS)
        self.fault_drives = min(fault_drives, self.parity)
        self.worker_kills = worker_kills
        self.peer_blackouts = peer_blackouts
        self.remote_disks = remote_disks
        self.blip_s = blip_s
        # 0 = leave the env-derived admission config alone; > 0 pins
        # tight write/read governors so the soak actually queues and
        # 503s under pressure (rejections are LEGAL outcomes the
        # conservation invariant accounts for).
        self.admission_slots = admission_slots
        self.lock_check = lock_check
        # Hung-drive tolerance pins for the run: the per-op stall bound
        # derives from THESE (deadline + grace + compute slack), never
        # from the fault durations.
        self.op_deadline_s = op_deadline_s
        self.straggler_grace_s = straggler_grace_s
        # Closed-loop load-gen shape (ISSUE 17): a shared hot keyspace
        # with zipfian rank popularity that `hot_gets` of plain GETs
        # read, so >= 64 clients contend realistically instead of each
        # reading only its private keys.
        self.hot_keys = hot_keys
        self.zipf_s = zipf_s if zipf_s is not None else _env_float(
            "MTPU_SOAK_ZIPF", 1.1)
        self.hot_gets = hot_gets
        # Bounded hang-kind drive faults armed BY DEFAULT: the first
        # `hang_drives` fault victims each get scripted hang calls that
        # stall hold_s then proceed (an NFS blip), proving the deadline
        # -> detach -> hedge path at soak scale under the stall bound.
        self.hang_drives = min(hang_drives, self.fault_drives)
        self.hang_hold_s = (hang_hold_s if hang_hold_s is not None
                            else 2 * op_deadline_s)

    def to_dict(self) -> dict:
        return {k: (list(v) if isinstance(v, tuple) else v)
                for k, v in vars(self).items()}


# ---------------------------------------------------------------------------
# plan: pure function of the spec (the determinism unit)


def client_stream(spec: ScenarioSpec, client: int) -> list[dict]:
    """Client `client`'s deterministic op stream. Op kinds/keys/sizes
    derive only from (seed, client); runtime choices that depend on
    earlier SUCCESSES (which committed object a GET re-reads) use the
    stream's own `pick` ordinal against the client's committed list, so
    two runs with identical outcomes choose identically."""
    rng = random.Random(spec.seed * 7919 + client)
    # Zipf draws come from a DERIVED stream so the fields the original
    # grammar planned stay byte-identical for a given seed — the new
    # hot-key fields are only ADDED (plan-replay compatibility).
    zrng = random.Random(spec.seed * 104729 + client)
    kinds = sorted(spec.op_weights)
    weights = [spec.op_weights[k] for k in kinds]
    ops: list[dict] = []
    for n in range(spec.ops_per_client):
        kind = rng.choices(kinds, weights=weights)[0]
        op: dict = {"op": kind, "n": n}
        if kind in (OP_PUT, OP_MULTIPART, OP_LIFECYCLE, OP_VERSIONED):
            op["size"] = rng.choice(spec.payload_sizes)
            op["pseed"] = rng.randrange(1 << 30)
            op["codec"] = rng.choice(_soak_codecs())
        if kind == OP_PUT:
            op["key"] = f"c{client}/o{n:03d}"
        elif kind == OP_MULTIPART:
            op["key"] = f"c{client}/mp{n:03d}"
            op["parts"] = rng.choice((2, 3))
        elif kind == OP_LIFECYCLE:
            op["key"] = f"exp/c{client}/e{n:03d}"
        elif kind == OP_VERSIONED:
            op["key"] = f"c{client}/v{n:03d}"
            # overwrite -> marker -> versioned delete of v1 (each step
            # independently allowed to fail under faults).
            op["steps"] = rng.choice((
                ("put", "put", "marker"),
                ("put", "put", "delete-oldest"),
                ("put", "marker"),
            ))
        elif kind in (OP_GET, OP_GET_DEGRADED, OP_HEAL):
            op["pick"] = rng.randrange(1 << 16)
            if kind == OP_GET and spec.hot_keys and \
                    zrng.random() < spec.hot_gets:
                # Zipfian rank over the shared hot keyspace: rank r
                # drawn with P(r) proportional to (r+1)^-s.
                op["hot"] = _zipf_rank(zrng, spec.hot_keys, spec.zipf_s)
        elif kind == OP_LIST:
            op["prefix"] = f"c{client}/"
        ops.append(op)
    return ops


def _zipf_rank(rng: random.Random, n: int, s: float) -> int:
    """One zipfian rank draw in [0, n): inverse-CDF over the n ranks
    with P(r) proportional to (r+1)^-s. O(n) per draw — the hot
    keyspace is small by design (tens of keys, not the namespace)."""
    weights = [(r + 1) ** -s for r in range(n)]
    x = rng.random() * sum(weights)
    for r, w in enumerate(weights):
        x -= w
        if x <= 0:
            return r
    return n - 1


def build_fault_plan(spec: ScenarioSpec, endpoints: list[str]) -> dict:
    """The composed fault plan, a pure function of (spec, disk
    endpoints): drive schedules for the first `fault_drives` odd-
    indexed endpoints plus the ordered process/network event list,
    keyed by GLOBAL completed-op count. Same seed => same plan."""
    rng = random.Random(spec.seed ^ 0xFA0175)
    total_ops = spec.clients * spec.ops_per_client
    drive_schedules = []
    victims = endpoints[1::2][: spec.fault_drives]
    for i, ep in enumerate(victims):
        specs = [
            {"kind": "latency", "probability": 0.12,
             "latency_s": 0.02},
            {"kind": "latency", "probability": 0.04,
             "latency_s": 0.25},
            {"kind": "error", "probability": 0.04,
             "error": "ErrDiskNotFound"},
            {"kind": "bitrot", "probability": 0.01,
             "ops": ["stream_read"]},
        ]
        if i < spec.hang_drives:
            # Bounded hang (ISSUE 17): the disk stalls hang_hold_s on
            # the scripted call numbers then proceeds — the deadline /
            # straggler-detach / hedge path must resolve the op within
            # the stall bound long before the hold elapses. Scripted
            # (not probabilistic) so a given seed always fires a known
            # number of hangs, and WITHOUT an ops filter: matches()
            # consults the filter before the call number, so a planned
            # call landing on a filtered op would be consumed silently.
            hi = max(40, (3 * total_ops) // 2)
            specs.append({
                "kind": "hang", "hold_s": spec.hang_hold_s,
                "calls": sorted(rng.sample(range(12, hi), 2)),
            })
        drive_schedules.append((ep, {
            "seed": spec.seed * 31 + i,
            "specs": specs,
        }))
    events = []
    for _ in range(spec.worker_kills):
        events.append({"at_op": rng.randrange(1, max(2, total_ops // 2)),
                       "kind": "worker_kill"})
    for _ in range(spec.peer_blackouts):
        events.append({"at_op": rng.randrange(1, max(2, total_ops)),
                       "kind": "peer_blackout", "blip_s": spec.blip_s})
    events.sort(key=lambda e: (e["at_op"], e["kind"]))
    return {"drive_schedules": drive_schedules, "events": events}


def scenario_plan(spec: ScenarioSpec) -> dict:
    """The full deterministic plan: spec + per-client op streams +
    composed fault plan. This is what `same seed => same fault
    sequence` means; the plan embeds verbatim in every artifact."""
    endpoints = [f"soak-d{i}" for i in range(spec.disks)]
    return {
        "spec": spec.to_dict(),
        "endpoints": endpoints,
        "clients": [client_stream(spec, c) for c in range(spec.clients)],
        "faults": build_fault_plan(spec, endpoints),
    }


# ---------------------------------------------------------------------------
# harness: the real stack under test


class ScenarioHarness:
    """Boots the stack a scenario drives: LocalStorage (optionally part
    storage-REST remote) -> FaultDisk -> health-checked MetricsDisk ->
    ErasureSets/Pools -> signed S3Server, plus scanner and governors
    pinned for the run. Restores every process-global it touches."""

    def __init__(self, root: str, spec: ScenarioSpec,
                 notify_targets: dict | None = None):
        from ..storage.diskcheck import robust_overrides

        self.root = root
        self.spec = spec
        self.srv = None
        self.storage_server = None
        self.notify = None
        self._notify_targets = notify_targets
        self._saved_env = {
            k: os.environ.get(k)
            for k in ("MTPU_INLINE_THRESHOLD",)
        }
        # Inline shards ride inside xl.meta (metadata bytes), which
        # would fold payload into the wmeta ledger channel and break
        # the put-write reconciliation invariant; stage everything.
        os.environ["MTPU_INLINE_THRESHOLD"] = "0"
        # Tight hung-drive tolerance for the run (the old chaos soak's
        # envelope): faults must resolve at the TOLERANCE bound, not
        # whenever the injected hang feels like ending.
        self._robust = robust_overrides(
            op_deadline_s=spec.op_deadline_s,
            long_op_deadline_s=spec.op_deadline_s,
            straggler_grace_s=spec.straggler_grace_s,
            hedge_delay_s=0.05, probe_interval_s=0.1,
            breaker_threshold=3,
        )
        self._robust.__enter__()
        try:
            self._boot(spec, root)
        except BaseException:
            # A half-booted harness must not leak its process-global
            # overrides (robust deadlines, inline threshold, a
            # started server) into the rest of the session.
            self.close()
            raise

    def _boot(self, spec: ScenarioSpec, root: str) -> None:
        from ..api import S3Server
        from ..background.scanner import DataScanner
        from ..bucket import BucketMetadataSys
        from ..iam import IAMSys
        from ..object.pools import ErasureServerPools
        from ..object.sets import ErasureSets
        from ..observability import ioflow
        from ..observability.metrics import Metrics
        from ..pipeline import admission
        from ..storage.diskcheck import DiskHealth, MetricsDisk
        from ..storage.local import LocalStorage
        from .injector import FaultDisk

        self.endpoints = [f"soak-d{i}" for i in range(spec.disks)]
        self.raw_disks = [
            LocalStorage(os.path.join(root, ep), endpoint=ep)
            for ep in self.endpoints
        ]
        self.storage_server = None
        self._remote_count = min(spec.remote_disks, spec.parity)
        inner: list = list(self.raw_disks)
        if self._remote_count:
            inner = self._wire_remote(inner)
        self.fault_disks = [FaultDisk(d) for d in inner]
        self.disks = [
            MetricsDisk(fd, health=DiskHealth(ep))
            for fd, ep in zip(self.fault_disks, self.endpoints)
        ]
        self.metrics = Metrics()
        # Span histograms land in THIS run's registry so the result can
        # attribute saturation p99 (admission-wait vs stage-stall vs
        # worker vs disk); close() unhooks.
        from ..observability import spans as _spans

        _spans.set_metrics(self.metrics)
        # Mesh-engine STATS baseline: the mesh_stats_clean invariant
        # judges only THIS scenario's deltas (jax-free import).
        from ..parallel.metrics import STATS as _mesh_stats

        self.mesh_stats0 = dict(_mesh_stats)
        sets = ErasureSets(
            self.disks, spec.disks, default_parity=spec.parity,
            deployment_id="50a45047-5047-5047-5047-504750475047",
            pool_index=0,
        )
        sets.init_format()
        self.sets = sets
        self.ol = ErasureServerPools([sets])
        self.iam = IAMSys(ACCESS, SECRET)
        self.bm = BucketMetadataSys(self.ol)
        self.scanner = DataScanner(self.ol, self.bm, metrics=self.metrics)
        if self._notify_targets:
            from ..event.system import EventNotifier

            self.notify = EventNotifier(self.bm,
                                        targets=self._notify_targets,
                                        metrics=self.metrics)
        self.srv = S3Server(self.ol, self.iam, self.bm,
                            notify=self.notify,
                            metrics=self.metrics).start()
        # Pin the admission planes when the spec asks for pressure; the
        # governors are process-global, so always swap in FRESH ones —
        # the conservation invariant then counts only this scenario.
        # Queue deadlines stay WELL under the per-op stall bound
        # (deadline + grace + STALL_SLACK_S): an admission wait that
        # rides its full deadline plus the op's own execution must
        # still not read as a stall — queueing is intended behavior,
        # the stall bound hunts wedges.
        cfg = None
        if spec.admission_slots:
            cfg = admission.AdmissionConfig(
                slots=spec.admission_slots,
                per_client_cap=spec.admission_slots,
                max_queue=4 * spec.admission_slots, deadline_s=5.0,
            )
        self.governor = admission.reconfigure(cfg)
        self.read_governor = admission.reconfigure_read(
            admission.AdmissionConfig(
                slots=spec.admission_slots * 2,
                per_client_cap=spec.admission_slots * 2,
                max_queue=8 * spec.admission_slots, deadline_s=5.0,
            ) if spec.admission_slots else None
        )
        ioflow.reset()
        self._provision()

    def _wire_remote(self, disks: list) -> list:
        """Serve the LAST `remote_disks` drives through a real
        storage-REST plane (loopback), so peer-blackout events sever a
        live RPC path, not a mock."""
        from ..distributed.storage_rest import (
            RemoteStorage,
            StorageRESTServer,
        )

        n = self._remote_count
        self._remote_raw = disks[-n:]
        self.storage_server = StorageRESTServer(
            self._remote_raw, SECRET, "127.0.0.1", 0
        ).start()
        self._storage_port = self.storage_server.rpc.port
        node = f"127.0.0.1:{self._storage_port}"
        out = list(disks[:-n])
        for d in self._remote_raw:
            out.append(RemoteStorage(node, d.endpoint(), SECRET,
                                     timeout=10.0))
        return out

    def blackout_peer(self, blip_s: float) -> None:
        """Stop the storage-REST plane, wait the blip, bring it back on
        the SAME port (re-admission is the clients' probe + the rest
        retry's job)."""
        from ..distributed.storage_rest import StorageRESTServer

        srv = self.storage_server
        if srv is None:
            return
        srv.stop()
        time.sleep(blip_s)
        self.storage_server = StorageRESTServer(
            self._remote_raw, SECRET, "127.0.0.1", self._storage_port
        ).start()

    def _provision(self) -> None:
        for b in (BUCKET, BUCKET_VER, BUCKET_EXP):
            st, _, _ = self.request("PUT", f"/{b}")
            assert st == 200, f"make_bucket {b}: {st}"
        st, _, _ = self.request(
            "PUT", f"/{BUCKET_VER}", query=[("versioning", "")],
            body=(b"<VersioningConfiguration><Status>Enabled</Status>"
                  b"</VersioningConfiguration>"),
        )
        assert st == 200, f"versioning: {st}"
        # Already-due Date rule on the exp/ prefix: every lifecycle-op
        # object expires at the drain scan cycle.
        lc = (b'<LifecycleConfiguration><Rule><ID>soak-exp</ID>'
              b'<Status>Enabled</Status><Filter><Prefix>exp/</Prefix>'
              b'</Filter><Expiration><Date>2001-01-01T00:00:00Z</Date>'
              b'</Expiration></Rule></LifecycleConfiguration>')
        st, _, _ = self.request("PUT", f"/{BUCKET_EXP}",
                                query=[("lifecycle", "")], body=lc)
        assert st == 200, f"lifecycle: {st}"
        # Shared hot keyspace (ISSUE 17): seeded AFTER ioflow.reset()
        # so the ledger prices them like any other put; bodies kept so
        # hot GETs verify byte-identity and run_scenario registers
        # them with the no-loss oracle.
        self.hot_bodies: dict[str, bytes] = {}
        codecs = _soak_codecs()
        for i in range(self.spec.hot_keys):
            key = f"hot/o{i:04d}"
            body = _payload(self.spec.seed * 65537 + i, 64 << 10)
            st, _, _ = self.request(
                "PUT", f"/{BUCKET}/{key}", body=body,
                headers={"x-mtpu-codec": codecs[i % len(codecs)]},
            )
            assert st == 200, f"hot seed {key}: {st}"
            self.hot_bodies[key] = body

    # -- signed HTTP client -------------------------------------------------

    def request(self, method: str, path: str, query=None, body=b"",
                headers=None, timeout: float = 120.0):
        from ..api.sign import sign_v4_request

        query = query or []
        qs = urllib.parse.urlencode(query)
        url = urllib.parse.quote(path) + (f"?{qs}" if qs else "")
        h = sign_v4_request(SECRET, ACCESS, method, self.srv.endpoint,
                            path, query, dict(headers or {}), body)
        conn = http.client.HTTPConnection(self.srv.endpoint,
                                          timeout=timeout)
        try:
            conn.request(method, url, body=body, headers=h)
            resp = conn.getresponse()
            data = resp.read()
            return resp.status, dict(resp.getheaders()), data
        finally:
            conn.close()

    # -- fault backdoors (below the S3 surface, above nothing) --------------

    def kill_data_shard(self, bucket: str, obj: str) -> str | None:
        """Remove ONE data-shard part file of a committed object via
        the raw disks (below the fault layer) — the deterministic
        degraded-GET trigger. Returns the endpoint hit, or None when
        no killable local shard exists."""
        for d in self.raw_disks[: len(self.raw_disks)
                                - self._remote_count]:
            try:
                fi = d.read_version(bucket, obj)
            except Exception:  # noqa: BLE001  # except-ok: disks without a copy of this object are simply not kill candidates
                continue
            if not fi.data_dir or fi.erasure.index - 1 >= \
                    fi.erasure.data_blocks:
                continue
            part = os.path.join(self.root, d.endpoint(), bucket, obj,
                                fi.data_dir, "part.1")
            try:
                os.remove(part)
            except OSError:
                continue
            return d.endpoint()
        return None

    # -- drain + teardown ---------------------------------------------------

    def drain_mrf(self, deadline_s: float = 45.0) -> int:
        """Heal the MRF backlog dry (bounded): entries that fail heal
        re-queue with their original timestamp and retry until the
        deadline; not-found entries are DROPPED as satisfied — the
        production MRF drain's convention (a version the quorum deleted
        vanishes from the straggler too; there is nothing left to
        repair). Returns entries left (0 == dry)."""
        from ..utils.errors import (
            ErrFileNotFound,
            ErrFileVersionNotFound,
            ErrObjectNotFound,
            ErrVersionNotFound,
        )

        deadline = time.monotonic() + deadline_s
        left = 0
        while time.monotonic() < deadline:
            entries = []
            for pool in self.ol.pools:
                for es in pool.sets:
                    entries.extend(
                        (es, b, o, v, t)
                        for b, o, v, t in es.drain_mrf(with_times=True)
                    )
            if not entries:
                return 0
            left = len(entries)
            for es, b, o, v, t in entries:
                try:
                    self.ol.heal_object(b, o, v, remove_dangling=True)
                except (ErrFileNotFound, ErrFileVersionNotFound,
                        ErrObjectNotFound, ErrVersionNotFound):
                    continue  # gone everywhere: the heal is satisfied
                except Exception:  # noqa: BLE001  # except-ok: failed heals RE-QUEUE with their original timestamp and retry until the drain deadline
                    es.queue_mrf(b, o, v, enqueued_at=t)
            time.sleep(0.05)
        return left

    def wait_readmit(self, deadline_s: float = 12.0) -> list[str]:
        """Wait for latched drive breakers to re-admit; returns the
        endpoints still faulty at the deadline."""
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            faulty = [d.health.endpoint for d in self.disks
                      if d.health.is_faulty()]
            if not faulty:
                return []
            time.sleep(0.05)
        return [d.health.endpoint for d in self.disks
                if d.health.is_faulty()]

    def close(self) -> None:
        """Unwind everything __init__/_boot touched. Safe on a
        half-booted harness (boot failure calls this too)."""
        from ..observability import spans as _spans
        from ..pipeline import admission

        try:
            if self.srv is not None:
                self.srv.stop()
        finally:
            if self.notify is not None:
                self.notify.close()
            if self.storage_server is not None:
                self.storage_server.stop()
            _spans.set_metrics(None)
            admission.reconfigure(None)
            admission.reconfigure_read(None)
            self._robust.__exit__(None, None, None)
            for k, v in self._saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


# ---------------------------------------------------------------------------
# workload execution


class _Oracle:
    """What the scenario PROVED committed: the no-loss invariant's
    ground truth. Per-client keyspaces keep it race-free (clients are
    sequential within themselves)."""

    def __init__(self):
        self.objects: dict[tuple, bytes] = {}   # (bucket,key) -> body
        self.versions: dict[tuple, list] = {}   # (bucket,key) -> [(vid, body)]
        self.markers: set = set()               # (bucket,key) with marker
        self.expiring: dict[tuple, bytes] = {}  # lifecycle-doomed objects
        self.degraded: set = set()              # shard-killed, heal pending
        # Payload of versions DELETED mid-run: their commit-fanout
        # shortfall (if any) is legitimately never healed, so the
        # full-redundancy reconciliation discounts it.
        self.deleted_payload = 0
        self._mu = threading.Lock()

    def commit(self, bucket: str, key: str, body: bytes) -> None:
        with self._mu:
            self.objects[(bucket, key)] = body

    def committed_keys(self, client: int) -> list:
        pre = f"c{client}/"
        with self._mu:
            return sorted(k for (b, k) in self.objects
                          if b == BUCKET and k.startswith(pre))


def _payload(seed: int, size: int) -> bytes:
    return random.Random(seed).randbytes(size)


def _pctl(sorted_samples: list[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted sample list."""
    if not sorted_samples:
        return 0.0
    idx = min(len(sorted_samples) - 1,
              int(q * (len(sorted_samples) - 1) + 0.5))
    return sorted_samples[idx]


class _LatencyBoard:
    """Per-op-class client latencies for the closed-loop load gen: the
    stall_bounded invariant scans it at drain, the artifact reports
    p50/p99 per class."""

    def __init__(self):
        self._mu = threading.Lock()
        self._samples: dict[str, list[float]] = {}  # guarded-by: _mu

    def note(self, kind: str, seconds: float) -> None:
        with self._mu:
            self._samples.setdefault(kind, []).append(seconds)

    def over(self, bound_s: float) -> list[tuple[str, float]]:
        with self._mu:
            return [(k, t) for k, ss in sorted(self._samples.items())
                    for t in ss if t > bound_s]

    def summary(self) -> dict:
        with self._mu:
            snap = {k: sorted(v) for k, v in self._samples.items()}
        out = {
            k: {"count": len(ss), "p50_s": round(_pctl(ss, 0.50), 4),
                "p99_s": round(_pctl(ss, 0.99), 4),
                "max_s": round(ss[-1], 4)}
            for k, ss in sorted(snap.items())
        }
        allv = sorted(t for ss in snap.values() for t in ss)
        if allv:
            out["all"] = {"count": len(allv),
                          "p50_s": round(_pctl(allv, 0.50), 4),
                          "p99_s": round(_pctl(allv, 0.99), 4),
                          "max_s": round(allv[-1], 4)}
        return out


class _Composer:
    """Fires the plan's process/network events as the global completed-
    op counter crosses their trigger points."""

    def __init__(self, harness: ScenarioHarness, events: list[dict],
                 log: list):
        self._h = harness
        self._pending = sorted(events, key=lambda e: e["at_op"])
        self._log = log
        self._ops = 0
        self._mu = threading.Lock()
        self._threads: list[threading.Thread] = []

    def op_done(self) -> None:
        with self._mu:
            self._ops += 1
            due, keep = [], []
            for e in self._pending:
                (due if e["at_op"] <= self._ops else keep).append(e)
            self._pending = keep
            at = self._ops
        for e in due:
            self._fire(e, at)

    def _fire(self, event: dict, at: int) -> None:
        entry = dict(event, fired_at_op=at)
        if event["kind"] == "worker_kill":
            entry["pid"] = self._kill_worker()
        elif event["kind"] == "peer_blackout":
            t = threading.Thread(
                target=self._h.blackout_peer,
                args=(event.get("blip_s", 1.0),),
                name="soak-blackout", daemon=True,
            )
            t.start()
            self._threads.append(t)
        self._log.append(entry)

    def _kill_worker(self) -> int | None:
        from ..pipeline import workers

        pool = workers.get_pool()
        if pool is None:
            return None  # 1-core / sandboxed host: pool inert by design
        pids = pool.live_pids()
        if not pids:
            return None
        os.kill(pids[0], signal.SIGKILL)
        return pids[0]

    def join(self, timeout_s: float = 30.0) -> None:
        for t in self._threads:
            t.join(timeout_s)


def _run_client(h: ScenarioHarness, oracle: _Oracle, client: int,
                stream: list[dict], composer: _Composer,
                counts: dict, violations: list, stall_bound_s: float):
    """Execute one client's op stream. Failures under faults are LEGAL
    (recorded, not raised); stalls past the tolerance bound and wrong
    bytes are violations."""
    for op in stream:
        t0 = time.monotonic()
        try:
            ok = _run_op(h, oracle, client, op)
        except Exception as exc:  # noqa: BLE001 - op outcome, not crash
            ok = False
            counts.setdefault("errors", []).append(
                f"c{client}/{op['op']}#{op['n']}: "
                f"{type(exc).__name__}: {exc}")
        took = time.monotonic() - t0
        board = getattr(h, "latency", None)
        if board is not None:
            board.note(op["op"], took)
        if took > stall_bound_s:
            violations.append(
                f"stall: c{client} {op['op']}#{op['n']} took "
                f"{took:.1f}s > {stall_bound_s:.1f}s bound")
        with oracle._mu:
            c = counts.setdefault(op["op"], {"ok": 0, "failed": 0})
            c["ok" if ok else "failed"] += 1
        composer.op_done()


def _run_op(h: ScenarioHarness, oracle: _Oracle, client: int,
            op: dict) -> bool:
    kind = op["op"]
    if kind == OP_PUT:
        body = _payload(op["pseed"], op["size"])
        st, _, _ = h.request("PUT", f"/{BUCKET}/{op['key']}", body=body,
                             headers=_codec_headers(op))
        if st == 200:
            oracle.commit(BUCKET, op["key"], body)
        return st == 200
    if kind == OP_GET:
        hot = op.get("hot")
        hot_bodies = getattr(h, "hot_bodies", None)
        if hot is not None and hot_bodies:
            # Zipfian hot read: rank into the SHARED keyspace — this is
            # where >= 64 closed-loop clients actually contend.
            keys = sorted(hot_bodies)
            key = keys[hot % len(keys)]
            st, _, got = h.request("GET", f"/{BUCKET}/{key}")
            if st != 200:
                return False
            if got != hot_bodies[key]:
                raise AssertionError(f"hot GET {key}: bytes differ")
            return True
        keys = oracle.committed_keys(client)
        if not keys:
            return True  # nothing to read yet: vacuous
        key = keys[op["pick"] % len(keys)]
        st, _, got = h.request("GET", f"/{BUCKET}/{key}")
        if st != 200:
            return False
        with oracle._mu:
            want = oracle.objects[(BUCKET, key)]
        if got != want:
            raise AssertionError(f"GET {key}: bytes differ")
        return True
    if kind == OP_GET_DEGRADED:
        keys = [k for k in oracle.committed_keys(client)
                if (BUCKET, k) not in oracle.degraded]
        if not keys:
            return True
        key = keys[op["pick"] % len(keys)]
        if h.kill_data_shard(BUCKET, key) is None:
            return True  # all copies remote/inline: nothing to kill
        with oracle._mu:
            oracle.degraded.add((BUCKET, key))
        st, _, got = h.request("GET", f"/{BUCKET}/{key}")
        if st != 200:
            return False
        with oracle._mu:
            want = oracle.objects[(BUCKET, key)]
        if got != want:
            raise AssertionError(f"degraded GET {key}: bytes differ")
        return True
    if kind == OP_HEAL:
        keys = oracle.committed_keys(client)
        if not keys:
            return True
        key = keys[op["pick"] % len(keys)]
        h.ol.heal_object(BUCKET, key)
        return True
    if kind == OP_LIST:
        st, _, raw = h.request(
            "GET", f"/{BUCKET}",
            query=[("list-type", "2"), ("prefix", op["prefix"]),
                   ("max-keys", "1000")],
        )
        if st != 200:
            return False
        listed = set(_xml_keys(raw))
        missing = [k for k in oracle.committed_keys(client)
                   if k not in listed]
        if missing:
            raise AssertionError(
                f"list {op['prefix']}: committed keys missing: "
                f"{missing[:4]}")
        return True
    if kind == OP_MULTIPART:
        return _run_multipart(h, oracle, op)
    if kind == OP_LIFECYCLE:
        body = _payload(op["pseed"], op["size"])
        st, _, _ = h.request("PUT", f"/{BUCKET_EXP}/{op['key']}",
                             body=body, headers=_codec_headers(op))
        if st == 200:
            with oracle._mu:
                oracle.expiring[(BUCKET_EXP, op["key"])] = body
        return st == 200
    if kind == OP_VERSIONED:
        return _run_versioned(h, oracle, op)
    raise ValueError(f"unknown op {kind}")


def _xml_keys(raw: bytes) -> list[str]:
    import re

    return [m.decode() for m in re.findall(rb"<Key>([^<]+)</Key>", raw)]


def _run_multipart(h: ScenarioHarness, oracle: _Oracle, op: dict) -> bool:
    """Client-side parallel multipart: initiate, upload the parts
    CONCURRENTLY, complete with the collected etags."""
    import re

    key = op["key"]
    body = _payload(op["pseed"], op["size"])
    nparts = op["parts"]
    st, _, raw = h.request("POST", f"/{BUCKET}/{key}",
                           query=[("uploads", "")],
                           headers=_codec_headers(op))
    if st != 200:
        return False
    m = re.search(rb"<UploadId>([^<]+)</UploadId>", raw)
    if not m:
        return False
    upload_id = m.group(1).decode()
    psize = max(1, len(body) // nparts)
    view = memoryview(body)
    etags: list = [None] * nparts
    errs: list = []

    def upload(i: int) -> None:
        lo = i * psize
        hi = len(body) if i == nparts - 1 else (i + 1) * psize
        st_i, hdr, _ = h.request(
            "PUT", f"/{BUCKET}/{key}",
            query=[("partNumber", str(i + 1)), ("uploadId", upload_id)],
            body=bytes(view[lo:hi]),  # copy-ok: meta — HTTP body framing of a test-harness part, not the serving hot path
        )
        if st_i != 200:
            errs.append(st_i)
            return
        etags[i] = hdr.get("ETag", "").strip('"')

    threads = [threading.Thread(target=upload, args=(i,))
               for i in range(nparts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120.0)
    if errs or any(e is None for e in etags):
        h.request("DELETE", f"/{BUCKET}/{key}",
                  query=[("uploadId", upload_id)])
        return False
    parts_xml = "".join(
        f"<Part><PartNumber>{i + 1}</PartNumber><ETag>{e}</ETag></Part>"
        for i, e in enumerate(etags)
    )
    st, _, _ = h.request(
        "POST", f"/{BUCKET}/{key}", query=[("uploadId", upload_id)],
        body=(f"<CompleteMultipartUpload>{parts_xml}"
              f"</CompleteMultipartUpload>").encode(),
    )
    if st != 200:
        return False
    oracle.commit(BUCKET, key, body)
    return True


def _run_versioned(h: ScenarioHarness, oracle: _Oracle, op: dict) -> bool:
    """Versioned overwrite / delete-marker / versioned-delete cycle on
    the versioned bucket; oracle records only what committed."""
    key = op["key"]
    committed: list = []
    ok = True
    for i, step in enumerate(op["steps"]):
        if step == "put":
            body = _payload(op["pseed"] + i, op["size"])
            st, hdr, _ = h.request("PUT", f"/{BUCKET_VER}/{key}",
                                   body=body,
                                   headers=_codec_headers(op))
            if st == 200:
                committed.append((hdr.get("x-amz-version-id", ""), body))
            else:
                ok = False
        elif step == "marker":
            st, _, _ = h.request("DELETE", f"/{BUCKET_VER}/{key}")
            if st in (200, 204):
                with oracle._mu:
                    oracle.markers.add((BUCKET_VER, key))
            else:
                ok = False
        elif step == "delete-oldest" and committed:
            vid, vbody = committed[0]
            if vid:
                st, _, _ = h.request("DELETE", f"/{BUCKET_VER}/{key}",
                                     query=[("versionId", vid)])
                if st in (200, 204):
                    committed.pop(0)
                    with oracle._mu:
                        oracle.deleted_payload += len(vbody)
                else:
                    ok = False
    if committed:
        with oracle._mu:
            oracle.versions[(BUCKET_VER, key)] = committed
    return ok


# ---------------------------------------------------------------------------
# invariants


def inv_no_loss(h: ScenarioHarness, oracle: _Oracle) -> list[str]:
    """Every op that REPORTED success reads back byte-identical —
    plain objects, multipart objects, and surviving versions; delete
    markers hide their key; expired objects are gone."""
    def fetch(path, query=None):
        # A 200 status line followed by a severed body (quorum lost
        # AFTER the header went out) is still a loss — report it as
        # one, not as a checker crash.
        try:
            return h.request("GET", path, query=query)
        except (OSError, http.client.HTTPException) as exc:
            return -1, {}, f"{type(exc).__name__}: {exc}".encode()

    out = []
    for (bucket, key), want in sorted(oracle.objects.items()):
        st, _, got = fetch(f"/{bucket}/{key}")
        if st != 200:
            out.append(f"no-loss: GET {bucket}/{key} -> {st} "
                       f"({got[:80]!r})" if st == -1 else
                       f"no-loss: GET {bucket}/{key} -> {st}")
        elif got != want:
            out.append(f"no-loss: {bucket}/{key} bytes differ "
                       f"({len(got)} vs {len(want)})")
    for (bucket, key), versions in sorted(oracle.versions.items()):
        for vid, want in versions:
            if not vid:
                continue
            st, _, got = fetch(f"/{bucket}/{key}",
                               query=[("versionId", vid)])
            if st != 200:
                out.append(f"no-loss: GET {bucket}/{key}?versionId="
                           f"{vid} -> {st}")
            elif got != want:
                out.append(f"no-loss: version {bucket}/{key}@{vid} "
                           f"bytes differ")
    for (bucket, key) in sorted(oracle.markers):
        st, _, _ = fetch(f"/{bucket}/{key}")
        if st != 404:
            out.append(f"marker: GET {bucket}/{key} -> {st}, want 404")
    return out


def inv_expiry(h: ScenarioHarness, oracle: _Oracle) -> list[str]:
    """Lifecycle-expired objects are GONE and their shard part files
    freed on every disk (expiry must reclaim bytes, not just hide
    keys)."""
    out = []
    for (bucket, key) in sorted(oracle.expiring):
        st, _, _ = h.request("GET", f"/{bucket}/{key}")
        if st != 404:
            out.append(f"expiry: GET {bucket}/{key} -> {st}, want 404")
        for d in h.raw_disks:
            obj_dir = os.path.join(h.root, d.endpoint(), bucket, key)
            if not os.path.isdir(obj_dir):
                continue
            parts = [f for dp, _, fs in os.walk(obj_dir)
                     for f in fs if f.startswith("part.")]
            if parts:
                out.append(f"expiry: {d.endpoint()}/{bucket}/{key} "
                           f"still holds {len(parts)} part file(s)")
    return out


def inv_mrf_dry(h: ScenarioHarness, _oracle) -> list[str]:
    out = []
    for pool in h.ol.pools:
        for es in pool.sets:
            stats = es.mrf_stats()
            if stats["pending"]:
                out.append(f"mrf: set {es.set_index} backlog "
                           f"{stats['pending']} not drained "
                           f"(oldest {stats['oldest_age_s']}s)")
    return out


def inv_pools_settled(_h, _oracle) -> list[str]:
    """Every shared buffer pool — in-process strips AND shm strip/ring
    pools — back to in_use == 0: the executor drop hooks returned every
    abandoned buffer across all the faulted/aborted streams."""
    from ..pipeline.buffers import _shared

    out = []
    for key, pool in sorted(_shared.items(), key=lambda kv: str(kv[0])):
        stats = pool.stats()
        if stats["in_use"]:
            out.append(f"pool {key}: in_use {stats['in_use']} != 0 "
                       f"({stats})")
    return out


def inv_lock_cycles(_h, _oracle) -> list[str]:
    """Zero lock acquisition-order cycles while the runtime lockgraph
    checker was armed (skips silently when tools/ is absent — a
    pip-installed deployment)."""
    try:
        from tools.analysis import lockgraph
    except ImportError:
        return []
    if not lockgraph.enabled():
        return []
    report = lockgraph.report()
    return [f"lock-cycle: {c}" for c in report["cycles"]]


def inv_no_orphan_workers(_h, _oracle) -> list[str]:
    """Every live encode-worker child of THIS process is accounted for
    in the pool registry: a kill -9'd worker must be respawned or
    reaped, never abandoned."""
    from ..pipeline import workers

    # Snapshot /proc BEFORE the registry: a respawn landing between
    # the two reads then shows up registered-but-not-scanned (benign)
    # instead of scanned-but-not-yet-registered (a false orphan).
    children = _worker_children()
    pool = workers.get_pool()
    registered = set(pool.live_pids()) if pool is not None else set()
    out = []
    for pid in children:
        if pid in registered:
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().split()[2]
        except OSError:
            continue  # raced exit: reaped
        if state != "Z":
            out.append(f"orphan worker pid {pid} (state {state})")
    return out


def _worker_children() -> list[int]:
    """PIDs of this process's children running the worker CLI."""
    me = os.getpid()
    out = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                fields = f.read().split()
            if int(fields[3]) != me:
                continue
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                cmd = f.read()
        except (OSError, IndexError, ValueError):
            continue
        if b"minio_tpu.pipeline.workers" in cmd:
            out.append(int(entry))
    return out


def inv_admission_conserved(h: ScenarioHarness, _oracle) -> list[str]:
    """Admission conservation on BOTH governors: every arrival was
    granted or rejected — grants + rejections - late-grant-returns ==
    arrivals (pipeline/admission.py documents the identity)."""
    out = []
    for name, gov in (("put", h.governor), ("get", h.read_governor)):
        s = gov.snapshot()
        lhs = (s["admitted_total"] + s["rejected_queue_full"]
               + s["rejected_deadline"] - s["late_grant_returns"])
        if lhs != s["arrivals_total"]:
            out.append(
                f"admission[{name}]: admitted {s['admitted_total']} + "
                f"rejected {s['rejected_queue_full']}+"
                f"{s['rejected_deadline']} - late "
                f"{s['late_grant_returns']} = {lhs} != arrivals "
                f"{s['arrivals_total']}")
        # A handler whose client already saw its response (or a severed
        # socket) can still be a few instructions from its slot release
        # — and MRF/on-read-heal service threads take slots of their
        # own. Give in-release threads a beat; only a slot that NEVER
        # returns is a leak.
        deadline = time.monotonic() + 2.0
        while (s["inflight"] or s["waiting"]) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
            s = gov.snapshot()
        if s["inflight"] or s["waiting"]:
            out.append(f"admission[{name}]: not drained "
                       f"(inflight {s['inflight']}, waiting "
                       f"{s['waiting']})")
    return out


# Bitrot framing adds 32 bytes per shard chunk; aborted mid-stream PUTs
# stage extra bytes that tmp cleanup removes from disk but not from the
# (monotonic) ledger. Tolerances absorb framing; failures only push the
# written side UP, so the lower bound is strict.
_RECON_TOL = 0.02


def inv_ioflow_reconciles(h: ScenarioHarness, _oracle,
                          counts: dict | None = None) -> list[str]:
    """Byte-flow ledger reconciliation that must hold EVEN when ops
    fail mid-stream:

    - conservation floor: a committed put/multipart stream wrote at
      least write_quorum/k x payload (a quorum commit may detach up to
      m - 1 faulted shard writers; fewer would not have committed);
    - full redundancy at drain: put + multipart + heal writes cover
      (k+m)/k x payload — whatever the commit fan-out missed, the MRF
      drain healed, and every byte of both is in the ledger;
    - the clean-path equality: with ZERO failed ops and ZERO drive-
      fault fires, put writes == (k+m)/k x payload within framing
      tolerance (the arXiv 1412.3022 dense-RS baseline);
    - heal read/healed within the dense-RS bounds [k/m, k];
    - degraded-GET reads >= the payload logically served from them.
    """
    from ..observability import ioflow

    snap = ioflow.snapshot()
    ops = ioflow.op_totals(snap)
    out = []
    k = h.spec.disks - h.spec.parity
    m = h.spec.parity
    factor = (k + m) / k
    write_quorum = k + (1 if k == m else 0)
    quorum_factor = write_quorum / k
    payload = 0
    payload_writes = 0
    clean = not getattr(h, "fault_fired", 0)
    for op_class in ("put", "multipart"):
        logical = snap["logical"].get(op_class, 0)
        written = ops.get(op_class, {}).get("write", 0)
        payload += logical
        payload_writes += written
        if not logical:
            continue
        floor = quorum_factor * logical * (1 - _RECON_TOL)
        if written < floor:
            out.append(
                f"ioflow: {op_class} writes {written} < write_quorum/k "
                f"x logical {logical} (floor {floor:.0f}) — committed "
                f"bytes vanished from the ledger")
        failed = (counts or {}).get(op_class, {}).get("failed", 0)
        if clean and not failed:
            lo = factor * logical * (1 - _RECON_TOL)
            hi = factor * logical * (1 + _RECON_TOL)
            if not lo <= written <= hi:
                out.append(
                    f"ioflow: {op_class} writes {written} != (k+m)/k x "
                    f"logical {logical} (want [{lo:.0f}, {hi:.0f}]) "
                    f"on the clean path")
    heal = ops.get("heal", {})
    durable = payload - getattr(_oracle, "deleted_payload", 0)
    if durable > 0 and payload_writes + heal.get("write", 0) < \
            factor * durable * (1 - _RECON_TOL):
        out.append(
            f"ioflow: payload writes {payload_writes} + heal writes "
            f"{heal.get('write', 0)} < (k+m)/k x durable payload "
            f"{durable} — drain did not restore full redundancy in "
            f"the ledger")
    if heal.get("write", 0):
        ratio = heal.get("read", 0) / heal["write"]
        # Dense RS can never rebuild cheaper than k survivor reads for
        # m rebuilt shards — the lower bound holds under ANY chaos
        # (only a regenerating-code engine may legitimately go below).
        lo = (k / m) * (1 - _RECON_TOL)
        if ratio < lo:
            out.append(f"ioflow: heal read/healed {ratio:.2f} below "
                       f"the dense-RS floor {lo:.2f}")
        # The k upper bound is a CLEAN-path property: hedged reads and
        # heal attempts that fault out mid-read (reads ledgered, no
        # writes) push the ratio above k legitimately under chaos.
        if clean and ratio > k * (1 + _RECON_TOL):
            out.append(f"ioflow: heal read/healed {ratio:.2f} > k={k} "
                       f"on the clean path")
    deg = ops.get("get-degraded", {})
    logical_deg = snap["logical"].get("get-degraded", 0)
    if logical_deg and not deg.get("read", 0):
        # A mid-stream promotion retags only the REMAINING bytes (the
        # pre-failure reads stay op=get), so read >= logical does not
        # hold here — but reconstruction always reads at least one
        # extra shard AFTER the promotion, so zero degraded reads
        # against nonzero degraded payload means the retag leaked.
        out.append(f"ioflow: {logical_deg} payload bytes served "
                   f"degraded with ZERO reads ledgered as "
                   f"get-degraded — the mid-stream retag leaked")
    return out


def inv_stall_bounded(h: ScenarioHarness, _oracle) -> list[str]:
    """No client op exceeded the configured stall bound (ISSUE 17):
    with hang faults live, the deadline -> straggler-detach -> hedge
    path must resolve EVERY op within deadline + grace + slack — a
    single over-bound sample means a hang leaked past the tolerance
    machinery. No-op when the run recorded no latencies (unit-test
    harnesses that never attach a board)."""
    board = getattr(h, "latency", None)
    bound = getattr(h, "stall_bound_s", None)
    if board is None or bound is None:
        return []
    return [
        f"stall-bound: {kind} took {took:.1f}s > {bound:.1f}s "
        f"with faults armed"
        for kind, took in board.over(bound)
    ]


def inv_hot_object_coherent(h: ScenarioHarness, _oracle) -> list[str]:
    """Hot-object tier coherence at drain (ISSUE 19). For every shared
    hot key: a tier-bypassed GET (MTPU_READTIER=off forces a fresh
    erasure decode) establishes ground truth; that truth must be a
    generation the run actually wrote (h.hot_gens when a mutating
    scenario tracked overwrites, else the seeded body); and two
    tier-path GETs — the first may lead a fresh decode, the second is
    then servable straight off the decoded-block cache — must both
    return the ground-truth bytes. A divergence is a stale or corrupt
    cached block surviving the write-path invalidation. Also asserts
    the single-flight registry drained: a leaked flight would wedge the
    next follower behind a decode that no longer exists. No-op for
    harnesses without a hot keyspace."""
    hot = getattr(h, "hot_bodies", None)
    if not hot:
        return []
    from ..object import readtier

    out = []
    gens = getattr(h, "hot_gens", None)
    # knob-ok: save/restore — None must mean "was unset", not a default
    saved = os.environ.get("MTPU_READTIER")
    truths: dict[str, bytes] = {}
    try:
        os.environ["MTPU_READTIER"] = "off"
        for key in sorted(hot):
            st, _, got = h.request("GET", f"/{BUCKET}/{key}")
            if st != 200:
                out.append(f"hot-coherent: tier-bypassed GET {key} -> "
                           f"{st}")
                continue
            truths[key] = got
    finally:
        if saved is None:
            os.environ.pop("MTPU_READTIER", None)
        else:
            os.environ["MTPU_READTIER"] = saved
    for key, truth in sorted(truths.items()):
        allowed = gens.get(key, []) if gens else [hot[key]]
        if truth not in allowed:
            out.append(f"hot-coherent: {key} decodes to bytes no "
                       f"generation of the run ever wrote")
        for pass_ in ("first", "second"):
            st, _, got = h.request("GET", f"/{BUCKET}/{key}")
            if st != 200:
                out.append(f"hot-coherent: tier GET {key} ({pass_}) "
                           f"-> {st}")
            elif got != truth:
                out.append(
                    f"hot-coherent: {key} ({pass_} tier pass) diverges "
                    f"from the tier-bypassed decode — a stale or "
                    f"corrupt cached block survived invalidation")
    snap = readtier.snapshot()
    if snap and snap["flights"]:
        out.append(f"hot-coherent: {snap['flights']} single-flight "
                   f"entr(ies) leaked past drain")
    return out


def inv_repair_bandwidth(h: ScenarioHarness, _oracle) -> list[str]:
    """Heal byte economics at drain (ISSUE 20). Whatever mix of codecs
    the run healed under, the ledger's heal disk-read ratio must land
    in the union envelope [k/m, k]: the dense path reads k whole
    shards per rebuilt shard (ratio k, or k/m when one pass rebuilds
    all m), and the regenerating repair plane reads (n-1)/m — which
    sits strictly inside that envelope for every m >= 2 geometry. A
    ratio above k means some heal read MORE than the dense worst case
    (a repair fan-out that fell back after reading, doubled reads);
    below k/m means heal writes landed without their reads being
    ledgered. Wire bytes (rwire, remote repair symbols) can never
    exceed the disk reads that produced them. No-op when the run
    healed nothing."""
    from ..observability import ioflow

    spec = getattr(h, "spec", None)
    if spec is None:
        return []
    k = spec.disks - spec.parity
    m = spec.parity
    heal = ioflow.op_totals(ioflow.snapshot()).get("heal", {})
    w = heal.get("write", 0)
    if not w:
        return []
    out = []
    r = heal.get("read", 0) / w
    if r < (k / m) * (1 - _RECON_TOL):
        out.append(f"repair-bandwidth: heal ratio {r:.2f} below k/m="
                   f"{k / m:.2f} — heal writes without ledgered reads")
    if r > k * (1 + _RECON_TOL):
        out.append(f"repair-bandwidth: heal ratio {r:.2f} above the "
                   f"dense-RS ceiling k={k} — a heal read more than "
                   f"the read-k-shards worst case")
    rw = heal.get("rwire", 0)
    if rw > heal.get("read", 0) * (1 + _RECON_TOL):
        out.append(f"repair-bandwidth: {rw} repair wire bytes exceed "
                   f"{heal.get('read', 0)} heal disk reads — wire "
                   f"symbols appeared from nowhere")
    return out


def inv_mesh_stats_clean(h: ScenarioHarness, _oracle) -> list[str]:
    """Mesh-engine STATS contract as a drain invariant (ISSUE 17): over
    the scenario, every mesh dispatch carried exactly one dp-group
    batch accounting (dispatches == batches), and — once warmed up
    (MTPU_MESH_WARM=1, set by the second run of the subprocess gate) —
    zero retraces: the jit cache must be shape-stable under the full
    mixed workload. No-op under the host-einsum engine."""
    if os.environ.get("MTPU_ENCODE_ENGINE", "").lower() != "mesh":
        return []
    from ..parallel.metrics import STATS

    base = getattr(h, "mesh_stats0", None) or {}
    out = []
    d = STATS["mesh_dispatches_total"] - base.get(
        "mesh_dispatches_total", 0)
    b = STATS["mesh_batches_total"] - base.get("mesh_batches_total", 0)
    if d != b:
        out.append(f"mesh: dispatches {d} != batches {b} over the "
                   f"scenario — a collective fired without its dp-group "
                   f"batch accounting")
    if os.environ.get("MTPU_MESH_WARM", "") not in ("", "0"):
        r = STATS["mesh_retraces_total"] - base.get(
            "mesh_retraces_total", 0)
        if r:
            out.append(f"mesh: {r} steady-state retrace(s) — the jit "
                       f"cache must be shape-stable after warm-up")
    return out


# Ordered registry: the drain-time gate runs every one, IN THIS ORDER —
# mrf_dry asserts the drain state BEFORE the no-loss verification reads
# (which may legitimately queue fresh heal hints if they find residual
# degradation; the runner drains and reports those separately).
INVARIANTS = {
    "mrf_dry": inv_mrf_dry,
    "no_loss": inv_no_loss,
    "expiry": inv_expiry,
    "pools_settled": inv_pools_settled,
    "lock_cycles": inv_lock_cycles,
    "no_orphan_workers": inv_no_orphan_workers,
    "admission_conserved": inv_admission_conserved,
    "ioflow_reconciles": inv_ioflow_reconciles,
    "hot_object_coherent": inv_hot_object_coherent,
    "stall_bounded": inv_stall_bounded,
    "mesh_stats_clean": inv_mesh_stats_clean,
    "repair_bandwidth": inv_repair_bandwidth,
}

_CONTINUOUS = ("lock_cycles", "no_orphan_workers")


def _span_p99s(metrics) -> dict:
    """Per-kind span p99 from the run registry's histogram buckets
    (linear interpolation inside the winning bucket) — the saturation
    attribution the bench section reports: where the tail actually
    went (admission-wait vs stage-stall vs worker vs disk)."""
    import re

    pat = re.compile(
        r'^mtpu_span_seconds_bucket\{kind="([^"]+)"(?:,op="[^"]*")?,'
        r'le="([^"]+)"\} (\d+)$',
        re.M,
    )
    # one series per (kind, op): a kind's buckets are summed over its ops
    sums: dict[str, dict[float, int]] = {}
    for kind, le, cum in pat.findall(metrics.render_prometheus()):
        bound = float("inf") if le == "+Inf" else float(le)
        by_bound = sums.setdefault(kind, {})
        by_bound[bound] = by_bound.get(bound, 0) + int(cum)
    out: dict[str, float] = {}
    for kind, by_bound in sorted(sums.items()):
        bs = sorted(by_bound.items())
        total = bs[-1][1]
        if not total:
            continue
        target = 0.99 * total
        lo_bound, lo_cum = 0.0, 0
        for bound, cum in bs:
            if cum >= target:
                if bound == float("inf"):
                    # Open bucket: the last finite boundary is the
                    # honest lower estimate.
                    out[kind] = round(lo_bound, 4)
                else:
                    span = cum - lo_cum
                    frac = (target - lo_cum) / span if span else 1.0
                    out[kind] = round(
                        lo_bound + frac * (bound - lo_bound), 4)
                break
            lo_bound, lo_cum = bound, cum
    return out


# ---------------------------------------------------------------------------
# the runner


class ScenarioResult:
    """The failure artifact (docs/SOAK.md "reading a failure
    artifact"): plan + outcome counts + fault log + per-invariant
    violations. JSON-able and self-contained — the plan inside it
    replays the scenario."""

    def __init__(self, plan: dict):
        self.plan = plan
        self.counts: dict = {}
        self.fault_log: list = []
        self.violations: dict[str, list[str]] = {}
        self.wall_s = 0.0
        self.bytes_moved = 0
        self.drained_ok = True
        # Heal entries the no-loss verification reads themselves
        # queued (residual degradation found and repaired post-gate):
        # visible in the artifact, not a gate failure by itself.
        self.verify_requeued = 0
        # Drive-fault injections that actually fired (vs armed).
        self.drive_faults_fired = 0
        # Per-schedule status() dicts at disarm (endpoint + per-spec
        # fired counts) — proves WHICH fault kinds actually fired
        # (the hang-armed gate asserts on this).
        self.fault_status: list = []
        # Client-observed latency summary (per op class, p50/p99/max).
        self.latency: dict = {}
        # Span-attributed p99 breakdown (admission-wait vs stage-stall
        # vs worker vs disk), from the run's span histograms.
        self.span_p99: dict = {}

    @property
    def passed(self) -> bool:
        return self.drained_ok and not any(self.violations.values())

    @property
    def throughput_gbps(self) -> float:
        return (self.bytes_moved / self.wall_s / 1e9
                if self.wall_s else 0.0)

    def to_dict(self) -> dict:
        return {
            "passed": self.passed,
            "plan": self.plan,
            "counts": self.counts,
            "fault_log": self.fault_log,
            "violations": {k: v for k, v in self.violations.items()
                           if v},
            "wall_s": round(self.wall_s, 3),
            "bytes_moved": self.bytes_moved,
            "throughput_gbps": round(self.throughput_gbps, 4),
            "verify_requeued": self.verify_requeued,
            "drive_faults_fired": self.drive_faults_fired,
            "fault_status": self.fault_status,
            "latency": self.latency,
            "span_p99": self.span_p99,
        }


def run_scenario(spec: ScenarioSpec, root: str) -> ScenarioResult:
    """Execute one full scenario: boot the harness, arm the plan's
    faults, run every client stream concurrently with the continuous
    checker, then drain (disarm -> re-admit -> MRF dry -> lifecycle
    scan -> MRF dry) and run the full invariant gate."""
    from ..storage.diskcheck import ROBUST

    plan = scenario_plan(spec)
    result = ScenarioResult(plan)
    lockgraph = None
    if spec.lock_check:
        try:
            from tools.analysis import lockgraph as _lg

            if not _lg.enabled():
                _lg.reset()
                _lg.enable()
                lockgraph = _lg
        except ImportError:
            pass  # pip-installed deployment without tools/: documented skip
    h = None
    oracle = _Oracle()
    try:
        h = ScenarioHarness(root, spec)
        # Closed-loop queueing: on a saturated host per-op wall time
        # grows ~linearly with clients-per-core (every op waits behind
        # the other issuers' CPU slices). Scale the slack with that
        # oversubscription so the 64-client gate measures WEDGES, not
        # scheduler weather — at the original 8-client-per-core shape
        # the bound is unchanged.
        over = max(1.0, spec.clients / (8.0 * (os.cpu_count() or 1)))
        stall_bound_s = (ROBUST.long_op_deadline_s
                         + ROBUST.straggler_grace_s
                         + STALL_SLACK_S * over)
        # Attach the load-gen latency board + bound so the
        # stall_bounded invariant (and the artifact's p50/p99 summary)
        # see every client op; register the shared hot keyspace with
        # the no-loss oracle — hot keys must survive the chaos too.
        h.latency = _LatencyBoard()
        h.stall_bound_s = stall_bound_s
        for key, body in getattr(h, "hot_bodies", {}).items():
            oracle.commit(BUCKET, key, body)
        scheds = []
        for ep, sched in plan["faults"]["drive_schedules"]:
            fd = h.fault_disks[h.endpoints.index(ep)]
            scheds.append(fd.arm(sched))
        composer = _Composer(h, plan["faults"]["events"],
                             result.fault_log)
        violations: list[str] = []
        stop = threading.Event()

        def continuous():
            while not stop.wait(0.5):
                for name in _CONTINUOUS:
                    for v in INVARIANTS[name](h, oracle):
                        # Dedup on the STORED form: a violation that
                        # persists all soak must not append one line
                        # per 0.5s tick to the artifact.
                        entry = f"[mid-run] {v}"
                        if entry not in violations:
                            violations.append(entry)

        checker = threading.Thread(target=continuous,
                                   name="soak-invariants", daemon=True)
        checker.start()
        t0 = time.monotonic()
        threads = [
            threading.Thread(
                target=_run_client,
                args=(h, oracle, c, plan["clients"][c], composer,
                      result.counts, violations, stall_bound_s),
                name=f"soak-c{c}",
            )
            for c in range(spec.clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600.0)
            if t.is_alive():
                violations.append(f"client {t.name} wedged past 600s")
                result.drained_ok = False
        result.wall_s = time.monotonic() - t0
        stop.set()
        checker.join(5.0)
        composer.join()

        # ---- drain ----
        h.fault_fired = sum(s.fired for s in scheds)
        result.drive_faults_fired = h.fault_fired
        result.fault_status = [
            dict(s.status(), endpoint=ep)
            for (ep, _), s in zip(plan["faults"]["drive_schedules"],
                                  scheds)
        ]
        for s in scheds:
            s.disarm()
        still_faulty = h.wait_readmit()
        if still_faulty:
            violations.append(
                f"drives never re-admitted after disarm: {still_faulty}")
        left = h.drain_mrf()
        if left:
            result.drained_ok = False
        # Lifecycle expiry + scanner heal sampling, then heal whatever
        # the scan queued.
        h.scanner.scan_cycle()
        left = h.drain_mrf()
        if left:
            result.drained_ok = False

        # ---- the gate ----
        result.violations["run"] = violations
        for name, fn in INVARIANTS.items():
            try:
                if fn is inv_ioflow_reconciles:
                    result.violations[name] = fn(h, oracle,
                                                 result.counts)
                else:
                    result.violations[name] = fn(h, oracle)
            except Exception as exc:  # noqa: BLE001 - checker crash IS a failure
                result.violations[name] = [
                    f"invariant checker crashed: "
                    f"{type(exc).__name__}: {exc}"]
        result.bytes_moved = sum(
            len(b) for b in oracle.objects.values()
        ) + sum(len(b) for b in oracle.expiring.values())
        result.latency = h.latency.summary()
        result.span_p99 = _span_p99s(h.metrics)
        # The verification reads above may have FOUND residual
        # degradation and queued heal hints: repair it now and report
        # the count — the gate already judged the drain state.
        result.verify_requeued = sum(
            es.mrf_stats()["pending"]
            for pool in h.ol.pools for es in pool.sets
        )
        if result.verify_requeued:
            h.drain_mrf(deadline_s=15.0)
    finally:
        if h is not None:
            h.close()
        if lockgraph is not None:
            lockgraph.disable()
            report = lockgraph.report()
            lockgraph.reset()
            if report["cycles"]:
                result.violations.setdefault("lock_cycles", []).extend(
                    f"lock-cycle (final): {c}" for c in report["cycles"]
                )
    return result


# ---------------------------------------------------------------------------
# dead-drive heal storm under foreground load (ISSUE 17)


def run_heal_storm(spec: ScenarioSpec, root: str, *,
                   storm_objects: int = 24, fg_clients: int = 4,
                   fg_ops: int = 30, payload: int = 64 << 10,
                   p99_mult: float | None = None,
                   pace_tokens: int = 2, codec: str = "",
                   repair_ceiling: float | None = None) -> dict:
    """One drive dead (fresh-disk replacement: its objects wiped below
    the fault layer), the whole backlog queued into the MRF, and the
    paced healer drains it WHILE zipfian foreground traffic runs.
    Verifies the ISSUE 17 degraded-mode contract:

    - degraded foreground GET p99 <= p99_mult x the unfaulted baseline
      p99 (MTPU_HEAL_P99_MULT, default 8.0 — generous because 1-core
      CI measures scheduling weather as much as pacing);
    - the MRF backlog reaches DRY despite pacing (deadline grants make
      starvation impossible by construction);
    - the ledger heal read/healed ratio stays within the dense-RS
      bounds: >= k/m at every sample, and inside [k/m, k] (with
      reconciliation tolerance) once the drain completes — mid-run
      samples get in-flight slack (reads ledger before their write);
    - every storm object reads back byte-identical and the victim
      drive holds its shard again (the heal actually landed).

    `codec` forces every storm PUT onto one codec id instead of
    cycling the full registry — the regenerating-codec gate variant
    (ISSUE 20) runs with codec="msr-pm" and `repair_ceiling`=4.5,
    which additionally asserts the heal disk-read ratio stays at or
    under the ceiling at EVERY ledger sample and at the final drain:
    the repair plane's (n-1)/m economics must hold mid-storm, not
    just on average.
    """
    import shutil

    from ..background import healpace
    from ..background.heal import MRFHealer
    from ..observability import ioflow

    if p99_mult is None:
        p99_mult = _env_float("MTPU_HEAL_P99_MULT", 8.0)
    k = spec.disks - spec.parity
    m = spec.parity
    reasons: list[str] = []
    artifact: dict = {"spec": spec.to_dict(), "p99_mult": p99_mult}
    pacer = healpace.reconfigure(healpace.PaceConfig(
        enabled=True, tokens=max(1, pace_tokens), queue_high=2,
        disk_p99_ms=75.0, max_wait_s=0.5, yield_s=0.02,
    ))
    h = None
    healer = None
    mon_stop = threading.Event()
    try:
        h = ScenarioHarness(root, spec)
        bodies: dict[str, bytes] = {}
        codecs = [codec] if codec else _soak_codecs()
        artifact["codec"] = codec or "mixed"
        for i in range(storm_objects):
            key = f"storm/o{i:04d}"
            body = _payload(spec.seed * 92821 + i, payload)
            st, _, _ = h.request(
                "PUT", f"/{BUCKET}/{key}", body=body,
                headers={"x-mtpu-codec": codecs[i % len(codecs)]},
            )
            assert st == 200, f"storm seed {key}: {st}"
            bodies[key] = body
        keys = sorted(bodies)

        def fg_phase(tag: str) -> _LatencyBoard:
            """One closed-loop foreground phase: fg_clients threads,
            zipfian GETs over the storm keyspace + periodic small PUTs,
            deterministic per (seed, client, phase)."""
            board = _LatencyBoard()

            def client(c: int) -> None:
                zrng = random.Random(
                    spec.seed * 31337 + c * 7 + (1 if tag != "base" else 0)
                )
                for n in range(fg_ops):
                    key = keys[_zipf_rank(zrng, len(keys), spec.zipf_s)]
                    t0 = time.monotonic()
                    st, _, got = h.request("GET", f"/{BUCKET}/{key}")
                    board.note("get", time.monotonic() - t0)
                    if st == 200 and got != bodies[key]:
                        reasons.append(f"{tag}: {key} bytes differ")
                    if n % 5 == 4:
                        t0 = time.monotonic()
                        h.request(
                            "PUT",
                            f"/{BUCKET}/fg/{tag}/c{c}o{n:03d}",
                            body=_payload(spec.seed + c * 1009 + n,
                                          16 << 10),
                        )
                        board.note("put", time.monotonic() - t0)

            threads = [threading.Thread(target=client, args=(c,),
                                        name=f"storm-{tag}-c{c}")
                       for c in range(fg_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(300.0)
                if t.is_alive():
                    reasons.append(f"{tag}: client {t.name} wedged")
            return board

        baseline = fg_phase("base")
        artifact["baseline"] = baseline.summary()

        # ---- kill the drive: fresh-disk semantics (wipe its storm
        # objects below the fault layer, keep the format) and queue the
        # whole keyspace into the MRF — the heal storm.
        victim = h.endpoints[1]
        shutil.rmtree(os.path.join(root, victim, BUCKET, "storm"),
                      ignore_errors=True)
        es = h.ol.pools[0].sets[0]
        for key in keys:
            es.queue_mrf(BUCKET, key, "")
        artifact["victim"] = victim
        artifact["queued"] = len(keys)

        # Ledger heal-ratio monitor: floor holds at EVERY sample;
        # the ceiling gets in-flight slack mid-run (k survivor reads
        # ledger before the rebuilt shard's write lands).
        ratio_floor = (k / m) * (1 - _RECON_TOL)
        ratio_samples: list[float] = []

        def monitor() -> None:
            floor_broken = False
            ceiling_broken = False
            while not mon_stop.wait(0.2):
                heal = ioflow.op_totals(ioflow.snapshot()).get("heal", {})
                w = heal.get("write", 0)
                if w < 2 * (payload // max(1, k)):
                    continue  # too early: nothing meaningfully healed
                r = heal.get("read", 0) / w
                ratio_samples.append(r)
                if r < ratio_floor and not floor_broken:
                    floor_broken = True
                    reasons.append(
                        f"heal ratio {r:.2f} below dense-RS floor "
                        f"k/m={k / m:.2f} mid-drain")
                if (repair_ceiling is not None and r > repair_ceiling
                        and not ceiling_broken):
                    ceiling_broken = True
                    reasons.append(
                        f"heal ratio {r:.2f} above the repair-plane "
                        f"ceiling {repair_ceiling:.2f} mid-drain — a "
                        f"heal read whole shards where β-slices "
                        f"sufficed")

        mon = threading.Thread(target=monitor, name="storm-ratio-mon")
        mon.start()
        healer = MRFHealer(h.ol, metrics=h.metrics).start(0.05)

        degraded = fg_phase("degraded")
        artifact["degraded"] = degraded.summary()

        # ---- drain dry: pacing may slow the drain, never wedge it.
        left = h.drain_mrf(deadline_s=60.0)
        healer.stop()
        mon_stop.set()
        mon.join(5.0)
        artifact["mrf_left"] = left
        if left:
            reasons.append(f"MRF backlog not dry: {left} left")

        heal = ioflow.op_totals(ioflow.snapshot()).get("heal", {})
        final_ratio = (heal.get("read", 0) / heal["write"]
                       if heal.get("write") else 0.0)
        artifact["heal_ratio"] = {
            "final": round(final_ratio, 3),
            "samples": len(ratio_samples),
            "min": round(min(ratio_samples), 3) if ratio_samples else None,
            "max": round(max(ratio_samples), 3) if ratio_samples else None,
        }
        if not heal.get("write"):
            reasons.append("no heal writes ledgered — the storm never "
                           "healed anything")
        else:
            if final_ratio < ratio_floor:
                reasons.append(f"final heal ratio {final_ratio:.2f} < "
                               f"k/m floor {k / m:.2f}")
            if final_ratio > k * (1 + _RECON_TOL):
                reasons.append(f"final heal ratio {final_ratio:.2f} > "
                               f"k={k} dense-RS ceiling")
            if (repair_ceiling is not None
                    and final_ratio > repair_ceiling):
                reasons.append(f"final heal ratio {final_ratio:.2f} > "
                               f"repair-plane ceiling {repair_ceiling}")
            artifact["heal_ratio"]["wire"] = round(
                heal.get("rwire", 0) / heal["write"], 3)

        # ---- content + placement verification.
        for key in keys:
            st, _, got = h.request("GET", f"/{BUCKET}/{key}")
            if st != 200 or got != bodies[key]:
                reasons.append(f"post-heal {key}: status {st} or bytes "
                               f"differ")
        restored = sum(
            1 for key in keys
            if os.path.isdir(os.path.join(root, victim, BUCKET, key))
        )
        artifact["victim_restored"] = restored
        if restored < len(keys):
            reasons.append(f"victim {victim} holds only {restored}/"
                           f"{len(keys)} storm objects after drain")

        # ---- tail-latency contract + pacer evidence.
        base_p99 = max(artifact["baseline"].get("get", {}).get("p99_s",
                                                               0.0),
                       0.005)
        deg_p99 = artifact["degraded"].get("get", {}).get("p99_s", 0.0)
        artifact["p99_ratio"] = round(deg_p99 / base_p99, 3)
        if deg_p99 > p99_mult * base_p99:
            reasons.append(
                f"degraded GET p99 {deg_p99:.3f}s > {p99_mult:.1f}x "
                f"baseline {base_p99:.3f}s")
        snap = pacer.snapshot()
        artifact["pacer"] = snap
        if snap["grants_total"] < len(keys):
            reasons.append(
                f"pacer granted {snap['grants_total']} < {len(keys)} "
                f"heals — heal traffic bypassed the pace plane")
    finally:
        mon_stop.set()
        if healer is not None:
            healer.stop()
        healpace.reset()
        if h is not None:
            h.close()
    artifact["reasons"] = reasons
    artifact["passed"] = not reasons
    return artifact


# ---------------------------------------------------------------------------
# hot-object tier under mutation chaos (ISSUE 19)


def run_hot_object(spec: ScenarioSpec, root: str, *,
                   readers: int = 4, reader_ops: int = 24,
                   overwrites: int = 8, ver_keys: int = 3,
                   ver_cycles: int = 3, heal_kills: int = 2,
                   crash_gets: int = 6) -> dict:
    """Hot-key chaos scenario (ISSUE 19): zipfian readers hammer the
    shared hot keyspace THROUGH the hot-object tier (hot-bytes
    threshold pinned to 1, so every key is tier-hot from its first
    served byte) while every mutation plane runs against the same
    sketch-hot keys concurrently:

    - **overwrite** — generation-tracked hot-key PUTs; a GET that
      begins after an overwrite's 200 must never serve an older
      generation (a stale cached block) — and no GET may ever serve
      bytes that match NO generation (a corrupt one);
    - **versioned-delete** — put/read-back/delete-oldest cycles on a
      parallel hot keyspace in the versioned bucket, proving the tier's
      (version-id, etag) keying plus delete-path invalidation;
    - **heal + drive-fault** — shard kills healed mid-traffic, with a
      mild error/latency schedule armed on one drive underneath.

    Then the leader-crash proof: with stream reads erroring on parity+1
    drives, K concurrent GETs of a cache-cold hot key share one doomed
    decode — every one must fail CLEAN (non-200 or a severed
    connection, never an intact 200 carrying a body), and the key reads
    back byte-identical after disarm. The full drain-invariant gate
    (hot_object_coherent included) closes the run."""
    from ..object import readtier
    from ..observability import ioflow

    reasons: list[str] = []
    artifact: dict = {"spec": spec.to_dict()}
    saved_env = {k: os.environ.get(k)
                 for k in ("MTPU_READTIER", "MTPU_READTIER_HOT_BYTES")}
    os.environ["MTPU_READTIER"] = "on"
    os.environ["MTPU_READTIER_HOT_BYTES"] = "1"
    readtier.reset()
    h = None
    counts: dict = {"reads_ok": 0, "clean_failures": 0, "stale_hits": 0}
    cmu = threading.Lock()
    try:
        h = ScenarioHarness(root, spec)
        if not h.hot_bodies:
            raise ValueError("run_hot_object needs spec.hot_keys > 0")
        keys = sorted(h.hot_bodies)
        # Generation history per hot key. Bodies are appended BEFORE
        # their PUT goes out (a racing reader must always be able to
        # match whatever the server serves it); committed[key] counts
        # only 200-acknowledged generations — the staleness floor a
        # reader snapshots at request start. Single overwriter thread,
        # so per-key ordering is the append ordering.
        h.hot_gens = {k: [h.hot_bodies[k]] for k in keys}
        committed = {k: 1 for k in keys}
        gmu = threading.Lock()

        # Drive-fault plane under everything: the mild shape on one
        # drive (same kinds the default soak plan arms).
        sched = h.fault_disks[1].arm({
            "seed": spec.seed * 53 + 1,
            "specs": [
                {"kind": "latency", "probability": 0.12,
                 "latency_s": 0.02},
                {"kind": "error", "probability": 0.04,
                 "error": "ErrDiskNotFound"},
            ],
        })

        def reader(r: int) -> None:
            zrng = random.Random(spec.seed * 48611 + r)
            for _ in range(reader_ops):
                key = keys[_zipf_rank(zrng, len(keys), spec.zipf_s)]
                with gmu:
                    floor = committed[key]
                try:
                    st, _, got = h.request("GET", f"/{BUCKET}/{key}")
                except (OSError, http.client.HTTPException):
                    with cmu:
                        counts["clean_failures"] += 1
                    continue
                if st != 200:
                    with cmu:
                        counts["clean_failures"] += 1
                    continue
                with gmu:
                    allowed = list(h.hot_gens[key])
                try:
                    idx = allowed.index(got)
                except ValueError:
                    reasons.append(
                        f"reader {r}: {key} served bytes matching NO "
                        f"generation — corrupt cached block")
                    continue
                # Client-side bookkeeping lands an instant after the
                # overwrite's 200, so a reader starting inside that
                # window legitimately carries the previous floor; any
                # reader starting after it must see >= floor-1.
                if idx < floor - 1:
                    with cmu:
                        counts["stale_hits"] += 1
                    reasons.append(
                        f"reader {r}: {key} served generation {idx} "
                        f"after generation {floor - 1} committed — "
                        f"stale hit")
                else:
                    with cmu:
                        counts["reads_ok"] += 1

        def overwriter() -> None:
            for n in range(overwrites):
                # Mutate the hottest ranks: the overwrites must race
                # cached blocks, not idle tail keys.
                key = keys[n % min(4, len(keys))]
                body = _payload(spec.seed * 263 + 7 * n + 1, 64 << 10)
                with gmu:
                    h.hot_gens[key].append(body)
                st, _, _ = h.request("PUT", f"/{BUCKET}/{key}",
                                     body=body)
                if st == 200:
                    with gmu:
                        committed[key] = h.hot_gens[key].index(body) + 1
                        h.hot_bodies[key] = body
                time.sleep(0.02)

        # Versioned plane: sequential per-key cycles on the versioned
        # bucket; `live` tracks surviving (version-id, body) pairs for
        # the no-loss gate. A non-200 anywhere taints the key (under
        # faults a failed status cannot prove the server-side outcome),
        # dropping it from verification instead of guessing.
        ver_bodies: dict[str, list] = {}

        def versioner() -> None:
            for ki in range(ver_keys):
                key = f"hotver/o{ki:02d}"
                live: list = []
                tainted = False
                for cyc in range(ver_cycles):
                    body = _payload(spec.seed * 521 + ki * 97 + cyc,
                                    64 << 10)
                    st, hdr, _ = h.request(
                        "PUT", f"/{BUCKET_VER}/{key}", body=body)
                    if st != 200:
                        tainted = True
                        break
                    live.append((hdr.get("x-amz-version-id", ""), body))
                    st, _, got = h.request("GET", f"/{BUCKET_VER}/{key}")
                    if st == 200 and got != body:
                        reasons.append(
                            f"versioned: {key} read back an older "
                            f"generation right after its overwrite "
                            f"committed — stale hit")
                    # Versioned-delete the oldest noncurrent version:
                    # the delete-path invalidation plane (latest stays
                    # latest, so reader expectations are monotonic).
                    if len(live) >= 2 and live[0][0]:
                        vid0 = live[0][0]
                        st, _, _ = h.request(
                            "DELETE", f"/{BUCKET_VER}/{key}",
                            query=[("versionId", vid0)])
                        if st in (200, 204):
                            live.pop(0)
                        else:
                            tainted = True
                            break
                if not tainted:
                    ver_bodies[key] = live

        failed_heals: list[str] = []

        def healer() -> None:
            for i in range(heal_kills):
                key = keys[(2 * i) % len(keys)]
                if h.kill_data_shard(BUCKET, key) is None:
                    continue
                try:
                    h.ol.heal_object(BUCKET, key)
                except Exception:  # noqa: BLE001  # except-ok: heals failing under the armed fault schedule retry after disarm
                    failed_heals.append(key)
                time.sleep(0.02)

        threads = [threading.Thread(target=reader, args=(r,),
                                    name=f"hot-r{r}")
                   for r in range(readers)]
        threads += [threading.Thread(target=overwriter, name="hot-ow"),
                    threading.Thread(target=versioner, name="hot-ver"),
                    threading.Thread(target=healer, name="hot-heal")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300.0)
            if t.is_alive():
                reasons.append(f"{t.name} wedged past 300s")
        sched.disarm()
        h.fault_fired = sched.fired
        still = h.wait_readmit()
        if still:
            reasons.append(f"drives never re-admitted after disarm: "
                           f"{still}")
        for key in failed_heals:
            try:
                h.ol.heal_object(BUCKET, key)
            except Exception as exc:  # noqa: BLE001 - clean-path heal failure IS a finding
                reasons.append(f"heal plane: {key} unhealable after "
                               f"disarm: {type(exc).__name__}: {exc}")

        # ---- leader-crash proof: a doomed shared decode fails clean.
        crash_key = keys[0]
        readtier.invalidate(BUCKET, crash_key)  # cold cache, hot sketch
        crash_scheds = [
            h.fault_disks[i].arm({
                "seed": spec.seed * 101 + i,
                "specs": [{"kind": "error", "probability": 1.0,
                           "error": "ErrDiskNotFound",
                           "ops": ["stream_read"]}],
            })
            for i in range(spec.parity + 1)
        ]
        tier0 = readtier.snapshot() or {}
        outcomes: list[str] = []
        omu = threading.Lock()

        def crash_get() -> None:
            try:
                st, _, got = h.request("GET", f"/{BUCKET}/{crash_key}")
            except (OSError, http.client.HTTPException):
                with omu:
                    outcomes.append("severed")
                return
            with omu:
                if st != 200:
                    outcomes.append(f"status-{st}")
                else:
                    # ANY intact 200 is a violation: with reads failing
                    # below quorum there are no bytes to serve.
                    outcomes.append("intact-200")

        cthreads = [threading.Thread(target=crash_get,
                                     name=f"hot-crash{i}")
                    for i in range(crash_gets)]
        for t in cthreads:
            t.start()
        for t in cthreads:
            t.join(120.0)
        for s in crash_scheds:
            s.disarm()
        tier1 = readtier.snapshot() or {}
        artifact["crash_outcomes"] = sorted(outcomes)
        bad = [o for o in outcomes if o == "intact-200"]
        if bad:
            reasons.append(
                f"leader-crash: {len(bad)} GET(s) returned an intact "
                f"200 body through a decode that could not have "
                f"produced one")
        if tier1.get("leader_crashes_total", 0) <= \
                tier0.get("leader_crashes_total", 0):
            reasons.append("leader-crash: no leader crash ledgered — "
                           "the doomed GETs never reached a shared "
                           "decode")
        still = h.wait_readmit()
        if still:
            reasons.append(f"drives never re-admitted after the crash "
                           f"phase: {still}")
        # Recovery: the injected errors damaged nothing on disk.
        st, _, got = h.request("GET", f"/{BUCKET}/{crash_key}")
        if st != 200 or got not in h.hot_gens[crash_key]:
            reasons.append(f"leader-crash: {crash_key} unreadable "
                           f"after disarm ({st})")

        # ---- drain + the full gate.
        left = h.drain_mrf()
        if left:
            reasons.append(f"MRF backlog not dry: {left} left")
        oracle = _Oracle()
        for key, live in ver_bodies.items():
            if live:
                oracle.versions[(BUCKET_VER, key)] = live
        violations: dict = {"run": reasons}
        for name, fn in INVARIANTS.items():
            try:
                if fn is inv_ioflow_reconciles:
                    violations[name] = fn(h, oracle, counts)
                else:
                    violations[name] = fn(h, oracle)
            except Exception as exc:  # noqa: BLE001 - checker crash IS a failure
                violations[name] = [
                    f"invariant checker crashed: "
                    f"{type(exc).__name__}: {exc}"]
        tier = readtier.snapshot() or {}
        if not (tier.get("hits_total", 0)
                or tier.get("coalesced_total", 0)):
            violations["run"].append(
                "tier never served a byte: the hot keyspace stayed "
                "cold with the hot-bytes threshold at 1")
        artifact["counts"] = dict(counts)
        artifact["tier"] = tier
        artifact["served_bytes"] = dict(ioflow.snapshot()["served"])
        artifact["violations"] = {k: v for k, v in violations.items()
                                  if v}
        artifact["passed"] = not any(violations.values())
    finally:
        if h is not None:
            h.close()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        readtier.reset()
    return artifact


# ---------------------------------------------------------------------------
# replication + event delivery under faults (ISSUE 17)

NOTIF_XML = (
    "<NotificationConfiguration><QueueConfiguration><Id>soak-ev</Id>"
    "<Queue>{arn}</Queue><Event>s3:ObjectCreated:*</Event>"
    "</QueueConfiguration></NotificationConfiguration>"
)

REPL_XML = (
    '<ReplicationConfiguration xmlns='
    '"http://s3.amazonaws.com/doc/2006-03-01/">'
    "<Role>arn:minio:replication</Role>"
    "<Rule><ID>soak-repl</ID><Status>Enabled</Status>"
    "<Priority>1</Priority>"
    "<DeleteMarkerReplication><Status>Enabled</Status>"
    "</DeleteMarkerReplication>"
    "<Destination><Bucket>{arn}</Bucket></Destination></Rule>"
    "</ReplicationConfiguration>"
)


def _signed_req(endpoint: str, method: str, path: str, query=None,
                body: bytes = b"", headers=None, timeout: float = 30.0):
    """Signed request against an arbitrary server endpoint (the
    harness's request() is pinned to the primary)."""
    from ..api.sign import sign_v4_request

    query = query or []
    qs = urllib.parse.urlencode(query)
    url = urllib.parse.quote(path) + (f"?{qs}" if qs else "")
    h = sign_v4_request(SECRET, ACCESS, method, endpoint, path, query,
                        dict(headers or {}), body)
    conn = http.client.HTTPConnection(endpoint, timeout=timeout)
    try:
        conn.request(method, url, body=body, headers=h)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def run_event_delivery(spec: ScenarioSpec, root: str, *, targets: dict,
                       outage, recover, puts_per_phase: int = 3,
                       settle_s: float = 30.0) -> dict:
    """Replication + event-delivery-under-faults scenario: a primary
    with bucket notifications (store-backed targets, e.g. MySQL) AND
    CRR replication to an in-process replica. Three phases of PUTs:
    clean, during a composed blackout (the caller's `outage()` severs
    the event target; the replica server stops), and after recovery
    (`recover()` restores the target; the replica restarts on the SAME
    port). The contract: events queued during the blackout are
    DELIVERED after recovery (store drains to zero — no silent
    queue-only degrade; the caller asserts exactly-once on its target's
    wire log), the blackout was VISIBLE (drain failures latched), and
    replication converges for every phase's keys."""
    from ..object.pools import ErasureServerPools
    from ..object.sets import ErasureSets
    from ..storage.local import LocalStorage
    from ..utils.errors import ErrUnformattedDisk

    arn = next(iter(targets))
    reasons: list[str] = []
    artifact: dict = {"arn": arn}
    h = None
    replica = None

    def boot_replica(port: int = 0):
        from ..api import S3Server
        from ..bucket import BucketMetadataSys
        from ..iam import IAMSys

        disks = [
            LocalStorage(os.path.join(root, "replica", f"rep-d{i}"),
                         endpoint=f"rep-d{i}")
            for i in range(4)
        ]
        sets = ErasureSets(
            disks, 4, deployment_id="deadbeef-dead-dead-dead-deaddeadbeef",
            pool_index=0,
        )
        try:
            sets.load_format()
        except ErrUnformattedDisk:
            sets.init_format()
        ol = ErasureServerPools([sets])
        return S3Server(ol, IAMSys(ACCESS, SECRET),
                        BucketMetadataSys(ol), port=port).start()

    try:
        h = ScenarioHarness(root, spec, notify_targets=targets)
        replica = boot_replica()
        replica_port = int(replica.endpoint.rsplit(":", 1)[1])
        dst_bucket = f"{BUCKET_VER}-copy"
        ver_xml = (b"<VersioningConfiguration><Status>Enabled</Status>"
                   b"</VersioningConfiguration>")
        st, _, _ = _signed_req(replica.endpoint, "PUT", f"/{dst_bucket}")
        assert st == 200, f"replica bucket: {st}"
        st, _, _ = _signed_req(replica.endpoint, "PUT", f"/{dst_bucket}",
                               query=[("versioning", "")], body=ver_xml)
        assert st == 200, f"replica versioning: {st}"
        # Notifications + replication both on the versioned bucket.
        st, _, _ = h.request("PUT", f"/{BUCKET_VER}",
                             query=[("notification", "")],
                             body=NOTIF_XML.format(arn=arn).encode())
        assert st == 200, f"notification config: {st}"
        tgt = {"endpoint": replica.endpoint, "access_key": ACCESS,
               "secret_key": SECRET, "target_bucket": dst_bucket}
        st, _, body = h.request(
            "PUT", "/minio/admin/v3/set-remote-target",
            query=[("bucket", BUCKET_VER)],
            body=json.dumps(tgt).encode(),
        )
        assert st == 200, body
        repl_arn = json.loads(body)["arn"]
        st, _, body = h.request(
            "PUT", f"/{BUCKET_VER}", query=[("replication", "")],
            body=REPL_XML.format(arn=repl_arn).encode(),
        )
        assert st == 200, body

        store = targets[arn].store

        def put_phase(tag: str) -> list[str]:
            out = []
            for i in range(puts_per_phase):
                key = f"ev/{tag}-{i}"
                body_ = _payload(spec.seed + hash(tag) % 1000 + i,
                                 16 << 10)
                st_, _, _ = h.request("PUT", f"/{BUCKET_VER}/{key}",
                                      body=body_)
                if st_ != 200:
                    reasons.append(f"{tag}: PUT {key} -> {st_}")
                else:
                    out.append(key)
            return out

        def settle(keys_: list[str], deadline_s: float) -> bool:
            """Events drained + replication converged for keys_."""
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                if h.notify is not None:
                    h.notify.retry_stores()
                h.srv.repl_pool.drain(2)
                drained = len(store) == 0
                replicated = all(
                    _signed_req(replica.endpoint, "GET",
                                f"/{dst_bucket}/{k}")[0] == 200
                    for k in keys_
                )
                if drained and replicated:
                    return True
                time.sleep(0.25)
            return False

        clean_keys = put_phase("clean")
        if not settle(clean_keys, settle_s):
            reasons.append(
                f"clean phase did not settle: store {len(store)}, "
                f"target err {targets[arn].last_error}")
        artifact["clean_keys"] = clean_keys

        # ---- composed blackout: event target + replica peer.
        outage()
        replica.stop()
        outage_keys = put_phase("outage")
        artifact["outage_keys"] = outage_keys
        # The blackout must be VISIBLE, not a silent queue-only
        # degrade: the store backs up and a drain attempt latches its
        # failure counters.
        deadline = time.monotonic() + settle_s
        visible = False
        while time.monotonic() < deadline and not visible:
            targets[arn].drain()
            visible = (len(store) > 0
                       and (targets[arn].drain_failures > 0
                            or targets[arn].last_error is not None))
            if not visible:
                time.sleep(0.2)
        artifact["queued_during_outage"] = len(store)
        artifact["outage_visible"] = visible
        if not visible:
            reasons.append(
                f"blackout invisible: store {len(store)}, "
                f"drain_failures {targets[arn].drain_failures}")

        # ---- recovery: same-port replica restart + caller's target
        # recovery, then everything queued must DELIVER.
        recover()
        replica = boot_replica(replica_port)
        if not settle(clean_keys + outage_keys, settle_s):
            reasons.append(
                f"post-recovery settle failed: store {len(store)}, "
                f"target err {targets[arn].last_error}")
        artifact["store_len_final"] = len(store)
    finally:
        if h is not None:
            h.close()
        if replica is not None:
            replica.stop()
    artifact["reasons"] = reasons
    artifact["passed"] = not reasons
    return artifact


# ---------------------------------------------------------------------------
# whole-server crash scenario: SIGKILL mid-PUT + restart recovery


def host_memcpy_gbps(size_mib: int = 32, reps: int = 3) -> float:
    """Best-of-N host memcpy rate — the soak throughput floor's
    normalizer (same convention as bench.py: value/memcpy cancels the
    host weather, so one floor number holds across CI hosts)."""
    import numpy as np

    src = np.random.default_rng(0).integers(
        0, 256, size_mib * MIB, dtype=np.uint8
    )
    dst = np.empty_like(src)
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        dt = time.perf_counter() - t0
        best = max(best, size_mib * MIB / dt / 1e9)
    return best


def _count_tmp_entries(root: str, endpoints: list[str]) -> int:
    from ..storage.local import SYSTEM_META_BUCKET

    n = 0
    for ep in endpoints:
        base = os.path.join(root, ep, SYSTEM_META_BUCKET, "tmp")
        if os.path.isdir(base):
            n += len(os.listdir(base))
    return n


def crash_restart_put(root: str, seed: int = 7, payload_mib: int = 6,
                      disks: int = 8, parity: int = 4) -> dict:
    """The kill -9 recovery scenario: a real server subprocess dies
    mid-PUT (half the body on the wire), then a restart over the same
    drives must (a) purge the orphaned tmp staging, (b) show NO partial
    object — the pre-crash version reads back byte-identical — and
    (c) heal back to full redundancy with byte-identical content.
    Returns the evidence artifact."""
    import subprocess

    from ..api.sign import sign_v4_request
    from ..object.pools import ErasureServerPools
    from ..object.sets import ErasureSets
    from ..storage.local import LocalStorage

    endpoints = [f"crash-d{i}" for i in range(disks)]
    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    # One process per chip: the parent may hold it, the child never may.
    env["JAX_PLATFORMS"] = "cpu"
    env["MTPU_INLINE_THRESHOLD"] = "0"
    proc = subprocess.Popen(
        [sys.executable, "-m", "minio_tpu.faults.scenarios", "serve",
         root, str(disks), str(parity)] + endpoints,
        stdout=subprocess.PIPE, env=env, text=True,
    )
    artifact: dict = {"seed": seed}
    try:
        line = proc.stdout.readline()
        boot = json.loads(line)
        endpoint = boot["endpoint"]

        def req(method, path, body=b"", query=None):
            q = query or []
            headers = sign_v4_request(SECRET, ACCESS, method, endpoint,
                                      path, q, {}, body)
            conn = http.client.HTTPConnection(endpoint, timeout=60)
            try:
                qs = urllib.parse.urlencode(q)
                conn.request(method,
                             urllib.parse.quote(path)
                             + (f"?{qs}" if qs else ""),
                             body=body, headers=headers)
                r = conn.getresponse()
                return r.status, r.read()
            finally:
                conn.close()

        assert req("PUT", "/crash")[0] == 200
        committed = _payload(seed, payload_mib * MIB)
        st, _ = req("PUT", "/crash/victim", body=committed)
        assert st == 200, f"baseline PUT: {st}"

        # The overwrite that dies on the wire: send headers + half the
        # body, give the pipeline a beat to stage tmp shards, SIGKILL.
        overwrite = _payload(seed + 1, payload_mib * MIB)
        headers = sign_v4_request(SECRET, ACCESS, "PUT", endpoint,
                                  "/crash/victim", [], {}, overwrite)
        conn = http.client.HTTPConnection(endpoint, timeout=60)
        conn.putrequest("PUT", "/crash/victim")
        for k, v in headers.items():
            conn.putheader(k, v)
        if not any(k.lower() == "content-length" for k in headers):
            conn.putheader("Content-Length", str(len(overwrite)))
        conn.endheaders()
        conn.send(overwrite[: len(overwrite) // 2])
        time.sleep(0.4)  # let shard writers stage under tmp
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        conn.close()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)

    artifact["tmp_entries_after_crash"] = _count_tmp_entries(
        root, endpoints)

    # ---- restart over the same drives: the REAL recovery path ----
    raw = [LocalStorage(os.path.join(root, ep), endpoint=ep)
           for ep in endpoints]
    sets = ErasureSets(raw, disks, default_parity=parity, pool_index=0)
    sets.load_format()  # boot-time recovery: purges stale tmp
    ol = ErasureServerPools([sets])
    artifact["tmp_entries_after_restart"] = _count_tmp_entries(
        root, endpoints)

    import io as _io

    sink = _io.BytesIO()
    ol.get_object("crash", "victim", sink)
    artifact["pre_crash_version_intact"] = sink.getvalue() == committed
    # No partial overwrite anywhere: every disk's visible version must
    # carry the committed object's size.
    partials = []
    for d in raw:
        try:
            fi = d.read_version("crash", "victim")
        except Exception:  # noqa: BLE001  # except-ok: a disk the commit fan-out missed is exactly what the heal step below repairs
            continue
        if fi.size != len(committed):
            partials.append(d.endpoint())
    artifact["partial_visible_on"] = partials

    # Heal to full redundancy, then byte-identical re-read.
    ol.heal_object("crash", "victim")
    for pool in ol.pools:
        for es in pool.sets:
            for b, o, v in es.drain_mrf():
                ol.heal_object(b, o, v, remove_dangling=True)
    sink = _io.BytesIO()
    ol.get_object("crash", "victim", sink)
    artifact["healed_byte_identical"] = sink.getvalue() == committed
    artifact["recovered"] = (
        artifact["tmp_entries_after_restart"] == 0
        and artifact["pre_crash_version_intact"]
        and not partials
        and artifact["healed_byte_identical"]
    )
    return artifact


def _serve_cli() -> None:
    """`python -m minio_tpu.faults.scenarios serve <root> <disks>
    <parity> <ep...>`: boot a real signed S3 server over the given
    drive roots (loading an existing format if present — the restart
    half of the crash scenario), print {"endpoint": ...} and serve
    until killed."""
    from ..api import S3Server
    from ..bucket import BucketMetadataSys
    from ..iam import IAMSys
    from ..object.pools import ErasureServerPools
    from ..object.sets import ErasureSets
    from ..storage.local import LocalStorage
    from ..utils.errors import ErrUnformattedDisk

    root, n, parity = sys.argv[2], int(sys.argv[3]), int(sys.argv[4])
    endpoints = sys.argv[5:] or [f"crash-d{i}" for i in range(n)]
    disks = [LocalStorage(os.path.join(root, ep), endpoint=ep)
             for ep in endpoints]
    sets = ErasureSets(disks, n, default_parity=parity, pool_index=0)
    try:
        sets.load_format()
    except ErrUnformattedDisk:
        sets.init_format()
    ol = ErasureServerPools([sets])
    srv = S3Server(ol, IAMSys(ACCESS, SECRET),
                   BucketMetadataSys(ol)).start()
    print(json.dumps({"endpoint": srv.endpoint}), flush=True)
    while True:  # killed by the parent (that's the scenario)
        time.sleep(3600)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "serve":
        _serve_cli()
    else:
        sys.stderr.write(
            "usage: python -m minio_tpu.faults.scenarios serve "
            "<root> <disks> <parity> [endpoints...]\n")
        sys.exit(2)
