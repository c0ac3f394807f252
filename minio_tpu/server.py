"""Server bootstrap: assemble the full stack (object layer from endpoint
layout, IAM, bucket metadata, config, events, observability, background
services, S3 front-end) — behavioral parity with the reference's
serverMain (cmd/server-main.go:361-516: self-tests, endpoint parse,
subsystem init, HTTP start, background services).
"""

from __future__ import annotations

import os
import sys
import time

from .api import S3Server
from .background import DataScanner, DiskMonitor, MRFHealer
from .bucket import BucketMetadataSys
from .config import ConfigSys
from .event import EventNotifier, targets_from_config
from .iam import IAMSys, ObjectStoreBackend
from .object.fs import FSObjects
from .object.pools import ErasureServerPools
from .object.sets import ErasureSets
from .observability import Logger, Metrics, TraceHub
from .storage.fileinfo import new_uuid
from .storage.local import LocalStorage
from .utils import ellipses


def erasure_self_test():
    """Startup correctness gate (ref erasureSelfTest,
    cmd/erasure-coding.go:157-215): encode+reconstruct round trip for a
    few geometries; hard-fails the server on mismatch."""
    import numpy as np

    from .erasure.codec import Erasure

    rng = np.random.default_rng(0xC0DEC)
    for k, m in ((2, 2), (4, 2), (12, 4)):
        e = Erasure(k, m, k * 256)
        data = rng.integers(0, 256, k * 256, dtype=np.uint8).tobytes()
        shards = e.encode_data(data)
        for dead in range(m):
            shards[dead] = None
        e.decode_data_blocks(shards)
        if e.join(shards, len(data)) != data:
            raise RuntimeError("erasure self-test failed")


def bitrot_self_test():
    """ref bitrotSelfTest (cmd/bitrot.go:207-238)."""
    from .erasure.bitrot import BitrotAlgorithm

    vectors = {
        BitrotAlgorithm.SHA256:
            "40aff2e9d2d8922e47afd4648e6967497158785fbd1da870e7110266bf944880",
        BitrotAlgorithm.HIGHWAYHASH256S: None,  # checked vs numpy oracle
    }
    payload = bytes(range(256))
    h = BitrotAlgorithm.SHA256.new()
    h.update(payload)
    if h.hexdigest() != vectors[BitrotAlgorithm.SHA256]:
        raise RuntimeError("bitrot self-test failed: sha256")
    from .ops import highwayhash

    h = BitrotAlgorithm.HIGHWAYHASH256S.new()
    h.update(payload)
    if h.digest() != highwayhash.hash256(payload):
        raise RuntimeError("bitrot self-test failed: highwayhash")


def _split_url(ep: str) -> tuple[str, str]:
    """'http://host:port/path' -> ('host:port', '/path')."""
    import urllib.parse

    u = urllib.parse.urlsplit(ep)
    return u.netloc, u.path


class Server:
    """One assembled minio-tpu server process.

    Multi-node topology (ref registerDistErasureRouters +
    newErasureServerPools): endpoints given as URLs
    (`http://host:port/path`) split into local disks (netloc ==
    `storage_address`, served to peers over the storage REST plane bound
    at that address) and remote disks (RemoteStorage clients). The peer
    control plane binds at storage port + 1 on every node by convention.
    Internode RPC is authenticated with the root credential (the
    reference signs internode requests the same way)."""

    FORMAT_WAIT_S = 30.0

    def __init__(self, endpoint_args: list[str], address: str = "127.0.0.1",
                 port: int = 9000, root_user: str | None = None,
                 root_password: str | None = None, fs_mode: bool = False,
                 set_drive_count: int | None = None,
                 enable_scanner: bool = True,
                 storage_address: str | None = None,
                 certs_dir: str | None = None):
        erasure_self_test()
        bitrot_self_test()
        # --- TLS first: every plane (S3, storage, lock, peer) binds
        # after this, and the RPC clients consult the global manager, so
        # certs must be live before any listener or dial exists
        # (ref cmd/server-main.go:431-433 getTLSConfig before newAllSubsystems).
        from .utils import certs as certs_mod

        self.cert_manager = None
        certs_dir = certs_dir or os.environ.get("MTPU_CERTS_DIR", "")
        if certs_dir:
            pair = certs_mod.find_certs(certs_dir)
            if pair is None:
                # An explicitly requested TLS dir with no usable pair
                # must fail loudly — silently serving the RPC planes'
                # bearer secrets in plaintext is the worst outcome.
                raise ValueError(
                    f"--certs-dir {certs_dir!r}: public.crt/private.key "
                    "not found"
                )
            self.cert_manager = certs_mod.CertManager(*pair).start_watcher()
            certs_mod.set_global_tls(self.cert_manager)
        self.root_user = root_user or os.environ.get(
            "MTPU_ROOT_USER", "minioadmin"
        )
        self.root_password = root_password or os.environ.get(
            "MTPU_ROOT_PASSWORD", "minioadmin"
        )

        # Metrics come up first so the storage layer can record per-op
        # counters from the very first format read.
        self.metrics = Metrics()
        # The erasure hot paths flush per-stage pipeline telemetry
        # (put/get/heal stage timings) through this process-global hook
        # — plumbing a registry handle down into erasure/streaming.py
        # would thread it through every call site.
        from .pipeline import metrics as pipeline_metrics

        pipeline_metrics.set_registry(self.metrics)
        # Robustness telemetry (hedged reads, detached stragglers) and
        # dsync unlock-failure counts flow through the same hooks.
        from .distributed import dsync as _dsync
        from .distributed import rest as _rest
        from .erasure import streaming as _streaming
        from .utils import fanout as _fanout

        _streaming.set_metrics(self.metrics)
        _dsync.set_metrics(self.metrics)
        _fanout.set_metrics(self.metrics)
        # RPC transient-retry accounting (mtpu_rpc_retries_total).
        _rest.set_metrics(self.metrics)
        # The front end's bucket checks, memo against drives
        # (mtpu_bucket_check_total).
        from .object import pools as _pools

        _pools.set_metrics(self.metrics)
        # Concurrency plane: the encode/read admission governors and
        # the GIL-free worker pool mirror admitted/queued/rejected and
        # worker-health series onto the same registry (mtpu_admission_*
        # / mtpu_worker_*). The pool is DEFAULT-ON (ISSUE 11): arm it
        # at boot — auto-sized from the core count, inert on 1-core or
        # no-native hosts, MTPU_WORKER_POOL=0 opts out — so the first
        # request never pays the spawn and the worker_armed gauge
        # records the arm decision (and its reason) from the start.
        from .pipeline import admission as _admission
        from .pipeline import workers as _workers

        _admission.set_metrics(self.metrics)
        _workers.set_metrics(self.metrics)
        _workers.armed()
        # Codec registry: selection/dispatch counters and probe gauges
        # (mtpu_codec_*) for the pluggable erasure-codec plane.
        from .erasure import registry as _codec_registry

        _codec_registry.set_metrics(self.metrics)
        # A forced device/mesh engine reads its backend now, not at the
        # first large PUT: the banner names it, and a missing chip
        # stops the server here.
        self.engine_backend = _codec_registry.forced_engine_backend()
        # Request-span tracing plane (ISSUE 12): per-kind latency
        # histograms (mtpu_span_seconds) and slow-request capture
        # counts flow through the same registry; pub/sub buses count
        # their slow-subscriber drops (mtpu_pubsub_dropped_total).
        from .observability import pubsub as _pubsub
        from .observability import spans as _spans

        _spans.set_metrics(self.metrics)
        _pubsub.set_metrics(self.metrics)
        # Runtime lock-order checker (tools/analysis/lockgraph): armed
        # only when the operator sets MTPU_LOCK_CHECK=1 — instruments
        # every lock created from here on and exposes cycle/hold-time
        # reports (docs/ANALYSIS.md). The tools package lives at the
        # repo root, so a pip-installed deployment without it skips
        # silently.
        if os.environ.get("MTPU_LOCK_CHECK", "0") == "1":
            try:
                from tools.analysis import lockgraph as _lockgraph

                _lockgraph.enable_from_env()
            except ImportError as exc:
                # An explicit operator opt-in must never no-op
                # silently — say why the checker stayed off.
                sys.stderr.write(
                    f"minio-tpu: MTPU_LOCK_CHECK=1 ignored: "
                    f"tools.analysis.lockgraph not importable ({exc})\n"
                )
        # Mesh serving-engine counters (collective dispatches, dp-group
        # batches, per-lane bytes) mirror onto the same registry; the
        # module import is jax-free, so wiring it costs nothing on
        # hosts that never select the mesh engine.
        from .parallel import metrics as _mesh_metrics

        _mesh_metrics.set_metrics(self.metrics)
        # Hung-drive tolerance knobs (config subsystem `drive`): env
        # overrides apply immediately; persisted operator values re-apply
        # after config_sys.load() below.
        from .config.config import Config as _DriveCfg
        from .storage.diskcheck import configure_robustness

        configure_robustness(_DriveCfg().get("drive"))
        self.storage_server = None
        self.peer_server = None
        self.lock_server = None
        self.notification = None
        self._listing_coordinator = None

        # --- object layer from endpoint layout (ref newObjectLayer) ---
        if fs_mode or (
            len(endpoint_args) == 1
            and not ellipses.has_ellipses(endpoint_args[0])
            and "://" not in endpoint_args[0]
        ):
            self.object_layer = FSObjects(endpoint_args[0])
            self.mode = "fs"
        else:
            layout = ellipses.parse_server_endpoints(
                endpoint_args, set_drive_count
            )
            all_eps = [ep for pool in layout["pools"] for ep in pool]
            distributed = any("://" in ep for ep in all_eps)
            if distributed:
                mk_disk = self._start_storage_plane(
                    all_eps, storage_address
                )
            else:
                def mk_disk(ep):
                    return self._wrap_disk(
                        LocalStorage(ep, endpoint=ep, metrics=self.metrics),
                        ep)
            pools = []
            for pi, endpoints in enumerate(layout["pools"]):
                # Every disk is wrapped in the per-op metrics/disk-id
                # decorator (ref xl-storage-disk-id-check.go).
                disks = [mk_disk(ep) for ep in endpoints]
                es = ErasureSets(
                    disks, layout["set_drive_count"],
                    deployment_id=self._deployment_id(disks),
                    pool_index=pi,
                )
                if distributed:
                    # Only the node owning the FIRST endpoint formats a
                    # fresh deployment; everyone else waits for the
                    # format to appear (ref waitForFormatErasure).
                    leader = (
                        _split_url(all_eps[0])[0] == storage_address
                    )
                    self._format_distributed(es, leader)
                elif self._any_formatted(disks):
                    # Existing deployment: format must load; never
                    # reformat over data (a new deployment_id would
                    # reshuffle sipHash placement and orphan every
                    # object, ref waitForFormatErasure semantics).
                    es.load_format()
                else:
                    es.init_format()
                pools.append(es)
            self.object_layer = ErasureServerPools(pools)
            self.mode = "erasure"

        # --- subsystems (ref initAllSubsystems) ---
        self.trace = TraceHub()
        # Finished span trees stream to `mc admin trace ?spans=true`
        # subscribers through the same hub as call records.
        _spans.set_trace_hub(self.trace)
        self.logger = Logger()
        # IAM backend: etcd when configured (env MTPU_ETCD_ENDPOINTS /
        # config subsystem `etcd`, ref cmd/etcd.go + iam-etcd-store.go),
        # else the object layer. etcd config must come from env here:
        # IAM initializes before the persisted config loads, exactly
        # like the reference reads etcd env ahead of initAllSubsystems.
        from .config.config import Config as _Cfg

        etcd_kvs = _Cfg().get("etcd")
        self._iam_watcher = None
        if (etcd_kvs.get("endpoints", "") or "").strip():
            from .iam.etcd import EtcdIAMBackend, EtcdKV

            iam_store = EtcdIAMBackend(
                EtcdKV(etcd_kvs["endpoints"].split(",")),
                etcd_kvs.get("path_prefix", ""),
            )
        else:
            iam_store = ObjectStoreBackend(self.object_layer)
        self.iam = IAMSys(
            self.root_user, self.root_password, store=iam_store,
        )
        self.iam.load()
        if hasattr(iam_store, "start_watch"):
            # Watch-driven cross-node invalidation: any node's IAM write
            # reloads every node's cache within the watch latency.
            self._iam_watcher = iam_store.start_watch(self.iam.reload)
        self.bucket_meta = BucketMetadataSys(self.object_layer)
        self.config_sys = ConfigSys(
            self.object_layer, secret=self.root_password
        )
        self.config_sys.load()
        # Re-apply hung-drive knobs now that persisted operator values
        # are available (env still wins inside Config.get).
        from .storage.diskcheck import configure_robustness as _cfg_robust

        _cfg_robust(self.config_sys.config.get("drive"))
        # Optional disk cache in front of the API's object layer (the
        # background services keep the raw layer, like the reference's
        # cacheObjects wrapping only the served ObjectLayer).
        from .object.cache import build_cache_layer

        self.cache_layer = build_cache_layer(
            self.object_layer, self.config_sys.config
        )
        region = self.config_sys.config.get("region")["name"]
        targets = targets_from_config(self.config_sys.config, region)
        self.notifier = EventNotifier(
            self.bucket_meta, targets, region,
            metrics=self.metrics, logger=self.logger,
        )

        # --- background services (ref initAutoHeal/initDataScanner) ---
        self.mrf = MRFHealer(
            self.object_layer, metrics=self.metrics, logger=self.logger
        )
        # Update tracker (bloom of changed buckets, persisted): writes
        # mark it via the object layer; the scanner skips unchanged
        # buckets (ref cmd/data-update-tracker.go).
        from .background import DataUpdateTracker

        # Only wire a tracker when the object layer actually marks it on
        # writes (erasure pools do; FSObjects doesn't) — a never-marked
        # tracker would make the scanner skip every bucket forever.
        if hasattr(self.object_layer, "update_tracker"):
            self.update_tracker = DataUpdateTracker(self.object_layer)
            self.object_layer.update_tracker = self.update_tracker
        else:
            self.update_tracker = None
        # Remote tiers + the ILM transition engine (ref
        # cmd/bucket-lifecycle.go transitionState).
        from .tier import TierConfigMgr, TierEngine

        self.tiers = TierConfigMgr(self.object_layer)
        self.tier_engine = TierEngine(
            self.object_layer, self.tiers, metrics=self.metrics,
            logger=self.logger,
        ) if hasattr(self.object_layer, "transition_object") else None
        self.scanner = DataScanner(
            self.object_layer, self.bucket_meta,
            metrics=self.metrics, logger=self.logger,
            tracker=self.update_tracker, tier_engine=self.tier_engine,
        )
        # Disk liveness loop (ref monitorAndConnectEndpoints,
        # cmd/erasure-sets.go:282): offline detection + reconnect-driven
        # MRF heal.
        self.disk_monitor = DiskMonitor(
            self.object_layer, mrf_healer=self.mrf,
            metrics=self.metrics, logger=self.logger,
        )
        # Replaced-drive detection + resumable back-fill heal (ref
        # initAutoHeal / healingTracker).
        from .background import FreshDiskHealer

        self.fresh_disk_healer = FreshDiskHealer(
            self.object_layer, metrics=self.metrics, logger=self.logger,
        ) if self.mode != "fs" else None
        self._enable_scanner = enable_scanner

        # --- HTTP front-end ---
        from .crypto import SSEConfig

        from .bucket.quota import BucketQuotaSys

        def _scanner_usage():
            # None until the scanner has produced a usage snapshot (FS
            # mode / scanner disabled / first cycle pending): the quota
            # system then uses its bounded fallback walk instead of
            # treating every bucket as empty.
            if not self.scanner.usage.last_update_ns:
                return None
            return {
                b: u.objects_size
                for b, u in self.scanner.usage.buckets_usage.items()
            }

        # Peer mesh before the S3 front-end so admin fan-out endpoints
        # see the mesh from the first request.
        if self.storage_server is not None:
            self._start_peer_mesh()

        self.s3 = S3Server(
            self.cache_layer or self.object_layer, self.iam,
            self.bucket_meta,
            notify=self.notifier, region=region, host=address, port=port,
            metrics=self.metrics, trace=self.trace,
            config_sys=self.config_sys, notification=self.notification,
            # SSE-KMS default key id follows the kms_kes config subsystem
            # (ref cmd/crypto/kes.go key_name); the key-name registry
            # persists in the cluster meta bucket so admin-created keys
            # survive restarts.
            sse_config=SSEConfig(
                self.root_password,
                kms=self._build_kms(),
            ),
            # Quota admission reads the scanner's usage accounting, never
            # a live walk on the PUT path (ref BucketQuotaSys 1s-TTL
            # cache over loadDataUsageFromBackend).
            quota=BucketQuotaSys(self.object_layer, self.bucket_meta,
                                 usage_fn=_scanner_usage),
            tier_engine=self.tier_engine, tiers=self.tiers,
            logger=self.logger,
        )
        # One heal-sequence registry for the deployment — the admin API
        # owns it (background/healseq.py AllHealState).
        self.heal_state = self.s3.admin.heal_state
        # Scrape-time gauge collector over every live subsystem (the
        # reference computes most v2 metrics in the handler from global
        # state; ref cmd/metrics-v2.go).
        from .observability.metrics_v2 import MetricsCollector

        self.s3.admin.collector = MetricsCollector(
            self.metrics, object_layer=self.object_layer,
            scanner=self.scanner, repl_pool=self.s3.repl_pool,
            cache=self.cache_layer, iam=self.iam,
            mrf=self.mrf,
        )
        # Service control: `mc admin service restart|stop` unblocks
        # wait() with the requested action (ref cmd/service.go).
        self._service_event = __import__("threading").Event()
        self.service_action: str | None = None

        def _on_service(action: str):
            self.service_action = action
            self._service_event.set()

        self.s3.service_cb = _on_service
        self.started_ns = time.time_ns()

    def _build_kms(self):
        """KES-backed KMS when kms_kes.endpoint is configured (mTLS
        client to an external KES server, ref cmd/crypto/kes.go);
        otherwise LocalKMS whose key registry lives under `.minio.sys`
        in the object layer (key NAMES only; material derives from the
        root secret — ref pkg/kms + admin KMS key surface)."""
        import io as _io

        from .crypto.kes import kms_from_config
        from .utils.errors import StorageError

        ol = self.object_layer

        class _Persist:
            PATH = "kms/keys.json"

            def load(self):
                try:
                    return ol.get_object_bytes(".minio.sys", self.PATH)
                except StorageError:
                    return None

            def save(self, data: bytes):
                try:
                    ol.put_object(".minio.sys", self.PATH,
                                  _io.BytesIO(data), len(data))
                except StorageError:
                    ol.make_bucket(".minio.sys")
                    ol.put_object(".minio.sys", self.PATH,
                                  _io.BytesIO(data), len(data))

        return kms_from_config(
            self.config_sys.config.get("kms_kes"),
            self.root_password,
            persist=_Persist(),
        )

    # --- distributed plumbing ---

    def _start_storage_plane(self, all_eps: list[str],
                             storage_address: str | None):
        """Serve this node's disks to the mesh BEFORE the object layer
        initializes (ref registerDistErasureRouters running ahead of
        newObjectLayer), and return the local/remote disk factory."""
        from .distributed.storage_rest import (
            RemoteStorage,
            StorageRESTServer,
        )

        if storage_address is None:
            raise ValueError(
                "URL endpoints need storage_address=host:port naming "
                "this node's storage plane"
            )
        if any("://" not in ep for ep in all_eps):
            raise ValueError("cannot mix URL and plain path endpoints")
        secret = self.root_password
        local_disks = []
        for ep in all_eps:
            netloc, path = _split_url(ep)
            if netloc == storage_address:
                local_disks.append(LocalStorage(path, endpoint=ep,
                                               metrics=self.metrics))
        if not local_disks:
            raise ValueError(
                f"no endpoint matches this node ({storage_address})"
            )
        shost, sport = storage_address.rsplit(":", 1)
        self.storage_server = StorageRESTServer(
            local_disks, secret, shost, int(sport)
        ).start()
        self._storage_address = storage_address
        self._cluster_nodes = sorted(
            {_split_url(ep)[0] for ep in all_eps}
        )
        # The peer plane binds port+1 and the lock plane port+2: nodes
        # sharing a host need port spacing >= 3 or the planes collide.
        # Fail LOUDLY at boot, not with a cryptic EADDRINUSE later.
        by_host: dict[str, list[int]] = {}
        for n in self._cluster_nodes:
            h, p = n.rsplit(":", 1)
            by_host.setdefault(h, []).append(int(p))
        for h, ports in by_host.items():
            ports.sort()
            for a, b in zip(ports, ports[1:]):
                if b - a < 3:
                    raise ValueError(
                        f"storage ports {a} and {b} on {h} are closer "
                        "than 3 apart; the peer (+1) and lock (+2) "
                        "planes would collide"
                    )
        local_by_ep = {d.endpoint(): d for d in local_disks}

        def mk_disk(ep):
            if ep in local_by_ep:
                return self._wrap_disk(local_by_ep[ep], ep)
            netloc, _ = _split_url(ep)
            return self._wrap_disk(RemoteStorage(netloc, ep, secret), ep)

        return mk_disk

    def _wrap_disk(self, raw, ep: str):
        """Per-disk decorator stack: the env-gated fault injector
        (chaos drills; minio_tpu/faults) innermost, then the metrics +
        disk-id + health wrapper with its circuit breaker and per-op
        deadlines (ref xl-storage-disk-id-check.go)."""
        from . import faults
        from .storage.diskcheck import DiskHealth, MetricsDisk

        if faults.enabled():
            raw = faults.FaultDisk(raw)
        return MetricsDisk(raw, self.metrics, health=DiskHealth(ep))

    def _format_distributed(self, es, leader: bool):
        """Fresh-deployment format with cross-node coordination: the
        leader formats (retrying while peers' storage planes come up);
        followers poll until the format lands on their local disks."""
        deadline = time.monotonic() + self.FORMAT_WAIT_S
        last_err: Exception | None = None
        while time.monotonic() < deadline:
            try:
                if self._any_formatted(es.disks):
                    es.load_format()
                    return
                if leader:
                    es.init_format()
                    return
            except Exception as exc:  # noqa: BLE001 - peers still booting
                last_err = exc
            time.sleep(0.2)
        raise RuntimeError(
            f"format coordination timed out after {self.FORMAT_WAIT_S}s: "
            f"{last_err}"
        )

    def _start_peer_mesh(self):
        """Peer control plane + cross-node listing coordination + the
        dsync lock plane (ref peer-rest-server, metacache-server-pool,
        lock-rest-server). Lock plane binds at storage port + 2."""
        from .distributed.dsync import Dsync, LockRESTServer
        from .distributed.listing import ListingCoordinator
        from .distributed.peer import (
            NotificationSys,
            PeerClient,
            PeerRESTServer,
        )

        secret = self.root_password
        shost, sport = self._storage_address.rsplit(":", 1)
        # --- lock plane: quorum DRWMutex over every node's locker so
        # namespace locks hold CLUSTER-wide (ref cmd/namespace-lock.go
        # distributed branch).
        self.lock_server = LockRESTServer(
            secret, shost, int(sport) + 2
        ).start()

        def lock_addr(node: str) -> str:
            h, p = node.rsplit(":", 1)
            return f"{h}:{int(p) + 2}"

        dsync = Dsync(
            local=self.lock_server.locker,
            remote_endpoints=[
                lock_addr(n) for n in self._cluster_nodes
                if n != self._storage_address
            ],
            secret=secret,
        )
        for pool in self.object_layer.pools:
            for es in pool.sets:
                es.dist_lockers = dsync.lockers
                es.dist_owner = self._storage_address
        self.peer_server = PeerRESTServer(
            secret, shost, int(sport) + 1,
            bucket_meta=self.bucket_meta, iam=self.iam,
            object_layer=self.object_layer, trace=self.trace,
            logger=self.logger,
        ).start()

        def peer_addr(node: str) -> str:
            h, p = node.rsplit(":", 1)
            return f"{h}:{int(p) + 1}"

        others = [
            n for n in self._cluster_nodes if n != self._storage_address
        ]
        peer_clients = {
            peer_addr(n): PeerClient(peer_addr(n), secret) for n in others
        }
        self.notification = NotificationSys(list(peer_clients.values()))
        self._listing_coordinator = ListingCoordinator(
            self.object_layer, peer_addr(self._storage_address),
            peer_clients,
        )
        self.object_layer.listing_coordinator = self._listing_coordinator

    @staticmethod
    def _any_formatted(disks) -> bool:
        """True when any disk already carries a format.json."""
        from .object.sets import read_format

        for d in disks:
            try:
                read_format(d)
                return True
            except Exception:  # noqa: BLE001 - unformatted/unreadable disk
                continue
        return False

    @staticmethod
    def _deployment_id(disks) -> str:
        """Reuse the deployment id from any formatted disk, else mint one
        (ref waitForFormatErasure / formatErasureV3)."""
        from .object.sets import read_format

        for d in disks:
            try:
                fmt = read_format(d)
                return fmt["id"]
            except Exception:  # noqa: BLE001 - unformatted disk
                continue
        return new_uuid()

    def start(self):
        if self.mode == "erasure":
            # Disk liveness + MRF heal are correctness features, not
            # scanner load — they run regardless of enable_scanner.
            self.mrf.start()
            self.disk_monitor.start()
            if self.fresh_disk_healer is not None:
                self.fresh_disk_healer.start()
            # Tier configs gate READS of transitioned objects — load
            # them regardless of whether the scanner runs.
            self.tiers.load()
            if self._enable_scanner:
                if self.update_tracker is not None:
                    self.update_tracker.load()
                self.scanner.start()
        self.s3.start()
        return self

    def stop(self):
        if self._iam_watcher is not None:
            self._iam_watcher.stop()
        self.s3.stop()
        self.scanner.stop()
        self.mrf.stop()
        self.disk_monitor.stop()
        if self.fresh_disk_healer is not None:
            self.fresh_disk_healer.stop()
        self.notifier.close()
        if self._listing_coordinator is not None:
            self._listing_coordinator.close()
        if self.peer_server is not None:
            self.peer_server.stop()
        if getattr(self, "lock_server", None) is not None:
            self.lock_server.stop()
        if self.storage_server is not None:
            self.storage_server.stop()
        if self.cert_manager is not None:
            from .utils import certs as certs_mod

            self.cert_manager.stop()
            if certs_mod.global_tls() is self.cert_manager:
                certs_mod.set_global_tls(None)

    @property
    def endpoint(self) -> str:
        return self.s3.endpoint

    def wait(self) -> str | None:
        """Block until SIGTERM/SIGINT or an admin service action.
        Returns 'restart' / 'stop' for admin-driven shutdowns, None for
        signals (ref serverMain's signal loop + serviceSignalCh)."""
        import signal

        def handler(signum, frame):
            self._service_event.set()

        try:
            signal.signal(signal.SIGTERM, handler)
            signal.signal(signal.SIGINT, handler)
        except ValueError:
            pass  # not the main thread: admin service actions only
        self._service_event.wait()
        return self.service_action
