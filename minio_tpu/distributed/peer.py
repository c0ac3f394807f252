"""Peer control plane + bootstrap: the node mesh used for cache
invalidation, cluster info collection, and the startup config-consistency
handshake — behavioral parity with the reference's cmd/peer-rest-server.go
/ cmd/peer-rest-client.go / cmd/notification.go (hub) and
cmd/bootstrap-peer-server.go (verifyServerSystemConfig).
"""

from __future__ import annotations

import os
import threading
import time

from .rest import RPCClient, RPCError, RPCServer

PEER_PREFIX = "/mtpu/peer/v1"
BOOTSTRAP_PREFIX = "/mtpu/bootstrap/v1"


class PeerRESTServer:
    """Serve this node's control-plane methods to the mesh."""

    def __init__(self, secret: str, host: str = "127.0.0.1", port: int = 0,
                 bucket_meta=None, iam=None, object_layer=None,
                 lockers=None, trace=None, logger=None):
        self.bucket_meta = bucket_meta
        self.iam = iam
        self.object_layer = object_layer
        self.lockers = lockers
        self.trace = trace
        self.logger = logger
        self._profiler = None
        self._prof_lock = threading.Lock()
        self.started_ns = time.time_ns()
        self.rpc = RPCServer(PEER_PREFIX, secret, host, port)
        for name in ("ping", "load_bucket_metadata", "delete_bucket_metadata",
                     "load_user", "load_policy", "server_info",
                     "local_storage_info", "get_locks", "signal_service",
                     "list_page", "bump_listing_gen",
                     "trace_poll", "start_profiling", "download_profiling",
                     "console_log"):
            self.rpc.register(name, getattr(self, f"_h_{name}"))

    def start(self):
        self.rpc.start()
        return self

    def stop(self):
        self.rpc.stop()

    @property
    def endpoint(self) -> str:
        return self.rpc.endpoint

    # --- handlers ---

    def _h_ping(self, args, body):
        return {"ok": True}

    def _h_load_bucket_metadata(self, args, body):
        if self.bucket_meta is not None:
            self.bucket_meta.invalidate(args["bucket"])
        return {}

    def _h_delete_bucket_metadata(self, args, body):
        """A peer deleted the bucket: drop its metadata and the object
        layer's memo of it, so the next request here asks the drives."""
        if self.bucket_meta is not None:
            self.bucket_meta.invalidate(args["bucket"])
        if self.object_layer is not None:
            self.object_layer.forget_bucket(args["bucket"])
        return {}

    def _h_load_user(self, args, body):
        if self.iam is not None:
            self.iam.load()
        return {}

    def _h_load_policy(self, args, body):
        if self.iam is not None:
            self.iam.load()
        return {}

    def _h_server_info(self, args, body):
        return {
            "endpoint": self.endpoint,
            "uptime_ns": time.time_ns() - self.started_ns,
            "version": "minio-tpu/0.1",
            "pid": os.getpid(),
        }

    def _h_local_storage_info(self, args, body):
        if self.object_layer is None:
            return {"disks": []}
        disks = []
        for pool in getattr(self.object_layer, "pools", []):
            for d in pool.disks:
                if d is None:
                    continue
                try:
                    di = d.disk_info()
                    disks.append({
                        "endpoint": di.endpoint, "total": di.total,
                        "free": di.free, "used": di.used, "error": "",
                    })
                except Exception as exc:  # noqa: BLE001 - per-disk status
                    disks.append({"endpoint": d.endpoint(), "error": str(exc)})
        return {"disks": disks}

    def _h_get_locks(self, args, body):
        if self.lockers is None:
            return {"locks": {}}
        return {"locks": {
            res: [
                {"owner": g["owner"], "writer": g["writer"], "ts": g["ts"]}
                for g in self.lockers.held(res)
            ]
            for res in list(self.lockers._map)
        }}

    def _h_signal_service(self, args, body):
        # restart/stop signaling is a host-process concern; recorded only.
        return {"signal": args.get("signal", ""), "accepted": True}

    # --- metacache coordination (ref peerRESTMethodGetMetacacheListing;
    # --- see distributed/listing.py for the design) ---

    def _h_list_page(self, args, body):
        """Serve one listing page from THIS node's metacache — called by
        peers for listings this node owns."""
        ol = self.object_layer
        if ol is None or not hasattr(ol, "_metacache"):
            raise RuntimeError("no listing-capable object layer")
        bucket, prefix = args["bucket"], args.get("prefix", "")
        marker, count = args.get("marker", ""), int(args["count"])
        from ..object.metacache import StaleListingCache

        # Advance to at least the caller's generation: a node that just
        # wrote must never get a page older than its own write.
        caller_gen = int(args.get("gen", "0"))
        with ol._gen_lock:
            if ol._list_gen.get(bucket, 0) < caller_gen:
                ol._list_gen[bucket] = caller_gen
        while True:
            gen = ol._list_gen.get(bucket, 0)
            factory = ol._merged_stream_factory(bucket, prefix)
            try:
                entries, exhausted = ol._metacache.page(
                    bucket, prefix, gen, marker, count, factory
                )
                break
            except StaleListingCache:
                continue  # raced an invalidation; retry at the new gen
        return {
            "entries": [[n, bytes(b)] for n, b in entries],
            "exhausted": exhausted,
        }

    def _h_bump_listing_gen(self, args, body):
        """A peer mutated this bucket: move the local listing generation
        so caches built before the write die at the next page."""
        ol = self.object_layer
        if ol is not None and hasattr(ol, "invalidate_listings"):
            ol.invalidate_listings(args["bucket"])
        return {}

    # --- observability fan-in (ref peerRESTMethodTrace,
    # --- NotificationSys.StartProfiling cmd/notification.go:287,
    # --- peer /log console stream cmd/peer-rest-common.go:57) ---

    def _h_trace_poll(self, args, body):
        """Bounded poll of THIS node's trace bus for a mesh-wide
        `mc admin trace` (the reference streams; a poll window keeps the
        RPC plane request/response)."""
        if self.trace is None:
            return {"entries": []}
        import queue as _queue

        wait_s = min(float(args.get("wait", "1")), 10.0)
        q = self.trace.subscribe()
        out = []
        deadline = time.time() + wait_s
        try:
            while time.time() < deadline and len(out) < 1000:
                try:
                    out.append(q.get(
                        timeout=max(0.05, deadline - time.time())))
                except _queue.Empty:
                    break
        finally:
            self.trace.unsubscribe(q)
        return {"entries": out}

    def _h_start_profiling(self, args, body):
        from ..observability.profiler import SamplingProfiler

        with self._prof_lock:
            if self._profiler is not None and self._profiler.running:
                return {"status": "already running"}
            self._profiler = SamplingProfiler().start()
        return {"status": "started"}

    def _h_download_profiling(self, args, body):
        with self._prof_lock:
            prof, self._profiler = self._profiler, None
        if prof is None:
            return {"report": "", "running": False}
        return {"report": prof.stop_and_report(), "running": True}

    def _h_console_log(self, args, body):
        if self.logger is None:
            return {"entries": []}
        n = max(1, min(int(args.get("n", "100")), 1024))
        return {"entries": self.logger.recent(n)}


class PeerClient:
    """RPC client for one peer (ref cmd/peer-rest-client.go)."""

    def __init__(self, endpoint: str, secret: str):
        self.endpoint = endpoint
        self._c = RPCClient(endpoint, PEER_PREFIX, secret, timeout=10.0)

    def call(self, method: str, args: dict | None = None):
        return self._c.call(method, args)

    @property
    def online(self) -> bool:
        return self._c.online


class NotificationSys:
    """Fan-out hub over all peers (ref cmd/notification.go:1556 — the
    name is historical; it is the peer-broadcast mechanism)."""

    def __init__(self, peers: list[PeerClient]):
        self.peers = peers

    def _broadcast(self, method: str, args: dict | None = None) -> list:
        """Call every peer CONCURRENTLY (the reference fans out with one
        goroutine per peer; serial calls would stack trace-poll waits)."""
        from concurrent.futures import ThreadPoolExecutor

        if not self.peers:
            return []

        def one(p):
            try:
                return p.call(method, args)
            except RPCError as exc:
                return exc

        with ThreadPoolExecutor(max_workers=min(8, len(self.peers))) as ex:
            return list(ex.map(one, self.peers))

    def load_bucket_metadata(self, bucket: str):
        self._broadcast("load_bucket_metadata", {"bucket": bucket})

    def delete_bucket_metadata(self, bucket: str):
        self._broadcast("delete_bucket_metadata", {"bucket": bucket})

    def load_user(self):
        self._broadcast("load_user")

    def server_info(self) -> list[dict]:
        return [
            r for r in self._broadcast("server_info")
            if not isinstance(r, Exception)
        ]

    def storage_info(self) -> list[dict]:
        return [
            r for r in self._broadcast("local_storage_info")
            if not isinstance(r, Exception)
        ]

    def get_locks(self) -> list[dict]:
        return [
            r for r in self._broadcast("get_locks")
            if not isinstance(r, Exception)
        ]

    # --- observability fan-out (ref NotificationSys.StartProfiling,
    # --- DownloadProfilingData, peer trace subscribe) ---

    def trace_poll(self, wait_s: float = 1.0) -> list[dict]:
        """Merged trace entries from every peer's bus, time-ordered."""
        entries: list[dict] = []
        for r in self._broadcast("trace_poll", {"wait": str(wait_s)}):
            if not isinstance(r, Exception):
                entries.extend(r.get("entries", []))
        entries.sort(key=lambda e: e.get("time_ns", 0))
        return entries

    def start_profiling(self) -> dict:
        out = {}
        for p, r in zip(self.peers, self._broadcast("start_profiling")):
            out[p.endpoint] = (
                r.get("status") if not isinstance(r, Exception) else str(r)
            )
        return out

    def download_profiling(self) -> dict:
        """Per-node profile reports (the reference zips per-node pprof
        files, cmd/notification.go DownloadProfilingData)."""
        out = {}
        for p, r in zip(self.peers, self._broadcast("download_profiling")):
            if isinstance(r, Exception):
                out[p.endpoint] = f"error: {r}"
            elif r.get("running"):
                out[p.endpoint] = r.get("report", "")
        return out

    def console_log(self, n: int = 100) -> list[dict]:
        entries: list[dict] = []
        for p, r in zip(self.peers,
                        self._broadcast("console_log", {"n": str(n)})):
            if isinstance(r, Exception):
                continue
            for e in r.get("entries", []):
                e = dict(e)
                e["node"] = p.endpoint
                entries.append(e)
        entries.sort(key=lambda e: e.get("time", ""))
        return entries


class BootstrapServer:
    """Startup config handshake endpoint
    (ref cmd/bootstrap-peer-server.go:37 /verify)."""

    def __init__(self, secret: str, config: dict,
                 host: str = "127.0.0.1", port: int = 0):
        self.config = config
        self.rpc = RPCServer(BOOTSTRAP_PREFIX, secret, host, port)
        self.rpc.register("verify", self._h_verify)

    def start(self):
        self.rpc.start()
        return self

    def stop(self):
        self.rpc.stop()

    @property
    def endpoint(self) -> str:
        return self.rpc.endpoint

    def _h_verify(self, args, body):
        return dict(self.config)


def verify_cluster_config(local_config: dict, peer_endpoints: list[str],
                          secret: str, retries: int = 30,
                          delay_s: float = 0.2) -> None:
    """Loop until every peer reports an identical config fingerprint
    (ref cmd/server-main.go:446-460 verifyServerSystemConfig loop).
    Raises RuntimeError on persistent mismatch/unreachable peers."""
    last_err = None
    for _ in range(retries):
        ok = True
        for ep in peer_endpoints:
            client = RPCClient(ep, BOOTSTRAP_PREFIX, secret, timeout=5.0)
            try:
                remote = client.call("verify")
            except RPCError as exc:
                ok = False
                last_err = f"{ep} unreachable: {exc}"
                break
            if remote != local_config:
                ok = False
                last_err = (
                    f"{ep} config mismatch: {remote} != {local_config}"
                )
                break
        if ok:
            return
        time.sleep(delay_s)
    raise RuntimeError(f"cluster config verification failed: {last_err}")
