"""Generic node-to-node RPC: authed POST endpoints with msgpack bodies,
connection pooling, health checking — the equivalent of the reference's
cmd/rest/client.go (bearer-JWT authed per-method POSTs) re-designed on
Python http primitives with HMAC tokens.

All three distributed planes (storage, lock, peer-control) ride on this.
"""

from __future__ import annotations

import base64
import hashlib
import hmac
import http.client
import json
import logging
import random
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import msgpack

from ..observability import ioflow
from ..observability import spans as _spans

TOKEN_VALIDITY_S = 15 * 60

# --- transient-failure retry (idempotent methods only) ---------------------
# A 1s network blip (peer restart, conntrack flush) must not fail an
# in-flight GET whose shard read would succeed 100ms later. One
# jittered-backoff retry, only when the CALLER declared the method
# idempotent (reads/probes; a write retried after an ambiguous failure
# could apply twice), and only within the call's original deadline.
RETRY_MIN_BUDGET_S = 0.05
RETRY_BACKOFF_S = (0.02, 0.15)

RPC_DESCRIPTORS: list[tuple[str, str, str]] = [
    ("rpc_retries_total", "counter",
     "Idempotent RPC calls retried after a transient transport failure"),
    ("rpc_calls_total", "counter",
     "RPC calls this node made, every attempt counted, by plane "
     "(storage, lock, peer)"),
    ("rpc_sent_bytes_total", "counter",
     "Request body bytes of the RPC calls this node made, by plane"),
    ("rpc_served_seconds_total", "counter",
     "Seconds this node's RPC servers spent on calls, from the request "
     "read and parsed to the response written, by plane"),
]

# The planes every node of a deployment speaks: their series stand at 0
# from `set_metrics` on.
PLANES = ("storage", "lock", "peer")

_metrics = None  # guarded-by: _metrics_mu
_metrics_mu = threading.Lock()
# Process totals, importable by tests/bench without a registry.
RETRIES = {"total": 0}  # guarded-by: _metrics_mu


def set_metrics(registry) -> None:
    global _metrics
    with _metrics_mu:
        _metrics = registry
    if registry is not None:
        for plane in PLANES:
            for name in ("rpc_calls_total", "rpc_sent_bytes_total",
                         "rpc_served_seconds_total"):
                registry.inc(name, 0, plane=plane)


def _plane_of(prefix: str) -> str:
    """The plane a URL prefix serves: `/mtpu/storage/v1` -> `storage`."""
    parts = prefix.strip("/").split("/")
    return parts[1] if len(parts) > 2 else parts[0]


def _count(name: str, value: float, plane: str) -> None:
    """One series of one call, into the registry's own lock and no
    other: the call path takes that one already (its spans and the
    drive's op counters)."""
    # guardedby-ok: racy read of an atomically-rebound module global —
    # a call racing set_metrics counts into the old registry or none
    reg = _metrics
    if reg is not None:
        reg.inc(name, value, plane=plane)


def _note_retry() -> None:
    with _metrics_mu:
        RETRIES["total"] += 1
        reg = _metrics
    if reg is not None:
        reg.inc("rpc_retries_total")

# The byte-flow op tag crosses the wire in these headers so the node
# that OWNS the disk attributes its own syscall-layer bytes to the
# originating request's op-class — the proxy never counts remote bytes
# (each byte lands in exactly one node's ledger, correctly classified).
_IOFLOW_OP_HDR = "X-Mtpu-Ioflow-Op"
_IOFLOW_BUCKET_HDR = "X-Mtpu-Ioflow-Bucket"

_log = logging.getLogger("minio_tpu.rpc")


class RPCError(Exception):
    """Remote call failed; carries the remote error type name for
    re-raising typed storage errors client-side."""

    def __init__(self, kind: str, message: str):
        super().__init__(f"{kind}: {message}")
        self.kind = kind
        self.message = message


def make_token(secret: str, now: float | None = None) -> str:
    """HMAC cluster token: base64(payload).hexsig (the reference uses
    JWT with the root credential as signing key, cmd/rest/client.go:128)."""
    payload = json.dumps({
        "exp": (now or time.time()) + TOKEN_VALIDITY_S,
    }).encode()
    b64 = base64.urlsafe_b64encode(payload).decode()
    sig = hmac.new(secret.encode(), b64.encode(), hashlib.sha256).hexdigest()
    return f"{b64}.{sig}"


def verify_token(secret: str, token: str) -> bool:
    try:
        b64, sig = token.split(".", 1)
    except ValueError:
        return False
    want = hmac.new(secret.encode(), b64.encode(), hashlib.sha256).hexdigest()
    if not hmac.compare_digest(want, sig):
        return False
    try:
        payload = json.loads(base64.urlsafe_b64decode(b64))
    # except-ok: malformed credential classifies as invalid token; the False IS the outcome
    except Exception:
        return False
    return payload.get("exp", 0) > time.time()


class RPCServer:
    """HTTP server exposing named methods under a version prefix.

    Handlers: fn(args: dict, body: bytes) -> (result, stream) where
    result is msgpack-encoded and stream (optional file-like) is sent as
    the raw response body after the msgpack frame length header.
    """

    def __init__(self, prefix: str, secret: str, host: str = "127.0.0.1",
                 port: int = 0, tls=None):
        from ..utils import certs as _certs

        self.prefix = prefix.rstrip("/")
        self.plane = _plane_of(self.prefix)
        self.secret = secret
        # TLS: explicit manager, else the process-global one (set at
        # server boot) so every RPC plane upgrades together — bearer
        # secrets must never cross the wire in the clear when the
        # deployment has certs (ref cmd/server-main.go:431-433).
        self.tls = tls if tls is not None else _certs.global_tls()
        self._methods: dict = {}
        # Live connection sockets, so stop() can sever keep-alive peers —
        # shutdown() alone leaves pooled client connections being served
        # by their handler threads, which is not what "node died" means.
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def setup(self):
                super().setup()
                with outer._conns_lock:
                    outer._conns.add(self.connection)

            def finish(self):
                with outer._conns_lock:
                    outer._conns.discard(self.connection)
                super().finish()

            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                outer._handle(self)

        class _Server(ThreadingHTTPServer):
            def finish_request(self, request, client_address):
                # Per-connection TLS wrap in the HANDLER thread: wrapping
                # the listening socket would run handshakes in the accept
                # loop, letting one slow client stall every plane peer.
                if outer.tls is not None:
                    request = outer.tls.server_context.wrap_socket(
                        request, server_side=True
                    )
                super().finish_request(request, client_address)

            def handle_error(self, request, client_address):
                import ssl as _ssl
                import sys as _sys

                # Client resets/disconnects during node outages are
                # routine — never spray tracebacks to stderr for them;
                # ditto handshake failures from port scanners /
                # plaintext probes of a TLS plane.
                exc = _sys.exc_info()[1]
                if isinstance(exc, (ConnectionResetError,
                                    BrokenPipeError, TimeoutError,
                                    _ssl.SSLError)):
                    return
                super().handle_error(request, client_address)

        self.httpd = _Server((host, port), _Handler)
        self.port = self.httpd.server_address[1]
        self.host = host
        self._thread: threading.Thread | None = None

    def register(self, name: str, fn):
        self._methods[name] = fn
        # a client's `rpc` span of this method keeps it as its label
        _spans.name_rpc(self.plane, name)

    def start(self):
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        import socket as _socket

        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        if self._thread:
            self._thread.join(timeout=5)

    @property
    def endpoint(self) -> str:
        return f"{self.host}:{self.port}"

    def _handle(self, h: BaseHTTPRequestHandler):
        parsed = urllib.parse.urlsplit(h.path)
        if not parsed.path.startswith(self.prefix + "/"):
            self._reply_error(h, 404, "NotFound", parsed.path)
            return
        token = h.headers.get("Authorization", "").removeprefix("Bearer ")
        if not verify_token(self.secret, token):
            self._reply_error(h, 403, "AccessDenied", "bad cluster token")
            return
        method = parsed.path[len(self.prefix) + 1:]
        fn = self._methods.get(method)
        if fn is None:
            self._reply_error(h, 404, "UnknownMethod", method)
            return
        args = dict(urllib.parse.parse_qsl(parsed.query, keep_blank_values=True))
        clen = int(h.headers.get("Content-Length", "0") or "0")
        body = h.rfile.read(clen) if clen else b""
        t0 = time.monotonic_ns()
        try:
            self._serve(h, fn, args, body)
        finally:
            _count("rpc_served_seconds_total",
                   (time.monotonic_ns() - t0) / 1e9, self.plane)

    def _serve(self, h: BaseHTTPRequestHandler, fn, args: dict,
               body: bytes):
        # Dispatch under the caller's byte-flow op tag (token already
        # verified above, and unknown classes are dropped) so local
        # disk IO this call triggers is attributed, not "untagged".
        op = h.headers.get(_IOFLOW_OP_HDR, "")
        if op not in ioflow.OP_CLASSES:
            op = ""
        try:
            if op:
                with ioflow.tag(op, h.headers.get(_IOFLOW_BUCKET_HDR, "")):
                    out = fn(args, body)
            else:
                out = fn(args, body)
        except Exception as exc:  # noqa: BLE001 - typed error to client
            self._reply_error(h, 500, type(exc).__name__, str(exc))
            return
        result, stream = out if isinstance(out, tuple) else (out, None)
        frame = msgpack.packb(result, use_bin_type=True)
        try:
            h.send_response(200)
            h.send_header("Content-Type", "application/x-msgpack")
            h.send_header("X-Frame-Length", str(len(frame)))
            if stream is None:
                h.send_header("Content-Length", str(len(frame)))
                h.end_headers()
                h.wfile.write(frame)
            else:
                data = stream.read() if hasattr(stream, "read") else bytes(stream)
                h.send_header("Content-Length", str(len(frame) + len(data)))
                h.end_headers()
                h.wfile.write(frame)
                h.wfile.write(data)
        except (BrokenPipeError, ConnectionResetError):
            pass

    def _reply_error(self, h, status: int, kind: str, message: str):
        try:
            body = msgpack.packb(
                {"__error__": kind, "message": message}, use_bin_type=True
            )
            h.send_response(status)
            h.send_header("Content-Type", "application/x-msgpack")
            h.send_header("Content-Length", str(len(body)))
            h.end_headers()
            h.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass


class RPCClient:
    """Pooled, health-checked client for one peer's RPC plane
    (ref cmd/rest/client.go:120-188 Call + health check loop)."""

    def __init__(self, endpoint: str, prefix: str, secret: str,
                 timeout: float = 30.0):
        self.endpoint_str = endpoint
        self.prefix = prefix.rstrip("/")
        self.plane = _plane_of(self.prefix)
        self.secret = secret
        self.timeout = timeout
        self._online = True
        self._last_check = 0.0
        self._lock = threading.Lock()
        self._pool: list[http.client.HTTPConnection] = []
        # Serializes the lazy reconnect probe: without it, racing
        # threads reading .online double-probe the peer and clobber
        # _last_check (losing the 1s backoff).
        self._probe_lock = threading.Lock()
        # "" | "net: ..." | "auth: ..." — the last probe's failure
        # class, so an auth problem (clock skew, secret mismatch) is
        # distinguishable from a plain network outage.
        self.last_probe_error = ""

    # --- connection pool ---

    def _new_conn(self, timeout_s: float) -> http.client.HTTPConnection:
        from ..utils import certs as _certs

        ctx = _certs.client_ssl_context()
        if ctx is not None:
            return http.client.HTTPSConnection(
                self.endpoint_str, timeout=timeout_s, context=ctx
            )
        return http.client.HTTPConnection(
            self.endpoint_str, timeout=timeout_s
        )

    def _get_conn(self) -> http.client.HTTPConnection:
        with self._lock:
            if self._pool:
                return self._pool.pop()
        return self._new_conn(self.timeout)

    def _put_conn(self, conn):
        with self._lock:
            if len(self._pool) < 8:
                self._pool.append(conn)
                return
        conn.close()

    # --- health ---

    @property
    def online(self) -> bool:
        if self._online:
            return True
        if time.time() - self._last_check <= 1.0:
            return False
        # Lazy reconnect probe (ref: HealthCheckFn + 1s backoff). The
        # probe is network I/O inside a property getter, so it MUST be
        # single-flight: one thread probes, the others return the
        # current state instead of stacking probes and clobbering the
        # backoff stamp.
        # lock-ok: non-blocking single-flight probe gate; released in
        # the finally below, never held across a wait
        if not self._probe_lock.acquire(blocking=False):
            return self._online
        try:
            if self._online or time.time() - self._last_check <= 1.0:
                return self._online
            self._last_check = time.time()
            try:
                self.call("ping")
                self._online = True
                self.last_probe_error = ""
            except RPCError as exc:
                if exc.kind == "AccessDenied":
                    # The peer IS reachable but rejects our cluster
                    # token (secret mismatch / clock skew past token
                    # validity). Reporting this as a plain "offline"
                    # sends operators chasing the network; log the real
                    # cause once per transition.
                    if not self.last_probe_error.startswith("auth"):
                        _log.warning(
                            "peer %s rejects cluster token (%s): check "
                            "shared secret / clock skew, not the network",
                            self.endpoint_str, exc.message,
                        )
                    self.last_probe_error = f"auth: {exc.message}"
                else:
                    self.last_probe_error = f"net: {exc.message}"
            except Exception as exc:  # noqa: BLE001 - probe best effort
                self.last_probe_error = f"net: {exc}"
        finally:
            self._probe_lock.release()
        return self._online

    def mark_offline(self):
        self._online = False
        self._last_check = time.time()

    # --- calls ---

    def call(self, method: str, args: dict | None = None,
             body: bytes = b"", want_stream: bool = False,
             idempotent: bool = False):
        """POST one method. Returns the msgpack result, or
        (result, raw_rest_of_body) when want_stream.

        `idempotent=True` (reads/probes only — never a write, whose
        ambiguous first attempt may have applied) grants ONE
        jittered-backoff retry after a transient transport failure
        (connect reset/refused/timeout), inside the call's ORIGINAL
        deadline: the retry's connection timeout is the remaining
        budget, so a caller that asked for `timeout` seconds never
        waits longer because a blip happened."""
        deadline = time.monotonic() + self.timeout
        try:
            return self._call_once(method, args, body, want_stream)
        except RPCError as exc:
            if not idempotent or exc.kind != "Unreachable":
                raise
            remaining = deadline - time.monotonic()
            if remaining <= RETRY_MIN_BUDGET_S:
                raise  # no budget left: surface the first failure
            time.sleep(min(random.uniform(*RETRY_BACKOFF_S),
                           remaining / 4))
            remaining = deadline - time.monotonic()
            if remaining <= RETRY_MIN_BUDGET_S:
                raise
            _note_retry()
            out = self._call_once(method, args, body, want_stream,
                                  timeout_s=remaining)
            # The retry round-tripped: the peer is back. Re-admit it
            # immediately instead of waiting out the probe backoff.
            self._online = True
            self.last_probe_error = ""
            return out

    def _call_once(self, method: str, args: dict | None,
                   body: bytes, want_stream: bool,
                   timeout_s: float | None = None):
        """One attempt, under an `rpc` span: a leaf recorded and never
        mirrored, since a remote drive's op holds its `disk` twin open
        on this thread across the call."""
        t0 = time.monotonic_ns()
        try:
            return self._round_trip(method, args, body, want_stream,
                                    timeout_s)
        finally:
            _spans.record("rpc", f"{self.plane}:{method}",
                          time.monotonic_ns() - t0, t0)
            _count("rpc_calls_total", 1, self.plane)
            _count("rpc_sent_bytes_total", len(body), self.plane)

    def _round_trip(self, method: str, args: dict | None, body: bytes,
                    want_stream: bool, timeout_s: float | None):
        qs = urllib.parse.urlencode(args or {})
        url = f"{self.prefix}/{method}" + (f"?{qs}" if qs else "")
        headers = {
            "Authorization": f"Bearer {make_token(self.secret)}",
            "Content-Length": str(len(body)),
        }
        tag = ioflow.capture()
        if tag is not None:
            headers[_IOFLOW_OP_HDR] = tag.op
            if tag.bucket:
                headers[_IOFLOW_BUCKET_HDR] = tag.bucket
        # A deadline-propagated retry never draws from the pool: pooled
        # sockets carry the full default timeout, and a dead keep-alive
        # from before the blip would burn the remaining budget twice.
        conn = (self._get_conn() if timeout_s is None
                else self._new_conn(timeout_s))
        try:
            conn.request("POST", url, body=body, headers=headers)
            resp = conn.getresponse()
            raw = resp.read()
            if timeout_s is None:
                self._put_conn(conn)
            else:
                # Never pool the retry's short-timeout socket: a later
                # unrelated call inheriting the truncated budget would
                # time out spuriously and latch the peer offline.
                conn.close()
        except (OSError, http.client.HTTPException) as exc:
            conn.close()
            self.mark_offline()
            raise RPCError("Unreachable", str(exc)) from exc
        frame_len = int(resp.headers.get("X-Frame-Length", len(raw)))
        result = msgpack.unpackb(raw[:frame_len], raw=False)
        if isinstance(result, dict) and "__error__" in result:
            raise RPCError(result["__error__"], result.get("message", ""))
        if want_stream:
            return result, raw[frame_len:]
        return result
