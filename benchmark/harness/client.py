"""The client's side of the served path: signed S3 requests over HTTP and
what the child's metrics endpoint says. Nothing here imports jax.

Copied from `chip_smoke.py` (`S3`, `backend_info`, `dispatch_counts`),
with one connection kept per client, as `warp` keeps them.
"""

from __future__ import annotations

import http.client
import re
import time
import urllib.parse

from minio_tpu.api.sign import sign_v4_request

ACCESS = SECRET = "minioadmin"


class S3:
    """One client's connection. Not shared between threads."""

    def __init__(self, host: str, timeout: float = 120.0):
        self.host, self.timeout = host, timeout
        self._conn: http.client.HTTPConnection | None = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str, query=None, headers=None,
                body: bytes = b"", payload_hash: str | None = None):
        """-> (status, headers, body). Raises OSError or
        http.client.HTTPException where no answer came."""
        query = query or []
        hdrs = sign_v4_request(SECRET, ACCESS, method, self.host, path,
                               query, dict(headers or {}), body,
                               payload_hash=payload_hash)
        qs = urllib.parse.urlencode(query)
        url = urllib.parse.quote(path) + (f"?{qs}" if qs else "")
        for attempt in (0, 1):
            fresh = self._conn is None
            if fresh:
                self._conn = http.client.HTTPConnection(self.host,
                                                        timeout=self.timeout)
            try:
                self._conn.request(method, url, body=body, headers=hdrs)
                resp = self._conn.getresponse()
                data = resp.read()
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError):
                # a kept connection that the server closed meanwhile:
                # once more on a fresh one; a fresh one that fails, fails
                self.close()
                if fresh or attempt:
                    raise
                continue
            except BaseException:
                self.close()
                raise
            if resp.will_close:
                self.close()
            return resp.status, dict(resp.getheaders()), data
        raise AssertionError("unreachable")

    def metrics(self) -> str:
        st, _, data = self.request("GET", "/minio/v2/metrics/cluster")
        if st != 200:
            raise OSError(f"metrics endpoint answered {st}")
        return data.decode()


def wait_ready(host: str, gone, deadline_s: float) -> float:
    """Seconds until /minio/health/live answered 200. `gone()` names a
    server that has exited, or returns nothing."""
    t0 = time.monotonic()
    s3 = S3(host, timeout=5)
    while time.monotonic() - t0 < deadline_s:
        who = gone()
        if who:
            raise OSError(f"{who} before it answered")
        try:
            st, _, _ = s3.request("GET", "/minio/health/live")
            if st == 200:
                s3.close()
                return time.monotonic() - t0
        except (OSError, http.client.HTTPException):
            s3.close()
        time.sleep(0.2)
    raise OSError(f"the server did not answer within {deadline_s:.0f}s")


def backend_info(text: str) -> dict:
    """platform / device_kind / devices labels of the backend series."""
    m = re.search(r"^mtpu_backend_info\{([^}]*)\} 1", text, re.M)
    if m is None:
        raise OSError("metrics carry no mtpu_backend_info series")
    return dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))


def counters(text: str) -> dict[str, float]:
    """Every sample of the metrics page as `name{labels}` -> value; the
    layer-metric readers pick theirs by pattern."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        name, _, val = line.rpartition(" ")
        try:
            out[name] = float(val)
        except ValueError:
            continue
    return out


def add_up(pages: list[dict[str, float]]) -> dict[str, float]:
    """The nodes' samples added up, series by series: counters and a
    histogram's `_sum` and `_count` add, so a rise over the cluster reads
    as a rise on one node does."""
    total: dict[str, float] = {}
    for page in pages:
        for name, val in page.items():
            total[name] = total.get(name, 0.0) + val
    return total


def dispatch_count(samples: dict[str, float], engine: str) -> float:
    """Codec dispatches of one engine substrate. Matched by suffix: the
    prefix is doubled today (`mtpu_mtpu_codec_dispatch_total`, D9)."""
    total = 0.0
    for name, val in samples.items():
        m = re.match(r"\w*codec_dispatch_total\{([^}]*)\}$", name)
        if m and f'engine="{engine}"' in m.group(1):
            total += val
    return total
