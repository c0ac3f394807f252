#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served S3 path runs on the
chip: 12+4 PUT / GET / degraded GET / heal / restart through the normal
entry point (`python -m minio_tpu server`) with the device engine, checked
against an implementation independent of JAX and of the C library.

    python chip_smoke.py                 # one TPU chip, device engine
    python chip_smoke.py --chips 4       # four chips, mesh engine
    python chip_smoke.py --tiny          # same phases at 2+2 on the CPU
                                         # (debugging here; proves nothing
                                         # about the chip)

This process is the PARENT: it never imports jax, so the one child that
owns the chip — the server — can have it. It talks to the child only over
signed HTTP and reads the drive directories it gave the child. Without
--tiny a child whose platform is not `tpu` is a failure, never a fallback.

Exit code 0 and two lines of stdout when every phase passed: the run's
record (phases, dispatch counts, start times, host facts; also written to
chiprun_out/chip_smoke.json), then, as the LAST line, the verdict with
these keys and no others, the device as the child's JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Non-zero and nothing on stdout otherwise (diagnostics go to stderr and
chiprun_out/). Wall seconds in the record are smoke observations, not
benchmark metrics.
"""

from __future__ import annotations

import argparse
import hashlib
import http.client
import importlib.metadata
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.parse
import xml.etree.ElementTree as ET

import numpy as np

from minio_tpu.api.sign import sign_v4_request
from minio_tpu.erasure import registry
from minio_tpu.madmin import AdminClient
from minio_tpu.ops import gf, highwayhash
from minio_tpu.storage.xlmeta import read_xl_meta

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")
ACCESS = SECRET = "minioadmin"
BUCKET = "smoke"
MIB = 1 << 20
BLOCK = MIB                      # erasure block size (BASELINE.md)
DIGEST = 32                      # HighwayHash-256 frame header
DEADLINE_S = 1150                # the contract allows 1200
NS = "{http://s3.amazonaws.com/doc/2006-03-01/}"

# Host facts the first engine-selection PR (ROADMAP S2) needs, measured in
# a child pinned to the CPU so that it cannot reach for the chip: what
# `auto` resolves to and the probes behind it, and one native GF call at
# the probe shape and at the serving shape, next to the numpy route.
_HOST_FACTS = r"""
import json, statistics, time
import numpy as np
from minio_tpu.erasure import registry
from minio_tpu.ops import gf, gf_native

def median_ms(fn, n):
    fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter(); fn(); ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3

rng = np.random.default_rng(0)
out = {"engine_kind": gf_native.engine_kind(),
       "native_threads": gf_native._threads()}
if gf_native.available():
    for name, (b, k, m, s) in {"2x4x16384": (2, 4, 2, 16384),
                               "8x12x87382": (8, 12, 4, 87382)}.items():
        mat = gf.parity_matrix(k, m)
        blocks = rng.integers(0, 256, size=(b, k, s), dtype=np.uint8)
        out["native_call_ms_" + name] = median_ms(
            lambda: gf_native.apply_matrix_batch(mat, blocks), 15)
entry = registry.get(registry.DEFAULT_CODEC)
mat = gf.parity_matrix(4, 2)
blocks = rng.integers(0, 256, size=(2, 4, 16384), dtype=np.uint8)
out["numpy_call_ms_2x4x16384"] = median_ms(
    lambda: [entry.host_apply(mat, blk) for blk in blocks], 3)
out["auto_engine_at_87382"] = registry.select_engine(87382, 16)
out["auto_engine_at_64"] = registry.select_engine(64, 16)
out["probe_gbps"] = {e: registry.probe_gbps(registry.DEFAULT_CODEC, e)
                     for e in ("native", "numpy", "device", "mesh")}
print(json.dumps(out))
"""


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.monotonic()


# --- the deployment under test ------------------------------------------


class Sizes:
    """The run's geometry and data scale. Full size is BASELINE configs 2
    and 3 at the reference's own widths: 16 drives, 12+4, 1 MiB blocks,
    87,382-byte shards, 10 MiB objects, past the read tier's 64 MiB."""

    def __init__(self, tiny: bool):
        self.tiny = tiny
        if tiny:
            self.drives, self.k, self.m = 4, 2, 2
            self.n_large, self.large = 4, 5 * MIB
            self.n_small, self.part = 4, 5 * MIB
        else:
            self.drives, self.k, self.m = 16, 12, 4
            self.n_large, self.large = 48, 10 * MIB
            self.n_small, self.part = 16, 16 * MIB
        self.small = 64 << 10            # inline path
        # full blocks + a tail block (tiny: the large objects' batch
        # shape again, to spare the CPU a compile)
        self.tail = (5 if tiny else 3) * MIB + 17
        self.n_parts = 3
        self.shard = -(-BLOCK // self.k)
        # fused dispatches one large PUT costs: batches of 8 blocks
        self.batches = -(-(self.large // BLOCK) // 8)


def payload(seed: int, key: str, size: int) -> bytes:
    """The object's bytes, made from --seed and its key alone."""
    h = hashlib.sha256(f"{seed}:{key}".encode()).digest()
    return np.random.default_rng(
        np.frombuffer(h, dtype=np.uint32)
    ).bytes(size)


class S3:
    """Signed-HTTP client: the only channel to the child."""

    def __init__(self, host: str):
        self.host = host

    def request(self, method: str, path: str, query=None, headers=None,
                body: bytes = b"", timeout: float = 900.0):
        query = query or []
        hdrs = sign_v4_request(SECRET, ACCESS, method, self.host, path,
                               query, dict(headers or {}), body)
        qs = urllib.parse.urlencode(query)
        conn = http.client.HTTPConnection(self.host, timeout=timeout)
        try:
            conn.request(method,
                         urllib.parse.quote(path) + (f"?{qs}" if qs else ""),
                         body=body, headers=hdrs)
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), resp.read()
        finally:
            conn.close()

    def put(self, key: str, body: bytes) -> str:
        """PUT with the storage class that carries the set's parity;
        returns the ETag."""
        st, hdrs, data = self.request(
            "PUT", f"/{BUCKET}/{key}", body=body,
            headers={"x-amz-storage-class": "STANDARD"},
        )
        check(st == 200, f"PUT {key}: {st} {data[:300]!r}")
        return hdrs["ETag"].strip('"')

    def get(self, key: str, rng: tuple[int, int] | None = None) -> bytes:
        headers = {"Range": f"bytes={rng[0]}-{rng[1]}"} if rng else {}
        st, _, data = self.request("GET", f"/{BUCKET}/{key}",
                                   headers=headers)
        check(st in (200, 206), f"GET {key}: {st} {data[:300]!r}")
        return data

    def metrics(self) -> str:
        st, _, data = self.request("GET", "/minio/v2/metrics/cluster")
        check(st == 200, f"metrics: {st}")
        return data.decode()


class Child:
    """One server child: the process that owns the chip."""

    def __init__(self, sizes: Sizes, root: str, engine: str, chips: int,
                 ordinal: int):
        self.log_path = os.path.join(OUT_DIR,
                                     f"chip_smoke_server{ordinal}.log")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.host = f"127.0.0.1:{port}"
        env = dict(os.environ)
        env["MTPU_ENCODE_ENGINE"] = engine
        env["MTPU_STORAGE_CLASS_STANDARD"] = f"EC:{sizes.m}"
        if sizes.tiny:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={chips}"
            )
        self.started = time.monotonic()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "minio_tpu", "server",
             f"{root}/d{{1...{sizes.drives}}}", "--port", str(port)],
            cwd=REPO, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        CHILDREN.append(self)
        self.s3 = S3(self.host)
        self.ready_s = self._wait_ready()

    def _wait_ready(self) -> float:
        while True:
            check(self.proc.poll() is None,
                  f"server exited with {self.proc.returncode} before it "
                  f"answered:\n{self.log_tail()}")
            try:
                st, _, _ = self.s3.request("GET", "/minio/health/live",
                                           timeout=5)
                if st == 200:
                    return time.monotonic() - self.started
            except OSError:
                pass
            time.sleep(0.2)

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")

    def stop(self) -> None:
        """Stop the child and everything it started (its process group:
        the worker pool rides along)."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
                self.proc.wait(timeout=30)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._log.close()
        if self in CHILDREN:
            CHILDREN.remove(self)


CHILDREN: list[Child] = []


# --- evidence read from the child's metrics endpoint ---------------------


def backend_info(text: str) -> dict:
    """platform / device_kind / devices labels of the backend series."""
    m = re.search(r"^mtpu_backend_info\{([^}]*)\} 1", text, re.M)
    check(m is not None, "metrics carry no mtpu_backend_info series")
    return dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))


def dispatch_counts(text: str) -> dict:
    """Codec dispatches by engine substrate, plus the mesh engine's own
    collective-dispatch counter and the device count of its last output
    array."""
    out: dict = {}
    for labels, val in re.findall(
            r"^\w*codec_dispatch_total\{([^}]*)\} (\S+)", text, re.M):
        eng = dict(re.findall(r'(\w+)="([^"]*)"', labels))["engine"]
        out[eng] = out.get(eng, 0) + int(float(val))
    for name in ("mesh_dispatches_total", "mesh_output_devices"):
        m = re.search(rf"^mtpu_{name} (\S+)", text, re.M)
        if m:
            out[name] = int(float(m.group(1)))
    return out


# --- drive-directory helpers (the parent made these directories) ---------


def drive(root: str, i: int) -> str:
    return os.path.join(root, f"d{i}")


def shard_file_hashes(drive_dir: str) -> dict:
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


def wipe(drive_dir: str, key: str | None = None) -> None:
    """Remove the bucket's data (or one object's) from a drive, as a
    replaced drive would look; the bucket directory itself stays."""
    base = os.path.join(drive_dir, BUCKET)
    targets = [os.path.join(base, key)] if key else [
        os.path.join(base, e) for e in os.listdir(base)]
    for t in targets:
        shutil.rmtree(t)


def read_shards(root: str, sizes: Sizes, key: str):
    """The object's shard files as the drives hold them: returns
    (codec id, digests [n, blocks, 32], chunks [n, blocks, shard])
    indexed by the shard position recorded in each drive's xl.meta. Also
    checks the geometry, so the smoke cannot pass at the 16-drive default
    of 8+8."""
    n = sizes.k + sizes.m
    digests = chunks = None
    seen, codecs = set(), set()
    for i in range(1, sizes.drives + 1):
        odir = os.path.join(drive(root, i), BUCKET, key)
        with open(os.path.join(odir, "xl.meta"), "rb") as f:
            fi = read_xl_meta(f.read(), BUCKET, key, None)
        er = fi.erasure
        check((er.data_blocks, er.parity_blocks, er.block_size)
              == (sizes.k, sizes.m, BLOCK),
              f"{key} on d{i}: xl.meta says {er.data_blocks}+"
              f"{er.parity_blocks} @ {er.block_size}, want "
              f"{sizes.k}+{sizes.m} @ {BLOCK}")
        with open(os.path.join(odir, fi.data_dir, "part.1"), "rb") as f:
            raw = np.frombuffer(f.read(), dtype=np.uint8)
        frame = DIGEST + sizes.shard
        check(raw.size % frame == 0,
              f"{key} on d{i}: part.1 is {raw.size} B, not whole "
              f"{frame}-byte frames")
        frames = raw.reshape(-1, frame)
        if digests is None:
            digests = np.zeros((n, frames.shape[0], DIGEST), np.uint8)
            chunks = np.zeros((n, frames.shape[0], sizes.shard), np.uint8)
        digests[er.index - 1] = frames[:, :DIGEST]
        chunks[er.index - 1] = frames[:, DIGEST:]
        seen.add(er.index)
        codecs.add(er.codec)
    check(seen == set(range(1, n + 1)),
          f"{key}: shard indices on disk {sorted(seen)}, want 1..{n}")
    check(len(codecs) == 1, f"{key}: drives disagree on the codec {codecs}")
    return codecs.pop(), digests, chunks


# --- phases --------------------------------------------------------------


class Run:
    """State shared by the phases of one smoke run."""

    def __init__(self, args, sizes: Sizes, root: str):
        self.args, self.sizes, self.root = args, sizes, root
        self.seed = args.seed
        self.engine = "mesh" if args.chips > 1 else "device"
        self.child: Child | None = None
        self.large = [f"large/{i:03d}" for i in range(sizes.n_large)]
        self.first_half = self.large[: sizes.n_large // 2]
        self.second_half = self.large[sizes.n_large // 2:]
        rng = np.random.default_rng(self.seed)
        self.dead = sorted(
            int(d) + 1
            for d in rng.choice(sizes.drives, size=2, replace=False))
        self.before_wipe: dict = {}
        self.counts: dict = {}
        self.result: dict = {"phases": {}}

    def s3(self) -> S3:
        return self.child.s3

    def data(self, key: str, size: int) -> bytes:
        return payload(self.seed, key, size)

    def scrape(self, label: str) -> dict:
        self.counts[label] = dispatch_counts(self.s3().metrics())
        return self.counts[label]

    def moved(self, before: str, after: str) -> int:
        """Rise of the engine-under-test's dispatch counter between two
        scrapes."""
        return (self.counts[after].get(self.engine, 0)
                - self.counts[before].get(self.engine, 0))


def phase_start(run: Run) -> None:
    run.result["compile_cache_files"] = [cache_files()]
    run.child = Child(run.sizes, run.root, run.engine, run.args.chips, 1)
    info = backend_info(run.s3().metrics())
    run.result["device"] = {"platform": info["platform"],
                            "kind": info["device_kind"],
                            "count": int(info["devices"])}
    run.result["ready_s"] = [run.child.ready_s]
    log(f"server up in {run.child.ready_s:.1f}s on {info}")
    if not run.sizes.tiny:
        check(info["platform"] == "tpu",
              f"the server runs on platform={info['platform']} "
              f"device_kind={info['device_kind']!r}: this smoke needs a "
              "TPU (use --tiny to debug on the CPU)")
    check(int(info["devices"]) >= run.args.chips,
          f"{info['devices']} device(s) found, --chips {run.args.chips}")
    run.scrape("start")


def phase_load(run: Run) -> None:
    s3, sz = run.s3(), run.sizes
    st, _, body = s3.request("PUT", f"/{BUCKET}")
    check(st == 200, f"make bucket: {st} {body[:300]!r}")
    for n, key in enumerate(run.large):
        body = run.data(key, sz.large)
        etag = s3.put(key, body)
        check(etag == hashlib.md5(body).hexdigest(), f"{key}: ETag {etag}")
        if n == 0:
            run.result["first_put_s"] = [time.monotonic()
                                         - run.child.started]
            log(f"first {sz.large // MIB} MiB PUT answered "
                f"{run.result['first_put_s'][0]:.1f}s after start")
    smalls = [f"small/{i:02d}" for i in range(sz.n_small)]
    for key, size in [(k, sz.small) for k in smalls] + [("tail/0", sz.tail)]:
        body = run.data(key, size)
        check(s3.put(key, body) == hashlib.md5(body).hexdigest(),
              f"{key}: ETag is not the md5")
    # one multipart upload
    st, _, body = s3.request("POST", f"/{BUCKET}/multi/0",
                             query=[("uploads", "")],
                             headers={"x-amz-storage-class": "STANDARD"})
    check(st == 200, f"initiate multipart: {st} {body[:300]!r}")
    upload_id = ET.fromstring(body).find(f"{NS}UploadId").text
    etags = []
    for pn in range(1, sz.n_parts + 1):
        part = run.data(f"multi/0#{pn}", sz.part)
        st, hdrs, body = s3.request(
            "PUT", f"/{BUCKET}/multi/0", body=part,
            query=[("partNumber", str(pn)), ("uploadId", upload_id)])
        check(st == 200, f"part {pn}: {st} {body[:300]!r}")
        check(hdrs["ETag"].strip('"') == hashlib.md5(part).hexdigest(),
              f"part {pn}: ETag is not the md5")
        etags.append(hdrs["ETag"].strip('"'))
    complete = ("<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{i + 1}</PartNumber><ETag>{e}</ETag></Part>"
        for i, e in enumerate(etags)) + "</CompleteMultipartUpload>")
    st, _, body = s3.request("POST", f"/{BUCKET}/multi/0",
                             query=[("uploadId", upload_id)],
                             body=complete.encode())
    check(st == 200, f"complete multipart: {st} {body[:300]!r}")
    # HEAD, LIST, ranged GET, DELETE
    st, hdrs, _ = s3.request("HEAD", f"/{BUCKET}/{run.large[0]}")
    check(st == 200 and int(hdrs["Content-Length"]) == sz.large,
          f"HEAD: {st} {hdrs}")
    st, _, body = s3.request("GET", f"/{BUCKET}", query=[
        ("list-type", "2"), ("prefix", "large/"), ("max-keys", "1000")])
    keys = [e.text for e in ET.fromstring(body).iter(f"{NS}Key")]
    check(st == 200 and keys == run.large, f"LIST: {st} {len(keys)} keys")
    lo, hi = BLOCK - 6, 2 * BLOCK + 10  # straddles two block boundaries
    check(s3.get(run.large[0], (lo, hi))
          == run.data(run.large[0], sz.large)[lo:hi + 1], "ranged GET")
    check(s3.get("tail/0") == run.data("tail/0", sz.tail), "tail GET")
    st, _, _ = s3.request("DELETE", f"/{BUCKET}/{smalls[-1]}")
    check(st == 204, f"DELETE: {st}")
    st, _, _ = s3.request("GET", f"/{BUCKET}/{smalls[-1]}")
    check(st == 404, f"GET after DELETE: {st}")
    check(s3.get(smalls[0]) == run.data(smalls[0], sz.small), "inline GET")
    # the set really is k+m: shard count and xl.meta of one object
    read_shards(run.root, sz, run.large[0])
    run.scrape("load")
    rose = run.moved("start", "load")
    check(rose >= sz.batches * sz.n_large,
          f"{run.engine} dispatches rose by {rose} in load; {sz.n_large} "
          f"large objects of {sz.batches} batch(es) each were written")
    if run.engine == "mesh":
        # from the output array's own sharding, not from shape arithmetic
        held = run.counts["load"].get("mesh_output_devices")
        check(held == run.args.chips,
              f"the last mesh output lives on {held} device(s), "
              f"--chips {run.args.chips}")


def phase_healthy_get(run: Run) -> None:
    for key in run.first_half:
        check(run.s3().get(key) == run.data(key, run.sizes.large),
              f"{key}: GET differs from the payload")
    whole = b"".join(run.data(f"multi/0#{pn}", run.sizes.part)
                     for pn in range(1, run.sizes.n_parts + 1))
    check(run.s3().get("multi/0") == whole, "multipart GET differs")


def phase_reference(run: Run) -> None:
    """Every frame of two objects' 16 shard files against numpy: parity by
    ops/gf.gf_matmul_shards_ref over the parity matrix of the codec the
    object's xl.meta names (`auto` codec selection ranks by a timing
    probe, so a server may stamp cauchy-xor), digests by the
    ops/highwayhash oracle. This is what catches a kernel that compiles
    on the chip and answers wrongly."""
    sz = run.sizes
    for key in run.large[:2]:
        codec, digests, chunks = read_shards(run.root, sz, key)
        run.result.setdefault("codecs", []).append(codec)
        pmat = registry.get(codec).parity_matrix(sz.k, sz.m)
        blocks = sz.large // BLOCK
        check(chunks.shape[1] == blocks, f"{key}: {chunks.shape[1]} frames")
        body = np.frombuffer(run.data(key, sz.large), dtype=np.uint8)
        for b in range(blocks):
            want = np.zeros(sz.k * sz.shard, np.uint8)
            want[:BLOCK] = body[b * BLOCK:(b + 1) * BLOCK]
            want = want.reshape(sz.k, sz.shard)
            check(np.array_equal(chunks[:sz.k, b], want),
                  f"{key} block {b}: data shards differ from the payload")
            ref = gf.gf_matmul_shards_ref(pmat, want)
            check(np.array_equal(chunks[sz.k:, b], ref),
                  f"{key} block {b}: parity differs from the GF reference "
                  f"in {int((chunks[sz.k:, b] != ref).sum())} of "
                  f"{ref.size} bytes")
        check(np.array_equal(digests, highwayhash.hash256_batch(chunks)),
              f"{key}: bitrot digests differ from the numpy HighwayHash")


def phase_degraded_get(run: Run) -> None:
    for d in run.dead:
        run.before_wipe[d] = shard_file_hashes(drive(run.root, d))
        check(len(run.before_wipe[d]) >= run.sizes.n_large,
              f"d{d} holds {len(run.before_wipe[d])} shard files")
        wipe(drive(run.root, d))
    log(f"wiped the bucket on drives {run.dead} under the running server")
    # Never read before, so the read tier cannot answer from RAM.
    for key in run.second_half:
        check(run.s3().get(key) == run.data(key, run.sizes.large),
              f"{key}: degraded GET differs from the payload")
    run.scrape("degraded")
    check(run.moved("load", "degraded") > 0,
          f"{run.engine} dispatches did not move in degraded GET")


def phase_heal(run: Run) -> None:
    adm = AdminClient(run.child.host, ACCESS, SECRET, timeout=900.0)
    token = adm.heal(BUCKET)["clientToken"]
    st = adm.heal_wait(BUCKET, client_token=token, poll_s=0.5,
                       timeout=DEADLINE_S)
    check(st["Summary"] == "finished" and st["NumFailed"] == 0,
          f"heal: {st['Summary']} failed={st['NumFailed']} "
          f"{st['FailureDetail']}")
    for d in run.dead:
        check(shard_file_hashes(drive(run.root, d)) == run.before_wipe[d],
              f"d{d}: healed shard files differ from before the wipe")
    run.scrape("heal")
    check(run.moved("degraded", "heal") > 0,
          f"{run.engine} dispatches did not move in heal")
    run.result["heal"] = {"scanned": st["NumScanned"],
                          "healed": st["NumHealed"]}


def phase_restart(run: Run) -> None:
    sz = run.sizes
    run.child.stop()
    run.result["compile_cache_files"].append(cache_files())
    # One failure pattern of the degraded phase again, from the cache.
    again = run.second_half[0]
    for d in run.dead:
        wipe(drive(run.root, d), again)
    run.child = Child(sz, run.root, run.engine, run.args.chips, 2)
    run.result["ready_s"].append(run.child.ready_s)
    body = run.data("restart/0", sz.large)
    check(run.s3().put("restart/0", body) == hashlib.md5(body).hexdigest(),
          "PUT after restart: ETag")
    run.result["first_put_s"].append(time.monotonic() - run.child.started)
    for key in (again, run.first_half[0], "restart/0"):
        check(run.s3().get(key) == run.data(key, sz.large),
              f"{key}: GET after restart differs")
    check(run.scrape("restart").get(run.engine, 0) > 0,
          f"no {run.engine} dispatch after the restart")
    run.child.stop()
    files = run.result["compile_cache_files"]
    files.append(cache_files())
    check(files[1] > 0, "the first start wrote no compile cache")
    check(files[2] == files[1],
          f"the second start added {files[2] - files[1]} file(s) to the "
          "compile cache")


PHASES = [
    ("start", phase_start), ("load", phase_load),
    ("healthy_get", phase_healthy_get), ("reference", phase_reference),
    ("degraded_get", phase_degraded_get), ("heal", phase_heal),
    ("restart", phase_restart),
]


# --- host facts, compile cache, main -------------------------------------


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")


def cache_files() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except FileNotFoundError:
        return 0


def host_facts() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MTPU_ENCODE_ENGINE", None)
    r = subprocess.run([sys.executable, "-c", _HOST_FACTS], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=300)
    check(r.returncode == 0, f"host facts child failed:\n{r.stderr[-2000:]}")
    facts = json.loads(r.stdout.strip().splitlines()[-1])
    check(facts["engine_kind"] >= 0,
          "the native library did not build on this machine")
    return facts


def version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sizes = Sizes(args.tiny)
    os.makedirs(OUT_DIR, exist_ok=True)
    tmpfs = "/dev/shm" if os.access("/dev/shm", os.W_OK) else None
    root = tempfile.mkdtemp(prefix="mtpu-smoke-", dir=tmpfs)
    run = Run(args, sizes, root)
    res = run.result
    res.update({
        "ok": False, "engine": run.engine, "seed": args.seed,
        "tiny": args.tiny, "geometry": f"{sizes.k}+{sizes.m}",
        "jax": version("jax"), "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"), "cpu_count": os.cpu_count(),
        "wiped_drives": run.dead,
    })
    failed = None

    def on_deadline(signum, frame):
        raise SmokeFailure(f"not done after {DEADLINE_S}s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)
    try:
        res["host"] = host_facts()
        for name, fn in PHASES:
            t0 = time.monotonic()
            log(f"phase {name}")
            try:
                fn(run)
                res["phases"][name] = {"ok": True}
            except Exception as exc:  # noqa: BLE001 - reported, run ends
                res["phases"][name] = {"ok": False,
                                       "error": f"{type(exc).__name__}: "
                                                f"{exc}"}
                failed = name
            res["phases"][name]["wall_s"] = round(time.monotonic() - t0, 2)
            if failed:
                break
    except SmokeFailure as exc:
        failed = failed or "host"
        res["error"] = str(exc)
    finally:
        signal.alarm(0)
        tails = {c.log_path: c.log_tail() for c in CHILDREN}
        for c in list(CHILDREN):
            c.stop()
        shutil.rmtree(root, ignore_errors=True)
    res["dispatches"] = run.counts
    res["wall_s"] = round(time.monotonic() - T0, 1)
    res["ok"] = failed is None
    # The parent stayed off jax: the child could have the chip.
    if "jax" in sys.modules:
        res["ok"], failed = False, failed or "parent imported jax"
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(res, f, indent=1)
    if not res["ok"]:
        for path, tail in tails.items():
            print(f"--- tail of {path} ---\n{tail}", file=sys.stderr)
        print(json.dumps(res), file=sys.stderr)
        print(f"chip_smoke: FAILED in {failed}: "
              f"{res['phases'].get(failed, {}).get('error', res.get('error'))}",
              file=sys.stderr)
        return 1
    print(json.dumps(res))
    # The last line is the verdict alone: these keys and no others.
    print(json.dumps({"ok": True, "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
