"""The deployment `node12-ec8p4-dev1` (12 drives, 8+4, bitrot-verified GET
with the decode on the device engine) held to its guarantees on the CPU,
through the served path, against the benchmark's independent reference
(`benchmark/harness/reference.py` for what the drives hold,
`reference_decode.py` for what a read returns).

The guarantees are stated in `benchmark/configs/node12-ec8p4-dev1.json`
(`guarantees`), which names this file; a change that weakens one of the
two finds the other here. Every comparison is of bytes and exact.

A real S3Server over twelve tmp drives, `EC:4`, the device engine forced
(JAX on the CPU), erasure blocks of 64 KiB (8,192-byte shards, above the
device threshold) so that 78 degraded reads and heals stay quick; one
case runs at the published 1 MiB block.
"""

from __future__ import annotations

import http.client
import io
import itertools
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark.harness import reference, reference_decode
from minio_tpu.api import S3Server
from minio_tpu.api.sign import sign_v4_request
from minio_tpu.bucket import BucketMetadataSys
from minio_tpu.erasure import registry
from minio_tpu.iam import IAMSys
from minio_tpu.object import erasure_objects
from minio_tpu.object.pools import ErasureServerPools
from minio_tpu.object.sets import ErasureSets
from minio_tpu.observability import spans
from minio_tpu.observability.metrics import Metrics
from minio_tpu.storage.local import LocalStorage
from minio_tpu.storage.xlmeta import read_xl_meta
from minio_tpu.utils.errors import (ErrErasureReadQuorum,
                                    ErrErasureWriteQuorum)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ACCESS, SECRET = "tpuadmin", "tpuadmin-secret-key"
BUCKET = "bench"
K, M, DRIVES = 8, 4, 12
BLOCK = 64 << 10
BLOCKS = 4
CODEC = "dense-gf8"
# every single drive (the cell `n12dev1-get10m`'s state) and every pair
LOST = [(d,) for d in range(1, DRIVES + 1)] + \
    list(itertools.combinations(range(1, DRIVES + 1), 2))


class Node:
    """The 12-drive node, its S3 endpoint and its metrics."""

    def __init__(self, tmp):
        self.tmp = str(tmp)
        self.disks = [LocalStorage(os.path.join(self.tmp, f"d{i}"),
                                   endpoint=f"d{i}")
                      for i in range(1, DRIVES + 1)]
        sets = ErasureSets(
            self.disks, DRIVES, default_parity=M, pool_index=0,
            deployment_id="5ba52d31-4f2e-4d69-92f5-926a51824ed9")
        sets.init_format()
        self.ol = ErasureServerPools([sets])
        self.srv = S3Server(self.ol, IAMSys(ACCESS, SECRET),
                            BucketMetadataSys(self.ol)).start()
        self.metrics = Metrics()
        registry.set_metrics(self.metrics)
        spans.set_metrics(self.metrics)
        assert self.request("PUT", f"/{BUCKET}")[0] == 200

    def close(self):
        self.srv.stop()
        registry.set_metrics(None)
        spans.set_metrics(None)

    def request(self, method, path, body=b""):
        host = self.srv.endpoint
        hdrs = sign_v4_request(SECRET, ACCESS, method, host, path, [], {},
                               body)
        conn = http.client.HTTPConnection(host, timeout=60)
        try:
            conn.request(method, path, body=body, headers=hdrs)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def span(self, kind: str) -> tuple[float, float]:
        """(sum of seconds, count) of one span kind under op=get_object."""
        got = []
        for part in ("sum", "count"):
            head = (f'mtpu_span_seconds_{part}{{kind="{kind}",'
                    'op="get_object"} ')
            got.append(next((float(ln[len(head):]) for ln in
                             self.metrics.render_prometheus().splitlines()
                             if ln.startswith(head)), 0.0))
        return got[0], got[1]

    def get(self, key: str):
        """GET over S3 -> (status, body), back only once the server has
        recorded the request: it raises its counters and closes its spans
        after the last byte has gone out, so the client can be first."""
        n = self.span("request")[1]
        try:
            return self.request("GET", f"/{BUCKET}/{key}")
        finally:
            deadline = time.monotonic() + 10
            while (self.span("request")[1] <= n
                   and time.monotonic() < deadline):
                time.sleep(0.01)

    def counter(self, name, **labels) -> float:
        return self.metrics.counter_value(name, **labels)

    def dispatched(self) -> dict[str, float]:
        """The device engine's read-side dispatches so far, by kind."""
        return {kind: self.counter("codec_dispatch_kind_total",
                                   engine="device", kind=kind)
                for kind in ("apply", "reconstruct")}

    def put(self, key: str, seed: int, blocks: int = BLOCKS,
            block: int = BLOCK) -> bytes:
        body = reference.payload(seed, key, blocks * block)
        status, data = self.request("PUT", f"/{BUCKET}/{key}", body)
        assert status == 200, data
        return body

    def object_dir(self, drive: int, key: str) -> str:
        return os.path.join(self.tmp, f"d{drive}", BUCKET, key)

    def shard_of(self, drive: int, key: str):
        """(shard index as xl.meta counts it, the shard file's bytes)."""
        odir = self.object_dir(drive, key)
        with open(os.path.join(odir, "xl.meta"), "rb") as f:
            fi = read_xl_meta(f.read(), BUCKET, key, None)
        er = fi.erasure
        assert (er.data_blocks, er.parity_blocks) == (K, M)
        with open(os.path.join(odir, fi.data_dir, "part.1"), "rb") as f:
            return er.index, f.read()

    def files(self, key: str, but=()) -> dict[int, bytes]:
        return dict(self.shard_of(d, key) for d in range(1, DRIVES + 1)
                    if d not in but)

    def lose(self, key: str, drives) -> None:
        for d in drives:
            shutil.rmtree(self.object_dir(d, key))


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("MTPU_ENCODE_ENGINE", "device")
    mp.setenv("MTPU_CODEC", CODEC)
    # every read of this file is a read of the drives, but for the one
    # test that turns the tier on
    mp.setenv("MTPU_READTIER", "off")
    # shard files of four small blocks would ride in xl.meta otherwise
    mp.setenv("MTPU_INLINE_THRESHOLD", "0")
    mp.setattr(erasure_objects, "BLOCK_SIZE_V2", BLOCK)
    n = Node(tmp_path_factory.mktemp("ec8p4"))
    yield n
    n.close()
    mp.undo()


@pytest.fixture(scope="module")
def whole(node):
    """One object, PUT through the served path, and what the reference
    says its twelve shard files hold."""
    body = node.put("whole", 35)
    chunks, digests = reference.expected_shards([body], K, M, BLOCK, CODEC)
    return body, chunks[0], digests[0]         # [blocks, 12, S], [.., 32]


def _frames(chunks, digests, idx: int) -> bytes:
    return np.concatenate([digests[:, idx - 1], chunks[:, idx - 1]],
                          axis=1).tobytes()


def test_the_configuration_names_this_file_and_its_geometry():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "node12-ec8p4-dev1.json")) as f:
        cfg = json.load(f)
    dep, g = cfg["deployment"], cfg["guarantees"]
    assert (dep["drives"], dep["data"], dep["parity"]) == (DRIVES, K, M)
    assert dep["shard_size"] == reference.shard_size(dep["block_size"], K)
    assert (g["write_quorum"], g["read_quorum"]) == (K, K)
    assert "up to 4 of 12 shards missing" in g["degraded_read"]
    assert os.path.basename(__file__) in g["held_by"]
    assert "reference_decode.py" in g["held_by"]
    assert cfg["reduced"] == ["run_data_scale"]


def test_shard_files_equal_the_reference(node, whole):
    body, chunks, digests = whole
    files = node.files("whole")
    assert sorted(files) == list(range(1, DRIVES + 1))
    for idx, raw in files.items():
        assert raw == _frames(chunks, digests, idx), f"shard {idx}"
    # and the reference reads them back to the object
    got = reference_decode.decode(files, K, M, BLOCK, len(body), CODEC)
    assert got.body == body and not got.rebuilt and not got.dropped


@pytest.mark.parametrize("pair", LOST,
                         ids=lambda p: "-".join(f"d{d}" for d in p))
def test_get_with_drives_lost(node, whole, pair):
    """Every single drive and every pair of the twelve: the GET over S3
    returns the payload, the reference's decode of the surviving files
    returns the same bytes, and what the system rebuilds (the heal's
    shard files) equals what the reference computes again, byte for
    byte."""
    body, chunks, digests = whole
    lost = [node.shard_of(d, "whole")[0] for d in pair]
    node.lose("whole", pair)
    rebuilt = node.counter("get_reconstructed_blocks_total")
    verified = node.counter("bitrot_verified_bytes_total", path="get")
    dispatched = node.dispatched()
    calls = node.span("device-call")[1]

    status, got = node.get("whole")
    assert status == 200 and got == body

    ref = reference_decode.decode(node.files("whole", but=pair), K, M,
                                  BLOCK, len(body), CODEC)
    assert ref.body == body and not ref.dropped
    assert sorted(ref.rebuilt) == sorted(lost)
    for idx in lost:
        assert np.array_equal(ref.rebuilt[idx], chunks[:, idx - 1])

    # the counters say what the read did: k shards of every block
    # verified, every block rebuilt where a data shard was lost, and the
    # four of them, one reader batch, in one fused dispatch
    assert (node.counter("bitrot_verified_bytes_total", path="get")
            - verified) == K * BLOCKS * reference.shard_size(BLOCK, K)
    degraded = any(i <= K for i in lost)
    assert (node.counter("get_reconstructed_blocks_total") - rebuilt) == \
        (BLOCKS if degraded else 0)
    assert {kind: n - dispatched[kind]
            for kind, n in node.dispatched().items()} == \
        {"apply": 0, "reconstruct": 1 if degraded else 0}
    # a `device-call` on the GET's own span tree
    assert node.span("device-call")[1] - calls == (1 if degraded else 0)

    healed = node.counter("bitrot_verified_bytes_total", path="heal")
    node.ol.heal_object(BUCKET, "whole")
    # the heal verified what it read, under its own label
    assert (node.counter("bitrot_verified_bytes_total", path="heal")
            - healed) >= K * BLOCKS * reference.shard_size(BLOCK, K)
    for d, idx in zip(pair, lost):
        assert node.shard_of(d, "whole") == \
            (idx, _frames(chunks, digests, idx)), f"d{d}, shard {idx}"


def _flip(node, key: str, drive: int, at: int = 40) -> None:
    odir = node.object_dir(drive, key)
    part = next(os.path.join(dp, "part.1") for dp, _, fs in os.walk(odir)
                if "part.1" in fs)
    with open(part, "r+b") as f:
        f.seek(at)
        byte = f.read(1)
        f.seek(at)
        f.write(bytes([byte[0] ^ 1]))


def _drive_of_shard(node, key: str, idx: int) -> int:
    return next(d for d in range(1, DRIVES + 1)
                if node.shard_of(d, key)[0] == idx)


def test_a_flipped_byte_is_caught_and_read_around(node):
    """One bad byte in a frame of a data shard: the shard fails its
    digest and is dropped, the GET answers rightly from the others, and
    a heal of the object is queued."""
    body = node.put("rot1", 36)
    _flip(node, "rot1", _drive_of_shard(node, "rot1", 3))
    verified = node.counter("bitrot_verified_bytes_total", path="get")
    queued = node.counter("get_mrf_queued_total")
    rebuilt = node.counter("get_reconstructed_blocks_total")
    status, got = node.get("rot1")
    assert status == 200 and got == body
    s = reference.shard_size(BLOCK, K)
    # seven data shards and the parity shard read in its place passed;
    # the bad one is not counted
    assert (node.counter("bitrot_verified_bytes_total", path="get")
            - verified) == K * BLOCKS * s
    assert node.counter("get_mrf_queued_total") - queued == 1
    assert node.counter("get_reconstructed_blocks_total") - rebuilt == BLOCKS
    assert (BUCKET, "rot1", "") in [e[:3] for e in
                                    node.ol.pools[0].sets[0].drain_mrf()]
    # the reference drops the same shard and gives the same bytes
    ref = reference_decode.decode(node.files("rot1"), K, M, BLOCK,
                                  len(body), CODEC)
    assert ref.dropped == [3] and ref.body == body
    assert ref.verified_bytes == (K + M - 1) * BLOCKS * s


def test_five_bad_shards_fail_the_get_and_say_so(node):
    body = node.put("rot5", 37)
    for idx in (1, 2, 3, 9, 10):
        _flip(node, "rot5", _drive_of_shard(node, "rot5", idx))
    with pytest.raises(ErrErasureReadQuorum):
        node.ol.get_object(BUCKET, "rot5", io.BytesIO())
    try:
        status, got = node.get("rot5")
    except (http.client.HTTPException, OSError):
        status, got = 0, b""                   # severed, never a short 200
    assert status != 200 or len(got) < len(body)
    with pytest.raises(reference_decode.TooFewShards):
        reference_decode.decode(node.files("rot5"), K, M, BLOCK, len(body),
                                CODEC)


def test_write_quorum_and_read_quorum_are_eight(node):
    body = node.put("quorum", 38)
    try:
        for d in node.disks[:4]:
            d.set_online(False)
        # four drives gone: both still answer
        status, got = node.get("quorum")
        assert status == 200 and got == body
        node.put("quorum4", 39)
        node.disks[4].set_online(False)
        # five gone: a PUT is refused, a GET is refused
        with pytest.raises(ErrErasureWriteQuorum):
            node.ol.put_object(BUCKET, "quorum5", io.BytesIO(body),
                               len(body))
        status, data = node.request("PUT", f"/{BUCKET}/quorum5", body)
        assert status == 503, data
        with pytest.raises(ErrErasureReadQuorum):
            node.ol.get_object(BUCKET, "quorum", io.BytesIO())
        status, data = node.get("quorum")
        assert status == 503, data
    finally:
        for d in node.disks[:5]:
            d.set_online(True)
    status, got = node.get("quorum")
    assert status == 200 and got == body


def test_a_degraded_get_at_the_published_block_size(node, monkeypatch):
    """The deployment's own geometry: 1 MiB blocks, 131,072-byte shards,
    three blocks so that the fused driver runs, two data shards lost:
    one dispatch rebuilds both shards of all three blocks."""
    mib = 1 << 20
    monkeypatch.setattr(erasure_objects, "BLOCK_SIZE_V2", mib)
    body = node.put("mib", 40, blocks=3, block=mib)
    chunks, digests = reference.expected_shards([body], K, M, mib, CODEC)
    for idx, raw in node.files("mib").items():
        assert raw == _frames(chunks[0], digests[0], idx)
    pair = (_drive_of_shard(node, "mib", 2), _drive_of_shard(node, "mib", 7))
    node.lose("mib", pair)
    rebuilt = node.counter("get_reconstructed_blocks_total")
    verified = node.counter("bitrot_verified_bytes_total", path="get")
    dispatched = node.dispatched()
    status, got = node.get("mib")
    assert status == 200 and got == body
    ref = reference_decode.decode(node.files("mib", but=pair), K, M, mib,
                                  len(body), CODEC)
    assert ref.body == body and sorted(ref.rebuilt) == [2, 7]
    assert node.counter("get_reconstructed_blocks_total") - rebuilt == 3
    assert {kind: n - dispatched[kind]
            for kind, n in node.dispatched().items()} == \
        {"apply": 0, "reconstruct": 1}
    assert (node.counter("bitrot_verified_bytes_total", path="get")
            - verified) == K * 3 * 131072


KINDS = ("request", "object", "admission", "stream")


def test_a_get_opens_the_object_span_and_its_children(node, whole):
    """`request` > `object` > `admission` (the read slot) and `stream`
    for a GET as for a PUT, so request - object and object - stream
    read; a degraded GET's `stream` holds a `device-h2d`, a
    `device-call` and a `device-wait` a reader batch, a healthy one
    none."""
    body, _, _ = whole
    data_drive = _drive_of_shard(node, "whole", 1)
    for lost, leaves in (((), 0), ((data_drive,), 1)):
        node.lose("whole", lost)
        kinds = KINDS + ("device-h2d", "device-call", "device-wait")
        before = {k: node.span(k) for k in kinds}
        status, got = node.get("whole")
        assert status == 200 and got == body
        took = {}
        for k in kinds:
            seconds, n = node.span(k)
            want = leaves if k.startswith("device-") else 1
            assert n - before[k][1] == want, (k, lost)
            took[k] = seconds - before[k][0]
        assert took["request"] >= took["object"] >= \
            took["stream"] + took["admission"] > 0
        assert took["stream"] >= took["device-h2d"] + \
            took["device-call"] + took["device-wait"]
        assert (took["device-call"] > 0) == bool(lost)
    node.ol.heal_object(BUCKET, "whole")


def test_one_lost_drive_costs_a_rebuild_in_8_of_12_rotations(node, whole):
    """Why every seed of `n12dev1-get10m` does the same work: a drive
    holds one shard of the object, which of the twelve is a turn of the
    ring, and eight of the twelve are data."""
    held = sorted(node.shard_of(d, "whole")[0] for d in range(1, DRIVES + 1))
    assert held == list(range(1, DRIVES + 1))
    assert sum(idx <= K for idx in held) == 8


def test_the_read_tier_answers_under_a_leaf_of_its_own(node, monkeypatch):
    """A key the tier holds is answered without a decode: the GET's tree
    has a `readtier` leaf and no `stream`."""
    from minio_tpu.object import readtier

    monkeypatch.setenv("MTPU_READTIER", "on")
    monkeypatch.setenv("MTPU_READTIER_HOT_BYTES", "1")
    readtier.reset()
    body = node.put("hot", 41)
    for _ in range(3):                         # turns hot, then leads
        assert node.get("hot") == (200, body)
    streams = node.span("stream")[1]
    tiers = node.span("readtier")[1]
    assert node.get("hot") == (200, body)
    assert node.span("readtier")[1] - tiers == 1
    assert node.span("stream")[1] == streams
    readtier.reset()
