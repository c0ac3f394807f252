"""The deployment `node4x4-ec12p4-dev4` (four nodes x four drives, one 12+4
set over all sixteen, the device engine on JAX's CPU) held to its
guarantees through the served path, against the benchmark's independent
reference (`benchmark/harness/reference.py`): what a GET through another
node returns, and what all sixteen drives hold.

The four nodes are in-process `Server`s booted at once, as
`test_multinode.py` boots two: disjoint port triples (storage, peer and
lock planes), the same endpoint list on every node, flat drives `d1..d16`
as the benchmark's harness lays them out, a fresh boot where a bind was
lost. Every comparison is of bytes and exact. The node-stopping test
comes last: the cluster stays one node short after it.

    JAX_PLATFORMS=cpu python -m pytest tests/test_cluster_ec12p4.py -q
"""

from __future__ import annotations

import contextlib
import http.client
import json
import os
import signal
import threading

import numpy as np
import pytest

from benchmark.harness import child as childmod
from benchmark.harness import reference
from minio_tpu.api.sign import sign_v4_request
from minio_tpu.distributed import rest
from minio_tpu.object.pools import ErasureServerPools
from minio_tpu.observability import spans
from minio_tpu.server import Server
from minio_tpu.storage.xlmeta import read_xl_meta

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AK, SK = "minioadmin", "minioadmin"
BUCKET = "bench"
NODES, PER, K, M = 4, 4, 12, 4
DRIVES = NODES * PER
MIB = 1 << 20
CODEC = "dense-gf8"
SIZES = [1, MIB - 1, 3 * MIB + 17, 10 * MIB]
BOOT_S = 60


@contextlib.contextmanager
def limit(seconds: float):
    """The test's own time limit: SIGALRM raises in the test's thread."""
    def on_alarm(signum, frame):
        raise TimeoutError(f"over this test's limit of {seconds} s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def request(srv, method: str, path: str, body: bytes = b""):
    """One signed request; a PUT asks for the STANDARD class, which the
    configuration's `EC:4` makes 12+4 (as the harness asks for it)."""
    host = srv.endpoint
    extra = {"x-amz-storage-class": "STANDARD"} if method == "PUT" else {}
    hdrs = sign_v4_request(SK, AK, method, host, path, [], extra, body)
    conn = http.client.HTTPConnection(host, timeout=60)
    try:
        conn.request(method, path, body=body, headers=hdrs)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _boot(tmp: str) -> tuple[list, dict]:
    """One boot attempt of the four nodes at once -> (servers, errors)."""
    sp = childmod.free_triples(NODES, set())
    eps = [f"http://127.0.0.1:{sp[i]}{tmp}/d{i * PER + j + 1}"
           for i in range(NODES) for j in range(PER)]
    servers: list = [None] * NODES
    errors: dict = {}

    def boot(i):
        try:
            servers[i] = Server(list(eps), port=0, root_user=AK,
                                root_password=SK, enable_scanner=False,
                                storage_address=f"127.0.0.1:{sp[i]}").start()
        except Exception as exc:  # noqa: BLE001 - surfaced by the caller
            errors[i] = exc

    threads = [threading.Thread(target=boot, args=(i,)) for i in range(NODES)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(BOOT_S)
    if any(t.is_alive() for t in threads):
        errors["boot"] = TimeoutError(f"a node did not boot in {BOOT_S} s")
    return servers, errors


class Cluster:
    def __init__(self, tmp_factory):
        for attempt in range(3):
            self.tmp = str(tmp_factory.mktemp(f"c4x4-{attempt}"))
            self.nodes, errors = _boot(self.tmp)
            if not errors:
                break
            self.stop()
        assert not errors, errors
        assert request(self.nodes[0], "PUT", f"/{BUCKET}")[0] == 200

    def stop(self):
        for s in self.nodes:
            if s is not None:
                s.stop()

    def put(self, via: int, key: str, body: bytes) -> None:
        status, data = request(self.nodes[via], "PUT", f"/{BUCKET}/{key}",
                               body)
        assert status == 200, data

    def get(self, via: int, key: str) -> bytes:
        status, data = request(self.nodes[via], "GET", f"/{BUCKET}/{key}")
        assert status == 200, data
        return data

    def files(self, key: str, drives=range(1, DRIVES + 1)) -> dict:
        """{shard index as xl.meta counts it: the shard file's bytes}, the
        file inline in xl.meta where the object is small."""
        out = {}
        for d in drives:
            odir = os.path.join(self.tmp, f"d{d}", BUCKET, key)
            with open(os.path.join(odir, "xl.meta"), "rb") as f:
                fi = read_xl_meta(f.read(), BUCKET, key, None)
            er = fi.erasure
            assert (er.data_blocks, er.parity_blocks) == (K, M)
            if fi.data:
                raw = fi.data[1]
            else:
                with open(os.path.join(odir, fi.data_dir, "part.1"),
                          "rb") as f:
                    raw = f.read()
            assert er.index not in out, f"shard {er.index} on two drives"
            out[er.index] = bytes(raw)
        return out


def expected_files(body: bytes) -> dict:
    """The reference's sixteen shard files of `body`: block by block (the
    last one as long as it is), data split and GF(2^8) parity under the
    codec, each chunk framed behind its HighwayHash-256 digest."""
    mat = reference.parity_matrix(CODEC, K, M)
    files = {i: [] for i in range(1, K + M + 1)}
    for off in range(0, len(body), MIB):
        block = body[off:off + MIB]
        s = reference.shard_size(len(block), K)
        data = np.zeros(K * s, dtype=np.uint8)
        data[:len(block)] = np.frombuffer(block, dtype=np.uint8)
        data = data.reshape(1, 1, K, s)
        chunks = np.concatenate([data, reference.apply_matrix(mat, data)],
                                axis=2)
        digests = reference.highwayhash256(chunks)
        for i in files:
            files[i].append(digests[0, 0, i - 1].tobytes()
                            + chunks[0, 0, i - 1].tobytes())
    return {i: b"".join(parts) for i, parts in files.items()}


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    # the configuration's env
    mp.setenv("MTPU_ENCODE_ENGINE", "device")
    mp.setenv("MTPU_STORAGE_CLASS_STANDARD", "EC:4")
    mp.setenv("MTPU_CODEC", CODEC)
    # every request's span tree is kept, for the series' test
    mp.setenv("MTPU_TRACE_SLOW_MS", "0")
    with limit(3 * BOOT_S + 30):
        c = Cluster(tmp_path_factory)
    yield c
    c.stop()
    mp.undo()


def test_the_configuration_names_this_file_and_its_geometry():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "node4x4-ec12p4-dev4.json")) as f:
        cfg = json.load(f)
    dep, g = cfg["deployment"], cfg["guarantees"]
    assert (dep["nodes"], dep["drives"], dep["chips"]) == (NODES, DRIVES, 4)
    assert (dep["data"], dep["parity"], dep["block_size"]) == (K, M, MIB)
    assert dep["shard_size"] == reference.shard_size(MIB, K)
    assert (g["write_quorum"], g["read_quorum"]) == (K, K)
    assert g["lock_quorum"].startswith("3 of 4")
    assert os.path.basename(__file__) in g["held_by"]
    assert cfg["reduced"] == ["run_data_scale", "hosts"]


@pytest.mark.parametrize("size", SIZES)
def test_a_put_through_one_node_reads_back_through_the_next(cluster, size):
    with limit(60):
        for via in range(NODES):
            key = f"obj-{size}-{via}"
            body = reference.payload(39, key, size)
            cluster.put(via, key, body)
            assert cluster.get((via + 1) % NODES, key) == body
            assert cluster.files(key) == expected_files(body)


def test_two_puts_of_one_key_through_two_nodes_leave_one_whole(cluster):
    with limit(60):
        key = "contended"
        bodies = [reference.payload(3900 + i, key, 2 * MIB + i)
                  for i in range(2)]
        statuses = [None, None]

        def put(i):
            statuses[i] = request(cluster.nodes[i], "PUT",
                                  f"/{BUCKET}/{key}", bodies[i])[0]

        threads = [threading.Thread(target=put, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(50)
        assert statuses == [200, 200]
        got = cluster.get(2, key)
        assert got in bodies
        assert cluster.files(key) == expected_files(got)


def _counter(name: str, **labels) -> float:
    """A counter of the whole in-process cluster: the four nodes share one
    process, whose module-level hook writes into the registry of the node
    that booted last."""
    # guardedby-ok: test read of the installed registry, no writer races
    return rest._metrics.counter_value(name, **labels)


def test_a_put_records_rpc_and_lock_spans_and_the_planes_counters(cluster):
    with limit(60):
        key = "traced"
        body = reference.payload(391, key, 10 * MIB)
        sent = _counter("rpc_sent_bytes_total", plane="storage")
        calls = {p: _counter("rpc_calls_total", plane=p) for p in rest.PLANES}
        served = _counter("rpc_served_seconds_total", plane="storage")
        spans.clear_slow_requests()
        cluster.put(0, key, body)
        tree = [s for t in spans.slow_requests() if t["api"] == "put_object"
                for s in t["spans"]]
        rpc = [s["label"] for s in tree if s["kind"] == "rpc"]
        planes = {label.split(":")[0] for label in rpc}
        assert planes == {"storage", "lock"}, rpc
        methods = [label.split(":", 1)[1] for label in rpc
                   if label.startswith("storage:")]
        # node 1 holds drives 1-4: the other twelve drives' files go over
        # the storage plane
        for method in ("create_file", "rename_data"):
            assert methods.count(method) == DRIVES - PER, methods
        assert [s for s in tree if s["kind"] == "lock"]
        remote = sum(len(f) for f in cluster.files(
            key, range(PER + 1, DRIVES + 1)).values())
        assert remote == (DRIVES - PER) * 10 * (reference.shard_size(MIB, K)
                                                + 32)
        assert _counter("rpc_sent_bytes_total", plane="storage") - sent > remote
        assert _counter("rpc_calls_total", plane="storage") - calls["storage"] \
            >= len(methods)
        assert _counter("rpc_calls_total", plane="lock") - calls["lock"] >= 6
        assert _counter("rpc_served_seconds_total", plane="storage") > served


def test_a_put_on_one_node_records_no_rpc_span(tmp_path, monkeypatch):
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "0")
    with limit(60):
        srv = Server([f"{tmp_path}/d{{1...16}}"], port=0, root_user=AK,
                     root_password=SK, enable_scanner=False).start()
        try:
            assert request(srv, "PUT", f"/{BUCKET}")[0] == 200
            spans.clear_slow_requests()
            status, _ = request(srv, "PUT", f"/{BUCKET}/one",
                                reference.payload(5, "one", 3 * MIB))
            assert status == 200
            tree = [s for t in spans.slow_requests()
                    if t["api"] == "put_object" for s in t["spans"]]
            kinds = {s["kind"] for s in tree}
            assert "lock" in kinds and "object" in kinds
            assert "rpc" not in kinds
        finally:
            srv.stop()


def test_a_bucket_deleted_through_one_node_is_refused_by_another(
        cluster, monkeypatch):
    """Node 1 answers its bucket checks from a memo of buckets seen on the
    drives; node 2's DeleteBucket makes every peer forget the bucket, so
    node 1's very next request asks the drives, and no commit brings the
    bucket's directory back. The memo is held from lapsing, so that only
    the peer's forgetting can make node 1 ask."""
    monkeypatch.setattr(ErasureServerPools, "_BUCKET_SEEN_TTL_S", 3600.0)
    with limit(60):
        bucket, key = "gone", "k"
        body = reference.payload(40, key, MIB + 3)
        assert request(cluster.nodes[0], "PUT", f"/{bucket}")[0] == 200
        # the memo warm on node 1
        assert request(cluster.nodes[0], "PUT", f"/{bucket}/{key}",
                       body)[0] == 200
        assert request(cluster.nodes[1], "DELETE",
                       f"/{bucket}/{key}")[0] == 204
        assert request(cluster.nodes[1], "DELETE", f"/{bucket}")[0] == 204
        for method, data in (("PUT", body), ("GET", b"")):
            status, resp = request(cluster.nodes[0], method,
                                   f"/{bucket}/{key}", data)
            assert status == 404, resp
            assert b"<Code>NoSuchBucket</Code>" in resp
        for d in range(1, DRIVES + 1):
            assert not os.path.exists(os.path.join(cluster.tmp, f"d{d}",
                                                   bucket))


def test_one_node_stopped_leaves_put_and_get_served(cluster):
    """Twelve drives are the write and read quorum, three of four lockers
    the lock quorum. Last in the file: the cluster stays one node short."""
    with limit(60):
        cluster.nodes[3].stop()
        cluster.nodes[3] = None
        for via in range(3):
            key = f"degraded-{via}"
            body = reference.payload(392, key, 3 * MIB + 17)
            cluster.put(via, key, body)
            assert cluster.get((via + 1) % 3, key) == body
            want = expected_files(body)
            got = cluster.files(key, range(1, DRIVES - PER + 1))
            assert len(got) == DRIVES - PER
            assert {i: want[i] for i in got} == got
