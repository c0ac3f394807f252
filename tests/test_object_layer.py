"""Object-layer suite, modeled on the reference's backend-agnostic object
API tests (/root/reference/cmd/object_api_suite_test.go,
object-api-putobject_test.go, erasure-healing_test.go): put/get round
trips, inline small objects, versioning + delete markers, listing, disk
failures, and heal convergence."""

import io
import os
import shutil

import numpy as np
import pytest

from minio_tpu.object.erasure_objects import ErasureObjects
from minio_tpu.object.pools import ErasureServerPools
from minio_tpu.object.sets import ErasureSets
from minio_tpu.object.types import ObjectOptions
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils.errors import (
    ErrBucketNotFound,
    ErrErasureReadQuorum,
    ErrObjectNotFound,
)


def make_pools(tmp_path, n_disks=4, set_drive_count=None, parity=None, pools=1):
    all_pools = []
    disks_all = []
    for p in range(pools):
        disks = [
            LocalStorage(str(tmp_path / f"pool{p}-disk{i}"), endpoint=f"p{p}d{i}")
            for i in range(n_disks)
        ]
        sets = ErasureSets(
            disks, set_drive_count or n_disks,
            deployment_id="8d29483c-bbdb-4d35-8a86-b5b99a1c1a99",
            default_parity=parity, pool_index=p,
        )
        sets.init_format()
        all_pools.append(sets)
        disks_all.append(disks)
    z = ErasureServerPools(all_pools)
    return z, disks_all


@pytest.fixture
def layer(tmp_path):
    z, disks = make_pools(tmp_path, n_disks=4)
    z.make_bucket("bkt")
    return z, disks[0]


def test_put_get_roundtrip_inline(layer):
    z, _ = layer
    data = b"hello tpu object store"
    oi = z.put_object("bkt", "small.txt", io.BytesIO(data), len(data))
    assert oi.size == len(data)
    assert oi.etag  # md5 hex
    got = z.get_object_bytes("bkt", "small.txt")
    assert got == data
    info = z.get_object_info("bkt", "small.txt")
    assert info.size == len(data)
    assert info.data_blocks == 2 and info.parity_blocks == 2


def test_put_get_roundtrip_large(layer):
    z, disks = layer
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=3 * (1 << 20) + 12345, dtype=np.uint8).tobytes()
    z.put_object("bkt", "dir/large.bin", io.BytesIO(data), len(data))
    assert z.get_object_bytes("bkt", "dir/large.bin") == data
    # Range read.
    assert z.get_object_bytes("bkt", "dir/large.bin", 1 << 20, 4096) == \
        data[1 << 20 : (1 << 20) + 4096]
    # Shard part files actually exist (not inline at this size).
    found = 0
    for d in disks:
        for root, _, files in os.walk(d.root):
            found += sum(1 for f in files if f.startswith("part."))
    assert found == 4


def test_get_with_disk_failures(layer):
    z, disks = layer
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(1 << 20) + 7, dtype=np.uint8).tobytes()
    z.put_object("bkt", "obj", io.BytesIO(data), len(data))
    # 2+2 tolerates 2 dead disks for reads.
    disks[0].set_online(False)
    disks[3].set_online(False)
    assert z.get_object_bytes("bkt", "obj") == data
    disks[1].set_online(False)
    with pytest.raises(Exception):
        z.get_object_bytes("bkt", "obj")
    for d in disks:
        d.set_online(True)


def test_overwrite_and_delete(layer):
    z, _ = layer
    z.put_object("bkt", "o", io.BytesIO(b"v1"), 2)
    z.put_object("bkt", "o", io.BytesIO(b"version2"), 8)
    assert z.get_object_bytes("bkt", "o") == b"version2"
    z.delete_object("bkt", "o")
    with pytest.raises(ErrObjectNotFound):
        z.get_object_info("bkt", "o")


def test_versioned_put_and_delete_marker(layer):
    z, _ = layer
    opts = ObjectOptions(versioned=True)
    oi1 = z.put_object("bkt", "v", io.BytesIO(b"one"), 3, opts)
    oi2 = z.put_object("bkt", "v", io.BytesIO(b"two"), 3, opts)
    assert oi1.version_id and oi2.version_id and oi1.version_id != oi2.version_id
    assert z.get_object_bytes("bkt", "v") == b"two"
    assert z.get_object_bytes(
        "bkt", "v", opts=ObjectOptions(version_id=oi1.version_id)
    ) == b"one"
    # Versioned delete -> delete marker; latest read now 404s.
    dm = z.delete_object("bkt", "v", ObjectOptions(versioned=True))
    assert dm.delete_marker and dm.version_id
    with pytest.raises(ErrObjectNotFound):
        z.get_object_bytes("bkt", "v")
    # Old version still readable by id.
    assert z.get_object_bytes(
        "bkt", "v", opts=ObjectOptions(version_id=oi2.version_id)
    ) == b"two"


def test_list_objects(layer):
    z, _ = layer
    for name in ["a/1", "a/2", "b/1", "top1", "top2"]:
        z.put_object("bkt", name, io.BytesIO(b"x"), 1)
    res = z.list_objects("bkt")
    assert [o.name for o in res.objects] == ["a/1", "a/2", "b/1", "top1", "top2"]
    res = z.list_objects("bkt", prefix="a/")
    assert [o.name for o in res.objects] == ["a/1", "a/2"]
    res = z.list_objects("bkt", delimiter="/")
    assert [o.name for o in res.objects] == ["top1", "top2"]
    assert res.prefixes == ["a/", "b/"]
    res = z.list_objects("bkt", max_keys=2)
    assert res.is_truncated and len(res.objects) == 2
    with pytest.raises(ErrBucketNotFound):
        z.list_objects("nosuch")


def test_heal_object_missing_shards(tmp_path):
    # Mirror erasure-healing_test.go: delete shard files + xl.meta on some
    # disks, heal, verify bytes identical.
    z, disks_all = make_pools(tmp_path, n_disks=6, parity=2)
    disks = disks_all[0]
    z.make_bucket("bkt")
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=2 * (1 << 20) + 17, dtype=np.uint8).tobytes()
    z.put_object("bkt", "heal-me", io.BytesIO(data), len(data))

    # Wipe the object dir entirely on 2 disks.
    for i in (1, 4):
        obj_dir = os.path.join(disks[i].root, "bkt", "heal-me")
        shutil.rmtree(obj_dir)
    res = z.heal_object("bkt", "heal-me")
    assert len(res["healed"]) == 2
    # All disks can now serve even if the originally-healthy ones die.
    disks[0].set_online(False)
    disks[2].set_online(False)
    assert z.get_object_bytes("bkt", "heal-me") == data


def test_heal_inline_object(tmp_path):
    z, disks_all = make_pools(tmp_path, n_disks=4)
    disks = disks_all[0]
    z.make_bucket("bkt")
    z.put_object("bkt", "tiny", io.BytesIO(b"inline-data"), 11)
    shutil.rmtree(os.path.join(disks[2].root, "bkt", "tiny"))
    res = z.heal_object("bkt", "tiny")
    assert len(res["healed"]) == 1
    disks[0].set_online(False)
    disks[1].set_online(False)
    assert z.get_object_bytes("bkt", "tiny") == b"inline-data"


def test_heal_dangling_object(tmp_path):
    z, disks_all = make_pools(tmp_path, n_disks=4)
    disks = disks_all[0]
    z.make_bucket("bkt")
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(1 << 20) + 1, dtype=np.uint8).tobytes()
    z.put_object("bkt", "dang", io.BytesIO(data), len(data))
    # Destroy beyond repair: only 1 of 4 shards left (need 2).
    for i in (0, 1, 2):
        shutil.rmtree(os.path.join(disks[i].root, "bkt", "dang"))
    with pytest.raises(ErrErasureReadQuorum):
        z.heal_object("bkt", "dang")
    res = z.heal_object("bkt", "dang", remove_dangling=True)
    assert res["dangling"]
    with pytest.raises(ErrObjectNotFound):
        z.get_object_info("bkt", "dang")


def test_mrf_queued_on_degraded_read(tmp_path):
    z, disks_all = make_pools(tmp_path, n_disks=4)
    disks = disks_all[0]
    z.make_bucket("bkt")
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(1 << 20) * 2, dtype=np.uint8).tobytes()
    z.put_object("bkt", "deg", io.BytesIO(data), len(data))
    # Remove one shard's part file (xl.meta intact) -> the bitrot reader
    # fails with FileNotFound mid-read, read still succeeds, heal queued
    # (ref cmd/erasure-object.go:319-338).
    obj_dir = os.path.join(disks[3].root, "bkt", "deg")
    for root, _, files in os.walk(obj_dir):
        for f in files:
            if f.startswith("part."):
                os.remove(os.path.join(root, f))
    assert z.get_object_bytes("bkt", "deg") == data
    the_set = z.pools[0].get_hashed_set("deg")
    queued = the_set.drain_mrf()
    assert ("bkt", "deg", "") in queued


def test_set_placement_is_deterministic(tmp_path):
    z, _ = make_pools(tmp_path, n_disks=8, set_drive_count=4)
    sets = z.pools[0]
    assert sets.set_count == 2
    idx1 = sets.get_hashed_set_index("some/object/name")
    for _ in range(5):
        assert sets.get_hashed_set_index("some/object/name") == idx1
    # Objects spread across sets.
    spread = {sets.get_hashed_set_index(f"obj-{i}") for i in range(64)}
    assert spread == {0, 1}
    z.make_bucket("bkt")
    z.put_object("bkt", "routed", io.BytesIO(b"abc"), 3)
    assert z.get_object_bytes("bkt", "routed") == b"abc"


def test_multi_pool_routing(tmp_path):
    z, _ = make_pools(tmp_path, n_disks=4, pools=2)
    z.make_bucket("bkt")
    z.put_object("bkt", "x", io.BytesIO(b"data1"), 5)
    assert z.get_object_bytes("bkt", "x") == b"data1"
    # Overwrite stays in the same pool; still one logical object.
    z.put_object("bkt", "x", io.BytesIO(b"data22"), 6)
    assert z.get_object_bytes("bkt", "x") == b"data22"
    names = [o.name for o in z.list_objects("bkt").objects]
    assert names == ["x"]
    z.delete_object("bkt", "x")
    with pytest.raises(ErrObjectNotFound):
        z.get_object_info("bkt", "x")


def test_empty_object(layer):
    z, _ = layer
    z.put_object("bkt", "empty", io.BytesIO(b""), 0)
    assert z.get_object_bytes("bkt", "empty") == b""
    assert z.get_object_info("bkt", "empty").size == 0


# ---------- pipelined ETag hashing (r5 PUT-stage overlap) ----------


def test_tee_md5_pipelined_matches_inline():
    """The pipelined (worker-thread) hasher produces the identical
    digest as inline hashing through read() AND readinto() — including
    when the caller clobbers the readinto buffer immediately after
    consumption (the async snapshot contract)."""
    import hashlib

    from minio_tpu.object.types import TeeMD5Reader

    data = os.urandom(5 << 20)
    want = hashlib.md5(data).hexdigest()
    for pipelined in (False, True):
        t = TeeMD5Reader(io.BytesIO(data), pipelined=pipelined)
        got = b""
        while True:
            chunk = t.read(1 << 20)
            if not chunk:
                break
            got += chunk
        assert got == data
        assert t.md5_hex() == want
        assert t.md5_hex() == want  # idempotent after drain

        t2 = TeeMD5Reader(io.BytesIO(data), pipelined=pipelined)
        buf = bytearray(1 << 20)
        while True:
            n = t2.readinto(buf)
            if not n:
                break
            buf[:n] = b"\x00" * n  # clobber after the pipeline consumed
        assert t2.bytes_read == len(data)
        assert t2.md5_hex() == want, f"pipelined={pipelined}"


def test_tee_md5_overlap_speedup_on_multicore():
    """Prove or retire the pipelined tee (ROADMAP D7). The
    worker-thread hasher's reason to exist is REAL md5/encode overlap:
    hashing batch N on the worker while the caller's thread runs the
    GIL-releasing native encode. On >=2 cores that must measure
    faster than the inline tee driving the same work serially
    (speedup > 1.0; this gate asserts > 1.05 to clear timer noise —
    measured ~1.19x on the 2-core CI host, so the worker path STAYS).
    On a 1-core host the serial-sum bound holds by physics (r5
    measured 0.978x) and the tee already auto-selects inline hashing
    — skip, don't fail.

    The measurement runs in a FRESH subprocess
    (tests/_md5_overlap_child.py): inside a pytest process that has run
    ~500 tests, leftover threads and GIL churn reliably flatten the
    fine-grained 1 MiB-handoff overlap to ~1.0x even when a coarse
    two-thread hashing probe says a second core is free (1.19x fresh vs
    1.00-1.03x mid-suite on the same host). A server process — what the
    tee actually serves in — looks like the fresh interpreter, not the
    suite veteran; the child still gates on cpu_count / native engine /
    live two-thread scaling, and its verdict is differential — the tee
    must only match a hand-rolled ideal-overlap control measured under
    the same conditions, so host weather reports as a skip while a
    genuine worker-path regression still fails."""
    import json
    import subprocess
    import sys

    if (os.cpu_count() or 1) < 2:
        pytest.skip("1-core host: overlap cannot exist (inline tee wins)")
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [sys.executable, os.path.join(tests_dir, "_md5_overlap_child.py")],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(tests_dir),
    )
    assert r.returncode == 0, (
        f"md5-overlap child failed rc={r.returncode}\n--- stdout ---\n"
        f"{r.stdout}\n--- stderr ---\n{r.stderr}"
    )
    line = next(
        ln for ln in r.stdout.splitlines() if ln.startswith("MD5_OVERLAP ")
    )
    res = json.loads(line[len("MD5_OVERLAP "):])
    if "skip" in res:
        pytest.skip(res["skip"])
    assert res["speedup"] > 1.05, (
        f"pipelined tee shows no overlap on {os.cpu_count()} cores in a "
        f"fresh process: serial={res['serial']:.4f}s "
        f"parallel={res['parallel']:.4f}s — if no multicore host can "
        "clear 1.0, retire the worker-thread path"
    )


def test_tee_md5_abandoned_reader_stops_worker():
    """An error path that never reaches md5_hex must not leak the
    hashing thread: GC of the reader shuts it down."""
    import gc
    import threading
    import time

    from minio_tpu.object.types import TeeMD5Reader

    before = threading.active_count()
    t = TeeMD5Reader(io.BytesIO(os.urandom(1 << 20)), pipelined=True)
    t.read(1 << 20)
    del t
    gc.collect()
    deadline = time.time() + 5
    while time.time() < deadline and threading.active_count() > before:
        time.sleep(0.02)
    assert threading.active_count() <= before


def test_put_uses_pipelined_etag_correctly(tmp_path):
    """End-to-end: a PUT through the object layer with the pipelined
    hasher forced on yields the correct S3 ETag."""
    import hashlib

    from minio_tpu.object import types as types_mod

    ol, _ = (lambda r: (r[0], r[1]))(make_pools(tmp_path))
    ol.make_bucket("pipetag")
    data = os.urandom(3 << 20)
    orig = types_mod.TeeMD5Reader

    class ForcedPipelined(orig):
        def __init__(self, src, pipelined=None, size=None):
            super().__init__(src, pipelined=True, size=size)

    types_mod.TeeMD5Reader = ForcedPipelined
    try:
        import minio_tpu.object.erasure_objects as eo

        saved = eo.TeeMD5Reader
        eo.TeeMD5Reader = ForcedPipelined
        try:
            oi = ol.put_object("pipetag", "obj", io.BytesIO(data),
                               len(data), ObjectOptions())
        finally:
            eo.TeeMD5Reader = saved
    finally:
        types_mod.TeeMD5Reader = orig
    assert oi.etag == hashlib.md5(data).hexdigest()
    sink = io.BytesIO()
    ol.get_object("pipetag", "obj", sink)
    assert sink.getvalue() == data
