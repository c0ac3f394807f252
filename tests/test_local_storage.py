"""LocalStorage (xl-storage equivalent) behavior tests: volumes, blobs,
version journal, rename-commit, walk, verify-file."""

import io
import os

import pytest

from minio_tpu.storage.fileinfo import ErasureInfo, FileInfo, new_uuid
from minio_tpu.storage.local import SYSTEM_TMP, XL_META_FILE, LocalStorage
from minio_tpu.storage.xlmeta import FanoutMetaPack
from minio_tpu.utils.errors import (
    ErrFileNotFound,
    ErrFileVersionNotFound,
    ErrVolumeExists,
    ErrVolumeNotEmpty,
    ErrVolumeNotFound,
)


@pytest.fixture
def disk(tmp_path):
    return LocalStorage(str(tmp_path / "disk0"), endpoint="test-disk-0")


def test_volume_crud(disk):
    disk.make_vol("bucket1")
    with pytest.raises(ErrVolumeExists):
        disk.make_vol("bucket1")
    assert disk.stat_vol("bucket1").name == "bucket1"
    names = [v.name for v in disk.list_vols()]
    assert "bucket1" in names
    with pytest.raises(ErrVolumeNotFound):
        disk.stat_vol("nope")
    disk.write_all("bucket1", "a/b", b"x")
    with pytest.raises(ErrVolumeNotEmpty):
        disk.delete_vol("bucket1")
    disk.delete_vol("bucket1", force_delete=True)
    with pytest.raises(ErrVolumeNotFound):
        disk.stat_vol("bucket1")


def test_blob_and_stream_io(disk):
    disk.make_vol("b")
    disk.write_all("b", "cfg/x.json", b"hello")
    assert disk.read_all("b", "cfg/x.json") == b"hello"
    with pytest.raises(ErrFileNotFound):
        disk.read_all("b", "missing")
    disk.create_file("b", "data/big", 5000, io.BytesIO(b"z" * 5000))
    assert disk.read_file("b", "data/big", 100, 50) == b"z" * 50
    r = disk.read_file_stream("b", "data/big", 4990, 10)
    assert r.read() == b"z" * 10
    r.close()


def test_version_journal_and_rename_data(disk):
    disk.make_vol("b")
    fi = FileInfo.new("b", "obj1")
    fi.version_id = new_uuid()
    fi.size = 11
    fi.data_dir = new_uuid()
    fi.erasure = ErasureInfo(data_blocks=2, parity_blocks=2, block_size=1 << 20,
                             index=1, distribution=[1, 2, 3, 4])
    fi.add_part(1, 11, 11)

    # Stage shard under tmp then commit, like putObject.
    tmp_id = new_uuid()
    disk.create_file(SYSTEM_TMP.split("/")[0], f"tmp/{tmp_id}/part.1", 5,
                     io.BytesIO(b"shard"))
    disk.rename_data(".mtpu.sys", f"tmp/{tmp_id}", fi, "b", "obj1")

    got = disk.read_version("b", "obj1")
    assert got.version_id == fi.version_id
    assert got.size == 11
    assert got.is_latest
    part_path = f"obj1/{fi.data_dir}/part.1"
    assert disk.read_file("b", part_path, 0, 5) == b"shard"

    # Second version becomes latest.
    fi2 = FileInfo.new("b", "obj1")
    fi2.version_id = new_uuid()
    fi2.size = 3
    fi2.mod_time_ns = fi.mod_time_ns + 10
    disk.write_metadata("b", "obj1", fi2)
    assert disk.read_version("b", "obj1").version_id == fi2.version_id
    assert disk.read_version("b", "obj1", fi.version_id).version_id == fi.version_id
    assert len(disk.list_versions("b", "obj1").versions) == 2

    # Delete latest; older becomes latest again.
    disk.delete_version("b", "obj1", fi2)
    assert disk.read_version("b", "obj1").version_id == fi.version_id
    with pytest.raises(ErrFileVersionNotFound):
        disk.read_version("b", "obj1", fi2.version_id)
    # Deleting last version drops xl.meta entirely.
    disk.delete_version("b", "obj1", fi)
    with pytest.raises(ErrFileNotFound):
        disk.read_version("b", "obj1")


def _staged_version(disk, body: bytes, data_dir: str = ""):
    """A version of 'b'/obj staged under tmp as putObject stages it, with
    the fan-out's shared journal pack; -> (tmp path, FileInfo)."""
    fi = FileInfo.new("b", "obj")
    fi.version_id = new_uuid()
    fi.size = len(body)
    fi.data_dir = data_dir or new_uuid()
    fi.erasure = ErasureInfo(data_blocks=2, parity_blocks=2,
                             block_size=1 << 20, index=1,
                             distribution=[1, 2, 3, 4])
    fi.add_part(1, len(body), len(body))
    fi.fanout_pack = FanoutMetaPack()
    tmp = f"tmp/{new_uuid()}"
    disk.create_file(SYSTEM_TMP.split("/")[0], f"{tmp}/part.1", len(body),
                     io.BytesIO(body))
    return tmp, fi


def test_a_fresh_commit_writes_the_shared_pack_and_the_next_merges(disk):
    disk.make_vol("b")
    tmp, fi = _staged_version(disk, b"first")
    disk.rename_data(".mtpu.sys", tmp, fi, "b", "obj")
    obj_dir = os.path.join(disk.root, "b", "obj")
    with open(os.path.join(obj_dir, XL_META_FILE), "rb") as f:
        assert f.read() == fi.fanout_pack.bytes_for(fi)
    tmp2, fi2 = _staged_version(disk, b"second")
    fi2.mod_time_ns = fi.mod_time_ns + 10
    disk.rename_data(".mtpu.sys", tmp2, fi2, "b", "obj")
    assert {v.version_id for v in disk.list_versions("b", "obj").versions} \
        == {fi.version_id, fi2.version_id}
    assert disk.read_version("b", "obj").version_id == fi2.version_id
    assert disk.read_file("b", f"obj/{fi2.data_dir}/part.1", 0, 6) \
        == b"second"
    # no tmp journal is left beside xl.meta
    assert sorted(n for n in os.listdir(obj_dir) if n.startswith(".")) == []


def test_a_commit_whose_staged_dir_is_gone_leaves_no_directory(disk):
    disk.make_vol("b")
    tmp, fi = _staged_version(disk, b"lost")
    disk.delete(SYSTEM_TMP.split("/")[0], tmp, recursive=True)
    with pytest.raises(ErrFileNotFound):
        disk.rename_data(".mtpu.sys", tmp, fi, "b", "deep/prefix/obj")
    assert os.listdir(os.path.join(disk.root, "b")) == []


def test_a_re_commit_of_one_data_dir_replaces_it_whole(disk):
    """A heal commits the data dir of the version the drive already
    has, under the same id: the staged files take the old ones' place,
    and the journal still holds one version."""
    disk.make_vol("b")
    tmp, fi = _staged_version(disk, b"stale")
    disk.rename_data(".mtpu.sys", tmp, fi, "b", "obj")
    tmp2, again = _staged_version(disk, b"fresh", data_dir=fi.data_dir)
    again.version_id = fi.version_id
    disk.rename_data(".mtpu.sys", tmp2, again, "b", "obj")
    assert disk.read_file("b", f"obj/{fi.data_dir}/part.1", 0, 5) \
        == b"fresh"
    assert len(disk.list_versions("b", "obj").versions) == 1


def test_journal_writes_with_fsync(tmp_path):
    disk = LocalStorage(str(tmp_path / "d"), endpoint="d", fsync=True)
    disk.make_vol("b")
    tmp, fi = _staged_version(disk, b"synced")
    disk.rename_data(".mtpu.sys", tmp, fi, "b", "obj")
    fi2 = FileInfo.new("b", "obj")
    fi2.version_id = new_uuid()
    fi2.mod_time_ns = fi.mod_time_ns + 10
    disk.write_metadata("b", "obj", fi2)
    assert {v.version_id for v in disk.list_versions("b", "obj").versions} \
        == {fi.version_id, fi2.version_id}


def test_a_journal_write_to_a_missing_volume_makes_nothing(disk):
    fi = FileInfo.new("nobucket", "obj")
    fi.version_id = new_uuid()
    fi.data = {1: b"tiny"}
    with pytest.raises(ErrVolumeNotFound):
        disk.write_metadata("nobucket", "obj", fi)
    assert not os.path.exists(os.path.join(disk.root, "nobucket"))


def test_inline_data_roundtrip(disk):
    disk.make_vol("b")
    fi = FileInfo.new("b", "small")
    fi.version_id = new_uuid()
    fi.size = 4
    fi.data = {1: b"tiny"}
    disk.write_metadata("b", "small", fi)
    got = disk.read_version("b", "small", read_data=True)
    assert got.data[1] == b"tiny"
    got2 = disk.read_version("b", "small", read_data=False)
    assert got2.data == {}


def test_walk_dir(disk):
    disk.make_vol("b")
    for name in ["z/obj2", "a/obj1", "a/obj0", "top"]:
        fi = FileInfo.new("b", name)
        fi.version_id = new_uuid()
        disk.write_metadata("b", name, fi)
    entries = list(disk.walk_dir("b"))
    assert [e[0] for e in entries] == ["a/obj0", "a/obj1", "top", "z/obj2"]
    assert all(meta.startswith(b"XLT1") for _, meta in entries)
    fwd = list(disk.walk_dir("b", forward_to="a/obj1"))
    assert [e[0] for e in fwd] == ["a/obj1", "top", "z/obj2"]


def test_offline_disk_raises(disk):
    disk.make_vol("b")
    disk.set_online(False)
    from minio_tpu.utils.errors import ErrDiskNotFound
    with pytest.raises(ErrDiskNotFound):
        disk.read_all("b", "x")
    disk.set_online(True)


def test_drive_perf_probe(disk):
    """The OBD drive-perf probe (madmin.DrivePerfInfo analog): measured
    sequential write+read GB/s and per-op latency from a size-bounded
    tmp-file pass, O_DIRECT when the filesystem accepts it (reported
    either way via `direct`), probe file cleaned up."""
    perf = disk.drive_perf(size_bytes=1 << 20, io_bytes=256 << 10)
    assert perf["write_gbps"] > 0
    assert perf["read_gbps"] > 0
    assert perf["write_lat_us"] >= 0 and perf["read_lat_us"] >= 0
    assert perf["probe_bytes"] == 1 << 20
    assert perf["io_bytes"] == 256 << 10
    assert isinstance(perf["direct"], bool)
    tmp_dir = os.path.join(disk.root, *SYSTEM_TMP.split("/"))
    assert not [f for f in os.listdir(tmp_dir) if f.startswith("drive-perf")]
    # Size bound: an oversized request clamps instead of hammering IO.
    perf = disk.drive_perf(size_bytes=1 << 40, io_bytes=1 << 20)
    assert perf["probe_bytes"] == 64 << 20


def test_drive_perf_in_health_bundle(tmp_path):
    """admin.health_info embeds the measured per-drive probe when the
    caller opts in with ?perf=true (?perfsize bounds it); the default
    bundle stays read-only — no injected drive IO on a plain poll."""
    import json as _json

    from minio_tpu.api.admin import AdminHandlers

    class _Pool:
        def __init__(self, disks):
            self.disks = disks

    class _OL:
        def __init__(self, disks):
            self.pools = [_Pool(disks)]

    class _Ctx:
        def __init__(self, qdict):
            self.qdict = qdict

    disks = [LocalStorage(str(tmp_path / f"hd{i}"), endpoint=f"hd{i}")
             for i in range(2)]
    admin = AdminHandlers(_OL(disks), iam=None)
    resp = admin.health_info(_Ctx({"perf": "true", "perfsize": "1"}))
    info = _json.loads(resp.body)
    assert len(info["disks"]) == 2
    for d in info["disks"]:
        assert d["perf"]["write_gbps"] > 0, d
        assert d["perf"]["read_gbps"] > 0, d
        assert d["perf"]["probe_bytes"] == 1 << 20
    resp = admin.health_info(_Ctx({}))
    info = _json.loads(resp.body)
    assert all("perf" not in d for d in info["disks"])
