"""What decides `correct`: the answers of the timed window itself, at the
timed sizes, against the plain reference (`reference.py`).

Every comparison is exact, so every limit is 0. Numbers compared:

- `answers_wrong`: requests of the window whose answer came and said the
  wrong thing (a PUT's ETag that is not the md5 of its body, a GET whose
  body is not what was PUT, ...), over every request;
- `never_answered`: requests that got no answer within the wait after the
  close (a late answer is late, not wrong);
- `readback_bytes_differ`: bytes of a seed-drawn sample of the window's
  PUTs, read back over S3 (front end, object layer, quorum; in a
  deployment of several nodes through another node than the one that
  acknowledged the PUT), that differ from what was sent, a missing byte
  counting as one;
- `nothing_compared`: 1 where the window finished nothing that could be
  compared;
- `layout_faults`: sampled objects whose xl.meta or shard files do not
  have the configuration's geometry (k+m shards, block size, whole
  frames) or name another codec than the configuration's;
- `data_bytes_differ`, `parity_bytes_differ`, `digest_bytes_differ`: bytes
  of the sampled objects' k+m shard files on the drives that differ from
  the reference's data split, GF(2^8) parity under the configuration's
  codec (the xl.meta's only where the configuration leaves the choice to
  the program), and HighwayHash-256 bitrot digests;
- heal: `heal_failed` (the sequence's NumFailed), `healed_files_missing`
  and `healed_files_differ` (shard files of the objects the sequence
  reported healed in the window, on the wiped drives, against their
  sha256 before the wipe: every one), and the three `*_bytes_differ`
  above over a sample of healed objects, read from the wiped drives alone.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

from minio_tpu.storage.xlmeta import read_xl_meta

from . import reference
from .client import S3
from .traffic import BUCKET, NO_ANSWER, Load, Window

DIGEST = reference.DIGEST


class Checks:
    """Numbers compared, each beside its limit."""

    def __init__(self):
        self.rows: dict[str, list] = {}
        self.notes: list[str] = []

    def add(self, name: str, value, limit=0) -> None:
        self.rows[name] = [value, limit]

    def bump(self, name: str, by: int = 1) -> None:
        self.rows[name][0] += by

    def note(self, text: str) -> None:
        if len(self.notes) < 20:
            self.notes.append(text)

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.rows.values())

    def lines(self) -> list[str]:
        return [f"check {name}: {v} (limit {lim})"
                + ("" if v <= lim else "  <-- over")
                for name, (v, lim) in self.rows.items()]


def _sample(items: list, n: int, seed: int, always=None) -> list:
    """n of items drawn from the seed, `always` among them."""
    rng = reference.rng_for(seed, 9)
    idx = list(rng.permutation(len(items))[:n])
    picked = [items[i] for i in idx]
    if always is not None and always not in picked and picked:
        picked[-1] = always
    return picked


def read_object_shards(root: str, cell, key: str, only_drives=None):
    """The object's shard files as the drives hold them ->
    (codec, {shard index: (digests [blocks, 32], chunks [blocks, S])},
    faults). Geometry comes from the configuration, not from the file."""
    k, m, bs = cell.k, cell.m, cell.block_size
    s = reference.shard_size(bs, k)
    frame = DIGEST + s
    shards, codecs, faults = {}, set(), []
    drives = only_drives or range(1, cell.drives + 1)
    for d in drives:
        odir = os.path.join(root, f"d{d}", BUCKET, key)
        try:
            with open(os.path.join(odir, "xl.meta"), "rb") as f:
                fi = read_xl_meta(f.read(), BUCKET, key, None)
            er = fi.erasure
            if (er.data_blocks, er.parity_blocks, er.block_size) != (k, m,
                                                                     bs):
                faults.append(f"{key} on d{d}: xl.meta says "
                              f"{er.data_blocks}+{er.parity_blocks} @ "
                              f"{er.block_size}, want {k}+{m} @ {bs}")
                continue
            # absent on disk means dense
            if cell.codec and (er.codec or "dense-gf8") != cell.codec:
                faults.append(f"{key} on d{d}: xl.meta names the codec "
                              f"{er.codec!r}, the configuration "
                              f"{cell.codec!r}")
                continue
            with open(os.path.join(odir, fi.data_dir, "part.1"), "rb") as f:
                raw = np.frombuffer(f.read(), dtype=np.uint8)
        except Exception as exc:  # noqa: BLE001 - a fault of the layout
            faults.append(f"{key} on d{d}: {type(exc).__name__}: {exc}")
            continue
        if raw.size == 0 or raw.size % frame:
            faults.append(f"{key} on d{d}: part.1 is {raw.size} B, not "
                          f"whole {frame}-byte frames")
            continue
        frames = raw.reshape(-1, frame)
        if er.index in shards:
            faults.append(f"{key}: shard {er.index} is on two drives")
        shards[er.index] = (frames[:, :DIGEST], frames[:, DIGEST:])
        codecs.add(er.codec)
    if len(codecs) > 1:
        faults.append(f"{key}: drives disagree on the codec {codecs}")
    if not only_drives and set(shards) != set(range(1, k + m + 1)):
        faults.append(f"{key}: shard indices on disk {sorted(shards)}, "
                      f"want 1..{k + m}")
    return (codecs.pop() if codecs else ""), shards, faults


def compare_shards(checks: Checks, root: str, cell, objects, pool_of,
                   only_drives=None) -> None:
    """objects: [(key, size, body index)] of one size. Adds to the three
    *_bytes_differ rows and to layout_faults."""
    if not objects:
        return
    k, m = cell.k, cell.m
    on_disk = []
    for key, size, bi in objects:
        codec, shards, faults = read_object_shards(root, cell, key,
                                                   only_drives)
        for f in faults:
            checks.note(f)
        if faults or not shards:
            checks.bump("layout_faults")
            continue
        on_disk.append((key, size, bi, codec, shards))
    by_codec: dict[tuple, list] = {}
    for item in on_disk:
        by_codec.setdefault((item[3], item[1]), []).append(item)
    for (codec, size), items in by_codec.items():
        try:
            chunks, digests = reference.expected_shards(
                [pool_of(size)[bi].data for _, _, bi, _, _ in items],
                k, m, cell.block_size, codec)
        except (KeyError, ValueError) as exc:
            checks.note(f"{items[0][0]}: {exc}")
            checks.bump("layout_faults", len(items))
            continue
        for n, (key, _, _, _, shards) in enumerate(items):
            for idx, (dg, ch) in shards.items():
                want_ch = chunks[n, :, idx - 1]
                want_dg = digests[n, :, idx - 1]
                if ch.shape != want_ch.shape:
                    checks.note(f"{key} shard {idx}: {ch.shape[0]} frames, "
                                f"want {want_ch.shape[0]}")
                    checks.bump("layout_faults")
                    continue
                row = ("data_bytes_differ" if idx <= k
                       else "parity_bytes_differ")
                bad = int((ch != want_ch).sum())
                if bad:
                    checks.note(f"{key} shard {idx}: {bad} of {ch.size} "
                                "bytes differ from the reference")
                    checks.bump(row, bad)
                bad = int((dg != want_dg).sum())
                if bad:
                    checks.note(f"{key} shard {idx}: {bad} digest bytes "
                                "differ from the reference's HighwayHash")
                    checks.bump("digest_bytes_differ", bad)


def check_window(cell, load: Load, win: Window, seed: int, hosts: list[str],
                 root: str) -> Checks:
    checks = Checks()
    n_sample = int(cell.traffic.get("check_sample", 8))
    for name in ("layout_faults", "data_bytes_differ", "parity_bytes_differ",
                 "digest_bytes_differ"):
        checks.add(name, 0)
    if load.kind == "heal":
        _check_heal(checks, cell, load, win, seed, root, n_sample)
        return checks
    wrong = [o for o in win.ops if o.wrong]
    for o in wrong:
        checks.note(f"{o.kind} {o.key}: {o.wrong}")
    checks.add("answers_wrong", len(wrong))
    lost = [o for o in win.ops if o.error and "timed out" in o.error]
    checks.add("never_answered", len(lost))
    # a PUT that the mix deleted again has nothing to read back
    gone = {o.key for o in win.ops if o.kind == "DELETE"}
    puts = sorted((o for o in win.ops
                   if o.kind == "PUT" and o.ok and o.key not in gone),
                  key=lambda o: o.key)
    checks.add("readback_bytes_differ", 0)
    checks.add("nothing_compared", 0 if puts else 1)
    if not puts:
        return checks
    longest = max(puts, key=lambda o: o.latency)
    picked = _sample(puts, n_sample, seed, always=longest)
    checks.note(f"compared {len(picked)} of {len(puts)} PUTs of the window, "
                f"the longest ({longest.latency * 1e3:.0f} ms) among them")
    # "An acknowledged write is readable" is the cluster's promise: every
    # sampled object is read back through the node after the one that
    # acknowledged it (the same one where there is one)
    conns = [S3(h) for h in hosts]

    def via(o) -> int:
        return (o.node + 1) % len(hosts)

    if len(hosts) > 1:
        checks.note("each read back through the node after the one that "
                    "acknowledged it: " + ", ".join(
                        f"{o.key} {o.node + 1}->{via(o) + 1}" for o in picked))
    for o in picked:
        want = load.pool(o.size)[o.body].data
        s3 = conns[via(o)]
        try:
            st, _, data = s3.request("GET", f"/{BUCKET}/{o.key}")
        except NO_ANSWER as exc:
            st, data = 0, str(exc).encode()
        if st != 200:
            checks.note(f"read back {o.key}: {st} {data[:120]!r}")
            checks.bump("readback_bytes_differ", len(want))
            continue
        got = np.frombuffer(data, dtype=np.uint8)
        ref = np.frombuffer(want, dtype=np.uint8)
        n = min(got.size, ref.size)
        bad = int((got[:n] != ref[:n]).sum()) + abs(got.size - ref.size)
        if bad:
            checks.note(f"read back {o.key}: {bad} bytes differ")
            checks.bump("readback_bytes_differ", bad)
    for s3 in conns:
        s3.close()
    compare_shards(checks, root, cell,
                   [(o.key, o.size, o.body) for o in picked], load.pool)
    return checks


def _check_heal(checks: Checks, cell, load: Load, win: Window, seed: int,
                root: str, n_sample: int) -> None:
    healed_n = win.heal_polls[-1][1] if win.heal_polls else 0
    at_close = int(np.interp(win.end, [p[0] for p in win.heal_polls],
                             [p[1] for p in win.heal_polls]))
    checks.add("heal_failed", win.heal_polls[-1][2] if win.heal_polls else 0)
    checks.add("healed_files_missing", 0)
    checks.add("healed_files_differ", 0)
    checks.add("nothing_compared", 0 if healed_n else 1)
    checks.note(f"{at_close} objects healed at the close, {healed_n} when "
                "the sequence stood still; every one of them compared")
    # The sequence walks the listing in order, one object at a time: the
    # objects it reported healed are the first of the sorted keys. Items
    # name them where the status still held them.
    keys = [p[0] for p in load.preloaded]
    reported = sorted(set(win.healed_keys)) or keys[:healed_n]
    if len(reported) < healed_n:
        reported = keys[:healed_n]
    by_key = {p[0]: p for p in load.preloaded}
    for d in load.wiped:
        base = os.path.join(load.drive(d), BUCKET)
        for key in reported:
            for rel, digest in load.before_wipe[d].items():
                if not rel.startswith(key + "/"):
                    continue
                try:
                    with open(os.path.join(base, rel), "rb") as f:
                        now = hashlib.sha256(f.read()).hexdigest()
                except OSError:
                    checks.note(f"d{d}/{rel}: reported healed, not there")
                    checks.bump("healed_files_missing")
                    continue
                if now != digest:
                    checks.note(f"d{d}/{rel}: differs from before the wipe")
                    checks.bump("healed_files_differ")
    picked = _sample([by_key[k] for k in reported if k in by_key], n_sample,
                     seed)
    compare_shards(checks, root, cell, picked, load.pool,
                   only_drives=load.wiped)
