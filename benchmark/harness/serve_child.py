#!/usr/bin/env python3
"""The benchmark's launcher of the server child: `minio_tpu.cli.main`,
unchanged, in the one process that owns the chip.

It adds three things, none of them on the served path:

- `prctl(PR_SET_PDEATHSIG, SIGKILL)`, so that the server dies with a
  parent that is itself killed;
- a thread that sleeps on a named pipe (`MTPU_BENCH_CTL`) and acts on the
  parent's cues: `device <file>` writes what JAX says of this process's
  devices (platform, kind, ids, coordinates, the chips' device files it
  holds open) and their peak memory, `trace <dir> <seconds> <until> <file>` takes a
  `jax.profiler` trace of that many seconds (only the process that holds
  the chip can trace it). With `--trace 0` the only cue is one `device`, after the
  window has closed;
- where the harness's Python entry asks for it (`MTPU_BENCH_FAULT`, which
  the command line never sets), a fault planted under the timed path, for
  the tests and controls that have to see `correct` come out false.
"""

from __future__ import annotations

import ctypes
import glob
import json
import os
import re
import shutil
import signal
import sys
import threading
import time

PR_SET_PDEATHSIG = 1


def die_with_parent() -> None:
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_PDEATHSIG, signal.SIGKILL,
                                            0, 0, 0)
    want = os.environ.get("MTPU_BENCH_PARENT")
    if want and os.getppid() != int(want):
        os._exit(3)             # the parent went before the call above


def _write_json(path: str, doc: dict) -> None:
    with open(path + ".part", "w") as f:
        json.dump(doc, f)
    os.replace(path + ".part", path)


def _device(path: str) -> None:
    import jax

    devs = jax.local_devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    _write_json(path, {"platform": devs[0].platform,
                       "kind": devs[0].device_kind,
                       "count": len(jax.devices()),
                       "memory_peak_bytes": max(peaks),
                       "memory_peak_bytes_per_device": peaks,
                       "ids": [int(d.id) for d in devs],
                       "coords": [[int(c) for c in getattr(d, "coords", ())]
                                  + [int(getattr(d, "core_on_chip", 0))]
                                  for d in devs],
                       # which chips this process holds: a process that
                       # is given some of a host's chips numbers them
                       # from 0 again, so the ids do not tell two nodes'
                       # chips apart; the device files it has open do
                       "chip_files": _chip_files()})


def _chip_files() -> list[str]:
    """The accelerator device files this process holds open."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            link = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if re.match(r"/dev/(accel|vfio/)\d+$", link):
            held.add(link)
    return sorted(held)


def _device_ran(trace_dir: str) -> bool:
    """Whether the trace holds an operation of a device."""
    try:
        from jaxlib._profile_data import ProfileData
    except ImportError:
        from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return False
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                for _ in line.events:
                    return True
    return False


def _trace(trace_dir: str, slice_s: float, until: float, done: str) -> None:
    """A `jax.profiler` trace of `slice_s` seconds. The slice is timed
    here, where the tracer runs: timed by the parent, a start that waited
    for the interpreter lock left no slice at all. A short slice of a
    host-paced server may fall between two bursts of dispatches and hold
    no operation of the device (its file is written in a few seconds);
    such a slice is taken again, as long as it ends before `until` (wall
    clock), the window's close, and six times at most. A trace
    that holds the device is written only once: that takes a minute."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    # no setting of the host tracer's level changes what a trace costs
    # (PERF.md); 1 keeps the runtime's own spans for the idle gaps' labels
    opts.host_tracer_level = 1
    for attempt in range(1, 7):
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        start = time.time()
        time.sleep(slice_s)
        stop = time.time()
        jax.profiler.stop_trace()
        written = time.time()
        held = _device_ran(trace_dir)
        print(f"[serve_child] trace attempt {attempt}: the device ran in "
              f"it: {held}; written in {written - stop:.1f}s", flush=True)
        if held or time.time() + slice_s > until:
            break
    _write_json(done, {"start": start, "stop": stop, "written": written,
                       "attempts": attempt})


def watch(ctl_path: str) -> None:
    with open(ctl_path) as ctl:
        for line in ctl:
            words = line.split()
            try:
                if words[0] == "device":
                    _device(words[1])
                elif words[0] == "trace":
                    _trace(words[1], float(words[2]), float(words[3]),
                           words[4])
            except Exception as exc:  # noqa: BLE001 - reported to the parent
                print(f"[serve_child] cue {line!r} failed: "
                      f"{type(exc).__name__}: {exc}", flush=True)
                if len(words) > 1:
                    _write_json(words[-1], {"error": f"{exc}"})


def plant(fault: str) -> None:
    """Break the timed path underneath, where the answer is produced."""
    if os.environ.get("MTPU_ENCODE_ENGINE") == "mesh":
        from minio_tpu.parallel.mesh_engine import MeshCodec as cls
    else:
        from minio_tpu.erasure.device_engine import DeviceCodec as cls
    encode, recon = cls.encode_async, cls.reconstruct_async

    def flip(arr):
        return arr.at[(0,) * arr.ndim].set(arr[(0,) * arr.ndim] ^ 1)

    def half(arr):
        return arr.at[arr.shape[0] // 2:].set(0)

    if fault == "parity_flip":
        def encode_async(self, blocks, with_hashes):
            parity, digests = encode(self, blocks, with_hashes)
            return flip(parity), digests
        cls.encode_async = encode_async
    elif fault == "digest_flip":
        def encode_async(self, blocks, with_hashes):
            parity, digests = encode(self, blocks, with_hashes)
            return parity, None if digests is None else flip(digests)
        cls.encode_async = encode_async
    elif fault == "half_batch":
        def encode_async(self, blocks, with_hashes):
            parity, digests = encode(self, blocks, with_hashes)
            return half(parity), digests
        cls.encode_async = encode_async
    elif fault == "recon_flip":
        def reconstruct_async(self, src, present, targets,
                              with_hashes=False):
            rebuilt, digests = recon(self, src, present, targets,
                                     with_hashes)
            return flip(rebuilt), digests
        cls.reconstruct_async = reconstruct_async
    else:
        raise SystemExit(f"serve_child: unknown fault {fault!r}")
    print(f"[serve_child] fault planted: {fault}", flush=True)


def main() -> int:
    die_with_parent()
    ctl = os.environ.get("MTPU_BENCH_CTL")
    if ctl:
        threading.Thread(target=watch, args=(ctl,), daemon=True,
                         name="bench-cues").start()
    if os.environ.get("MTPU_BENCH_FAULT"):
        plant(os.environ["MTPU_BENCH_FAULT"])
    from minio_tpu import cli

    sys.argv = ["minio_tpu", *sys.argv[1:]]
    return cli.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
