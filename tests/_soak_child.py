"""Forced-multicore child for the soak gate's worker-kill proof
(tests/test_chaos_soak.py): cpu_count is pinned to 4 (the
_span_child/_ioflow_child convention) for the worker-pool probe alone
(pipeline/workers.py disarms with reason `cores` under two), so the
pool REALLY spawns child processes on any CI host — the scenario's
kill -9 then lands on a live worker pid, and the pool must fall back
byte-identically, respawn, and leave no orphans.

Prints the scenario artifact plus the pool snapshot as JSON."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("MTPU_WORKER_POOL", None)
os.cpu_count = lambda: 4  # read by workers.armed() and the governors


def main(tmp: str, seed: int) -> None:
    from minio_tpu.faults.scenarios import ScenarioSpec, run_scenario
    from minio_tpu.pipeline import workers

    pool = workers.armed()
    out: dict = {"arm_reason": workers.arm_reason()}
    if pool is None:
        # Sandboxed CI that cannot spawn: report and let the parent
        # skip — the pool degrading to in-process is itself by design.
        print(json.dumps(out))
        return

    spec = ScenarioSpec(
        seed=seed, clients=4, ops_per_client=6, disks=8, parity=4,
        payload_sizes=(256 << 10, 1 << 20), fault_drives=1,
        worker_kills=1, lock_check=False,
    )
    res = run_scenario(spec, tmp)
    out["artifact"] = res.to_dict()
    # The parent's failure message leads with the verdict, not the
    # (large) embedded plan.
    out["artifact"]["plan"] = {"spec": out["artifact"]["plan"]["spec"]}
    out["pool"] = workers.get_pool().snapshot() \
        if workers.get_pool() is not None else None
    pids = pool.live_pids()
    workers.shutdown()
    out["shutdown_pids"] = pids
    out["orphans"] = [
        pid for pid in pids
        if os.path.exists(f"/proc/{pid}")
        and open(f"/proc/{pid}/stat").read().split()[2] != "Z"
    ]
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4242)
