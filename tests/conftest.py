"""Test configuration: force the CPU backend with 8 virtual devices so the
multi-chip sharding paths (jax.sharding.Mesh over dp/lane axes) are
exercised without TPU hardware (minio_tpu.utils.jaxenv.force_cpu, shared
with __graft_entry__.dryrun_multichip). The chip itself is proven by
chip_smoke.py through the chip tool, never by a test.

Also arms a per-test faulthandler watchdog: if any single test runs past
the dump timeout (a hung drive path that escaped its deadline, a leaked
lock), every thread's stack is dumped to stderr so the hang
self-diagnoses instead of dying silently in the CI timeout.

The `mesh` marker's tests prove the mesh SERVING path (ObjectLayer
PutObject -> GetObject(degraded) -> HealObject through
MTPU_ENCODE_ENGINE=mesh): they spawn a fresh interpreter on an 8-device
host-platform CPU mesh via the `mesh_subprocess` fixture — process
isolation keeps a hung collective from wedging the suite (the hard
timeout kills the child, whose own faulthandler dump lands in the
captured output first). They are tier-1, NOT slow-marked: the serving
path must stay CI-proven.
"""

import faulthandler
import os
import subprocess
import sys

import pytest

from minio_tpu.utils.jaxenv import force_cpu

force_cpu(8)

# Well below the tier-1 suite timeout so the dump lands in the log while
# the run is still alive; exit=False keeps pytest in control.
_TEST_DUMP_TIMEOUT_S = 240.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/soak tests kept out of tier-1 "
        "(run with -m slow)",
    )
    config.addinivalue_line(
        "markers",
        "mesh: ObjectLayer mesh-serving proofs on an 8-device "
        "host-platform subprocess (tier-1)",
    )
    config.addinivalue_line(
        "markers",
        "soak: the tier-2 production scenario gate "
        "(minio_tpu/faults/scenarios.py engine; run with -m soak — "
        "see docs/SOAK.md)",
    )


def pytest_runtest_setup(item):
    faulthandler.dump_traceback_later(_TEST_DUMP_TIMEOUT_S, exit=False)


def pytest_runtest_teardown(item, nextitem):
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def mesh_subprocess():
    """Runner for `mesh`-marked tests: spawn tests/_mesh_child.py under
    a fresh 8-device virtual CPU mesh with MTPU_ENCODE_ENGINE=mesh and
    a HARD timeout. The child arms its own faulthandler
    dump_traceback_later just inside that deadline, so a hung
    collective prints every thread's stack before the kill — the
    failure self-diagnoses instead of reading as a bare TimeoutExpired."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(tests_dir)

    def run(shape: str, payload_mib: int = 8,
            timeout_s: float = 300.0,
            extra_env: dict | None = None) -> str:
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "MTPU_ENCODE_ENGINE": "mesh",
            "MTPU_MESH_SHAPE": shape,
            "MTPU_MESH_CHILD_TIMEOUT_S": str(timeout_s),
        })
        # e.g. MTPU_CODEC to drive the whole proof under a non-default
        # erasure codec (test_cauchy_codec's mesh substrate proof).
        env.update(extra_env or {})
        try:
            r = subprocess.run(
                [sys.executable, os.path.join(tests_dir, "_mesh_child.py"),
                 shape, str(payload_mib)],
                capture_output=True, text=True, timeout=timeout_s,
                env=env, cwd=repo_root,
            )
        except subprocess.TimeoutExpired as exc:
            raise AssertionError(
                f"mesh child ({shape}) hung past the {timeout_s}s hard "
                f"timeout\n--- stdout ---\n{exc.stdout}\n"
                f"--- stderr ---\n{exc.stderr}"
            ) from exc
        assert r.returncode == 0, (
            f"mesh child ({shape}) failed rc={r.returncode}\n"
            f"--- stdout ---\n{r.stdout}\n--- stderr ---\n{r.stderr}"
        )
        return r.stdout

    return run
