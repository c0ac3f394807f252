"""Bring-up contracts (ISSUE 21): where the compile cache lives, a CPU
test backend made from public JAX flags only, a device engine that
refuses a CPU it did not ask for, a native library keyed to its sources
and its CPU, jax-free worker children, and chip_smoke.py end to end at
--tiny."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_set: dict | None = None, env_drop=(),
         timeout: float = 300.0):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_set or {})
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_dir(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR wins and no other directory is set;
    unset, the cache is the fixed <checkout>/.jax_cache."""
    code = ("import minio_tpu.ops.rs, minio_tpu.ops.highwayhash_jax, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    if placed:
        r = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        want = str(tmp_path)
    else:
        r = _run(code, env_drop=("JAX_COMPILATION_CACHE_DIR",))
        want = os.path.join(REPO, ".jax_cache")
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == want


def test_force_cpu_uses_public_jax_only():
    """conftest ran force_cpu(8): eight CPU devices, and neither it nor
    the mesh discovery reaches into jax's private modules."""
    import jax

    devs = jax.devices()
    assert devs[0].platform == "cpu" and len(devs) >= 8
    for rel in ("minio_tpu/utils/jaxenv.py", "minio_tpu/parallel/placement.py",
                "tests/conftest.py", "bench.py"):
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        assert "xla_bridge" not in src and "jax._src" not in src, rel


def test_device_engine_refuses_a_cpu_it_did_not_ask_for():
    """JAX_PLATFORMS unset and no accelerator: JAX falls back to XLA:CPU,
    and a forced device engine must raise instead of serving from it."""
    code = ("from minio_tpu.erasure import registry; "
            "print(registry.select_engine(87382, 16))")
    r = _run(code, {"MTPU_ENCODE_ENGINE": "device"},
             env_drop=("JAX_PLATFORMS",))
    assert r.returncode != 0
    assert "RuntimeError" in r.stderr and "platform=cpu" in r.stderr
    r = _run(code, {"MTPU_ENCODE_ENGINE": "device", "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == "device"


def test_native_build_is_keyed_to_sources_and_cpu(tmp_path, monkeypatch):
    """The key moves with one source byte and with the CPU-flag string,
    and a library that is not this build — whatever its name — is
    rebuilt, never loaded."""
    from minio_tpu import native

    if shutil.which("cc") is None:
        pytest.skip("no C compiler")
    src_dir = tmp_path / "src"
    shutil.copytree(native._DIR, src_dir,
                    ignore=shutil.ignore_patterns("_build", "__pycache__"))
    monkeypatch.setattr(native, "_DIR", str(src_dir))
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
    key = native._build_key()
    with open(src_dir / "snappy.c", "a") as f:
        f.write("\n")
    key_src = native._build_key()
    monkeypatch.setattr(native, "_cpu_flags", lambda: "some other cpu")
    key_cpu = native._build_key()
    assert len({key, key_src, key_cpu}) == 3

    os.makedirs(native._BUILD_DIR)
    foreign = [os.path.join(native._BUILD_DIR, n)
               for n in ("libmtpu_native.so", f"libmtpu_native-{key}.so")]
    for p in foreign:
        with open(p, "wb") as f:
            f.write(b"built elsewhere")
    so = native._build()
    assert so is not None and so not in foreign
    assert key_cpu in os.path.basename(so)
    with open(so, "rb") as f:
        assert f.read(4) == b"\x7fELF"
    assert not any(os.path.exists(p) for p in foreign)


def test_small_native_call_beats_the_numpy_route():
    """The probe shape [2,4,16384] — what `auto` ranks the native engine
    by — must not cost a thread team's wake-up: a host with many cores
    once read it slower than numpy and sent every large shard to the
    device engine."""
    from minio_tpu.erasure import registry
    from minio_tpu.ops import gf_native

    if not gf_native.available():
        pytest.skip("no native library")
    registry.probe_gbps.cache_clear()
    native = registry.probe_gbps(registry.DEFAULT_CODEC, "native")
    numpy = registry.probe_gbps(registry.DEFAULT_CODEC, "numpy")
    assert native > numpy, (native, numpy)


def test_worker_child_refuses_jax():
    r = _run("import jax; from minio_tpu.pipeline.workers import "
             "_worker_cli; _worker_cli()")
    assert r.returncode != 0 and "worker child imported jax" in r.stderr
    r = _run("import sys; from minio_tpu.pipeline.workers import "
             "_worker_cli; _worker_cli(); assert 'jax' not in sys.modules")
    assert r.returncode == 0, r.stderr


def _smoke(patch: str = ""):
    code = ("import sys, chip_smoke as c\n" + patch
            + "\nsys.exit(c.main(['--tiny']))")
    return _run(code, timeout=600.0)


def test_chip_smoke_tiny_passes():
    import json

    r = _smoke()
    assert r.returncode == 0, r.stderr[-4000:]
    record, verdict = r.stdout.strip().splitlines()[-2:]
    res = json.loads(record)
    # the last line is the verdict alone: these keys and no others
    assert json.loads(verdict) == {"ok": True, "device": res["device"]}
    assert set(res["device"]) == {"platform", "kind", "count"}
    assert isinstance(res["device"]["count"], int)
    assert res["ok"] and all(p["ok"] for p in res["phases"].values())
    assert list(res["phases"]) == [
        "start", "load", "healthy_get", "reference", "degraded_get",
        "heal", "restart"]
    assert res["device"]["platform"] == "cpu" and res["geometry"] == "2+2"
    files = res["compile_cache_files"]
    assert files[1] > 0 and files[2] == files[1]
    d = res["dispatches"]
    assert d["load"]["device"] < d["degraded"]["device"] < d["heal"]["device"]


def test_chip_smoke_fails_when_a_phase_fails():
    r = _smoke("def boom(run): raise RuntimeError('made to fail')\n"
               "c.PHASES = [(n, boom if n == 'load' else f) "
               "for n, f in c.PHASES]")
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "FAILED in load" in r.stderr and "made to fail" in r.stderr


def test_chip_smoke_fails_when_the_device_counter_stands_still():
    r = _smoke("c.dispatch_counts = lambda text: {}")
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "dispatches rose by 0" in r.stderr
