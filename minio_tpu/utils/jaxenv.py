"""JAX environment: the CPU test backend, the compile cache's place,
and the identity of the backend the device and mesh engines run on.

One process per chip: a process that has initialised a JAX backend
holds the accelerator, and a child that needs it then fails or hangs.
Whatever starts children (the worker pool, the fault scenarios, the
chip smoke) either stays off jax itself or pins the child to the CPU.

This module must not import jax at module-import time (callers decide
when backend init happens).
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

# <checkout>/.jax_cache — derived from the package's location, so every
# process of one checkout shares it and the path (part of the cache
# key) never moves.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def force_cpu(n_devices: int | None = None) -> None:
    """Force the CPU backend, optionally with N virtual devices (the
    test configuration). Must run before jax initialises a backend in
    this process; raises RuntimeError when the backend that is up does
    not satisfy the request (loud failure beats a silent wrong-device
    run)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = [
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < (n_devices or 1):
        raise RuntimeError(
            "force_cpu needs a fresh process: jax already initialized "
            f"with {len(devs)} {devs[0].platform} device(s), cannot "
            f"force a {n_devices or 1}-device CPU backend"
        )


def place_compile_cache() -> None:
    """Give XLA's persistent compile cache a fixed home before the first
    compilation. Where JAX_COMPILATION_CACHE_DIR is set JAX reads it
    itself and no other directory is set here; otherwise the cache is
    <checkout>/.jax_cache. Every program is kept, whatever its compile
    time, so a restart over the same shapes compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class Backend(NamedTuple):
    platform: str
    device_kind: str
    count: int


@functools.cache
def backend() -> Backend:
    """The backend the device and mesh engines dispatch to, read from
    jax.devices() once per process. A CPU backend is refused unless
    JAX_PLATFORMS names `cpu`: with the variable unset JAX falls back
    to XLA:CPU when the accelerator fails to initialise, and a server
    that asked for a device must not serve "on the device engine" from
    the host."""
    import jax

    devs = jax.devices()
    found = Backend(devs[0].platform, devs[0].device_kind, len(devs))
    named = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    if found.platform == "cpu" and "cpu" not in named:
        raise RuntimeError(
            "the device/mesh erasure engine needs an accelerator, but "
            f"JAX found only platform={found.platform} "
            f"device_kind={found.device_kind!r} x{found.count}; set "
            "JAX_PLATFORMS=cpu to run these engines on the host on purpose"
        )
    return found
