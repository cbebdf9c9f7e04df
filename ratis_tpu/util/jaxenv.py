"""Where this package meets the JAX runtime: the one ``jit`` every compiled
program goes through (so the persistent compile cache is placed before the
first compilation), and the one CPU pin for tests and CPU-only tools.

Nothing here chooses a device for the engine: a server runs on whatever
backend JAX initialises (the TPU where there is one), and a measurement
entry that needs the chip asserts ``jax.default_backend()`` itself — there
is no fallback to the CPU anywhere on a measurement path.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — fixed (a cache that moves never hits) and ignored
# by git.  Used only when JAX_COMPILATION_CACHE_DIR is not set.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it.  ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself; no
    directory is set in code then), else :data:`DEFAULT_COMPILE_CACHE_DIR`.

    The engine's programs are small — most compile in well under JAX's
    default 1 s persistence threshold, which would keep the whole prewarm
    grid (~31 programs per process) out of the cache — so the threshold is
    dropped to zero unless the environment sets its own."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def jit(fn, **kwargs):
    """``jax.jit`` behind :func:`place_compile_cache`.  Every program this
    package compiles (engine steps, vote tally, ledger pass, the sharded
    forms in parallel.mesh) is built here, so the cache is configured before
    the first compilation whichever entry point reaches it first."""
    place_compile_cache()
    import jax
    return jax.jit(fn, **kwargs)


def require_backend(platform: str) -> dict:
    """The guard of every measurement entry: JAX's default backend is
    ``platform`` or this raises — a chip run never falls back to another
    platform.  Returns the device block each result carries, as JAX
    reports it."""
    import jax
    backend = jax.default_backend()
    if backend != platform:
        raise RuntimeError(
            f"JAX came up on platform {backend!r}, not {platform!r}")
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def pin_cpu(virtual_devices: int = 0) -> None:
    """Pin this process's JAX to the CPU platform — or raise.

    For tests and CPU-only tools (the multi-process harness children, the
    profiling tool, the virtual-device dry run); never on a measurement
    path.  Must run before the first backend initialisation: a process that
    already holds another backend fails here instead of quietly using it.
    ``virtual_devices`` requests that many host devices (the ``mesh`` tests
    and the multi-chip dry run)."""
    if virtual_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{virtual_devices}").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    # initialises the backend now; another one already up fails here
    count = require_backend("cpu")["count"]
    if count < virtual_devices:
        raise RuntimeError(
            f"pin_cpu: need {virtual_devices} virtual CPU devices, have "
            f"{count}; pin before the first backend initialisation (fresh "
            f"process)")
