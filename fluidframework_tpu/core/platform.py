"""Backend selection and the persistent compile cache.

- ``force_host_platform`` pins the CPU backend with virtual devices. Tests
  (tests/conftest.py) and the forced-CPU mesh dry run use it; it must run
  before anything initializes a JAX backend. The program itself takes the
  backend JAX finds: the TPU on a machine with a chip.
- ``enable_compile_cache`` places JAX's persistent compilation cache.
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_host_platform(n_devices: int = 8) -> None:
    """Force the CPU backend with ``n_devices`` virtual devices.

    Must run BEFORE any jax backend initialization.  Safe to call whether
    or not jax is already imported.  If backends are already initialized
    with an incompatible platform/device count, raises RuntimeError with a
    clear message instead of silently running on the wrong backend.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG in flags:
        # Replace, don't defer: a stale count from an earlier run would
        # leave fewer virtual devices than the caller requires.
        flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}",
                       flags)
    else:
        flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    initialized = False
    try:
        from jax._src import xla_bridge

        initialized = xla_bridge.backends_are_initialized()
    except (ImportError, AttributeError):  # pragma: no cover - jax internals
        pass
    if initialized:
        if jax.default_backend() != "cpu" or len(jax.devices()) < n_devices:
            raise RuntimeError(
                "jax backends already initialized "
                f"({jax.default_backend()}, {len(jax.devices())} devices); "
                f"cannot force cpu x {n_devices} — call force_host_platform "
                "before any jax.devices()/jit use")
        return
    jax.config.update("jax_platforms", "cpu")


REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Persistent XLA compilation cache shared by the tests, bench.py,
    chip_smoke.py and the server: the same kernel shapes recompile in
    every process otherwise. ``JAX_COMPILATION_CACHE_DIR`` places it when
    set; else it lives at the fixed ``<repo>/.jax_cache`` (git-ignored) —
    a fixed path, because the path is part of the cache key. Returns the
    directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
