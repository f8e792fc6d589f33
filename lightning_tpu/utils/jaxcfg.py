"""Shared JAX configuration: compile cache, device selection, backend line.

The crypto kernels are scan-heavy (256-step field inversions, 64-step
windowed point multiplies); a cold compile takes minutes on a small host.
The persistent cache lets every process after the first skip the compile,
which matters for the subdaemon architecture (each daemon process jits the
same kernels) and for repeated smoke/test runs.

One rule for the cache: where ``JAX_COMPILATION_CACHE_DIR`` is set, jax
reads it itself and this module sets no directory in code; where it is
not, the cache is ``<checkout>/.jax_cache``.  The path is part of nothing
the program derives at run time (no temporary, pid- or time-derived
directory), so a second start finds what the first one wrote.

One rule for the device: a process takes whatever backend jax gives it
and says which (``backend_line``); only ``force_cpu`` — tests and the
daemon's ``--cpu`` — pins the CPU, and nothing switches by itself.
"""
import os
import re

import jax

_DEFAULT_CACHE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")

def setup_cache() -> None:
    """Turn on the persistent compilation cache.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when the environment
    sets it (jax picks it up on its own — no config.update here), else
    the fixed ``<checkout>/.jax_cache``.

    LIGHTNING_TPU_JAX_CACHE_MODE gates how the process uses it:
      rw (default) — read + write
      ro           — read-only: warm programs still load, nothing new is
                     serialized (child daemons of a harness that must
                     not grow the cache they share)
      off          — no persistent cache at all (cold compiles every
                     process; only for debugging the cache itself).
    """
    mode = os.environ.get("LIGHTNING_TPU_JAX_CACHE_MODE", "rw")
    if mode == "off":
        jax.config.update("jax_enable_compilation_cache", False)
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_DEFAULT_CACHE, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE)
    # read-only: an absurd write threshold keeps every lookup live but
    # makes no compile ever eligible for serialization
    min_secs = 1.0 if mode != "ro" else 1e9
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def backend_line() -> str:
    """The one start-up line naming the backend this process dispatches
    on, as jax reports it.  Initializes the backend; raises what jax
    raises when the platform it was told to use cannot start."""
    devs = jax.devices()
    return (f"backend: platform={devs[0].platform} "
            f"kind={devs[0].device_kind!r} count={len(devs)}")


def force_cpu(n_devices: int | None = None) -> None:
    """Pin the CPU platform, with >= n_devices virtual devices if given.

    Must run before the first jax backend use: XLA_FLAGS is read when
    the CPU client is created, and the platform choice is fixed once a
    backend is up.  Sets both the environment (inherited by child
    processes) and jax.config (this process, if jax was imported under
    a different JAX_PLATFORMS).  Used by tests/conftest.py, the
    daemon's --cpu, and __graft_entry__.dryrun_multichip.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if n_devices:
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            flags = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
        elif int(m.group(1)) < n_devices:
            flags = flags.replace(
                m.group(0), f"--xla_force_host_platform_device_count={n_devices}"
            )
    os.environ["XLA_FLAGS"] = flags
    jax.config.update("jax_platforms", "cpu")
