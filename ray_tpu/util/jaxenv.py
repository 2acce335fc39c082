"""Which JAX platform a process runs on, and where it keeps compiled code.

One owner per chip: exactly one process holds a TPU chip — the process
that calls `SpmdTrainer.fit()` / builds an `LLMEngine` itself, or one
worker that was granted `TPU` resources. The runtime (driver, node
agents, workers without the grant) never takes it: it counts chips
without initialising a backend (core/resources.py) and pins every worker
it spawns through `subprocess_env_for_worker` below. A pinned
`JAX_PLATFORMS` makes JAX raise when a listed platform cannot be
created, so a worker that was granted a chip it cannot open fails
instead of computing on the CPU.
"""
from __future__ import annotations

import os

# The path is part of how a cache entry is found again: it must not move
# between runs, so never a temporary name, a pid or a time.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_cpu(n_virtual_devices: int | None = None) -> None:
    """Pin this process to the CPU backend, optionally with N virtual
    devices (for testing multi-chip sharding without chips).

    Safe to call even after jax has been imported (or initialized on a
    different platform): `jax_num_cpu_devices` takes effect at client
    creation, so clearing already-created backends is sufficient — unlike
    XLA_FLAGS, which absl parses only once per process (we still set it
    for child processes that inherit the environment).
    """
    if n_virtual_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                        f"{n_virtual_devices}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    # Clear any live backends FIRST: jax refuses jax_num_cpu_devices
    # updates while a client exists, and config changes only apply at the
    # next client creation anyway.
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        from jax.extend.backend import clear_backends
        clear_backends()
    jax.config.update("jax_platforms", "cpu")
    if n_virtual_devices is not None:
        jax.config.update("jax_num_cpu_devices", n_virtual_devices)


def subprocess_env_cpu(env: dict) -> dict:
    """Environment for a child process that must never touch the TPU."""
    env["JAX_PLATFORMS"] = "cpu"
    return env


def subprocess_env_tpu(env: dict) -> dict:
    """Environment for a child process that owns a chip: the TPU is its
    default backend and failing to create it raises in JAX (the host CPU
    backend stays listed; orbax and host offloads use it)."""
    env["JAX_PLATFORMS"] = "tpu,cpu"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", _DEFAULT_CACHE_DIR)
    return env


def subprocess_env_for_worker(env: dict, tpu_capable: bool) -> dict:
    """The pin every spawned worker gets (driver and node agents alike):
    the TPU for a worker that was granted TPU resources, the CPU for
    every other."""
    return (subprocess_env_tpu if tpu_capable else subprocess_env_cpu)(env)


def enable_compile_cache() -> None:
    """Persistent compile cache for a process that compiles for the chip.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX's own reading of it
    stands and nothing is set here; otherwise the directory is
    `<checkout>/.jax_cache`. A CPU process is left alone: XLA:CPU cache
    entries embed host CPU features and are not safe on another machine.
    Initialises the backend — call it where the process is about to
    compile anyway.
    """
    import jax
    if jax.default_backend() != "tpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
