"""Platform selection and the persistent compile cache for CLI processes.

``JAX_PLATFORMS`` in the environment selects the backend; the ``platform``
argument here overrides it through ``jax.config`` before any backend
initializes (``--platform cpu`` on the examples).
"""

from __future__ import annotations

import os
from pathlib import Path


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at a stable place and return
    where that is.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment jax reads it
    itself and nothing is set in code. Otherwise the cache lives at
    ``<checkout>/.jax_cache``, computed from this file so every working
    directory resolves the same path.

    What it serves (PERF.md §6, PR 22, measured on a v5e): a program of XLA
    ops alone hits from any checkout. A program that holds a Pallas kernel
    hits only from the same checkout path with the same source lines: the
    Mosaic payload inside ``tpu_custom_call`` carries the Python locations
    of the kernel's call stack, and jax strips debug info from the module
    around it, not from the payload. ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0``
    in the environment takes the locations out (and with them the source
    lines in Mosaic's errors); then it hits from anywhere."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def configure_platform(
    platform: str = "", cpu_devices: int = 0, cpu_collectives: str = ""
) -> None:
    """Set the jax platform ("cpu"/"tpu"/"" = environment default), the CPU
    virtual device count (0 = leave as-is), and the CPU cross-process
    collectives backend ("gloo" for multi-process CPU clusters — required
    before :func:`init_distributed` on CPU)."""
    import jax

    configure_compile_cache()
    if platform:
        jax.config.update("jax_platforms", platform)
    if cpu_devices:
        jax.config.update("jax_num_cpu_devices", cpu_devices)
    if cpu_collectives:
        jax.config.update("jax_cpu_collectives_implementation", cpu_collectives)


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> int:
    """Join a multi-host JAX cluster (DCN scale-out) and return this
    process's index.

    The mesh/collective layers are host-count-agnostic: ``jax.devices()``
    spans every host after this call, so the same ``build_mesh`` +
    ``shard_map`` programs run across pods — DCN traffic is inserted by XLA
    where mesh axes cross hosts (SURVEY.md §5.8's "TPU-native equivalent").
    On TPU pods all three arguments auto-detect from the environment; pass
    them explicitly elsewhere (e.g. CPU clusters for tests).

    No-op (returns 0) when num_processes == 1 or JAX was already
    initialized for this cluster.
    """
    import jax

    if num_processes == 1:
        return 0
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:  # double-init → idempotent no-op
        if "should only be called once" not in str(e):
            raise
    return jax.process_index()
