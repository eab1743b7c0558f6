"""Multi-host bootstrap — the ``init_process_group('nccl')`` equivalent.

Reference parity (SURVEY.md §3.1): the reference launches one process per GPU
under ``torchrun``, which sets ``RANK``/``WORLD_SIZE``/``LOCAL_RANK`` and
rendezvouses through a TCP store before constructing ``ProcessGroupNCCL``.
On TPU the unit is one process per *host* (each host drives its local chips),
and the rendezvous is ``jax.distributed.initialize(coordinator_address)``;
afterwards every process sees the global device list and all collectives are
compiled into the step over ICI/DCN — there is no runtime process-group
object to pass around.

Environment contract (compatible with torchrun-style launchers and with our
``launch.py``):

    COORDINATOR_ADDRESS | MASTER_ADDR:MASTER_PORT  — rendezvous endpoint
    NUM_PROCESSES       | WORLD_SIZE               — number of host processes
    PROCESS_ID          | RANK                     — this host's index
"""

from __future__ import annotations

import logging
import os

import jax

log = logging.getLogger(__name__)

_initialized = False


def init_process_group(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialize the multi-host runtime (idempotent).

    Single-host (the common dev case, and under a gang-scheduled TPU runtime
    that pre-wires the cluster) requires no arguments: if no coordinator can
    be determined and no cluster env is present this is a no-op — matching
    the reference's non-``--distributed`` path running without a process
    group.
    """
    global _initialized
    if _initialized:
        return

    env = os.environ
    if coordinator_address is None:
        coordinator_address = env.get("COORDINATOR_ADDRESS")
        if coordinator_address is None and "MASTER_ADDR" in env:
            coordinator_address = f"{env['MASTER_ADDR']}:{env.get('MASTER_PORT', '12355')}"
    if num_processes is None:
        raw = env.get("NUM_PROCESSES", env.get("WORLD_SIZE"))
        num_processes = int(raw) if raw is not None else None
    if process_id is None:
        raw = env.get("PROCESS_ID", env.get("RANK"))
        process_id = int(raw) if raw is not None else None

    if coordinator_address is None and num_processes in (None, 1):
        # Single-process mode; nothing to rendezvous.
        _initialized = True
        return

    if (jax.config.jax_platforms or "").startswith("cpu"):
        # Local CPU pods (launch.py --cpu-devices; JAX_PLATFORMS=cpu or
        # main.py --platform cpu): XLA:CPU refuses any computation spanning
        # processes ("Multiprocess computations aren't implemented on the
        # CPU backend") unless a CPU collectives backend is selected before
        # the backend initializes. Gloo ships in jaxlib.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    log.info(
        "distributed initialized: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )


def rank() -> int:
    """Host-process index (the reference's RANK; chips are below this level)."""
    return jax.process_index()


def world_size() -> int:
    return jax.process_count()


def is_main_process() -> bool:
    """The 'rank 0' predicate used for logging/checkpoint gating."""
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Cross-host sync point (reference: ``dist.barrier()``)."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
