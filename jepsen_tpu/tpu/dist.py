"""Multi-host device meshes: the checking plane's distributed
communication backend.

Capability reference: SURVEY §2.5 — the reference's control plane
talks to nodes over SSH; its analysis plane is single-JVM. Here the
analysis plane is a JAX program: within one host, history shards ride
ICI via the mesh in jepsen_tpu.tpu.ensemble; across hosts, JAX's
distributed runtime (jax.distributed.initialize) brings every
process's devices into one global mesh, with collectives crossing DCN.

Environment contract (standard JAX multi-process variables):
  JAX_COORDINATOR_ADDRESS  host:port of process 0
  JAX_NUM_PROCESSES        world size
  JAX_PROCESS_ID           this process's rank
On TPU pods these can all be inferred by the runtime, so
ensure_initialized() also honors a bare JEPSEN_TPU_MULTIHOST=1.
Without any of them it is a no-op: single-host behavior unchanged.
"""

from __future__ import annotations

import logging
import os
import threading

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_initialized = False


def multihost_requested() -> bool:
    return bool(os.environ.get("JAX_COORDINATOR_ADDRESS")
                or os.environ.get("JEPSEN_TPU_MULTIHOST"))


def ensure_initialized() -> bool:
    """Initializes jax.distributed once, iff multi-host env is set.
    Returns True when running multi-host."""
    global _initialized
    if _initialized:
        return True
    if not multihost_requested():
        return False
    with _lock:
        if _initialized:
            return True
        import jax

        kwargs = {}
        coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
        if coord:
            kwargs["coordinator_address"] = coord
        n = os.environ.get("JAX_NUM_PROCESSES")
        if n:
            kwargs["num_processes"] = int(n)
        pid = os.environ.get("JAX_PROCESS_ID")
        if pid:
            kwargs["process_id"] = int(pid)
        logger.info("initializing jax.distributed (%s)", kwargs)
        try:
            jax.distributed.initialize(**kwargs)
        except RuntimeError as e:
            # initialize() must precede the first JAX computation;
            # call ensure_initialized() at program entry (core.run)
            # — reaching here later degrades to single-host rather
            # than crashing the check
            logger.warning("jax.distributed.initialize failed "
                           "(call earlier in the program): %s", e)
            return False
        _initialized = True
        return True


def process_info() -> dict:
    """Rank/size for logging and sharded store paths."""
    import jax

    return {"process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "local_devices": len(jax.local_devices()),
            "global_devices": len(jax.devices())}
