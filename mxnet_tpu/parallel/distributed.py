"""Multi-host bootstrap.

Reference: the ps-lite scheduler + DMLC_* env topology
(docs/faq/distributed_training.md:218-233, tools/launch.py).  TPU-native:
``jax.distributed.initialize`` plays the scheduler role; the actual data
plane is compiled collectives (ICI within a slice, DCN across), so after
init there are no server/worker processes to manage — every process runs
the same SPMD program on its local chips.

Env compatibility: DMLC_PS_ROOT_URI/PORT + DMLC_WORKER_ID/DMLC_NUM_WORKER
from the reference's launcher map onto coordinator_address/process_id/
num_processes, so `tools/launch.py`-style scripts keep working.
"""
from __future__ import annotations

import os

__all__ = ["init_distributed", "rank", "num_workers", "is_initialized"]

_STATE = {"initialized": False}


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, local_device_ids=None):
    """Initialize multi-host jax (reference: ps-lite Postoffice::Start)."""
    import jax

    if _STATE["initialized"]:
        return
    if coordinator_address is None:
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT", "9000")
        if uri:
            coordinator_address = "%s:%s" % (uri, port)
    if num_processes is None and "DMLC_NUM_WORKER" in os.environ:
        num_processes = int(os.environ["DMLC_NUM_WORKER"])
    if process_id is None and "DMLC_WORKER_ID" in os.environ:
        process_id = int(os.environ["DMLC_WORKER_ID"])
    if coordinator_address is None:
        # single-process: nothing to do, collectives stay intra-process
        _STATE["initialized"] = True
        return
    # CPU backend needs an explicit cross-process collective transport
    # (gloo); harmless on TPU where ICI/DCN collectives are native
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id,
                               local_device_ids=local_device_ids)
    _STATE["initialized"] = True


def is_initialized():
    return _STATE["initialized"]


def rank():
    """Reference: KVStore::get_rank (kvstore.h:319)."""
    import jax
    return jax.process_index()


def num_workers():
    """Reference: KVStore::get_group_size (kvstore.h:326)."""
    import jax
    return jax.process_count()
