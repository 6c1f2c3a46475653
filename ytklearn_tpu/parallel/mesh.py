"""Device mesh construction — the TPU equivalent of ytk-mp4j topology.

The reference's communication world is `slaveNum × threadNum` ranks joined
through a CommMaster TCP rendezvous (reference: worker/TrainWorker.java:139,
bin/local_optimizer.sh:38-47). Here the world is a `jax.sharding.Mesh`:
devices are the ranks, `jax.distributed.initialize` is the rendezvous on
multi-host pods, and collectives ride ICI instead of ethernet.

One named axis, DATA_AXIS, carries row-sharded data parallelism (the
reference's only cross-worker axis). Model-parallel shardings (L-BFGS
history slices, GBDT histogram bin slices) reuse the same axis via
psum_scatter / all_gather, exactly mirroring how the reference overlays
slice ownership on the same rank grid (reference:
optimizer/HoagOptimizer.java:442-449, data/gbdt/HistogramBuilder.java:95).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None, axis_name: str = DATA_AXIS) -> Mesh:
    """1-D mesh over (a prefix of) the available devices."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis_name,))


def local_device_count(mesh: Mesh) -> int:
    """This process's device count within the mesh."""
    me = jax.process_index()
    return sum(1 for d in mesh.devices.flat if d.process_index == me)


def put_row_sharded(arr, mesh: Mesh):
    """Row-shard dim 0 over the data axis. Single-process: a plain
    device_put. Multi-process: `arr` is THIS process's row shard and the
    global array is assembled from per-process shards (the TPU-native
    replacement for the reference's per-worker CoreData ownership —
    each worker's parsed rows become its device shard, no gather)."""
    sh = row_sharding(mesh)
    if jax.process_count() == 1:
        return jax.device_put(arr, sh)
    return jax.make_array_from_process_local_data(sh, arr)


def put_col_sharded(arr, mesh: Mesh):
    """Shard dim 1 (the sample axis of a transposed matrix) over data."""
    sh = NamedSharding(mesh, P(None, DATA_AXIS))
    if jax.process_count() == 1:
        return jax.device_put(arr, sh)
    return jax.make_array_from_process_local_data(sh, arr)


def equal_row_target(n_local: int, mesh: Mesh, multiple: int = 1) -> int:
    """Local row count every process should pad to so the global row axis
    splits evenly across all mesh devices: max over processes, rounded up
    to a multiple of (local device count x `multiple`)."""
    ld = max(local_device_count(mesh), 1) * max(multiple, 1)
    if jax.process_count() == 1:
        return max(ld, -(-n_local // ld) * ld)
    from .collectives import host_allgather_objects

    counts = host_allgather_objects(int(n_local))
    return max(ld, -(-max(counts) // ld) * ld)


def distributed_initialize_if_needed(**kwargs) -> None:
    """Multi-host rendezvous: replaces the reference's CommMaster process
    (reference: worker/TrainWorker.java:139, bin/local_optimizer.sh:38-47).

    MUST run before any other JAX API touches the backend — querying
    `jax.process_count()` first would initialize the local backend and make
    distributed init a no-op (ADVICE r1). Set YTKLEARN_TPU_DISTRIBUTED=1 (or
    pass coordinator kwargs) in each process of a multi-host launch; on TPU
    pods coordinator discovery comes from the runtime metadata, on CPU/GPU
    clusters the standard jax.distributed env vars/kwargs apply.
    """
    if os.environ.get("YTKLEARN_TPU_DISTRIBUTED", "0") != "1" and not kwargs:
        return
    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(**kwargs)


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Shard dim 0 (rows/samples) across the data axis; replicate the rest."""
    return NamedSharding(mesh, P(DATA_AXIS))

def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_rows_to_multiple(n: int, k: int) -> int:
    """Rows must pad to a multiple of the mesh size for even sharding; the
    reference instead allowed ragged per-worker row counts
    (dataflow/DataFlow.java:391-410) — padding + weight-masking is the
    static-shape equivalent."""
    return (n + k - 1) // k * k


def shard_rows(arr, mesh: Mesh):
    """Device-put a host array with rows sharded over the data axis."""
    return jax.device_put(arr, row_sharding(mesh))
