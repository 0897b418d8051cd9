"""Device mesh construction.

The reference's unit of parallelism is the Spark executor + partition;
ours is a 1-D ``jax.sharding.Mesh`` whose single axis ("shard") carries
both roles the reference splits between data partitioning and shuffle:
read batches are sharded along rows, genome fragments along coordinates,
and cross-shard movement is an XLA collective (psum / all_to_all /
ppermute) over ICI instead of a TCP shuffle (SURVEY.md §2.6).

Multi-host: `initialize_distributed` wires `jax.distributed` so the same
mesh spans hosts over DCN; the device axis ordering keeps intra-host
neighbors adjacent so halo exchanges ride ICI.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shard"

#: Axis name of the streamed pipeline's per-window data-parallel mesh
#: (parallel/partitioner.MeshPartitioner): each window's [N, L] arrays
#: shard their read-row axis over it, observe histograms psum across it.
BATCH_AXIS = "batch"

def on_tpu(device=None) -> bool:
    """Whether ``device`` (the default device when None) is a TPU — the
    one platform question the pipeline asks: it decides the BQSR
    backend default, buffer donation and the sweep's per-device
    probes."""
    d = device if device is not None else jax.devices()[0]
    return getattr(d, "platform", None) == "tpu"


def genome_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over all (or the given) devices."""
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (SHARD_AXIS,))


def batch_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D ``batch`` mesh over the given (or all local) devices — the
    streamed pipeline's SPMD execution mesh.  Distinct from
    :func:`genome_mesh` only in axis name, so the partitioner's
    shardings read as what they are: data-parallel over read rows."""
    devices = list(devices) if devices is not None else jax.local_devices()
    return Mesh(np.array(devices), (BATCH_AXIS,))


def batch_row_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (read-row) axis over the ``batch`` mesh."""
    return NamedSharding(mesh, P(BATCH_AXIS))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Shard leading (read-row) axis across the mesh."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up (jax.distributed over DCN).

    No-op when single-process (the common test path); mirrors the role of
    the reference's Spark cluster deployment (driver + executors) with
    jax's coordinator + workers.
    """
    if coordinator_address is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
