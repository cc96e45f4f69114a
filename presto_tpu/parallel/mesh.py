"""Device mesh management.

The TPU equivalent of the reference's node inventory + discovery
(metadata/DiscoveryNodeManager.java:70, execution/scheduler/NodeScheduler.java:59):
"workers" are chips in a jax.sharding.Mesh. One mesh axis ("w") carries the engine's
inter-node parallelism; partitioned exchanges ride ICI collectives over it.

Multi-host: jax.distributed initializes process groups; the mesh spans all hosts'
devices and DCN handles cross-host legs of collectives — the control plane (split
assignment, task lifecycle) stays on the Python coordinator exactly like the
reference keeps HTTP for control while this design moves the data plane to XLA.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

WORKER_AXIS = "w"


@dataclasses.dataclass
class WorkerNode:
    """A schedulable worker = one chip (Node analogue, spi/Node)."""
    node_id: str
    device: jax.Device
    index: int

    @property
    def is_coordinator(self) -> bool:
        return self.index == 0


class MeshContext:
    """Holds the engine's mesh + sharding helpers."""

    def __init__(self, devices: Optional[List[jax.Device]] = None,
                 n_workers: Optional[int] = None):
        devs = devices if devices is not None else jax.devices()
        if n_workers is not None:
            devs = devs[:n_workers]
        self.devices = list(devs)
        self.mesh = Mesh(np.asarray(self.devices), (WORKER_AXIS,))
        self.nodes = [WorkerNode(f"worker-{i}", d, i) for i, d in enumerate(self.devices)]

    @property
    def n_workers(self) -> int:
        return len(self.devices)

    def sharded(self, *axes) -> NamedSharding:
        """NamedSharding with the leading dim over workers."""
        return NamedSharding(self.mesh, P(WORKER_AXIS, *axes))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def active_nodes(self) -> List[WorkerNode]:
        return self.nodes


_default_mesh: Optional[MeshContext] = None


def default_mesh() -> MeshContext:
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = MeshContext()
    return _default_mesh


def set_default_mesh(ctx: MeshContext) -> None:
    global _default_mesh
    _default_mesh = ctx
