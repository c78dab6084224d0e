"""Device-placement map: which cluster nodes' shards are slices of the
local serving mesh.

The scale-out story (docs/serving.md "Cluster on the mesh") needs the
cluster layer to know, per owner node, whether that node's fragments are
directly addressable from this process — i.e. whether its shards live on
the same accelerator mesh the serving executor launches over.  When they
are, ``cluster/dist.py`` plans those shards into a mesh-local partition
(one jit-sharded launch, collective reduction) instead of an HTTP relay.

A node advertises itself by registering its holder here on ``start()``
and withdrawing on ``stop()`` (server/node.py).  In production — one
process per host — only the local node ever registers, so the registry
is a no-op and every peer stays on the HTTP fan-out.  In an
``InProcessCluster`` (tests, a future one-process-many-chips
deployment) every member registers, so the whole cluster collapses onto
the mesh.

This is deliberately process-global rather than per-cluster: being in
the same process IS the locality property that makes a peer's fragments
mesh-addressable, and node ids are unique across live in-process
clusters (uuid-suffixed in testing.cluster).
"""

from __future__ import annotations

import itertools
import os
import threading


class MeshHandle:
    """One registered node: its holder plus a generation stamp that
    changes on every (re-)registration, so placement-keyed executor
    caches invalidate when a node restarts with a fresh holder."""

    __slots__ = ("node_id", "holder", "generation")

    def __init__(self, node_id: str, holder, generation: int):
        self.node_id = node_id
        self.holder = holder
        self.generation = generation


class MeshPlacement:
    def __init__(self):
        self._lock = threading.Lock()
        self._handles: dict[str, MeshHandle] = {}
        self._gen = itertools.count(1)

    def register(self, node_id: str, holder) -> None:
        with self._lock:
            self._handles[node_id] = MeshHandle(node_id, holder, next(self._gen))

    def unregister(self, node_id: str) -> None:
        with self._lock:
            self._handles.pop(node_id, None)

    def handle(self, node_id: str) -> MeshHandle | None:
        with self._lock:
            return self._handles.get(node_id)

    def snapshot(self) -> dict:
        """Placement map for /debug/vars: node id -> registration info."""
        with self._lock:
            return {
                nid: {"generation": h.generation}
                for nid, h in sorted(self._handles.items())
            }


_placement = MeshPlacement()


def default_placement() -> MeshPlacement:
    return _placement


def enabled() -> bool:
    """Mesh dispatch kill switch: ``PILOSA_MESH_DISPATCH=0`` forces every
    fan-out back onto the HTTP relay without touching any node config."""
    return os.environ.get("PILOSA_MESH_DISPATCH", "1").lower() not in (
        "0", "false", "no", "off",
    )
