"""Device mesh construction.

One mesh axis covers this workload's parallelism (SURVEY §2.3):

* ``shards`` — the data-parallel axis: columns striped into 2^20-wide
  shards, each device owning the shards :func:`chip_of_shard` deals it
  (the analogue of the reference's shard→node jump-hash placement,
  cluster.go:858-934, made static because TPU meshes are static).

A second ``rows`` (tensor-parallel-style) axis existed through round 4
but was DELIBERATELY collapsed (r05): every serving kernel's work is
embarrassingly parallel along shards, so whenever the index has at
least as many shards as the mesh has devices — the regime this design
targets — an all-``shards`` split gives the identical per-device FLOP
count with ZERO cross-device gathers, while a rows split forces a
row-block all-gather into every pair/gram kernel.  Splitting rows only
pays when shards < devices (a tiny index on a large pod), which the
stacked layout handles anyway by padding the shard axis.  The axis name
is kept in ``default_mesh`` signatures (size 1) so ShardedField's
specs stay stable.

The CLUSTER layer rides this same mesh: nodes whose holders live in
this process register in ``parallel/meshplace.py``, and
``cluster/dist.py`` then plans their shard groups into one jit-sharded
launch over ``serving_mesh()`` instead of an HTTP relay — the cluster
disappears into the mesh (docs/serving.md "Cluster on the mesh")."""

from __future__ import annotations

from functools import lru_cache

import numpy as np

import jax
from jax.sharding import Mesh


def mesh_shape_for(n_devices: int) -> tuple[int, int]:
    """(shards, rows) axis sizes — all devices on the ``shards`` axis
    (see the module docstring for why the rows factor was dropped)."""
    return n_devices, 1


def default_mesh(n_devices: int | None = None, axis_names=("shards", "rows")) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    s, r = mesh_shape_for(len(devices))
    return Mesh(np.array(devices).reshape(s, r), axis_names)


_serving_mesh: Mesh | None = None
_serving_max_devices: int | None = None


def configure_serving(max_devices: int | None) -> None:
    """Cap the serving mesh at the first ``max_devices`` devices (None =
    all). The analogue of the reference's cluster-size config; also lets
    a dryrun model an exact device count on a larger virtual backend."""
    global _serving_max_devices, _serving_mesh
    _serving_max_devices = max_devices
    _serving_mesh = None


def serving_mesh() -> Mesh | None:
    """1-D ``("shards",)`` mesh over the visible devices, used by the
    serving executor's field stacks so each device owns its share of
    the shards (:func:`stack_order`) — the reference's shard→node placement
    (cluster.go:858-934) made static. None on a single-device host (the
    plain single-device path is faster than a degenerate mesh)."""
    global _serving_mesh
    # local_devices, not devices: each process serves the shards it owns
    # (the cluster layer routes cross-host queries); a mesh spanning
    # non-addressable devices would make device_put raise mid-query.
    devices = jax.local_devices()
    if _serving_max_devices is not None:
        devices = devices[:_serving_max_devices]
    if len(devices) <= 1:
        return None
    if _serving_mesh is None or list(_serving_mesh.devices.flat) != devices:
        _serving_mesh = Mesh(np.array(devices), ("shards",))
    return _serving_mesh


def chip_of_shard(shard: int, n_dev: int) -> int:
    """Which of a serving mesh's ``n_dev`` chips holds shard ``shard``:
    THE rule, read by both sides.  A fragment's device copy goes there
    (core/fragment.py ``_to_device``: a fragment is made knowing only its
    own number), and a field stack puts the shard into that chip's share
    of its shard axis (:func:`stack_order`), so a refresh after a write
    gathers the block on the chip that keeps it (exec/stacks.py)."""
    return shard % n_dev


@lru_cache(maxsize=256)
def stack_order(shards: tuple[int, ...], n_dev: int) -> tuple[int | None, ...]:
    """The shard at every position of a stack's shard axis over ``n_dev``
    chips, None where the axis is padded.  The axis is ``n_dev`` shares of
    ``ceil(len(shards) / n_dev)`` positions, chip ``d`` holding positions
    ``[d * k, (d + 1) * k)``; a share takes the shards of its own chip
    (:func:`chip_of_shard`) in the order of ``shards``, as many as fit.
    Where the list has more of one chip's shards than a share holds (a
    list with gaps) the rest fill what other shares have left: the stack
    stays as small as it was, and those shards' refreshes cross chips.
    A pure function of its arguments, so stacks of different fields over
    one shard list share their positions (the cross-field kernels need
    that).  On one device the order is ``shards`` itself."""
    if n_dev <= 1:
        return shards
    k = -(-len(shards) // n_dev)
    shares: list[list[int | None]] = [[] for _ in range(n_dev)]
    spill = []
    for s in shards:
        own = shares[chip_of_shard(s, n_dev)]
        (own if len(own) < k else spill).append(s)
    spill.reverse()
    for own in shares:
        while len(own) < k:
            own.append(spill.pop() if spill else None)
    return tuple(s for own in shares for s in own)


def init_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> Mesh:
    """Join this process to a multi-host JAX job and return the global
    mesh over every host's devices.

    The reference scales across hosts with memberlist gossip + HTTP RPC
    (SURVEY §2.4); the TPU-native data plane instead uses the JAX
    distributed runtime: one coordinator process, XLA collectives riding
    ICI within a slice and DCN across slices. The ``shards`` axis is laid
    out so consecutive shards land on one host's devices first — keeping
    the reduce step of a multi-shard query on ICI, with only the final
    cross-host combine touching DCN.

    Args default from the standard JAX env (JAX_COORDINATOR_ADDRESS etc.)
    when omitted; on a single-host job this degrades to ``default_mesh``.
    The cluster layer (HTTP membership, resize, anti-entropy) still runs
    per-host for storage ownership — this function only wires the
    device-compute plane.
    """
    import os

    if coordinator_address is not None or num_processes is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    elif os.environ.get("JAX_COORDINATOR_ADDRESS") and jax.process_count() == 1:
        jax.distributed.initialize()
    return default_mesh()
