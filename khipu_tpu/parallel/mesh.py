"""Device-mesh construction for the node-sharding axis.

One 1-D mesh axis ("nodes") carries all data parallelism in this
framework: trie nodes are content-addressed and independent under
hashing, so the natural decomposition is an even split of the node
batch across chips — the role Akka Cluster Sharding of NodeEntity plays
in the reference (entity/NodeEntity.scala:28), with ICI collectives
replacing cluster gossip.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

AXIS = "nodes"


def device_mesh(n_devices: Optional[int] = None, axis_name: str = AXIS) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` available devices.

    On real hardware the devices are the host's v5e chips; in tests a
    virtual CPU mesh (``--xla_force_host_platform_device_count=8``, set
    from outside) stands in. The devices must exist — nothing here
    conjures a mesh.

    The replay path (bridge -> windowed replay -> fused commit -> RPC)
    does NOT come through here: it is single-device, everything it
    dispatches lands on ``jax.devices()[0]``. ``parallel/`` is reached
    only from ``__graft_entry__.py`` (dry run), chip_smoke's multi-chip
    leg and the tests.
    """
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if n_devices > len(devs):
        raise ValueError(
            f"requested {n_devices} devices, only {len(devs)} available"
        )
    return Mesh(np.asarray(devs[:n_devices]), (axis_name,))


def pad_to_shards(n: int, n_shards: int, floor: int = 1) -> int:
    """Smallest count >= max(n, floor) divisible by ``n_shards``."""
    n = max(n, floor)
    return ((n + n_shards - 1) // n_shards) * n_shards
