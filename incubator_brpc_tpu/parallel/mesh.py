"""Fabric mesh — the named device mesh every channel lowering runs over.

The reference addresses peers with EndPoint lists from naming services; the
TPU fabric addresses them with coordinates in a ``jax.sharding.Mesh``. Axis
vocabulary (fixed, sizes may be 1 so every code path exists at any device
count):

    dp — data/replica fan-out (ParallelChannel broadcast+merge)
    pp — pipeline stages (chained streaming RPC)
    tp — tensor/partitioned service shards (PartitionChannel)
    sp — sequence/stream ring (StreamingRPC over ICI neighbors)
    ep — expert/dynamic partition groups (DynamicPartitionChannel)

Shardings are laid out so collectives ride ICI, not DCN (scaling-book
recipe): the innermost axes (tp, sp) map to the fastest mesh dims.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

FABRIC_AXES = ("dp", "pp", "tp", "sp", "ep")


def default_axis_sizes(n_devices: int) -> Dict[str, int]:
    """Factor ``n_devices`` over the fabric axes.

    Powers of two are split round-robin in priority order dp, tp, pp, sp, ep
    (so 8 devices -> dp2·tp2·pp2, 32 -> all axes 2); any residual odd factor
    lands on dp.
    """
    sizes = {ax: 1 for ax in FABRIC_AXES}
    n = n_devices
    priority = ("dp", "tp", "pp", "sp", "ep")
    while n % 2 == 0 and n > 1:
        for ax in priority:
            if n % 2 != 0 or n == 1:
                break
            sizes[ax] *= 2
            n //= 2
    sizes["dp"] *= n  # odd residue
    return sizes


def covering_axis_sizes(n_devices: int) -> List[Dict[str, int]]:
    """Factorings of ``n_devices`` such that EVERY fabric axis is >= 2 in
    at least one of them, so each collective crosses devices somewhere
    instead of riding a size-1 axis. Needs a multiple of four devices:
    two meshes of three live axes from eight, three of two from four."""
    if n_devices % 8 == 0:
        k = n_devices // 8
        live = [
            {"dp": 2 * k, "pp": 2, "tp": 2},
            {"dp": k, "pp": 2, "sp": 2, "ep": 2},
        ]
    elif n_devices % 4 == 0:
        k = n_devices // 4
        live = [
            {"dp": 2 * k, "tp": 2},
            {"dp": k, "pp": 2, "sp": 2},
            {"dp": 2 * k, "ep": 2},
        ]
    else:
        raise ValueError(
            f"no factoring of {n_devices} devices makes every fabric axis "
            "live; use a multiple of four"
        )
    return [{ax: sizes.get(ax, 1) for ax in FABRIC_AXES} for sizes in live]


def make_fabric_mesh(
    n_devices: Optional[int] = None,
    axis_sizes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> jax.sharding.Mesh:
    """Build the fabric Mesh. Defaults to all visible devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    devices = list(devices)[:n_devices]
    if axis_sizes is None:
        axis_sizes = default_axis_sizes(n_devices)
    shape = tuple(axis_sizes.get(ax, 1) for ax in FABRIC_AXES)
    if int(np.prod(shape)) != n_devices:
        raise ValueError(f"axis sizes {axis_sizes} do not factor {n_devices} devices")
    dev_array = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(dev_array, FABRIC_AXES)
