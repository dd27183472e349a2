"""Device mesh construction — the communication substrate.

TPU-native replacement for the reference's Flink network stack
(SURVEY.md §2.4 P6: hash `keyBy` exchange, broadcast replication,
parallelism-1 funnels over Netty TCP): a 1-D `jax.sharding.Mesh` over
the available chips; exchanges become XLA collectives (`psum`, `pmin`,
`all_gather`) riding ICI inside a pod and DCN across slices.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

SHARD_AXIS = "shard"

def shard_map_norep(mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` partial with the vma check DISABLED —
    for bodies that trace a `lax.while_loop` (ops/unionfind.cc_fixpoint's
    fixpoint iteration; arbitrary user associative fns), which jax's
    checker has no replication rule for ("No replication rule for
    while"). Every site using this wrapper makes its outputs replicated
    EXPLICITLY with collectives (psum/pmin/pmax/all_gather), so the
    check is redundant there — disabling it changes what is verified,
    never what is computed."""
    return functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = SHARD_AXIS) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis_name,))  # gslint: disable=host-sync (device HANDLES into a mesh layout, no device value in sight)


def edge_sharding(mesh: Mesh) -> NamedSharding:
    """Edges: sharded along the batch dimension (strategy P1)."""
    return NamedSharding(mesh, P(SHARD_AXIS))

def replicated(mesh: Mesh) -> NamedSharding:
    """Vertex state / summaries: replicated (strategy P3)."""
    return NamedSharding(mesh, P())


def shard_count(mesh: Mesh) -> int:
    return mesh.shape[SHARD_AXIS]


def mesh_padded_len(n_items: int, mesh: Mesh) -> int:
    """Smallest multiple of the mesh size ≥ n_items (≥ one per shard)."""
    n = shard_count(mesh)
    return ((n_items + n - 1) // n) * n if n_items else n


def pad_edges_for_mesh(src: np.ndarray, dst: np.ndarray, mesh: Mesh,
                       sentinel: int):
    """Pad a COO batch so its length divides the mesh; padding slots point
    at the sentinel vertex (one past the real vertex range)."""
    target = mesh_padded_len(len(src), mesh)
    pad = target - len(src)
    if pad:
        src = np.concatenate([src, np.full(pad, sentinel, src.dtype)])
        dst = np.concatenate([dst, np.full(pad, sentinel, dst.dtype)])
    return src, dst
