"""Pallas prototype of the dominant sparse kernel: per-edge sorted-row
intersection counting (the `intersect_local` compare of
ops/triangles.py:77-121, lowering WindowTriangles.java:61-140).

The XLA lowering is a chunked broadcast equality compare whose
[Ep, chunk, K] hit tensor is fused into its `any`-reduce by XLA. This
kernel makes the fusion explicit: each grid step owns a TILE_E-edge
slice, keeps the pre-gathered neighbor rows in VMEM, runs the K×K
compare chunk loop entirely in registers/VMEM, and writes ONE partial
count per tile — no intermediate ever exists in HBM.

The row gather (nbr[ea], nbr[eb]) stays in XLA outside the kernel:
dynamic row gathers from HBM inside a Pallas kernel would serialize
into per-edge DMAs, and XLA's gather is already bandwidth-optimal.
What the kernel can win is the compare loop's scheduling; what it can
lose is XLA's fusion of the gather INTO the compare (which skips the
[Ep, K] rows_a/rows_b round trip to HBM entirely). tools/
profile_kernels.py measures both on-chip; ops/triangles.py builds
the XLA compare into its programs.

On non-TPU backends the kernel runs in interpreter mode (virtual CPU
mesh tests), keeping behavior identical everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_triangles import _need_interpret

TILE_E = 64      # default edges per grid step: the [T, CHUNK_K, K]
                 # broadcast compare materializes in VMEM, so
                 # T=64/Ck=128/K<=256 stays under the 16M scoped-vmem
                 # limit (T=256 OOMs)
CHUNK_K = 128    # default compare-chunk width (lane-aligned)
MAX_TILES = 2048 # grid steps per pallas_call: the [g] partial vector
                 # lives wholly in SMEM (scarce scalar memory), so cap
                 # it at 8KB and slab larger edge buckets over several
                 # calls (each slab shape is identical -> one compile)


def tile_intersect_count(ra, rb, va, chunk_k: int):
    """The seed kernel's inner compare loop on one pre-gathered tile
    pair — ra/rb: [T, K] int32 neighbor rows, va: [T, K] bool validity
    of ra entries (sentinel/padding pre-masked) — as a plain traceable
    function, so the fused window megakernel (ops/pallas_window.py)
    runs the IDENTICAL K-bucket intersection inside its own
    pallas_call: one [T, Ck, K] broadcast-equality chunk at a time,
    never materializing more than a chunk in VMEM. Rows are
    deduplicated, so each ra entry matches at most one rb entry and
    the `any` over the compare axis counts it exactly once. Returns
    the int32 tile total."""
    k = ra.shape[1]
    total = jnp.int32(0)
    for c in range(-(-k // chunk_k)):
        ck = min(chunk_k, k - c * chunk_k)
        a_chunk = ra[:, c * chunk_k:c * chunk_k + ck]   # [T, Ck]
        v_chunk = va[:, c * chunk_k:c * chunk_k + ck]
        hit = jnp.any(
            a_chunk[:, :, None] == rb[:, None, :], axis=2)
        total += jnp.sum(jnp.where(hit & v_chunk, 1, 0),
                         dtype=jnp.int32)
    return total


def _make_kernel(chunk_k: int):
    def _intersect_kernel(ra, rb, va, out):
        """ra/rb: [T, K] int32 neighbor rows; va: [T, K] bool validity
        of ra entries (sentinel/padding pre-masked). out: [g] int32
        partial counts in SMEM — the whole array is the block (a
        size-1 block per step is not lowerable on TPU), each grid step
        writes its own slot. The compare math lives in
        tile_intersect_count, shared with the window megakernel."""
        out[pl.program_id(0)] = tile_intersect_count(
            ra[:], rb[:], va[:], chunk_k)

    return _intersect_kernel


@functools.partial(jax.jit,
                   static_argnames=("interpret", "tile_e", "chunk_k"))
def _intersect_tiles(rows_a: jax.Array, rows_b: jax.Array,
                     valid: jax.Array, interpret: bool,
                     tile_e: int = TILE_E,
                     chunk_k: int = CHUNK_K) -> jax.Array:
    ep, k = rows_a.shape
    assert ep % tile_e == 0, (ep, tile_e)
    g = ep // tile_e
    return pl.pallas_call(
        _make_kernel(chunk_k),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((tile_e, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_e, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_e, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        # One scalar per grid step. A PER-STEP size-1 output block
        # ((1,) block over a (g,) array, g>1) is not lowerable on TPU
        # in any memory space; a block whose size EQUALS the array dim
        # is always legal (this also covers g==1). So expose the whole
        # [g] vector as one SMEM block and index by program_id.
        out_specs=pl.BlockSpec((g,), lambda i: (0,),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((g,), jnp.int32),
        interpret=interpret,
    )(rows_a, rows_b, valid)


def intersect_local_pallas(nbr: jax.Array, ea: jax.Array, eb: jax.Array,
                           emask: jax.Array, tile_e: int = TILE_E,
                           chunk_k: int = CHUNK_K) -> jax.Array:
    """Drop-in for ops/triangles.intersect_local (same contract: count
    of |N_out(a) ∩ N_out(b)| over all valid oriented edges). The
    profiler passes explicit tile shapes to sweep."""
    sentinel = nbr.shape[0] - 1
    ep = ea.shape[0]
    slab_e = MAX_TILES * tile_e
    pad = (-ep) % (tile_e if ep <= slab_e else slab_e)
    if pad:
        ea = jnp.concatenate([ea, jnp.full(pad, sentinel, ea.dtype)])
        eb = jnp.concatenate([eb, jnp.full(pad, sentinel, eb.dtype)])
        emask = jnp.concatenate([emask, jnp.zeros(pad, emask.dtype)])
    interpret = _need_interpret()
    total = jnp.int32(0)
    for s in range(0, ea.shape[0], slab_e):
        rows_a = nbr[ea[s:s + slab_e]]
        rows_b = nbr[eb[s:s + slab_e]]
        valid = (rows_a < sentinel) & emask[s:s + slab_e, None]
        partials = _intersect_tiles(rows_a, rows_b, valid, interpret,
                                    tile_e, chunk_k)
        total = total + jnp.sum(partials, dtype=jnp.int32)
    return total
