"""Pallas TPU kernel for the dense window triangle count.

The dense path computes 6·T = Σᵢⱼ (A·A)ᵢⱼ ⊙ Aᵢⱼ for the window's V×V
adjacency matrix (ops/triangles.py `triangle_count_dense`, lowering
WindowTriangles.java:61-66). XLA's version materializes the V×V
two-path count matrix `A@A` in HBM before the elementwise mask and
reduce. This kernel fuses the whole contraction: each (i,j) output
tile accumulates its A[i,k]@A[k,j] partials in VMEM scratch across the
k grid dimension and reduces `partial ⊙ A[i,j]` to a single scalar on
the last k step — the only HBM traffic is reading A (three tiled
views) and writing one f32 per tile.

Per-tile counts are ≤ TILE²·V < 2³¹ and every entry of A@A is ≤ V, so
f32 accumulation (exact to 2²⁴) is exact for V ≤ 4096 — twice the
XLA dense limit, at one third the HBM footprint.

On non-TPU backends the kernel runs in interpreter mode (tests use the
virtual CPU mesh), keeping behavior identical everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # MXU-aligned


def _need_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tri_kernel(a_ik, a_kj, a_ij, out, acc):
    """Grid (i, j, k), k innermost. acc: VMEM (TILE, TILE) scratch.

    The output is the masked tile folded to one (8, TILE) block (sums of
    TILE/8 rows each), not a single scalar: an (8, 128) out block is
    what the TPU's tiling accepts, and each entry is ≤ TILE·V ≤ 2¹⁹ so
    it stays exact in f32; the global reduction finishes in int64 on
    the host."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    acc[:] += jnp.dot(a_ik[:], a_kj[:], preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        out[:] = jnp.sum((acc[:] * a_ij[:]).reshape(TILE // 8, 8, TILE),
                         axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _six_t_partials(a: jax.Array, interpret: bool) -> jax.Array:
    v = a.shape[0]
    g = v // TILE
    return pl.pallas_call(
        _tri_kernel,
        grid=(g, g, g),
        in_specs=[
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((8, TILE), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8 * g, g * TILE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((TILE, TILE), jnp.float32)],
        interpret=interpret,
    )(a, a, a)


@functools.partial(jax.jit, static_argnames=("num_vertices", "interpret"))
def _adjacency_six_t(src: jax.Array, dst: jax.Array, num_vertices: int,
                     interpret: bool) -> jax.Array:
    """Build the simple undirected adjacency (dupes/self-loops dropped by
    the set-to-one scatter) padded to a TILE multiple, then contract."""
    v = num_vertices
    vp = ((v + TILE - 1) // TILE) * TILE
    a = jnp.zeros((vp, vp), jnp.float32)
    # scatter rows at [0, v); padding slots (id == v from the host-side
    # pad fill) are clipped onto row v..vp-1 only when vp > v, otherwise
    # dropped via the drop mode of scatter
    a = a.at[src, dst].set(1.0, mode="drop")
    a = a.at[dst, src].set(1.0, mode="drop")
    diag = jnp.arange(vp)
    a = a.at[diag, diag].set(0.0)
    # zero any rows/cols past v (padding sentinel may have landed there)
    live = (jnp.arange(vp) < v).astype(jnp.float32)
    a = a * live[:, None] * live[None, :]
    return _six_t_partials(a, interpret)


def triangle_count_dense_pallas(src, dst, num_vertices: int) -> int:
    """Drop-in for ops/triangles.triangle_count_dense. src/dst may carry
    padding pointing at index >= num_vertices (masked out here)."""
    import numpy as np

    from . import segment as seg_ops

    vb = seg_ops.bucket_size(num_vertices)
    eb = seg_ops.bucket_size(len(src))
    s = seg_ops.pad_to(np.asarray(src, np.int32), eb, fill=vb)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
    d = seg_ops.pad_to(np.asarray(dst, np.int32), eb, fill=vb)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
    partials = _adjacency_six_t(jnp.asarray(s), jnp.asarray(d), vb,
                                _need_interpret())
    return int(np.asarray(partials).astype(np.int64).sum()) // 6  # gslint: disable=host-sync (sanctioned result boundary: the dense count's ONE d2h)
