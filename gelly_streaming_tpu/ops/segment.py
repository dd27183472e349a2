"""Segment (per-vertex) reduction kernels — the TPU lowering of the
reference's per-(vertex, window) incremental folds and reduces
(GraphWindowStream.java:62-121: `WindowedStream.fold/reduce` panes).

A window's edges arrive as COO arrays; grouping a vertex's neighborhood
is a segment reduction over edges sorted by vertex. Three tiers:

- named monoids (sum/min/max/count) → `jax.ops.segment_*`, fully parallel;
- arbitrary associative reduce fns → segmented scan over sorted edges;
- arbitrary (non-associative) fold fns → sequential-in-arrival-order
  segmented `lax.scan`, which reproduces the reference's per-pane fold
  semantics exactly (fold order = arrival order within each key).

All kernels take padded, power-of-two-bucketed shapes so XLA compiles a
small number of programs regardless of per-window edge counts
(SURVEY.md §7 "Hard parts: dynamic shapes").
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_MIN_BUCKET = 8


def bucket_size(n: int) -> int:
    """Next power-of-two ≥ n (min 8) — bounds XLA recompilation."""
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return b


def pad_to(arr: np.ndarray, size: int, fill=0) -> np.ndarray:
    if arr.shape[0] == size:
        return arr
    pad = np.full((size - arr.shape[0],) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


# ----------------------------------------------------------------------
# named-monoid fast path
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_segments", "kind"))
def segment_reduce(values: jax.Array, segment_ids: jax.Array, num_segments: int,
                   kind: str = "sum") -> jax.Array:
    """Parallel segment reduction. Padded elements must carry
    segment_id == num_segments - 1 reserved padding row, or a neutral value."""
    if kind == "sum":
        return jax.ops.segment_sum(values, segment_ids, num_segments)
    if kind == "min":
        return jax.ops.segment_min(values, segment_ids, num_segments)
    if kind == "max":
        return jax.ops.segment_max(values, segment_ids, num_segments)
    if kind == "count":
        return jax.ops.segment_sum(jnp.ones_like(values), segment_ids, num_segments)
    raise ValueError(kind)


@jax.jit
def degree_update(state: jax.Array, src: jax.Array,
                  dst: jax.Array) -> jax.Array:
    """Fold one window batch into a running degree vector on device
    (continuous-degree semantics of SimpleEdgeStream.java:465-482,
    emitted per window). src/dst are padded with sentinel id
    len(state)-1, whose slot absorbs the padding contributions."""
    return state.at[src].add(1).at[dst].add(1)


# ----------------------------------------------------------------------
# generic segmented fold (sequential within segment, parallel-free scan)
# ----------------------------------------------------------------------

def _fold_kernel(fold_fn: Callable, init_tree: Any, seg: jax.Array,
                 mask: jax.Array, fields: Tuple[jax.Array, ...],
                 num_segments: int):
    """Scan edges (sorted by segment, stable) carrying the accumulator;
    reset at segment starts; per-segment result = accumulator at the
    segment's last element."""
    n = seg.shape[0]
    is_start = jnp.concatenate(
        [jnp.array([True]), seg[1:] != seg[:-1]]
    )

    def body(acc, x):
        start, m, s_fields = x
        acc = jax.tree_util.tree_map(
            lambda i, a: jnp.where(start, i, a), init_tree, acc
        )
        new_acc = fold_fn(acc, *s_fields)
        new_acc = jax.tree_util.tree_map(
            lambda nv, a: jnp.where(m, nv, a), new_acc, acc
        )
        return new_acc, new_acc

    _, accs = jax.lax.scan(body, init_tree, (is_start, mask, fields))
    # index of last (masked-valid) element in each segment
    idx = jnp.arange(n)
    last_idx = jax.ops.segment_max(
        jnp.where(mask, idx, -1), seg, num_segments + 1
    )[:num_segments]
    has_any = last_idx >= 0
    safe_idx = jnp.maximum(last_idx, 0)
    result = jax.tree_util.tree_map(lambda a: a[safe_idx], accs)
    return result, has_any


@functools.lru_cache(maxsize=256)
def _jit_fold(fold_fn, num_segments_bucket):
    @jax.jit
    def run(init_tree, seg, mask, fields):
        return _fold_kernel(fold_fn, init_tree, seg, mask, fields,
                            num_segments_bucket)
    return run


def segmented_fold(fold_fn: Callable, init_tree: Any, segment_ids: np.ndarray,
                   fields: Tuple[np.ndarray, ...], num_segments: int):
    """Host wrapper: pads, buckets, runs the jitted scan fold.

    fold_fn(acc_tree, *field_scalars) -> acc_tree, jax-traceable.
    Returns (result_tree_stacked[num_segments], has_any[num_segments]).
    """
    n = segment_ids.shape[0]
    nb = bucket_size(n)
    sb = bucket_size(num_segments)
    seg = pad_to(np.asarray(segment_ids, np.int32), nb, fill=sb)
    mask = pad_to(np.ones(n, bool), nb, fill=False)
    fpad = tuple(pad_to(np.asarray(f), nb) for f in fields)
    init = jax.tree_util.tree_map(jnp.asarray, init_tree)
    result, has_any = _jit_fold(fold_fn, sb)(init, seg, mask, fpad)
    return (
        jax.tree_util.tree_map(lambda a: np.asarray(a[:num_segments]), result),
        np.asarray(has_any[:num_segments]),
    )


@functools.lru_cache(maxsize=256)
def _jit_assoc_reduce(reduce_fn, num_segments_bucket):
    @jax.jit
    def run(seg, mask, vals):
        n = seg.shape[0]
        flags = jnp.concatenate(
            [jnp.array([True]), seg[1:] != seg[:-1]])

        def comb(a, b):
            fa, va = a
            fb, vb = b
            # segment-start flag resets the running combine: classic
            # flagged associative scan (requires reduce_fn associative)
            return fa | fb, jnp.where(fb, vb, reduce_fn(va, vb))

        _, scanned = jax.lax.associative_scan(comb, (flags, vals))
        idx = jnp.arange(n)
        last_idx = jax.ops.segment_max(
            jnp.where(mask, idx, -1), seg, num_segments_bucket + 1
        )[:num_segments_bucket]
        has_any = last_idx >= 0
        return scanned[jnp.maximum(last_idx, 0)], has_any

    return run


def segmented_reduce_associative(reduce_fn: Callable,
                                 segment_ids: np.ndarray,
                                 values: np.ndarray, num_segments: int):
    """Per-segment reduce for a user fn DECLARED associative: a flagged
    `lax.associative_scan` runs in O(log E) parallel steps instead of
    the O(E) sequential pane scan of `segmented_reduce` — the fast tier
    between named monoids and arbitrary fns (the combine tree reorders
    the applications, which is exactly what associativity licenses).
    Same contract as segmented_reduce: segment_ids sorted (stable),
    returns (results[num_segments], has_any[num_segments])."""
    values = np.asarray(values)
    n = segment_ids.shape[0]
    nb = bucket_size(n)
    sb = bucket_size(num_segments)
    seg = pad_to(np.asarray(segment_ids, np.int32), nb, fill=sb)
    mask = pad_to(np.ones(n, bool), nb, fill=False)
    vals = pad_to(values, nb)
    res, has_any = _jit_assoc_reduce(reduce_fn, sb)(
        jnp.asarray(seg), jnp.asarray(mask), jnp.asarray(vals))
    return (np.asarray(res[:num_segments]),
            np.asarray(has_any[:num_segments]))


def segmented_reduce(reduce_fn: Callable, segment_ids: np.ndarray,
                     values: np.ndarray, num_segments: int):
    """Generic per-segment reduce of edge values in arrival order
    (reference: EdgesReduceFunction, GraphWindowStream.java:107-121).

    Implemented as a segmented fold seeded from the first element:
    acc = (value, seen); fold(acc, v) = reduce(acc, v) if seen else v.
    """
    values = np.asarray(values)
    init = (jnp.zeros((), jnp.asarray(values).dtype), jnp.zeros((), jnp.bool_))

    def fold(acc, v):
        val, seen = acc
        return (jnp.where(seen, reduce_fn(val, v), v), jnp.ones((), jnp.bool_))

    (res, _seen), has_any = segmented_fold(
        fold, init, segment_ids, (values,), num_segments
    )
    return res, has_any


def warm_stream_buckets(kernel) -> None:
    """AOT-compile every stream-chunk program a window kernel's
    _run_stack_loop can dispatch at its current configuration — the full
    MAX_STREAM_WINDOWS chunk and each power-of-two ragged window
    bucket — via the kernel's own `_stream_exec(wb)` executable cache.
    Compile-only: nothing executes, so warming costs compile time, not
    a full-size dispatch per bucket. Shared by
    TriangleWindowKernel.warm_chunks and
    ShardedTriangleWindowKernel.warm_chunks so both kernels always
    warm the same program set."""
    sizes = {kernel.MAX_STREAM_WINDOWS}
    w = bucket_size(1)
    while w < kernel.MAX_STREAM_WINDOWS:
        sizes.add(w)
        w *= 2
    for w in sorted(sizes):
        kernel._stream_exec(w)


def window_stack(src: np.ndarray, dst: np.ndarray, eb: int,
                 sentinel: int):
    """Pad a COO stream to whole `eb`-sized windows and reshape to
    [W, eb] stacks plus the validity mask — the shared layout of every
    batched window dispatch (triangles.count_stream, sharded
    count_stream, scan_analytics.process)."""
    src = np.asarray(src, np.int32)  # gslint: disable=host-sync (host-input normalization: stream payloads are numpy, never device values)
    dst = np.asarray(dst, np.int32)  # gslint: disable=host-sync (host-input normalization: stream payloads are numpy, never device values)
    n = len(src)
    num_w = -(-n // eb)
    s = pad_to(src, num_w * eb, fill=sentinel).reshape(num_w, eb)
    d = pad_to(dst, num_w * eb, fill=sentinel).reshape(num_w, eb)
    valid = pad_to(np.ones(n, bool), num_w * eb,
                   fill=False).reshape(num_w, eb)
    return num_w, s, d, valid


def stack_window_list(windows, eb: int, sentinel: int):
    """Pad a list of (src, dst) window batches of varying lengths
    (each ≤ eb) into [W, eb] stacks + validity mask — shared by the
    single-chip and sharded count_windows batched dispatches."""
    num_w = len(windows)
    s = np.full((num_w, eb), sentinel, np.int32)
    d = np.full((num_w, eb), sentinel, np.int32)
    valid = np.zeros((num_w, eb), bool)
    for w, (ws, wd) in enumerate(windows):
        n = len(ws)
        if n > eb:
            raise ValueError(f"window of {n} edges exceeds edge "
                             f"bucket {eb}")
        s[w, :n] = ws
        d[w, :n] = wd
        valid[w, :n] = True
    return s, d, valid


def stack_window_rows(pairs, wb: int, eb: int, sentinel: int):
    """Pack a chunk's dense (src, dst) window arrays into [wb, eb]
    stacks + validity mask (rows past len(pairs) stay all-sentinel) —
    the driver's snapshot-scan chunk prep, factored here so the
    ingress pipeline can run it on the prep worker pool
    (ops/ingress_pipeline) while the previous chunk executes on
    device."""
    s_w = np.full((wb, eb), sentinel, np.int32)
    d_w = np.full((wb, eb), sentinel, np.int32)
    valid = np.zeros((wb, eb), bool)
    for i, (s, d) in enumerate(pairs):
        s_w[i, :len(s)] = s
        d_w[i, :len(d)] = d
        valid[i, :len(s)] = True
    return s_w, d_w, valid


def pad_window_chunk(s, d, valid, at: int, hi: int, max_w: int,
                     eb: int, sentinel: int):
    """Slice [at:hi] of a [W, eb] stack and pad the window axis to a
    power-of-two bucket (≤ max_w) with all-invalid rows, so ragged
    final chunks reuse O(log max_w) compiled programs. Returns
    (s, d, valid, n) with n = the real window count."""
    n = hi - at
    wb = min(bucket_size(n), max_w)
    if n == wb:  # full chunk (the steady state): zero-copy views
        return s[at:hi], d[at:hi], valid[at:hi], n
    sc = np.full((wb, eb), sentinel, np.int32)
    dc = np.full((wb, eb), sentinel, np.int32)
    vc = np.zeros((wb, eb), bool)
    sc[:n], dc[:n], vc[:n] = s[at:hi], d[at:hi], valid[at:hi]
    return sc, dc, vc, n


# ----------------------------------------------------------------------
# vertex interning (dense ids for device kernels)
# ----------------------------------------------------------------------

def intern(*id_arrays: np.ndarray):
    """Map arbitrary numeric vertex ids in the given arrays to dense
    0..V-1 ints (SURVEY.md §7 'vertex-id interning'). Returns
    (unique_ids, [dense_arrays...])."""
    stacked = np.concatenate([np.asarray(a) for a in id_arrays])
    uniq, inv = np.unique(stacked, return_inverse=True)
    out = []
    off = 0
    for a in id_arrays:
        n = np.asarray(a).shape[0]
        out.append(inv[off:off + n].astype(np.int32))
        off += n
    return uniq, out
