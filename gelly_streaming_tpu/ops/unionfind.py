"""Array union-find: min-label propagation with pointer jumping.

The device-side replacement for the reference's pointer-chasing
`DisjointSet` hot loop (example/util/DisjointSet.java:71-123, the
per-edge `find`/`union` in UpdateCC, library/ConnectedComponents.java:87-90)
and for `Candidates`' O(C²·V) merge (example/util/Candidates.java:76-138):

- `cc_labels`: per-window weakly-connected-component labels for a COO
  edge batch as one XLA program — scatter-min both directions plus
  `labels = labels[labels]` compression inside a `lax.while_loop`,
  converging in O(log diameter) rounds.
- `cc_fixpoint(carried=True)`: fold a batch into a carried forest of
  any shape — the forest's parent links ride along as edges in every
  round, so a round sweeps every slot.
- `cc_fold_rooted`: the fold of the driver's snapshot scan, on one
  chip and on a mesh (which gathers each chunk's edges and runs the
  same body on every chip, core/driver.snapshot_fold_body). Its
  carry is always flat and min-rooted (a converged fixpoint's
  labeling), so each edge can be contracted to its endpoints' roots
  once; the loop is then a fresh labeling of the contracted graph,
  sized by the window's edges, and one gather after it relabels the
  table. Same labels, bit for bit. The sharded summary scan and the
  per-window sharded kernels (a pmin over shards each round), the
  cohort scan, the Pallas window kernel and the per-window host
  wrappers keep `cc_fixpoint(carried=True)`.
- `bipartite_labels`: 2-coloring via the bipartite double cover — the
  graph is bipartite iff (v,+) and (v,−) never share a component —
  which reduces bipartiteness to the same cc kernel (idiomatic
  vectorizable replacement per SURVEY.md §7 step 4).

Padded edge slots must point at the sentinel vertex `num_vertices`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import segment as seg_ops


def cc_round(labels: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    """One local min-label sweep: scatter-min each edge's smaller label
    to both endpoints AND to both endpoints' current roots
    (Shiloach-Vishkin hooking). The root hook matters for carried
    state: when a new edge merges two already-flat forests through
    non-root members, only the root relink lets the losing component's
    untouched members reach the smaller label via pointer jumping.
    Shared by the single-chip loop, the sharded loop (which adds a pmin
    exchange per round), and the fused entry step."""
    return _hook(labels, src, dst, labels[src], labels[dst])


def _hook(labels, src, dst, ls, ld):
    """cc_round's scatter-mins, given the endpoints' labels."""
    m = jnp.minimum(ls, ld)
    return (labels.at[src].min(m).at[dst].min(m)
            .at[ls].min(m).at[ld].min(m))


def cc_fixpoint(labels0: jax.Array, src: jax.Array, dst: jax.Array,
                exchange=None, carried: bool = True, rounds: bool = False):
    """Run cc_round + pointer jumping to the fixpoint inside a
    while_loop; `exchange` (e.g. a pmin over the mesh axis) merges
    labels across shards each round. The loop carries an int32 count
    of its rounds, the sweeps it took (1 when the edges change
    nothing); with `rounds` it returns (labels, rounds).

    With `carried` (labels0 is a prior forest, not a fresh arange), the
    forest's parent links (v, labels0[v]) participate as edges in every
    round. Without them, carried state can SPLIT a component: if an old
    root simultaneously merges into two different trees in one round
    (e.g. batch edges (child_of_r, x) with label m1 and (r, y) with
    label m2 < m1), the scatter-min keeps only the m2 link — the
    m1-side island stays separate forever, because the evidence
    connecting it ran through prior batches' edges that are not
    replayed. The forest edges re-expose exactly that connectivity.
    Fresh-labeling callers pass carried=False to skip the dead
    self-loop edges (the flag is trace-time static)."""
    if carried:
        fsrc = jnp.arange(labels0.shape[0], dtype=jnp.int32)
        fdst = labels0.astype(jnp.int32)
        src = jnp.concatenate([src.astype(jnp.int32), fsrc])
        dst = jnp.concatenate([dst.astype(jnp.int32), fdst])

    def cond(state):
        _, changed, _ = state
        return changed

    def body(state):
        labels, _, n = state
        new = cc_round(labels, src, dst)
        if exchange is not None:
            new = exchange(new)
        # pointer jumping: jump each label to its label's label
        new = new[new]
        return new, jnp.any(new != labels), n + 1

    labels, _, n = jax.lax.while_loop(
        cond, body, (labels0, jnp.array(True), jnp.int32(0)))
    return (labels, n) if rounds else labels


def cc_fold_rooted(labels0: jax.Array, src: jax.Array, dst: jax.Array):
    """Fold a window of edges into a FLAT, MIN-ROOTED carried forest
    (`labels0[labels0] == labels0`, every slot labelled by its
    component's least slot — what a converged fixpoint leaves) through
    its roots. Returns (labels, rounds), labels bit-identical to
    `cc_fixpoint(labels0, src, dst, carried=True, rounds=True)`'s.

    Each edge is contracted once to its endpoints' roots
    (labels0[src], labels0[dst]); a root starts at its own id, so the
    loop is a fresh labeling of the contracted graph, which needs no
    forest links (the island split cannot arise: no contracted edge
    touches a non-root). Hooking, pointer jumping and the change test
    run over the touched roots only, so every operation inside the
    loop is sized by the window's edges, not by the table; the one
    whole-table step is the flatten `lab[labels0]` after it, which
    hands each root's new label to its members. The least slot of a
    union of components is the least of their roots, so the result is
    again flat and min-rooted."""
    labels0 = labels0.astype(jnp.int32)
    rs = labels0[src]
    rd = labels0[dst]
    touched = jnp.concatenate([rs, rd])
    e = rs.shape[0]

    def cond(state):
        return state[2]

    def body(state):
        # `cur` is lab[touched], carried so that nothing reads the table
        # after the round's scatters begin (no copy of it per round)
        lab, cur, _, n = state
        new = _hook(lab, rs, rd, cur[:e], cur[e:])
        # pointer jumping over the touched roots (duplicates write the
        # same value); hooked labels are themselves touched roots
        nxt = new[new[touched]]
        new = new.at[touched].set(nxt)
        return new, nxt, jnp.any(nxt != cur), n + 1

    lab, _, _, n = jax.lax.while_loop(
        cond, body, (labels0, touched, jnp.array(True), jnp.int32(0)))
    return lab[labels0], n


@functools.partial(jax.jit, static_argnames=("num_vertices",))
def cc_labels(src: jax.Array, dst: jax.Array, num_vertices: int) -> jax.Array:
    """Labels[v] = smallest vertex index in v's component.

    src/dst: int32 [E] with padding slots set to `num_vertices`.
    Returns int32 [num_vertices + 1] (last row is the padding sentinel).
    """
    labels0 = jnp.arange(num_vertices + 1, dtype=jnp.int32)
    return cc_fixpoint(labels0, src, dst, carried=False)


def connected_components(src: np.ndarray, dst: np.ndarray,
                         num_vertices: int) -> np.ndarray:
    """Host wrapper: pads to buckets and returns labels[:num_vertices]."""
    e = len(src)
    eb = seg_ops.bucket_size(e)
    vb = seg_ops.bucket_size(num_vertices)
    s = seg_ops.pad_to(np.asarray(src, np.int32), eb, fill=vb)
    d = seg_ops.pad_to(np.asarray(dst, np.int32), eb, fill=vb)
    labels = np.asarray(cc_labels(jnp.asarray(s), jnp.asarray(d), vb))
    # bucket-padding vertices are isolated; compress to true vertex range
    return labels[:num_vertices]


_cc_fixpoint_jit = jax.jit(cc_fixpoint)


def connected_components_with_labels(src: np.ndarray, dst: np.ndarray,
                                     labels: np.ndarray,
                                     num_vertices: int,
                                     vertex_bucket: int = 0,
                                     edge_bucket: int = 0) -> np.ndarray:
    """Carried-state variant: fold a batch of edges into an existing
    labeling (streaming-iteration semantics, strategy P5). `labels` is a
    dense int32 [num_vertices] forest pointing at equal-or-smaller
    slots; returns the converged labels of the same length.

    BOTH dimensions are bucketed — edges to the edge bucket, the label
    vector to the vertex bucket (padding slots are isolated identity
    labels; slot vb is the edge-padding sentinel) — so a stream whose
    vertex count grows every window compiles O(log² ) programs, not one
    per distinct count (a steady-state-recompile bug caught by
    tools/scale_run.py's jax_log_compiles assert in round 2). Callers
    that already hold a grown bucket (the streaming driver) pass it as
    `vertex_bucket` so every window reuses ONE program; passing
    `edge_bucket` likewise clamps smaller batches UP to the steady
    program (a stream's final partial window must not compile a fresh
    tiny-bucket ladder at the tail — caught by tools/endurance_run.py's
    steady-state compile assert)."""
    e = len(src)
    eb = seg_ops.bucket_size(max(e, edge_bucket))
    vb = seg_ops.bucket_size(max(num_vertices, vertex_bucket))
    s = seg_ops.pad_to(np.asarray(src, np.int32), eb, fill=vb)
    d = seg_ops.pad_to(np.asarray(dst, np.int32), eb, fill=vb)
    lab = np.concatenate([np.asarray(labels, np.int32),
                          np.arange(num_vertices, vb + 1, dtype=np.int32)])
    out = np.asarray(_cc_fixpoint_jit(jnp.asarray(lab), jnp.asarray(s),
                                      jnp.asarray(d)))
    return out[:num_vertices]


def double_cover_edges(src: np.ndarray, dst: np.ndarray,
                       num_vertices: int):
    """Build the bipartite double cover's edge list: (u,+)=u, (u,-)=u+v;
    edge u~w joins (u,+)-(w,-) and (u,-)-(w,+). Shared by the host
    and sharded bipartiteness paths."""
    src = np.asarray(src, np.int64)  # gslint: disable=host-sync (host-input normalization: cover construction is numpy-on-numpy)
    dst = np.asarray(dst, np.int64)  # gslint: disable=host-sync (host-input normalization: cover construction is numpy-on-numpy)
    v = num_vertices
    return np.concatenate([src, src + v]), np.concatenate([dst + v, dst])


def decode_double_cover(lab2: np.ndarray, num_vertices: int):
    """(labels, signs, odd) from converged cover labels [>= 2·v].

    For a bipartite component with min vertex m: the (+) cover of m's
    side and the (−) cover of the other side form one cover component
    whose min index is m itself; the other cover component's min index
    is the other side's min vertex m2 > m. Hence both cover labels are
    < v, their min is the component's min vertex, and a vertex is on
    the root's side iff its (+) cover carries the smaller label. An odd
    cycle collapses both covers into one component (plus == minus)."""
    v = num_vertices
    plus, minus = lab2[:v], lab2[v:2 * v]
    return np.minimum(plus, minus), plus <= minus, plus == minus


def bipartite_labels(src: np.ndarray, dst: np.ndarray, num_vertices: int):
    """2-coloring via the double cover.

    Returns (labels[num_vertices], signs[num_vertices], odd[num_vertices]):
    `labels` are component labels of the underlying graph, `signs` the
    side of the bipartition relative to the component's minimum vertex,
    and `odd[v]` True iff v's component contains an odd cycle.
    """
    s2, d2 = double_cover_edges(src, dst, num_vertices)
    lab2 = connected_components(s2, d2, 2 * num_vertices)
    return decode_double_cover(lab2, num_vertices)
