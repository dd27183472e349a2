"""Sharded window kernels: the multi-chip execution of the hot paths.

Implements the parallelism strategies of SURVEY.md §2.4 as `shard_map`
programs over a 1-D mesh:

- P1 (vertex-keyed data parallelism): a window's COO batch is sharded
  across chips along the edge dimension; per-vertex grouping happens in
  dense vertex space so no cross-chip regrouping is needed.
- P2 (partition-local fold + merge): per-shard partial aggregates are
  merged with collectives — `psum` for monoid summaries (degrees,
  counts, triangle partials), elementwise `pmin` label exchange for
  union-find — replacing the reference's parallelism-1 merger funnel
  (WindowGraphAggregation.java:58) with an ICI tree-reduce.
- P3 (broadcast replication): vertex state (labels, degree vectors,
  adjacency rows) is replicated; edges never move.

Every kernel is a single XLA program per window: the collective merge
is fused into the same computation as the local fold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from .mesh import (SHARD_AXIS, make_mesh, mesh_padded_len,
                   pad_edges_for_mesh, shard_count, shard_map_norep)
from ..ops import ingress_pipeline, scan_analytics
from ..ops import segment as seg_ops
from ..ops import triangles, unionfind
from ..utils import costmodel, faults, metrics, resilience, telemetry


# ----------------------------------------------------------------------
# mesh fault hooks + stage guards (utils/faults, utils/resilience)
#
# The single-chip stages earned their watchdogs and fault sites in the
# resilience round; these are the mesh-scoped twins: every sharded
# shard_map dispatch, replicated-output gather, and h2d wire passes a
# hook a fault plan can poison (dead shard / ICI stall / corrupt
# wire), and dispatches run under resilience.call_guarded when the
# stage knobs are armed. With no plan and inert knobs every helper is
# one dict lookup (or a plain call) — the hot path is unchanged.
# ----------------------------------------------------------------------

def fire_shard_dispatch(n_shards: int) -> None:
    """Fault hook before a sharded shard_map dispatch. One firing per
    SPMD dispatch (not per shard): a dead chip fails the whole
    program, so the fault plan models shard death via FaultSpec.shard
    metadata, not per-shard call counting."""
    faults.fire("shard_dispatch", n_shards)


def fire_shard_gather(n_shards: int) -> None:
    """Fault hook before the d2h gather of replicated sharded outputs
    (window stacks, engine state slabs)."""
    faults.fire("shard_gather", n_shards)


def guard_wire(arrays, n_shards: int, limit: int):
    """The mesh h2d wire hook: run the arrays through the fault plan
    (corrupt_shard poisons one shard's slice) and, when
    GS_MESH_WIRE_CHECK=1, validate every shard slice's vertex ids
    against `limit` (the bucket sentinel — the largest id any honest
    stack can carry). Returns the (possibly fault-transformed) arrays;
    raises RuntimeError naming the offending shard on a corrupt wire,
    which the stage guards surface as a typed StageFailed feeding the
    demotion ladder."""
    payload = faults.fire("shard_wire", (tuple(arrays), n_shards))
    if isinstance(payload, tuple) and len(payload) == 2:
        arrays = payload[0]
    if resilience.mesh_wire_check_enabled():
        _check_wire(arrays, n_shards, limit)
    return arrays


def _check_wire(arrays, n_shards: int, limit: int) -> None:
    """Range-check each shard's slice of the mesh-bound stacks: any
    integer id above `limit` is a corrupt wire (ids are interned dense
    slots ≤ bucket sentinel by construction)."""
    for a in arrays:
        a = np.asarray(a)
        if not np.issubdtype(a.dtype, np.integer) or not a.size:
            continue
        width = a.shape[-1] // n_shards
        if not width:
            continue
        for k in range(n_shards):
            sl = a[..., k * width:(k + 1) * width]
            if sl.size and int(sl.max()) > limit:
                raise RuntimeError(
                    "corrupt shard wire: shard %d of %d carries vertex"
                    " id %d > bucket sentinel %d (GS_MESH_WIRE_CHECK)"
                    % (k, n_shards, int(sl.max()), limit))


def _guarded_dispatch(chunk, fn, retries=None):
    """Run one PURE sharded dispatch under the stage watchdog/retry
    policy when the knobs are armed (resilience.call_guarded —
    retryable because every guarded sharded dispatch is
    carry-in/carry-out pure and rebinds state only on success); the
    bare call otherwise — exact legacy behavior and exception types
    with inert knobs."""
    if resilience.guard_active():
        return resilience.call_guarded("dispatch", chunk, fn,
                                       retries=retries)
    return fn()


# ----------------------------------------------------------------------
# sharded continuous degrees (P1 + P2: segment-sum + psum)
# ----------------------------------------------------------------------

def make_sharded_degree_fn(mesh, num_vertices_bucket: int):
    """Returns jitted fn(src, dst, counts) -> counts' where src/dst are
    edge-sharded and counts is the replicated running [V+1] degree
    vector (continuous-degree semantics of SimpleEdgeStream.java:465-482,
    batched)."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=P(),
    )
    def step(src, dst, counts):
        ones = jnp.ones_like(src, jnp.int32)
        # vb+2 rows: [0, vb) real vertices, vb+1 the padding sentinel
        local = jax.ops.segment_sum(ones, src, num_vertices_bucket + 2)
        local = local + jax.ops.segment_sum(ones, dst, num_vertices_bucket + 2)
        return counts + jax.lax.psum(local, SHARD_AXIS)

    return jax.jit(step)


# ----------------------------------------------------------------------
# sharded connected components (P1 + P2: scatter-min + pmin exchange)
# ----------------------------------------------------------------------

def make_sharded_cc_fn(mesh, num_vertices_bucket: int):
    """Returns jitted fn(src, dst, labels) -> labels' running min-label
    propagation to the fixpoint: each chip folds its edge shard into the
    replicated label vector, shards exchange labels with an elementwise
    `pmin` every round (the collective merge tree), then pointer-jump.
    `labels` holds [V+1] int32 (slot V = padding sentinel); pass
    arange for a fresh window or carry the previous state for the
    streaming-iteration semantics of IterativeConnectedComponents."""

    # shard_map_norep: cc_fixpoint's while_loop has no replication rule
    # in the checker; the pmin exchange makes the output replicated
    @shard_map_norep(
        mesh, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=P(),
    )
    def step(src, dst, labels):
        assert labels.shape[0] == num_vertices_bucket + 2, labels.shape
        return unionfind.cc_fixpoint(
            labels, src, dst,
            exchange=lambda lab: jax.lax.pmin(lab, SHARD_AXIS),  # ICI merge
        )

    return jax.jit(step)


# ----------------------------------------------------------------------
# sharded triangle count (P1 edges + P3 replicated adjacency + psum)
# ----------------------------------------------------------------------

def make_sharded_triangle_fn(mesh):
    """Returns jitted fn(nbr, ea, eb, emask) -> count with the oriented
    edge list sharded across chips and the sorted-adjacency matrix
    replicated; per-shard intersection partials reduce with one psum."""

    # compare on chip, binary search on CPU meshes
    intersect = triangles.resolve_xla_intersect()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(),
    )
    def step(nbr, ea, eb, emask):
        local = intersect(nbr, ea, eb, emask)
        return jax.lax.psum(local, SHARD_AXIS)

    return jax.jit(step)


# ----------------------------------------------------------------------
# sharded sliding-window pane reduce (P1 + P2 over (pane, vertex) cells)
# ----------------------------------------------------------------------

def make_sharded_pane_reduce(mesh, vertex_bucket: int, pane_bucket: int,
                             panes_per_window: int, name: str = None,
                             fn=None):
    """Sliding-window reduce at multi-chip scale — the sharded form of
    the single-chip pane path (ops/neighborhood.py _make_pane_reduce;
    see docs/DESIGN.md §1.1): edges sharded across chips (P1), each
    shard reduces its slice over flattened (pane, vertex) cell ids
    into a full [pane_bucket, V+1] partial, the partials merge across
    the mesh (P2), and every window is a static stack of
    panes_per_window shifted pane slices combined elementwise — all
    windows from one program, no edge duplication.

    Two tiers, mirroring the single-chip pane path:
    - `name` ('sum'|'min'|'max'): segment kernels per shard, ONE
      psum/pmin/pmax collective merge, identity-padded window combine.
    - `fn` + nothing else (a user fn DECLARED associative): flagged
      associative scan per shard over cell-sorted values, an
      all_gather of the [pb, V+1] cell partials + a left-fold over the
      shard axis with a presence mask (no identity element exists for
      a general fn, and no psum-style collective applies a custom
      combine), then the masked window combine. Shard index order =
      edge-position order (the edge axis splits contiguously), so the
      cross-shard fold preserves arrival order up to the reordering
      associativity licenses.

    Returns jitted fn(src, pane, val, valid) -> (win_vals, win_counts),
    both [pane_bucket + panes_per_window - 1, vertex_bucket + 1]; a
    (window, vertex) cell is meaningful iff win_counts[w, v] > 0.
    win_counts are real edge counts in BOTH tiers — counts have an
    identity (0) even when values don't, so the fn tier psums a
    segment_sum of valid edges alongside its value fold (ADVICE r3:
    switching name='min' to fn=jnp.minimum must not silently change
    count semantics). Window w covers dense panes
    [w - panes_per_window + 1, w];
    src/pane/val/valid are edge-sharded arrays (pad with valid=False).
    """
    assert (name is None) != (fn is None)
    assert name in (None, "sum", "min", "max"), name
    vbp = vertex_bucket + 1
    pb = pane_bucket
    wp = panes_per_window
    n_cells = pb * vbp
    n = shard_count(mesh)

    if name is not None:
        coll = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                "max": jax.lax.pmax}[name]

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                      P(SHARD_AXIS)),
            out_specs=(P(), P()),
        )
        def partials(src, pane, val, valid):
            ids = jnp.where(valid, pane * vbp + src, n_cells)
            # segment_min/max fill empty cells with dtype extremes
            # (+/-inf for floats — NOT _pane_identity); per-shard fills
            # absorb in pmin/pmax, and window_stack_combine
            # re-normalizes globally empty (count==0) cells to the
            # documented identity
            cells = seg_ops.segment_reduce(val, ids, n_cells + 1,
                                           name)[:-1].reshape(pb, vbp)
            counts = jax.ops.segment_sum(
                jnp.where(valid, 1, 0), ids,
                n_cells + 1)[:-1].reshape(pb, vbp)
            return coll(cells, SHARD_AXIS), jax.lax.psum(counts,
                                                         SHARD_AXIS)

        def run(src, pane, val, valid):
            from ..ops.neighborhood import window_stack_combine

            cells, counts = partials(src, pane, val, valid)
            return window_stack_combine(cells, counts, wp, name)

        return jax.jit(run)

    # shard_map_norep: a user fn declared associative may trace a
    # while_loop (e.g. np.gcd lowers to one), which the replication
    # checker has no rule for; outputs are made replicated explicitly
    # (psum counts, the no-op pmax on accv below)
    @shard_map_norep(
        mesh, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                        P(SHARD_AXIS)),
        out_specs=(P(), P()),
    )
    def assoc_partials(src, pane, val, valid):
        ids = jnp.where(valid, pane * vbp + src, n_cells)
        # real per-cell edge counts (identity 0 exists for counts even
        # when fn has none): ONE extra psum next to the all_gather
        # below, and the fn tier's win_counts match the monoid tier's
        # (ADVICE r3)
        counts = jax.lax.psum(
            jax.ops.segment_sum(jnp.where(valid, 1, 0), ids,
                                n_cells + 1)[:-1].reshape(pb, vbp),
            SHARD_AXIS)
        order = jnp.argsort(ids, stable=True)
        ids_s = ids[order]
        vals_s = val[order]
        flags = jnp.concatenate(
            [jnp.ones(1, bool), ids_s[1:] != ids_s[:-1]])

        def comb(x, y):
            fx, vx = x
            fy, vy = y
            # flagged associative scan: a cell-start flag resets the
            # running combine (same kernel shape as
            # seg_ops._jit_assoc_reduce, inlined here because this
            # body must trace inside shard_map)
            return fx | fy, jnp.where(fy, vy, fn(vx, vy))

        _, scanned = jax.lax.associative_scan(comb, (flags, vals_s))
        idx = jnp.arange(ids_s.shape[0])
        last = jax.ops.segment_max(
            jnp.where(ids_s < n_cells, idx, -1), ids_s,
            n_cells + 1)[:-1]
        present = (last >= 0).reshape(pb, vbp)
        cells = scanned[jnp.maximum(last, 0)].reshape(pb, vbp)

        # cross-shard merge: gather every shard's partials and fold in
        # shard order with a presence mask — no collective applies a
        # custom fn, and a general fn has no identity to pad with
        from ..ops.neighborhood import masked_combine

        allc = jax.lax.all_gather(cells, SHARD_AXIS)     # [n, pb, vbp]
        allp = jax.lax.all_gather(present, SHARD_AXIS)
        # balanced tree over the shard axis (O(log n) depth — exactly
        # what associativity licenses); adjacent pairs combine left-to-
        # right, so shard order (= edge-position order) is preserved
        vals = [allc[i] for i in range(n)]
        pres = [allp[i] for i in range(n)]
        while len(vals) > 1:
            nxt_v, nxt_p = [], []
            for i in range(0, len(vals) - 1, 2):
                v, p2 = masked_combine(fn, vals[i], pres[i],
                                       vals[i + 1], pres[i + 1])
                nxt_v.append(v)
                nxt_p.append(p2)
            if len(vals) % 2:
                nxt_v.append(vals[-1])
                nxt_p.append(pres[-1])
            vals, pres = nxt_v, nxt_p
        accv = vals[0]
        # every shard folded the same gathered partials, so accv is
        # value-identical everywhere; the no-op pmax makes that
        # replication explicit for shard_map's vma check (the [pb, vbp]
        # payload is tiny next to the all_gather above). counts is
        # already replicated by its psum.
        accv = jax.lax.pmax(accv, SHARD_AXIS)
        return accv, counts

    def run(src, pane, val, valid):
        from ..ops.neighborhood import _jit_assoc_combine

        cells, counts = assoc_partials(src, pane, val, valid)
        return _jit_assoc_combine(fn, wp)(cells, counts)

    return jax.jit(run)


# ----------------------------------------------------------------------
# full sharded window triangle pipeline (P1 + P6: all_to_all + pmax + psum)
# ----------------------------------------------------------------------

def resolve_table_mode(n: int, vb: int, kb: int, cap: int) -> str:
    """Neighbor-row distribution mode for the sharded window counter
    at its shapes: the mode whose per-window collectives move fewer
    bytes (window_collective_bytes), "replicated" on a tie (one
    shard moves nothing either way). The replicated pmax table grows
    with vb·kb whatever the window; the owner gather with the owned
    edges, so it wins once the table outgrows the window's rows."""
    repl = window_collective_bytes(n, vb, kb, cap, "replicated")["total"]
    owner = window_collective_bytes(n, vb, kb, cap, "owner")["total"]
    return "owner" if owner < repl else "replicated"


def window_collective_bytes(n: int, vb: int, kb: int, cap: int,
                            table: str = "replicated") -> dict:
    """Analytic per-chip ICI traffic (bytes) per window for every
    collective in build_sharded_window_counter — static shapes make
    this exact, not sampled (VERDICT r2 weak-4: the 'cheap on real ICI'
    claim must be accounted, not argued). The edge bucket enters only
    through `cap` (the per-(shard→shard) exchange capacity the kernel
    derives from it). Models: ring all-reduce (psum/pmax) moves
    2·(n-1)/n × payload per chip; all_gather and all_to_all move
    (n-1)/n × the full gathered/exchanged buffer."""
    assert table in ("replicated", "owner"), table
    i32 = 4
    f = (n - 1) / n if n > 1 else 0.0
    m = n * cap   # owned-edge slots per shard after the exchange
    out = {
        "psum_degrees": 2 * f * (vb + 1) * i32,
        "all_to_all_pairs": 2 * f * m * i32,          # send_a + send_b
        "psum_count_and_overflow": 2 * f * 3 * i32,
    }
    if table == "replicated":
        out["pmax_table"] = 2 * f * (vb + 1) * kb * i32
    else:
        out["all_gather_row_ids"] = f * n * 2 * m * i32
        out["all_to_all_row_slices"] = f * 2 * m * kb * i32
    out["total"] = sum(out.values())
    return out


# Public one-way ICI bandwidth figures (GB/s per chip) for the time
# model — the v5e value follows the public scaling-book figure of
# ~4.5e10 B/s per link with the single-link worst case taken (ring
# collectives bottleneck on one link direction). Overridable per call:
# the model is for DESIGN comparisons, not a measurement substitute.
ICI_GBPS = {"v5e": 45.0}


def ici_time_model(bytes_dict: dict, gbps: float = ICI_GBPS["v5e"]) -> dict:
    """Seconds per window each collective spends on the ICI at the
    modeled bandwidth (latency terms ignored — payloads here are KBs to
    MBs, far above the latency-bound regime)."""
    return {k: v / (gbps * 1e9) for k, v in bytes_dict.items()}


def make_sharded_window_triangle_fn(mesh, eb: int, vb: int, kb: int,
                                    cap: int, table: str = "replicated"):
    """The COMPLETE window triangle pipeline as one shard_map program
    over raw sharded COO — the multi-chip form of
    TriangleWindowKernel._build (ops/triangles.py), replacing the
    reference's three keyBy shuffles (WindowTriangles.java:61-66) with
    three ICI collectives:

    1. psum      — global degree vector for the (degree, id) orientation
    2. all_to_all — hash-partition each oriented edge (a,b) to its owner
       shard (the "keyBy(pair)" exchange): global dedup becomes a local
       sort on the owner, and every surviving edge is counted exactly
       once, on exactly one shard
    3. pmax      — merge the per-shard CSR column slices into the
       replicated neighbor table (each shard writes its own kb/n-wide
       slice, so slices never collide and elementwise max merges them)
    then a final psum of the per-shard intersection partials.

    Per-(shard→shard) bucket capacity is `cap`; a hub row overflowing
    its kb/n column slice or a bucket overflowing `cap` raises the
    overflow count, and the host escalates — exactness is never
    sacrificed.
    """
    n = shard_count(mesh)
    step = build_sharded_window_counter(n, eb, vb, kb, cap, table=table)
    return jax.jit(functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(), P(), P()),
    )(step))


def build_sharded_window_counter(n: int, eb: int, vb: int, kb: int,
                                 cap: int, axis: str = SHARD_AXIS,
                                 table: str = "replicated"):
    """Pure per-shard one-window body (unwrapped): callable inside any
    shard_map over `axis` — directly (make_sharded_window_triangle_fn)
    or within a lax.scan over window stacks (ShardedSummaryEngine).

    `table` picks how each owned edge reaches its endpoints' full
    neighbor rows (window_collective_bytes accounts both):

    - "replicated": every shard scatters its kb/n column slice of the
      [V+1, kb] table and ONE pmax all-reduce replicates it — O(V·kb)
      ICI bytes and O(V·kb) HBM per chip per window, independent of
      the edge count.
    - "owner": no replication — each shard keeps only its [V+1, kb/n]
      column slice, all shards all_gather the row ids their owned
      edges touch (aligned to owned-edge slots, so no index remap),
      take their local slices of every requested row, and one
      all_to_all returns each shard the full rows of exactly its own
      edges — O(owned_edges·kb) ICI bytes, which beats the replicated
      table whenever owned edges per shard ≪ V (the sparse-window
      regime the 10M-scale buckets live in: vb/(n·cap) ≈ 16× less
      traffic at vb=262144, eb=65536, n=8)."""
    assert table in ("replicated", "owner"), table
    assert eb % n == 0 and kb % n == 0, (eb, kb, n)
    sent = vb
    kslice = kb // n
    # Pinned to the broadcast compare, not resolve_xla_intersect():
    # the nbr table below is assembled as per-shard kslice column runs
    # merged by pmax, so each row is a CONCATENATION of sorted runs,
    # not globally sorted — the binary search's searchsorted contract
    # doesn't hold (the equality compare doesn't care about order).
    intersect = triangles.intersect_local

    def step(src, dst, valid):
        me = jax.lax.axis_index(axis)
        el = src.shape[0]  # = eb // n

        # ---- clean: drop self-loops and padding
        valid = valid & (src != dst)
        s = jnp.where(valid, src, sent)
        d = jnp.where(valid, dst, sent)

        # ---- global degrees for orientation (collective #1: psum)
        ones = jnp.where(valid, 1, 0)
        local_deg = (jax.ops.segment_sum(ones, s, vb + 1)
                     + jax.ops.segment_sum(ones, d, vb + 1))
        deg = jax.lax.psum(local_deg, axis)

        # ---- orient low(deg, id) -> high(deg, id)
        a, b = triangles.orient_by_degree(s, d, deg, sent)
        a, b = a.astype(jnp.int32), b.astype(jnp.int32)

        # ---- owner shard by multiplicative pair hash: duplicates of an
        # edge land on one shard regardless of origin, so dedup is local
        h = (a.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
             + b.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
        owner = ((h >> 8) % jnp.uint32(n)).astype(jnp.int32)

        # ---- bucket by owner: sort (owner, a, b), position within run
        owner = jnp.where(a < sent, owner, n)  # padding sorts last
        owner, a, b = jax.lax.sort((owner, a, b), num_keys=3)
        idx = jnp.arange(el)
        run_first = jax.ops.segment_min(
            jnp.where(owner < n, idx, el), owner, n + 1)
        pos = idx - run_first[owner]
        ok = (a < sent) & (pos < cap)
        bucket_overflow = jnp.sum((pos >= cap) & (a < sent))
        slot = jnp.where(ok, owner * cap + jnp.clip(pos, 0, cap - 1),
                         n * cap)  # trash slot for overflow/padding
        send_a = jnp.full(n * cap + 1, sent, jnp.int32).at[slot].set(a)
        send_b = jnp.full(n * cap + 1, sent, jnp.int32).at[slot].set(b)

        # ---- collective #2: all_to_all pair exchange over ICI
        recv_a = jax.lax.all_to_all(
            send_a[:n * cap].reshape(n, cap), axis,
            split_axis=0, concat_axis=0, tiled=True).reshape(n * cap)
        recv_b = jax.lax.all_to_all(
            send_b[:n * cap].reshape(n, cap), axis,
            split_axis=0, concat_axis=0, tiled=True).reshape(n * cap)

        # ---- local dedupe of owned edges (global dedup by ownership)
        # + CSR positions, fused into one sort (duplicates stay in
        # place behind rvalid)
        ra, rb, rvalid, pos2 = triangles.dedupe_and_positions(
            recv_a, recv_b, sent, vb)
        k_overflow = jnp.sum((pos2 >= kslice) & rvalid)
        ok2 = rvalid & (pos2 < kslice)
        rows = jnp.where(ok2, ra, vb)
        cols_local = jnp.clip(pos2, 0, kslice - 1)

        if table == "replicated":
            # ---- collective #3: pmax slice merge -> replicated table
            partial = jnp.full((vb + 1, kb), -1, jnp.int32)
            partial = partial.at[rows, me * kslice + cols_local].set(
                jnp.where(ok2, rb, -1))
            nbr = jax.lax.pmax(partial, axis)
            nbr = jnp.where(nbr < 0, sent, nbr)
            local = intersect(nbr, ra, rb, rvalid)
        else:
            # ---- collective #3 (owner-local): gather only the rows
            # this shard's owned edges touch. Requests are ALIGNED to
            # owned-edge slots (row ids = concat(ra, rb)), so the
            # returned rows index directly per edge — no remap, no
            # dedup (a hub row travels once per touching edge; still
            # O(owned·kb) ≪ O(V·kb) in the sparse-window regime).
            local_tab = jnp.full((vb + 1, kslice), -1, jnp.int32)
            local_tab = local_tab.at[rows, cols_local].set(
                jnp.where(ok2, rb, -1))
            m = ra.shape[0]
            req = jnp.concatenate([ra, rb])              # [2m]
            all_req = jax.lax.all_gather(req, axis)      # [n, 2m]
            send = local_tab[all_req]                    # [n, 2m, kb/n]
            recv = jax.lax.all_to_all(
                send, axis, split_axis=0, concat_axis=0, tiled=True)
            # [2m, n, kb/n] -> [2m, kb]: columns shard-major, the same
            # layout the replicated table's rows carry
            rows_full = jnp.transpose(recv, (1, 0, 2)).reshape(2 * m, kb)
            rows_full = jnp.where(rows_full < 0, sent, rows_full)
            local = triangles.intersect_rows(
                rows_full[:m], rows_full[m:], rvalid, sent)
        count = jax.lax.psum(local, axis)
        # separate signals so the host widens only the dimension that
        # overflowed (cap vs K): each (kb, cap) pair is a fresh compile
        bucket_overflow = jax.lax.psum(bucket_overflow, axis)
        k_overflow = jax.lax.psum(k_overflow, axis)
        return count, bucket_overflow, k_overflow

    return step


class ShardedTriangleWindowKernel:
    """Multi-chip TriangleWindowKernel: same exact counts, edges sharded
    across the mesh (P1), merges over ICI (P6). Escalates the neighbor
    table width and exchange capacity on overflow, ending at the exact
    host path — mirrors TriangleWindowKernel's ladder."""

    def __init__(self, mesh, edge_bucket: int, vertex_bucket: int,
                 k_bucket: int = 0, cap_factor: int = 2,
                 table: str = None):
        self.mesh = mesh
        self.n = n = shard_count(mesh)

        def _mult_of_n(x: int) -> int:  # shard_map splits the leading
            return -(-x // n) * n       # dim; K splits into n slices

        self.eb = _mult_of_n(seg_ops.bucket_size(edge_bucket))
        self.vb = seg_ops.bucket_size(vertex_bucket)
        # same measured starting K as the single-chip kernel (rounded
        # to a shard multiple); escalation still guards exactness
        kb0 = k_bucket if k_bucket else triangles._tuned_kb(self.eb)
        self.kb = _mult_of_n(seg_ops.bucket_size(kb0))
        self.kb_max = max(
            _mult_of_n(seg_ops.bucket_size(2 * int(np.sqrt(self.eb)))),
            self.kb)
        self.cap = min(max(8, cap_factor * (self.eb // n) // n),
                       self.eb // n)
        self.table = table if table else resolve_table_mode(
            n, self.vb, self.kb, self.cap)
        # per-stage counters of the shared ingress pipeline (same
        # contract as TriangleWindowKernel.stage_timers)
        self.stage_timers = ingress_pipeline.StageTimers()
        # counts finalized before the last escaping _run_stack error
        # (None = clean): the demoting caller's re-entry cursor
        self.drained_counts = None
        self._fns = {}

    def _fn(self, kb, cap):
        key = (kb, cap)
        if key not in self._fns:
            self._fns[key] = make_sharded_window_triangle_fn(
                self.mesh, self.eb, self.vb, kb, cap, table=self.table)
        return self._fns[key]

    def _next_kb(self, kb: int) -> int:
        return min(-(-(kb * 4) // self.n) * self.n, self.kb_max)

    def _next_cap(self, cap: int) -> int:
        return min(cap * 2, self.eb // self.n)

    def count(self, src: np.ndarray, dst: np.ndarray,
              failed_kb: int = 0, failed_cap: int = 0) -> int:
        """failed_kb/failed_cap mark rungs a batched count_stream
        dispatch already saw overflow, so the ladder starts past them
        (or goes straight to the exact host path when that dimension
        was already saturated)."""
        n = len(src)
        if n == 0:
            return 0
        if n > self.eb:
            raise ValueError(f"window of {n} edges exceeds edge bucket "
                             f"{self.eb}")
        if ((failed_kb and failed_kb >= self.kb_max)
                or (failed_cap and failed_cap >= self.eb // self.n)):
            return triangles.triangle_count_sparse(src, dst, self.vb)
        s = seg_ops.pad_to(np.asarray(src, np.int32), self.eb, fill=self.vb)
        d = seg_ops.pad_to(np.asarray(dst, np.int32), self.eb, fill=self.vb)
        valid = seg_ops.pad_to(np.ones(n, bool), self.eb, fill=False)
        s, d, valid = jnp.asarray(s), jnp.asarray(d), jnp.asarray(valid)
        kb = self._next_kb(failed_kb) if failed_kb else self.kb
        cap = self._next_cap(failed_cap) if failed_cap else self.cap
        while True:
            def _disp(kb=kb, cap=cap):
                # fire + MATERIALIZE inside the guard: a dead shard's
                # dispatch failure and a hung gather both surface as
                # the typed stage error the demotion ladder feeds on
                fire_shard_dispatch(self.n)
                got = self._fn(kb, cap)(s, d, valid)
                fire_shard_gather(self.n)
                return tuple(int(x) for x in got)

            count, bucket_ovf, k_ovf = _guarded_dispatch(
                ("sharded_window", kb, cap), _disp)
            if not bucket_ovf and not k_ovf:
                return int(count)
            kb_sat = kb >= self.kb_max
            cap_sat = cap >= self.eb // self.n
            if (kb_sat or not k_ovf) and (cap_sat or not bucket_ovf):
                break  # nothing left to widen: exact host path instead
            if k_ovf and not kb_sat:
                kb = self._next_kb(kb)
            if bucket_ovf and not cap_sat:
                cap = self._next_cap(cap)
        return triangles.triangle_count_sparse(src, dst, self.vb)

    MAX_STREAM_WINDOWS = 64

    def warm_chunks(self) -> None:
        """Compile every stream-chunk program _run_stack can dispatch
        at the current (K, cap) — same compile-only contract and
        shared body (seg_ops.warm_stream_buckets) as
        TriangleWindowKernel.warm_chunks."""
        seg_ops.warm_stream_buckets(self)

    def _stream_fn(self, kb, cap):
        key = ("stream", kb, cap)
        if key not in self._fns:
            window = self._fn(kb, cap)  # reuse the per-window compile cache

            @jax.jit
            def run_stream(src, dst, valid):  # [W, eb] each, edge-sharded
                return jax.lax.map(lambda t: window(*t), (src, dst, valid))

            self._fns[key] = run_stream
        return self._fns[key]

    def _chunk_sharding(self):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, P(None, SHARD_AXIS))

    def _stream_exec(self, wb: int):
        """AOT-compiled stream program for a [wb, eb] edge-sharded
        chunk at the current (K, cap) — the kernel's own executable
        cache, so warm_chunks is compile-only (same design as
        TriangleWindowKernel._stream_exec)."""
        key = ("exec", self.kb, self.cap, wb)
        ex = self._fns.get(key)
        if ex is None:
            sharding = self._chunk_sharding()
            sds_i = jax.ShapeDtypeStruct((wb, self.eb), jnp.int32,
                                         sharding=sharding)
            sds_b = jax.ShapeDtypeStruct((wb, self.eb), jnp.bool_,
                                         sharding=sharding)
            ex = self._stream_fn(self.kb, self.cap).lower(
                sds_i, sds_i, sds_b).compile()
            # cost observatory (utils/costmodel): register the
            # table-mode stream program's cost model (free off the AOT
            # executable) and tag armed dispatches program/sig
            ex = costmodel.wrap_exec(
                "sharded_table_stream", ex,
                metrics.abstract_sig((sds_i, sds_i, sds_b)))
            self._fns[key] = ex
        return ex

    def _run_stack(self, s, d, valid, get_window) -> list:
        """Dispatch a [W, eb] window stack in MAX_STREAM_WINDOWS chunks
        (edge axis sharded over the mesh) through the SAME three-stage
        ingress pipeline as the single-chip kernel
        (ops/ingress_pipeline.run_pipeline) — the sharded path keeps
        its own table contract and mesh sharding, only the chunk loop
        is shared: prep (pad_window_chunk) runs on the worker pool,
        h2d is the mesh-sharded device_put, and each chunk's d2h +
        overflow recount materializes one chunk behind its dispatch.
        `get_window(w)` returns the raw (src, dst) of window w for the
        rare exact overflow recount. Ragged final chunks pad the
        window axis to a power-of-two bucket so varying stream lengths
        reuse O(log) compiled programs.

        On ANY escaping error the pipeline drains the in-flight
        chunk's finalize first (ops/ingress_pipeline), and the counts
        finalized before the failure are stashed on
        `self.drained_counts` — the re-entry cursor a demoting caller
        (core/driver, the host twin hand-off) combines with, instead
        of recomputing delivered windows."""
        sharding = self._chunk_sharding()
        num_w = s.shape[0]
        counts: list = []
        self.drained_counts = None

        def prep(at):
            hi = min(at + self.MAX_STREAM_WINDOWS, num_w)
            sc, dc, vc, n = seg_ops.pad_window_chunk(
                s, d, valid, at, hi, self.MAX_STREAM_WINDOWS, self.eb,
                self.vb)
            return at, n, (sc, dc, vc)

        def h2d(payload):
            at, n, args = payload
            sc, dc = guard_wire(args[:2], self.n, self.vb)
            return at, n, tuple(jax.device_put(a, sharding)
                                for a in (sc, dc) + args[2:])

        def dispatch(dev_payload):
            at, n, dev = dev_payload
            fire_shard_dispatch(self.n)
            telemetry.event("sharded.round", engine="triangles",
                            window=at, windows=n, mesh=self.n)
            fn = self._stream_exec(dev[0].shape[0])
            return (at, n) + tuple(fn(*dev))

        def finalize(raw):
            at, n = raw[:2]
            fire_shard_gather(self.n)
            # np.array (not asarray): device outputs are read-only views
            c, b_ovf, k_ovf = (np.array(x)[:n] for x in raw[2:])  # gslint: disable=host-sync (sanctioned finalize boundary: the sharded chunk's ONE batched gather of replicated [W] scalars)
            for w in np.nonzero(b_ovf + k_ovf)[0]:  # rare: exact redo
                ws, wd = get_window(at + int(w))
                c[w] = self.count(
                    ws, wd,
                    failed_kb=self.kb if int(k_ovf[w]) else 0,  # gslint: disable=host-sync (k_ovf is a host numpy array — materialized by the finalize gather above, no device sync)
                    failed_cap=self.cap if int(b_ovf[w]) else 0)  # gslint: disable=host-sync (b_ovf is a host numpy array — materialized by the finalize gather above, no device sync)
            counts.extend(int(x) for x in c)

        try:
            ingress_pipeline.run_pipeline(
                range(0, num_w, self.MAX_STREAM_WINDOWS),
                prep, h2d, dispatch, finalize,
                timers=self.stage_timers)
        except Exception:
            self.drained_counts = list(counts)
            raise
        return counts

    def count_stream(self, src: np.ndarray, dst: np.ndarray) -> list:
        """Exact counts of every tumbling `edge_bucket`-sized window,
        batched into one sharded program per MAX_STREAM_WINDOWS windows
        (the multi-chip form of TriangleWindowKernel.count_stream): the
        COO chunk is laid out [W, eb] with the edge axis sharded over
        the mesh, a lax.map folds the windows, and overflowing windows
        are recounted individually down the escalation ladder."""
        src = np.asarray(src, np.int32)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/python COO, never device arrays)
        dst = np.asarray(dst, np.int32)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/python COO, never device arrays)
        if len(src) == 0:
            return []
        num_w, s, d, valid = seg_ops.window_stack(src, dst, self.eb,
                                                  sentinel=self.vb)
        eb = self.eb
        with telemetry.span("sharded.stream", tier="sharded",
                            engine="triangles", mesh=self.n,
                            windows=num_w, edges=len(src)):
            counts = self._run_stack(
                s, d, valid,
                lambda w: (src[w * eb:(w + 1) * eb],
                           dst[w * eb:(w + 1) * eb]))
        # health-plane mark at this top-level entry only: _run_stack
        # is shared with count_windows — the driver's flush path —
        # whose windows the driver already marks at its chunk boundary
        metrics.mark_window(len(counts), len(src),
                            engine="sharded_triangles",
                            tier="sharded", mesh_shape=[self.n])
        return counts

    def count_windows(self, windows) -> list:
        """Exact counts of a list of (src, dst) window batches of
        varying lengths (each ≤ edge_bucket) in chunked sharded
        dispatches — the multi-chip form of
        TriangleWindowKernel.count_windows (used by the driver's
        batched event-time windows)."""
        if not windows:
            return []
        s, d, valid = seg_ops.stack_window_list(windows, self.eb,
                                                self.vb)
        with telemetry.span("sharded.stream", tier="sharded",
                            engine="triangles", mesh=self.n,
                            windows=len(windows),
                            edges=sum(len(w[0]) for w in windows)):
            return self._run_stack(s, d, valid, lambda w: windows[w])


# ----------------------------------------------------------------------
# host-facing wrappers
# ----------------------------------------------------------------------

class ShardedWindowEngine:
    """Per-mesh compiled kernels for sharded window analytics.

    One engine per (mesh, vertex-bucket): the jitted programs are reused
    across windows, so steady-state streaming pays zero recompilation.
    """

    def __init__(self, mesh=None, num_vertices_bucket: int = 1 << 16):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n = shard_count(self.mesh)
        self.vb = num_vertices_bucket
        self.degree_fn = make_sharded_degree_fn(self.mesh, self.vb)
        self.cc_fn = make_sharded_cc_fn(self.mesh, self.vb)
        self.tri_fn = make_sharded_triangle_fn(self.mesh)
        self._degree_state = jnp.zeros(self.vb + 2, jnp.int32)
        self._labels = jnp.arange(self.vb + 2, dtype=jnp.int32)
        # bipartite double cover runs CC over 2·vb cover vertices
        # (ops/unionfind.bipartite_labels, sharded): built lazily
        self._bip_fn = None
        self._bip_labels = None
        # sliding pane-reduce programs, per (pane_bucket, wp, monoid)
        self._pane_fns = {}

    def reset(self) -> None:
        """Clear carried analytics state; compiled programs are kept, so
        a reset engine re-streams with zero recompilation (used by
        measurement warmup)."""
        self._degree_state = jnp.zeros(self.vb + 2, jnp.int32)
        self._labels = jnp.arange(self.vb + 2, dtype=jnp.int32)
        self._bip_labels = None

    def _prep(self, src, dst, sentinel: int = None):
        src, dst = pad_edges_for_mesh(
            np.asarray(src, np.int32), np.asarray(dst, np.int32),
            self.mesh,
            sentinel=self.vb + 1 if sentinel is None else sentinel,
        )
        src, dst = guard_wire(
            (src, dst), self.n,
            self.vb + 1 if sentinel is None else sentinel)
        return jnp.asarray(src), jnp.asarray(dst)

    def _dispatch(self, analytic: str, edges: int, fn):
        """One per-window sharded dispatch: tier-labeled span, the
        shard_dispatch fault hook, and the stage watchdog/retry when
        armed (every fn here is pure — state rebinds on success
        only)."""
        def _call():
            fire_shard_dispatch(self.n)
            return fn()

        with telemetry.span("sharded.window", tier="sharded",
                            analytic=analytic, mesh=self.n,
                            edges=edges):
            return _guarded_dispatch(("sharded", analytic), _call)

    def degrees(self, src, dst) -> np.ndarray:
        """Fold a window batch into the running degree vector."""
        s, d = self._prep(src, dst)
        # sentinel slot vb+1 absorbs padding; the kernel buckets to vb+1
        # rows plus sentinel, so state length is vb+2
        self._degree_state = self._dispatch(
            "degrees", len(np.atleast_1d(src)),
            lambda: self.degree_fn(s, d, self._degree_state))
        return np.asarray(self._degree_state[: self.vb])

    def cc_labels(self, src, dst, carry: bool = True) -> np.ndarray:
        """Label propagation over a window batch; carry=True keeps labels
        across windows (streaming iteration P5)."""
        s, d = self._prep(src, dst)
        labels = self._labels if carry else jnp.arange(
            self.vb + 2, dtype=jnp.int32
        )
        self._labels = self._dispatch(
            "cc", len(np.atleast_1d(src)),
            lambda: self.cc_fn(s, d, labels))
        return np.asarray(self._labels[: self.vb])

    def bipartite(self, src, dst, carry: bool = True):
        """Sharded bipartiteness via the double cover: edge u~w joins
        (u,+)-(w,-) and (u,-)-(w,+) over 2·vb cover vertices; a vertex
        whose covers share a component sits on an odd cycle
        (ops/unionfind.bipartite_labels, distributed with the same
        pmin label exchange as cc; replaces the reference's O(C²·V)
        Candidates.merge, example/util/Candidates.java:76-138).

        Returns (labels[vb], signs[vb], odd[vb]); carry=True folds the
        window into the running cover labeling (streaming semantics of
        the merge tree)."""
        if self._bip_fn is None:
            self._bip_fn = make_sharded_cc_fn(self.mesh, 2 * self.vb)
        fresh = jnp.arange(2 * self.vb + 2, dtype=jnp.int32)
        labels = self._bip_labels if (carry and self._bip_labels
                                      is not None) else fresh
        s2, d2 = unionfind.double_cover_edges(src, dst, self.vb)
        s2, d2 = pad_edges_for_mesh(s2.astype(np.int32),
                                    d2.astype(np.int32), self.mesh,
                                    sentinel=2 * self.vb + 1)
        s2, d2 = guard_wire((s2, d2), self.n, 2 * self.vb + 1)
        s2j, d2j = jnp.asarray(s2), jnp.asarray(d2)
        self._bip_labels = self._dispatch(
            "bipartite", len(np.atleast_1d(src)),
            lambda: self._bip_fn(s2j, d2j, labels))
        return unionfind.decode_double_cover(
            np.asarray(self._bip_labels), self.vb)

    # ------------------------------------------------------------------
    # checkpoint / resume (utils/checkpoint.py)
    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        """Gathered-host snapshot of the carried analytics state. The
        slabs are REPLICATED across the mesh (every merge ends in a
        psum/pmin), so the d2h gather of shard 0's copy IS the
        shard-count-independent layout: a checkpoint taken on a 4-way
        mesh loads into any mesh width, the single-chip driver
        mirrors, or the numpy twin (parallel/host_twin) unchanged.
        `mesh_shape` records provenance only — load ignores it."""
        fire_shard_gather(self.n)
        state = {
            "vb": self.vb,
            "mesh_shape": [self.n],  # gslint: disable=ckpt-symmetry (provenance only — load adopts any mesh width)
            "degree_state": np.asarray(self._degree_state),  # gslint: disable=host-sync (sanctioned checkpoint boundary: state_dict's batched gather, same discipline as scan_analytics.state_dict)
            "labels": np.asarray(self._labels),  # gslint: disable=host-sync (sanctioned checkpoint boundary: state_dict's batched gather)
        }
        if self._bip_labels is not None:
            state["bip_labels"] = np.asarray(self._bip_labels)  # gslint: disable=host-sync (sanctioned checkpoint boundary: state_dict's batched gather)
        return state

    def load_state_dict(self, state: dict) -> None:
        if state["vb"] != self.vb:
            raise ValueError(
                f"vertex bucket mismatch: checkpoint has {state['vb']}, "
                f"engine built with {self.vb}")
        self._degree_state = jnp.asarray(state["degree_state"])
        self._labels = jnp.asarray(state["labels"])
        # restore must be symmetric: a checkpoint taken before any
        # bipartite call clears post-checkpoint cover state
        self._bip_labels = (jnp.asarray(state["bip_labels"])
                            if "bip_labels" in state else None)

    @staticmethod
    def _pad_mesh_arrays(target: int, *arr_fill_pairs):
        """Pad each (array, fill) pair to `target` — the shared
        pad-to-mesh idiom of triangles() and sliding_reduce()."""
        return [seg_ops.pad_to(a, target, fill=f)
                for a, f in arr_fill_pairs]

    def triangles(self, nbr, ea, eb, emask) -> int:
        target = mesh_padded_len(len(ea), self.mesh)
        sentinel = nbr.shape[0] - 1
        ea, eb, emask = self._pad_mesh_arrays(
            target,
            (np.asarray(ea, np.int32), sentinel),
            (np.asarray(eb, np.int32), sentinel),
            (np.asarray(emask, bool), False))
        ea, eb = guard_wire((ea, eb), self.n, sentinel)
        nbr_j, ea_j, eb_j, em_j = (jnp.asarray(nbr), jnp.asarray(ea),
                                   jnp.asarray(eb), jnp.asarray(emask))
        return self._dispatch(
            "triangles", len(ea),
            lambda: int(self.tri_fn(nbr_j, ea_j, eb_j, em_j)))

    def sliding_reduce(self, src, pane, val, num_panes: int,
                       panes_per_window: int, name: str = "sum",
                       fn=None):
        """Sliding-window reduce over the mesh (the engine form of
        make_sharded_pane_reduce; docs/DESIGN.md §1.1): `pane` gives
        each edge's dense slide-index, windows cover panes_per_window
        consecutive panes. Pass `name` for a monoid, or `fn` (with
        name=None) for a user fn declared associative — the same two
        tiers as the single-chip pane path. Returns numpy
        (win_vals, win_counts), both
        [pane_bucket + panes_per_window - 1, vb + 1]; a (w, v) cell is
        meaningful iff win_counts[w, v] > 0 (real edge counts in both
        tiers), window w covering panes
        [w - panes_per_window + 1, w]. Programs are cached per
        (pane_bucket, panes_per_window, combine), so steady-state
        streaming pays zero recompilation."""
        if fn is not None:
            name = None
        pb = seg_ops.bucket_size(num_panes)
        key = (pb, panes_per_window, name or fn)
        pane_fn = self._pane_fns.get(key)
        if pane_fn is None:
            pane_fn = make_sharded_pane_reduce(self.mesh, self.vb, pb,
                                               panes_per_window,
                                               name=name, fn=fn)
            self._pane_fns[key] = pane_fn
        src = np.asarray(src, np.int32)
        pane = np.asarray(pane, np.int32)
        val = np.asarray(val)
        n = len(src)
        # power-of-two edge bucket FIRST, then the mesh multiple:
        # varying window edge counts reuse O(log E) compiled programs
        # (the docstring's zero-steady-state-recompilation claim) —
        # padded lanes are valid=False, routed to the trash cell
        target = mesh_padded_len(seg_ops.bucket_size(n), self.mesh)
        src, pane, val, valid = self._pad_mesh_arrays(
            target, (src, 0), (pane, 0), (val, 0),
            (np.ones(n, bool), False))
        src, pane = guard_wire((src, pane), self.n,
                               max(self.vb, pb))
        args = tuple(jnp.asarray(a) for a in (src, pane, val, valid))
        wv, wc = self._dispatch("sliding_reduce", n,
                                lambda: pane_fn(*args))
        return np.asarray(wv), np.asarray(wc)


# ----------------------------------------------------------------------
# sharded fused analytics scan: every analytic, every window, one
# multi-chip dispatch per chunk (the sharded ops/scan_analytics.py)
# ----------------------------------------------------------------------

def make_sharded_summary_scan(mesh, eb: int, vb: int, kb: int, cap: int,
                              table: str = "replicated"):
    """shard_map( lax.scan( per-window fused body ) ): the carry
    (degree vector, CC labels, double-cover labels) is replicated; each
    window's edges are sharded; all merges ride ICI collectives inside
    the scan. Cover layout matches ops/scan_analytics.py: (+) = v,
    (−) = vb+1+v, shared sentinel slot vb."""
    n = shard_count(mesh)
    sent = vb
    tri_body = build_sharded_window_counter(n, eb, vb, kb, cap,
                                            table=table)
    pmin_exchange = functools.partial(jax.lax.pmin, axis_name=SHARD_AXIS)

    def body(carry, xs):
        deg, labels, cover = carry
        src, dst, valid = xs
        s = jnp.where(valid, src, sent)
        d = jnp.where(valid, dst, sent)
        ones = jnp.where(valid, 1, 0)

        local = (jax.ops.segment_sum(ones, s, vb + 1)
                 + jax.ops.segment_sum(ones, d, vb + 1))
        deg = deg + jax.lax.psum(local, SHARD_AXIS)
        max_degree = jnp.max(deg[:vb])

        labels = unionfind.cc_fixpoint(labels, s, d,
                                       exchange=pmin_exchange)
        touched = deg[:vb] > 0
        num_components = jnp.sum(
            touched & (labels[:vb] == jnp.arange(vb)), dtype=jnp.int32)

        cover = unionfind.cc_fixpoint(
            cover, jnp.concatenate([s, s + (vb + 1)]),
            jnp.concatenate([d + (vb + 1), d]), exchange=pmin_exchange)
        odd = jnp.any(touched & (cover[:vb] == cover[vb + 1:2 * vb + 1]))

        tri, b_ovf, k_ovf = tri_body(src, dst, valid)
        return (deg, labels, cover), (
            max_degree, num_components, odd, tri, b_ovf, k_ovf)

    # shard_map_norep: the scan body runs cc_fixpoint (a while_loop
    # the replication checker cannot type); psum/pmin exchanges make
    # every output replicated
    @shard_map_norep(
        mesh, in_specs=(
            (P(), P(), P()),                               # carry
            P(None, SHARD_AXIS), P(None, SHARD_AXIS),      # [W, eb]
            P(None, SHARD_AXIS),
        ),
        out_specs=((P(), P(), P()),
                   (P(), P(), P(), P(), P(), P())),
    )
    def run(carry, src_w, dst_w, valid_w):
        return jax.lax.scan(body, carry, (src_w, dst_w, valid_w))

    return jax.jit(run)


def make_sharded_snapshot_scan(mesh, vb: int, analytics: tuple,
                               deltas: bool = False):
    """Sharded form of the driver's batched snapshot scan
    (core/driver._build_snapshot_scan) over the ShardedWindowEngine's
    state layouts — degrees and cc labels [vb+2] (sentinel vb+1),
    double cover [2vb+2] ((+) = v, (−) = vb + v, sentinel 2vb+1) —
    emitting per-window replicated snapshots, a whole chunk of windows
    in one multi-chip dispatch.

    The [W, eb] stacks arrive edge-sharded (P1); one all_gather per
    chunk hands every chip the chunk's whole windows, and each chip
    then runs the single-chip fold (core/driver.snapshot_fold_body:
    degrees by scatter-add, CC and the cover by
    ops/unionfind.cc_fold_rooted) on the replicated state (P3). The
    same program on the same inputs keeps the outputs replicated, so
    the fold loop holds no collective and nothing table-sized, and the
    scan emits the same `cc_rounds` / `cover_rounds` as one chip."""
    from ..core.driver import snapshot_fold_body

    body = snapshot_fold_body(vb, analytics, deltas=deltas)

    def whole(x):   # [W, eb/n] shard slice -> [W, eb] on every chip
        return jax.lax.all_gather(x, SHARD_AXIS, axis=1, tiled=True)

    # shard_map_norep: the fold's while_loop has no replication rule;
    # every chip folds the same gathered windows into the same carry
    @shard_map_norep(
        mesh, in_specs=((P(), P(), P()),
                        P(None, SHARD_AXIS), P(None, SHARD_AXIS),
                        P(None, SHARD_AXIS)),
        out_specs=((P(), P(), P()), P()),
    )
    def run(carry, s_w, d_w, valid_w):
        return jax.lax.scan(body, carry,
                            (whole(s_w), whole(d_w), whole(valid_w)))

    return jax.jit(run)


class ShardedSummaryEngine(scan_analytics.SummaryEngineBase):
    """Multi-chip StreamSummaryEngine (ops/scan_analytics.py): carried-
    state fused analytics over [W, eb] window stacks with the edge axis
    sharded over the mesh — one dispatch per MAX_WINDOWS windows.
    Triangle windows that overflow K or the exchange capacity are
    recounted exactly by the escalating per-window sharded kernel."""

    METRICS_TIER = "sharded"

    def __init__(self, mesh, edge_bucket: int, vertex_bucket: int,
                 k_bucket: int = 0):
        self.mesh = mesh
        self.n = shard_count(mesh)
        self._tri = ShardedTriangleWindowKernel(
            mesh, edge_bucket=edge_bucket, vertex_bucket=vertex_bucket,
            k_bucket=k_bucket)
        self.eb = self._tri.eb
        self.vb = self._tri.vb
        # summaries finalized before the last escaping process() error
        # (None = clean): the demoting caller's hand-off — see process
        self.drained_partial = None
        # same compile-size cap as the single-chip FUSED engine — the
        # same multi-analytic scan program class (the PER-DEVICE slice
        # is eb/n, but conservative is cheap here)
        self.MAX_WINDOWS = min(type(self).MAX_WINDOWS,
                               triangles.capped_chunk(self.eb))
        self._run = make_sharded_summary_scan(
            mesh, self.eb, self.vb, self._tri.kb, self._tri.cap,
            table=self._tri.table)
        self.reset()

    def _h2d(self, args):
        from jax.sharding import NamedSharding

        s, d = guard_wire(args[:2], self.n, self.vb)
        sharding = NamedSharding(self.mesh, P(None, SHARD_AXIS))
        return tuple(jax.device_put(a, sharding)
                     for a in (s, d) + tuple(args[2:]))

    def _dispatch_async(self, s, d, valid):
        fire_shard_dispatch(self.n)
        telemetry.event("sharded.round", engine="summary",
                        window=self.windows_done, windows=s.shape[0],
                        mesh=self.n)
        self._carry, res = self._run(self._carry, s, d, valid)
        return res

    def _materialize(self, raw):
        fire_shard_gather(self.n)
        return tuple(np.array(x) for x in raw)  # gslint: disable=host-sync (sanctioned finalize boundary: the engine's ONE batched d2h gather per chunk, pipelined one chunk behind dispatch)

    def _redo(self, src, dst, b_ovf: int, k_ovf: int) -> int:
        return self._tri.count(
            src, dst,
            failed_kb=self._tri.kb if k_ovf else 0,
            failed_cap=self._tri.cap if b_ovf else 0)

    # ------------------------------------------------------------------
    # error-path drain + mesh provenance
    # ------------------------------------------------------------------
    def _finalize_summaries(self, item, src, dst, out) -> None:
        # tee the finalized prefix by ALIAS (out only ever grows, and
        # process() snapshots it on the error path): the drain stays
        # O(W) total, not O(W²) of per-chunk copies
        super()._finalize_summaries(item, src, dst, out)
        self._partial_out = out

    def process(self, src: np.ndarray, dst: np.ndarray) -> list:
        """SummaryEngineBase.process over the mesh, with the sharded
        drain contract: when an error escapes (dead shard, ICI stall,
        corrupt wire), the in-flight chunk's finalize has already
        drained (ops/ingress_pipeline) and the summaries of every
        window finalized before the failure land on
        `self.drained_partial` — windows_done/resume_offset() then sit
        exactly past them, so a caller can hand the drained prefix
        over and re-enter on a twin (parallel/host_twin) or a fresh
        mesh from the last finalized window instead of recomputing
        delivered work."""
        self._partial_out = []
        self.drained_partial = None
        with telemetry.span("sharded.stream", tier="sharded",
                            engine="summary", mesh=self.n,
                            edges=len(np.atleast_1d(src))):
            try:
                return super().process(src, dst)
            except Exception:
                self.drained_partial = list(self._partial_out)
                raise
            finally:
                self._partial_out = []

    def state_dict(self) -> dict:
        fire_shard_gather(self.n)
        state = super().state_dict()
        # provenance only (load ignores it): the carry d2h'd above is
        # replicated, so the layout is shard-count independent — the
        # single-chip engine and the host twin load it unchanged
        state["mesh_shape"] = [self.n]
        return state
