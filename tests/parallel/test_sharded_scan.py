"""The mesh snapshot scan (parallel/sharded.make_sharded_snapshot_scan)
on a 4-device virtual CPU mesh: it runs the single-chip fold body on
the gathered windows, so its snapshots and round counts are the
single-chip scan's, and its labels are the carried pmin fixpoint's
that it replaced (kept here as the control); its carries enter flat
and min-rooted on every path; and its fold loop holds no collective
and nothing table-sized."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from gelly_streaming_tpu import StreamingAnalyticsDriver
from gelly_streaming_tpu.core.driver import _build_snapshot_scan
from gelly_streaming_tpu.ops import host_snapshot, unionfind
from gelly_streaming_tpu.parallel import sharded
from gelly_streaming_tpu.parallel.mesh import (SHARD_AXIS, make_mesh,
                                               shard_map_norep)
from tests.test_driver import _flat_min_rooted, _loop_ops_past

ANALYTICS = ("degrees", "cc", "bipartite")
VB, EB, W = 64, 32, 6


def _control_scan(mesh, vb):
    """The mesh scan as it was: degrees by a per-shard segment_sum and
    a psum, CC and the cover by cc_fixpoint(carried=True) with a pmin
    over the shards every round."""
    pmin = functools.partial(jax.lax.pmin, axis_name=SHARD_AXIS)

    def body(carry, xs):
        deg, labels, cover = carry
        src, dst, valid = xs
        sent, sent2 = vb + 1, 2 * vb + 1
        s = jnp.where(valid, src, sent)
        d = jnp.where(valid, dst, sent)
        ones = jnp.where(valid, 1, 0)
        deg = deg + jax.lax.psum(jax.ops.segment_sum(ones, s, vb + 2)
                                 + jax.ops.segment_sum(ones, d, vb + 2),
                                 SHARD_AXIS)
        labels, n_cc = unionfind.cc_fixpoint(labels, s, d, exchange=pmin,
                                             rounds=True)
        s2 = jnp.concatenate([jnp.where(valid, src, sent2),
                              jnp.where(valid, src + vb, sent2)])
        d2 = jnp.concatenate([jnp.where(valid, dst + vb, sent2),
                              jnp.where(valid, dst, sent2)])
        cover, n_cov = unionfind.cc_fixpoint(cover, s2, d2, exchange=pmin,
                                             rounds=True)
        return (deg, labels, cover), {
            "deg": deg, "labels": labels, "cover": cover,
            "cc_rounds": n_cc, "cover_rounds": n_cov}

    edges = P(None, SHARD_AXIS)
    return jax.jit(shard_map_norep(
        mesh, in_specs=((P(), P(), P()), edges, edges, edges),
        out_specs=((P(), P(), P()), P()))(
            lambda carry, s, d, v: jax.lax.scan(body, carry, (s, d, v))))


def _stack(case, rng):
    """(carry folded on the host, s_w, d_w, valid) for one case."""
    deg = np.zeros(VB, np.int32)
    lab = np.arange(VB, dtype=np.int32)
    cov = np.arange(2 * VB, dtype=np.int32)
    s_w = rng.integers(0, 60, (W, EB)).astype(np.int32)
    d_w = rng.integers(0, 60, (W, EB)).astype(np.int32)
    valid = np.ones((W, EB), bool)
    if case == "carried":
        host_snapshot.snapshot_windows(   # folds the carry in place
            rng.integers(0, 50, 4 * EB), rng.integers(0, 50, 4 * EB),
            np.arange(0, 4 * EB + 1, EB), VB, deg, lab, cov)
    elif case == "padding":
        valid[[1, 4]] = False             # sentinel windows
        valid[5, EB // 3:] = False        # a ragged one
    elif case == "all_padding":
        valid[:] = False
    elif case == "self_loops":
        d_w[:, ::3] = s_w[:, ::3]
    elif case == "island_split":
        # window 0 builds {1, 30}, {2, 20}, {5, 9}; window 1 merges 5's
        # tree into 2's through its child 9 and into 1's through 5
        # itself: the carried fold must not leave an island behind
        valid[:2] = False
        s_w[0, :3], d_w[0, :3] = [5, 2, 1], [9, 20, 30]
        s_w[1, :2], d_w[1, :2] = [9, 5], [20, 30]
        valid[0, :3] = valid[1, :2] = True
    return (deg, lab, cov), s_w, d_w, valid


@pytest.mark.parametrize("case", ["random", "carried", "padding",
                                  "all_padding", "self_loops",
                                  "island_split"])
def test_mesh_scan_matches_control_and_single_chip(case):
    mesh = make_mesh(4)
    (deg, lab, cov), s_w, d_w, valid = _stack(
        case, np.random.default_rng(len(case)))
    xs = tuple(jnp.asarray(a) for a in (s_w, d_w, valid))
    # mesh layout: one more slot before each sentinel
    mesh_carry = (np.append(deg, [0, 0]), np.append(lab, [VB, VB + 1]),
                  np.append(cov, [2 * VB, 2 * VB + 1]))
    mesh_carry = tuple(jnp.asarray(a) for a in mesh_carry)
    one_carry = tuple(jnp.asarray(a) for a in (
        np.append(deg, 0), np.append(lab, VB), np.append(cov, 2 * VB)))
    got_carry, got = sharded.make_sharded_snapshot_scan(
        mesh, VB, ANALYTICS)(mesh_carry, *xs)
    ctl_carry, ctl = _control_scan(mesh, VB)(mesh_carry, *xs)
    one_carry, one = _build_snapshot_scan(VB, ANALYTICS)(one_carry, *xs)
    got, ctl, one = ({k: np.asarray(v) for k, v in o.items()}
                     for o in (got, ctl, one))
    # the control reads its whole cover back; the scans, its odd flag
    ctl["odd"] = ctl["cover"][:, :VB] == ctl["cover"][:, VB:2 * VB]
    for key, n in (("deg", VB), ("labels", VB), ("odd", VB)):
        np.testing.assert_array_equal(got[key][:, :n], ctl[key][:, :n])
        np.testing.assert_array_equal(got[key][:, :n], one[key][:, :n])
    assert "cover" not in got and got["odd"].shape == (W, VB)
    # the final cover, read back from the carry once a chunk
    np.testing.assert_array_equal(np.asarray(got_carry[2])[:2 * VB],
                                  np.asarray(one_carry[2])[:2 * VB])
    for key in ("cc_rounds", "cover_rounds"):
        np.testing.assert_array_equal(got[key], one[key])
        assert np.all(got[key] <= ctl[key])
    # the sentinel slots absorb padding and feed no output
    for mine, theirs, n in zip(got_carry, ctl_carry, (VB, VB, 2 * VB)):
        np.testing.assert_array_equal(np.asarray(mine)[:n],
                                      np.asarray(theirs)[:n])
    if case in ("all_padding", "padding"):
        assert set(got["cc_rounds"][~valid.any(1)]) == {1}


@pytest.mark.parametrize("entry", [
    "fresh", "load_state_dict", "bucket_growth", "repromotion"])
def test_mesh_scan_carries_enter_flat_and_min_rooted(monkeypatch, entry):
    """Every way a carry enters the mesh scan gives a flat, min-rooted
    one: a fresh engine, a restored checkpoint, the engine rebuilt over
    a grown vertex bucket, and the engine re-staged from the host
    mirrors (what a re-promotion after a demotion does)."""
    carries = []
    real = sharded.make_sharded_snapshot_scan

    def spy(*args, **kwargs):
        fn = real(*args, **kwargs)

        def run(carry, *xs):
            carries.append([np.asarray(c) for c in carry])
            return fn(carry, *xs)
        return run

    monkeypatch.setattr(sharded, "make_sharded_snapshot_scan", spy)
    monkeypatch.setenv("GS_AUTOTUNE", "0")

    def make():
        return StreamingAnalyticsDriver(
            window_ms=0, analytics=ANALYTICS, vertex_bucket=64,
            edge_bucket=16, mesh=make_mesh(4))

    rng = np.random.default_rng(7)
    half = 12 * 16
    late = 200 if entry == "bucket_growth" else 50
    src = np.concatenate([rng.integers(0, 50, half),
                          rng.integers(0, late, half)])
    dst = np.concatenate([rng.integers(0, 50, half),
                          rng.integers(0, late, half)])
    drv = make()
    drv.run_arrays(src[:half], dst[:half])
    if entry == "load_state_dict":
        drv2 = make()
        drv2.load_state_dict(drv.state_dict())
        drv = drv2
        carries.clear()
    if entry == "repromotion":
        drv._sync_engine_from_mirrors()
        carries.clear()
    drv.run_arrays(src[half:], dst[half:])
    assert carries
    for deg, lab, cov in carries:
        assert len(lab) == len(deg) == drv.vb + 2 or entry == "bucket_growth"
        assert _flat_min_rooted(lab) and _flat_min_rooted(cov)
    if entry == "bucket_growth":
        assert drv.vb > 64
        assert {len(lab) for _d, lab, _c in carries} == {66, drv.vb + 2}


def test_mesh_fold_loop_has_no_collective_or_table_sized_op():
    """The mesh twin of test_fold_loop_has_no_table_sized_ops: inside
    the mesh scan's CC and cover fold loops nothing is as large as the
    table and no collective runs; the one collective of the program is
    the all_gather of the chunk's edges, outside every loop. The
    control (the pmin fixpoint) has both inside its loops."""
    mesh = make_mesh(4)
    vb, eb, w = 1000, 16, 2
    i32 = jnp.int32
    carry = (jax.ShapeDtypeStruct((vb + 2,), i32),
             jax.ShapeDtypeStruct((vb + 2,), i32),
             jax.ShapeDtypeStruct((2 * vb + 2,), i32))
    stack = jax.ShapeDtypeStruct((w, eb), i32)
    args = (carry, stack, stack, jax.ShapeDtypeStruct((w, eb), jnp.bool_))
    collectives = ("all_reduce", "all_gather", "all_to_all",
                   "reduce_scatter", "collective_permute")

    lowered = sharded.make_sharded_snapshot_scan(
        mesh, vb, ANALYTICS).lower(*args)
    assert _loop_ops_past(lowered, vb) == (2, [])
    text = lowered.as_text()
    assert text.count("stablehlo.all_gather") == 3
    assert all(text.count("stablehlo." + c) == 0 for c in collectives
               if c != "all_gather")
    for body in _loop_bodies(lowered):
        assert not any(c in body for c in collectives), body[:200]
    control = _control_scan(mesh, vb).lower(*args)
    loops, bad = _loop_ops_past(control, vb)
    assert loops == 2 and bad
    assert any("all_reduce" in body for body in _loop_bodies(control))


def _loop_bodies(lowered):
    """The op names inside each stablehlo.while that scatters into a
    table (the fold loops), one string per loop."""
    def walk(op):
        for region in op.regions:
            for block in region:
                for child in block:
                    yield child.operation
                    yield from walk(child.operation)

    module = lowered.compiler_ir("stablehlo")
    out = []
    for loop in walk(module.operation):
        if loop.name != "stablehlo.while":
            continue
        names = [o.name for o in walk(loop)]
        if "stablehlo.scatter" in names:
            out.append(" ".join(names).replace("stablehlo.", ""))
    return out


@pytest.mark.parametrize("ragged", [False, True], ids=["full", "ragged"])
@pytest.mark.parametrize("max_w", [2, 64], ids=["chunks", "one_chunk"])
@pytest.mark.parametrize("layout", ["one_chip", "mesh"])
def test_triangle_halves_equal_count_windows(monkeypatch, layout, max_w,
                                             ragged):
    """dispatch_windows then collect_windows, with another stack
    dispatched and read back between them, gives count_windows'
    counts, count_stream's and the exact host count, in one chunk or
    several, on one chip and on the mesh; a ragged final window too."""
    from gelly_streaming_tpu.ops import triangles as tri_ops

    eb, vb = 32, 64
    kind = (functools.partial(sharded.ShardedTriangleWindowKernel,
                              make_mesh(4))
            if layout == "mesh" else tri_ops.TriangleWindowKernel)
    kern = kind(edge_bucket=eb, vertex_bucket=vb)
    monkeypatch.setattr(kern, "MAX_STREAM_WINDOWS", max_w)
    rng = np.random.default_rng(max_w + ragged)
    src = rng.integers(0, 16, 6 * eb).astype(np.int32)
    dst = rng.integers(0, 16, 6 * eb).astype(np.int32)
    windows = [(src[i:i + eb], dst[i:i + eb])
               for i in range(0, len(src), eb)]
    if ragged:
        windows[-1] = (windows[-1][0][:eb // 3], windows[-1][1][:eb // 3])
    exact = [tri_ops.triangle_count_sparse(s, d, vb) for s, d in windows]
    handle = kern.dispatch_windows(windows)
    other = kern.count_windows(windows[::-1])
    assert kern.collect_windows(handle) == exact == other[::-1]
    assert kern.count_windows(windows) == exact and sum(exact) > 0
    if not ragged:
        assert kern.count_stream(src, dst) == exact
    assert kern.count_windows([]) == []
