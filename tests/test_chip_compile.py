"""Compile the main path's programs for a described TPU v5e, no chip
attached (ahead-of-time, against a described topology): the XLA fused scan, the driver's
snapshot scan at the benchmark's size, the Pallas
kernels that lower for the chip, and the sharded snapshot scan on a
2x2 mesh. A kernel the chip's compiler refuses is pinned here with the
reason docs and ROADMAP give, so a redesign that makes it compile
shows up as a failing case to flip.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file. Keep these cases in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from gelly_streaming_tpu.ops import pallas_intersect
from gelly_streaming_tpu.ops import pallas_triangles
from gelly_streaming_tpu.ops import pallas_window as pw
from gelly_streaming_tpu.ops import scan_analytics

EB, VB, KB = 4096, 8192, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_lowering(monkeypatch):
    """The kernels pick interpret mode from jax.default_backend(),
    which is the CPU here: steer them to the chip's lowering."""
    for mod in (pallas_intersect, pallas_triangles, pw):
        monkeypatch.setattr(mod, "_need_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _carry(sharding, vb=VB, lead=()):
    return (_sds(lead + (vb + 1,), jnp.int32, sharding),
            _sds(lead + (vb + 1,), jnp.int32, sharding),
            _sds(lead + (2 * (vb + 1),), jnp.int32, sharding))


def _window(sharding, eb=EB, lead=()):
    return (_sds(lead + (eb,), jnp.int32, sharding),
            _sds(lead + (eb,), jnp.int32, sharding),
            _sds(lead + (eb,), jnp.bool_, sharding))


def test_xla_fused_scan_compiles(one_chip):
    """The driver's and cohort's XLA fused scan, 4 windows."""
    body = scan_analytics._build_scan(EB, VB, KB, pallas_ok=False)

    def run(carry, xs):
        return jax.lax.scan(body, carry, xs)

    compiled = jax.jit(run).lower(
        _carry(one_chip), _window(one_chip, lead=(4,))).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30


def test_driver_snapshot_scan_compiles_with_round_counts(one_chip):
    """The driver's single-chip snapshot scan at the benchmark's size
    (2^20 slots, 8 windows of 32,768 edges) compiles for the chip, and
    emits each window's CC and double-cover round counts."""
    from gelly_streaming_tpu.core.driver import _build_snapshot_scan

    vb, w, eb = 1 << 20, 8, 32768
    carry = (_sds((vb + 1,), jnp.int32, one_chip),
             _sds((vb + 1,), jnp.int32, one_chip),
             _sds((2 * vb + 1,), jnp.int32, one_chip))
    fn = _build_snapshot_scan(vb, ("degrees", "cc", "bipartite"))
    args = (carry,) + _window(one_chip, eb=eb, lead=(w,))
    fn.lower(*args).compile()
    outs = jax.eval_shape(fn, *args)[1]
    for key in ("cc_rounds", "cover_rounds"):
        assert (outs[key].shape, outs[key].dtype) == ((w,), jnp.int32)
    # the cover reads back as each window's odd flag, never its labels
    assert (outs["odd"].shape, outs["odd"].dtype) == ((w, vb), jnp.bool_)
    assert "cover" not in outs
    # and the cut to a chunk's real rows compiles for the chip too
    from gelly_streaming_tpu.core.driver import _head_rows_fn

    rows = {k: _sds(v.shape, v.dtype, one_chip) for k, v in outs.items()}
    _head_rows_fn().lower(rows, w // 2).compile()


def test_intersect_pallas_compiles(one_chip, chip_lowering):
    k, ep, vb = 128, 16384, 65536
    fn = jax.jit(pallas_intersect.intersect_local_pallas)
    compiled = fn.lower(_sds((vb + 1, k), jnp.int32, one_chip),
                        _sds((ep,), jnp.int32, one_chip),
                        _sds((ep,), jnp.int32, one_chip),
                        _sds((ep,), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("v", [1024, 4096])
def test_dense_triangle_pallas_compiles(one_chip, v):
    """The fused A·A ⊙ A contraction; its (8, 128) out block is what
    the TPU's tiling accepts."""
    fn = jax.jit(lambda a: pallas_triangles._six_t_partials(a, False))
    compiled = fn.lower(_sds((v, v), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_snapshot_scan_compiles_on_2x2(topo):
    """The driver's mesh path at the four-chip cell's width: vb=2^20,
    16 windows of 32768 edges sharded over four chips, gathered once
    per chunk (the scan's only collective), with each window's round
    counts."""
    from gelly_streaming_tpu.parallel.sharded import (
        make_sharded_snapshot_scan)

    mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
    rep = NamedSharding(mesh, P())
    edges = NamedSharding(mesh, P(None, "shard"))
    vb, eb, w = 1 << 20, 32768, 16
    fn = make_sharded_snapshot_scan(
        mesh, vb, ("degrees", "cc", "bipartite", "triangles"))
    carry = (_sds((vb + 2,), jnp.int32, rep),
             _sds((vb + 2,), jnp.int32, rep),
             _sds((2 * vb + 2,), jnp.int32, rep))
    args = (carry, _sds((w, eb), jnp.int32, edges),
            _sds((w, eb), jnp.int32, edges),
            _sds((w, eb), jnp.bool_, edges))
    text = fn.lower(*args).compile().as_text()
    assert "all-gather" in text
    assert "all-reduce" not in text and "all-to-all" not in text
    outs = jax.eval_shape(fn, *args)[1]
    for key in ("cc_rounds", "cover_rounds"):
        assert (outs[key].shape, outs[key].dtype) == ((w,), jnp.int32)


def _build_window(one_chip):
    body = pw.build_window_body(EB, VB, KB, tile_e=EB)
    return body, (_carry(one_chip), _window(one_chip))


def _build_counter(one_chip):
    call = pw._counter_call(EB, VB, KB, EB, KB, False)
    g = (1, EB)
    return call, (_sds(g, jnp.int32, one_chip), _sds(g, jnp.int32, one_chip),
                  _sds(g, jnp.bool_, one_chip))


def _build_cohort(one_chip):
    nb = 8
    body = pw.build_cohort_window_body(EB, VB, KB, nb, tile_e=EB)
    return body, (_carry(one_chip, lead=(nb,)),
                  _window(one_chip, lead=(nb,)))


def _build_gnn(one_chip):
    eb, vb, f = 512, 1024, 16
    body = pw.build_gnn_window_body(eb, vb, f, "relu", tile_e=eb)
    return body, (_sds((vb + 1, f), jnp.float32, one_chip),
                  _sds((f, f), jnp.float32, one_chip),
                  _sds((f,), jnp.float32, one_chip),
                  _window(one_chip, eb=eb))


@pytest.mark.parametrize("build, reason", [
    (_build_window, "scatter-add"),
    (_build_counter, "scatter-add"),
    (_build_cohort, "scatter-add"),
    (_build_gnn, "Shape mismatch in input, indices and output"),
], ids=["window", "counter", "cohort", "gnn"])
def test_scatter_kernels_refused_for_the_chip(one_chip, chip_lowering,
                                              build, reason):
    """Mosaic lowers no in-kernel scatter-add, and no row gather of
    the GNN kernel's kind, so these kernels run in interpret mode only
    (ROADMAP queue 1). Pinned `on` for the chip they raise
    (tests/operations/test_pallas_window.py)."""
    fn, args = build(one_chip)
    with pytest.raises(Exception, match=reason):
        jax.jit(fn).lower(*args).compile()
