"""Fused window megakernel: ONE VMEM-tiled Pallas pass per edge slab,
shared across all analytics.

The dispatch observatory's committed cost-model rows (PERF_cpu.json
`cost_model`, ISSUE 10) prove every hot program — the fused scan, the
resident fused-compact super-batch, the triangle stream — is
bytes-bound at 0.25-0.28 FLOPs/byte with the fused scan at 0.096% of
roofline, because XLA's scan-of-gathers re-reads the COO edge slab
from HBM once per analytic: the degree fold, the CC fixpoint, the
double-cover fixpoint (twice — its edge list is the concatenated
cover), and the triangle stage each gather the same [eb] src/dst
arrays. This module is the IO-aware fix the PAPERS.md GNN-systems
literature prescribes: fuse compact-ingress decode → vertex bucketing
→ neighbor intersection → monoid reduce into a single `pallas_call`
per window, so the slab leaves HBM ONCE and every analytic (CC
union-find labels, bipartiteness sign, degree counts, triangle
counts) is computed from the VMEM-resident copy.

Kernel shape (grown from the two seeds, ops/pallas_intersect.py /
ops/pallas_triangles.py):

- grid = (eb // tile_e,) edge tiles; each step's [1, tile_e] src/dst
  (uint16 on the compact wire) blocks stream HBM→VMEM under Pallas's
  own double-buffered block pipeline — the tentpole's "edge-tile
  double-buffered copy into VMEM".
- per tile: compact decode (suffix mask from the window's valid
  count + uint16→int32 widening — the exact widen_stack semantics,
  fused instead of materialized), the degree monoid fold into the
  VMEM-resident carry slab, and the decoded tile staged into a VMEM
  slab scratch.
- the LAST tile runs the remaining analytics on the now
  VMEM-resident slab: the carried CC and double-cover min-label
  fixpoints (ops/unionfind.cc_fixpoint — composition over any edge
  partition converges to the same canonical labeling, so folding at
  window grain is bit-exact), then the triangle stage —
  build_window_counter's exact pipeline (orient_by_degree →
  dedupe_and_positions → K-bucket CSR scatter) with the K-bucket
  intersection running the intersect seed's OWN inner compare loop
  (pallas_intersect.tile_intersect_count) over bounded edge tiles.
- outputs: the three carry slabs plus one [8]-scalar SMEM summary
  row (max_degree, num_components, odd, triangles, K-overflow) —
  K-overflow hands off to the call sites' existing exact-redo
  escalation, so exactness is never sacrificed.

Selection (`resolve_*` family): `GS_PALLAS_WINDOW=on` pins the
kernel; unset or `off`, the XLA fused scan runs. Selection probes the
built kernel: a trace in interpret mode, a lowering and compile for
the chip otherwise. A kernel pinned `on` that the chip's compiler
refuses raises PallasUnavailable with the compiler's reason; one that
runs in interpret mode degrades to the XLA body with a durable
`selection.fallback` event.

On a v5e this megakernel, the triangle-only counter, the tenant-axis
cohort kernel and the GNN kernel are all refused today: Mosaic has no
lowering for their in-kernel scatter-add ("Unimplemented primitive in
Pallas TPU lowering: scatter-add"; ROADMAP queue 1). They run in
interpret mode only, as parity oracles.

Off-TPU the kernel runs in INTERPRET mode (the seeds' convention):
bit-identical to the XLA scan and the host twins by construction —
that is tier-1's parity oracle (tests/operations/
test_pallas_window.py, ci_check gate 7) — but it times nothing real.
Interpret also unrolls the grid at trace time, so off-TPU the default
edge tile is the whole slab (one grid step keeps the jaxpr linear); the tiled
path is exercised by tests at small buckets and is the shape the
chip session tunes (`pallas_window` DispatchTuner family: edge-tile
× K-chunk arms).

VMEM budget (the `supports()` gate, enforced on TPU backends only —
interpret has no VMEM): slab 2·4·eb + carry in/out 2·16·(vb+1) +
K-bucket table 4·(vb+1)·kb + the bounded [it, ck, kb] compare block
+ sort temps must fit under ~12MB of the 16MB scoped VMEM. At the
canonical eb=32768 / vb=65536 / kb=32 row the table alone is 8.4MB —
chip adoption at wide vertex buckets wants kb ≤ 16 or vb ≤ 32768;
see DESIGN.md §19 for the arithmetic.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_intersect
from . import triangles as tri_ops
from .pallas_triangles import _need_interpret
from ..utils import costmodel
from ..utils import knobs
from ..utils import telemetry

# default edge tile on a real chip; off-TPU the interpreter unrolls
# the grid at trace time, so the default degenerates to one whole-slab
# step (see default_tile)
TILE_E = 512
# the intersect stage's inner edge tile: bounds the [it, ck, kb]
# broadcast-compare block whatever the staging tile is (the seed's
# scoped-vmem lesson: T=256/Ck=128/K=256 already flirts with the 16M
# limit)
INTERSECT_TILE = 2048
# VMEM ceiling supports() enforces on TPU backends (headroom under the
# 16MB scoped-vmem limit for Mosaic's own temporaries)
VMEM_BUDGET = 12 * 1024 * 1024

_SUMS = 8  # [8]-int32 summary row (5 used; padded for alignment)

_CALLS = {}   # (eb,vb,kb,tile,ck,kind,interpret) -> pallas_call closure  # gslint: disable=thread-shared (idempotent memo: same key always builds the same program; a racing double-build is last-write-wins)
_PROBES = {}  # (vb,kb,kind) -> bool probe verdict  # gslint: disable=thread-shared (idempotent memo of a deterministic trace probe)


# ----------------------------------------------------------------------
# selection gate (the resolve_* family)
# ----------------------------------------------------------------------
def _reset_pallas_window() -> None:
    """Test hook: forget the memoized probe verdicts."""
    _PROBES.clear()


def resolve_pallas_window() -> bool:
    """Should the fused-scan/triangle window bodies run the Pallas
    megakernel instead of the XLA scan-of-gathers? Only when
    GS_PALLAS_WINDOW pins it `on`; the XLA body otherwise."""
    return knobs.get_str("GS_PALLAS_WINDOW") == "on"


def resolve_cohort_pallas() -> bool:
    """Should build_cohort_scan run the TENANT-AXIS Pallas megakernel
    (the tenant axis as a second grid dimension of one pallas_call,
    the whole cohort's carries VMEM-resident) instead of vmapping the
    XLA scan body over tenants? Only when GS_COHORT_PALLAS pins it
    `on`."""
    return knobs.get_str("GS_COHORT_PALLAS") == "on"


def resolve_gnn_pallas() -> bool:
    """Should the GNN engines (ops/gnn_window.py) run the fused
    Pallas GNN window kernel instead of the XLA gather/segment-sum
    round? Only when GS_GNN_PALLAS pins it `on`."""
    return knobs.get_str("GS_GNN_PALLAS") == "on"


# ----------------------------------------------------------------------
# tiling layer
# ----------------------------------------------------------------------
def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # gslint: disable=except-hygiene (availability probe: selects the interpret form, never correctness)
        return False


class PallasUnavailable(RuntimeError):
    """A Pallas kernel pinned `on` cannot be built for the chip this
    process runs on: the pin raises instead of silently running XLA."""


def _refuse(component: str, pin: str, fallback: str, error: str):
    """A selected kernel that cannot run. Pinned `on` while building
    for the chip (not interpret), raise PallasUnavailable; otherwise
    record a durable `selection.fallback` event and return None (the
    caller builds `fallback`)."""
    if knobs.get_str(pin) == "on" and not _need_interpret():
        raise PallasUnavailable("%s=on, but the %s kernel cannot run "
                                "here: %s" % (pin, component, error))
    telemetry.event("selection.fallback", durable=True,
                    component=component, fallback=fallback, error=error)
    return None


def _probe(fn, *shapes) -> None:
    """Check that `fn` builds at `shapes`: a trace in interpret mode;
    a lowering and compile for the chip otherwise, so a Mosaic refusal
    (e.g. an in-kernel scatter) surfaces at selection time."""
    if _need_interpret():
        jax.eval_shape(fn, *shapes)
    else:
        jax.jit(fn).lower(*shapes).compile()


def _why(e: Exception) -> str:
    return "%s: %s" % (type(e).__name__, str(e)[:200])


def default_tile(eb: int) -> int:
    """Edge tile when nothing pins one: min(512, eb) on a chip (the
    seed's measured ballpark); the WHOLE slab off-TPU — interpret
    mode unrolls the grid at trace time, so one step keeps the jaxpr
    linear in eb instead of quadratic-ish in tiles."""
    return min(TILE_E, eb) if _on_tpu() else eb


def tile_space(eb: int, kb: int) -> dict:
    """The `pallas_window` DispatchTuner arm space: edge-tile rungs
    under the slab size × K-chunk widths under the K bucket. Off-TPU
    the only tile is the whole slab — interpret mode unrolls the grid
    at trace time, so sub-slab tiles there trace the final stage once
    PER TILE and wedge the host compiler, measuring nothing a chip
    would ever run."""
    tiles = sorted({t for t in (256, 512, 1024, 2048) if t <= eb}
                   or {eb}) if _on_tpu() else [eb]
    cks = sorted({min(64, kb), min(128, kb)})
    return {"tile_e": tiles, "ck": cks}


def tile_tuner(eb: int, vb: int, kb: int):
    """The megakernel's autotuner family riding ops/autotune
    .DispatchTuner: `pallas_window:eb=…:vb=…:kb=…` with edge-tile ×
    K-chunk arms. tools/pallas_ab.py --sweep drives rounds offline
    (each arm is a distinct compiled program, so arms are explored
    between streams, not mid-stream); the persisted per-backend cache
    then seeds resolve_tiles for production builds."""
    from . import autotune

    space = tile_space(eb, kb)
    init = {"tile_e": (min(TILE_E, eb) if min(TILE_E, eb)
                       in space["tile_e"] else space["tile_e"][-1]),
            "ck": space["ck"][-1]}
    return autotune.DispatchTuner(tuner_key(eb, vb, kb), space, init)


def tuner_key(eb: int, vb: int, kb: int) -> str:
    return "pallas_window:eb=%d:vb=%d:kb=%d" % (eb, vb, kb)


def resolve_tiles(eb: int, kb: int, vb: int = 0,
                  tile_e: int = None, chunk_k: int = None):
    """(tile_e, ck) the megakernel builds at: explicit arguments (the
    A/B sweep) beat the GS_PALLAS_TILE/GS_PALLAS_CK pins beat the
    `pallas_window` tuner's persisted optimum for this shape beat the
    defaults. Called at BUILD time only (knob reads must not freeze
    inside a traced body)."""
    if tile_e is None:
        tile_e = knobs.get_int("GS_PALLAS_TILE") or 0
    if chunk_k is None:
        chunk_k = knobs.get_int("GS_PALLAS_CK") or 0
    if (not tile_e or not chunk_k) and vb:
        try:
            from . import autotune

            cached = autotune.load_cached_best(tuner_key(eb, vb, kb))
            if cached:
                arm = cached.get("arm") or {}
                tile_e = tile_e or int(arm.get("tile_e") or 0)  # gslint: disable=host-sync (tuner-cache JSON ints, no device value in sight)
                chunk_k = chunk_k or int(arm.get("ck") or 0)  # gslint: disable=host-sync (tuner-cache JSON ints, no device value in sight)
        except Exception:  # gslint: disable=except-hygiene (tuner-cache probe: absence/corruption selects the default tiles)
            pass
    tile_e = tile_e or default_tile(eb)
    tile_e = max(8, min(tile_e, eb))
    while eb % tile_e:
        tile_e //= 2
    chunk_k = max(8, min(chunk_k or min(128, kb), kb))
    return tile_e, chunk_k


# ----------------------------------------------------------------------
# VMEM budget + analytic cost model
# ----------------------------------------------------------------------
def slab_bytes(eb: int, compact: bool = False) -> int:
    """HBM bytes of ONE edge-slab read: 4 bytes/slot on the compact
    wire (2×uint16 + the per-window valid count), 9 on the standard
    wire (2×int32 + the bool mask)."""
    return eb * (2 + 2) + 4 if compact else eb * (4 + 4 + 1)


def carry_bytes(vb: int) -> int:
    """Bytes of one carry copy (degrees + labels + double cover)."""
    return 4 * ((vb + 1) + (vb + 1) + 2 * (vb + 1))


def window_bytes(eb: int, vb: int, compact: bool = False) -> int:
    """The megakernel's HBM traffic per window: ONE slab read, the
    carry read+write, the summary row."""
    return slab_bytes(eb, compact) + 2 * carry_bytes(vb) + 4 * _SUMS


def scan_of_gathers_bytes(eb: int, vb: int,
                          analytics: int = 4) -> int:
    """HBM bytes the XLA scan-of-gathers moves for the SAME window:
    each analytic re-gathers the standard-wire slab — degrees once,
    CC once, the double cover twice (its edge list is the
    concatenated cover), triangles once — plus the same carry
    read+write. The adoption story in one subtraction: the megakernel
    replaces `reads × slab` with `1 × slab`."""
    reads = {1: 1, 2: 2, 3: 4}.get(analytics, 5)
    return reads * slab_bytes(eb, False) + 2 * carry_bytes(vb)


def window_flops(eb: int, vb: int, kb: int) -> int:
    """Stated-model FLOP estimate per window (labeled `analytic` in
    the cost registry — a model, not a compiler measurement): the
    K-bucket compare dominates (2·eb·kb), plus the monoid folds, a
    nominal 8-round fixpoint over the three slabs, and the dedupe
    sort's eb·log2(eb) compares."""
    fix = 8 * (4 * (vb + 1))
    return (2 * eb * kb + 16 * eb + 3 * fix
            + eb * int(math.log2(max(eb, 2))))  # gslint: disable=host-sync (python-int bucket math, no device value in sight)


def vmem_window_bytes(eb: int, vb: int, kb: int,
                      tile_e: int = None, ck: int = None,
                      compact: bool = False) -> int:
    """The kernel's VMEM high-water estimate (DESIGN.md §19 walks the
    arithmetic): decoded slab scratch + carry in/out blocks + the
    K-bucket table + the bounded intersect compare block + the dedupe
    sort's temporaries."""
    if tile_e is None or ck is None:
        tile_e, ck = resolve_tiles(eb, kb)
    it = min(tile_e, INTERSECT_TILE, eb)
    slab = 2 * 4 * eb
    carry = 2 * carry_bytes(vb)
    nbr = 4 * (vb + 1) * kb
    compare = 2 * 4 * it * kb + it * min(ck, kb) * kb
    sort_tmp = 6 * 4 * eb
    return slab + carry + nbr + compare + sort_tmp


def supports(eb: int, vb: int, kb: int, tile_e: int = None,
             ck: int = None, compact: bool = False) -> bool:
    """Does this (eb, vb, kb) fit the chip's VMEM budget? Enforced on
    TPU backends only — interpret mode has no VMEM, and refusing a
    CPU parity run over a budget the backend doesn't have would gate
    the oracle out of existence."""
    if not _on_tpu():
        return True
    return vmem_window_bytes(eb, vb, kb, tile_e, ck,
                             compact) <= VMEM_BUDGET


def cohort_vmem_window_bytes(eb: int, vb: int, kb: int, nb: int,
                             tile_e: int = None,
                             ck: int = None) -> int:
    """The TENANT-AXIS kernel's VMEM high-water estimate (DESIGN.md
    §19's cohort term): the single-window arithmetic with the carry
    in/out blocks multiplied by the N cohort rows held VMEM-resident
    across the whole grid — slab scratch, the K-bucket table, and the
    bounded compare block stay single-tenant (one tenant's final
    stage runs at a time)."""
    if tile_e is None or ck is None:
        tile_e, ck = resolve_tiles(eb, kb)
    it = min(tile_e, INTERSECT_TILE, eb)
    slab = 2 * 4 * eb
    carry = 2 * nb * carry_bytes(vb)
    nbr = 4 * (vb + 1) * kb
    compare = 2 * 4 * it * kb + it * min(ck, kb) * kb
    sort_tmp = 6 * 4 * eb
    return slab + carry + nbr + compare + sort_tmp


def supports_cohort(eb: int, vb: int, kb: int, nb: int,
                    tile_e: int = None, ck: int = None) -> bool:
    """Does an N-row cohort at (eb, vb, kb) fit the chip's VMEM
    budget? Same contract as supports(): enforced on TPU backends
    only — interpret mode has no VMEM. The N-row carry term tightens
    the kb-at-wide-vb frontier; see the DESIGN.md §19 table."""
    if not _on_tpu():
        return True
    return cohort_vmem_window_bytes(eb, vb, kb, nb,
                                    tile_e, ck) <= VMEM_BUDGET


def register_cost_model(eb: int, vb: int, kb: int,
                        compact: bool = False) -> None:
    """Register the megakernel's analytic cost model with the
    observatory (utils/costmodel.record_analytic, armed only), under
    EVERY program label this body can dispatch as — the scan engine's
    and the resident tier's wrap_jit names — so the ledger spans join
    the stated model (one slab read vs the scan-of-gathers' summed
    reads) at their own abstract signatures, never a compiler capture
    of the interpret lowering. explain_perf then reports achieved
    GB/s and roofline fraction for the new program on any backend.
    The template carries the most recent registration's shape — one
    engine shape per program per process is the operating regime."""
    wire = "compact" if compact else "standard"
    programs = (("pallas_window_compact", "resident_pallas_compact")
                if compact else ("pallas_window", "resident_pallas"))
    for program in programs:
        costmodel.record_analytic(
            program,
            "eb=%d,vb=%d,kb=%d,%s" % (eb, vb, kb, wire),
            flops=window_flops(eb, vb, kb),
            bytes_accessed=window_bytes(eb, vb, compact),
            slab_bytes=slab_bytes(eb, compact),
            scan_of_gathers_bytes=scan_of_gathers_bytes(eb, vb),
            model="analytic",
            # the model is PER WINDOW; a chunk dispatch folds W of
            # them, so a reader scaling against per-dispatch span
            # seconds multiplies by the sig's leading window count
            unit="window")


# ----------------------------------------------------------------------
# the kernel
# ----------------------------------------------------------------------
def _tri_stage(sa, da, va, vb: int, kb: int, it: int, ck: int):
    """build_window_counter's EXACT per-window triangle pipeline on
    the VMEM-resident slab — same cleanup, orientation
    (tri_ops.orient_by_degree), fused dedupe+CSR positions
    (tri_ops.dedupe_and_positions), K-bucket scatter, and overflow
    accounting, so counts and the K-overflow handoff are
    bit-identical to the XLA body by construction. The K-bucket
    intersection runs the intersect seed's inner compare loop
    (pallas_intersect.tile_intersect_count) over `it`-edge tiles:
    per-tile [it, kb] row gathers + one [it, ck, kb] compare block —
    never more than a tile of rows in flight."""
    sent = vb
    valid = va & (sa != da)
    s = jnp.where(valid, sa, sent)
    d = jnp.where(valid, da, sent)
    ones = jnp.where(valid, 1, 0)
    deg = jax.ops.segment_sum(ones, s, vb + 1)
    deg = deg + jax.ops.segment_sum(ones, d, vb + 1)
    a, b = tri_ops.orient_by_degree(s, d, deg, sent)
    a, b, evalid, pos = tri_ops.dedupe_and_positions(a, b, sent, vb)
    overflow = jnp.sum((pos >= kb) & evalid)
    ok = evalid & (pos < kb)
    rows = jnp.where(ok, a, vb)
    cols = jnp.clip(pos, 0, kb - 1)
    nbr = jnp.full((vb + 1, kb), sent, jnp.int32)
    nbr = nbr.at[rows, cols].set(
        jnp.where(ok, b, sent).astype(jnp.int32))
    eb = sa.shape[0]
    a32, b32 = a.astype(jnp.int32), b.astype(jnp.int32)
    count = jnp.int32(0)
    for t in range(0, eb, it):
        hi = min(t + it, eb)
        ra = nbr[a32[t:hi]]
        rb = nbr[b32[t:hi]]
        va_t = (ra < sent) & evalid[t:hi, None]
        count = count + pallas_intersect.tile_intersect_count(
            ra, rb, va_t, ck)
    return count, overflow


def _final_summaries(vb, deg, lab, cov):
    """The per-window summary scalars off the folded carries — the
    same expressions as scan_analytics._build_scan's body."""
    touched = deg[:vb] > 0
    mdeg = jnp.max(deg[:vb])
    iota_v = jax.lax.broadcasted_iota(jnp.int32, (vb, 1), 0)[:, 0]
    ncomp = jnp.sum(touched & (lab[:vb] == iota_v), dtype=jnp.int32)
    odd = jnp.any(touched & (cov[:vb] == cov[vb + 1:2 * vb + 1]))
    return mdeg, ncomp, odd


def _pack_sums(*vals):
    out = list(vals) + [jnp.int32(0)] * (_SUMS - len(vals))
    return jnp.stack([jnp.asarray(v, jnp.int32) for v in out])


def _window_call(eb: int, vb: int, kb: int, tile_e: int, ck: int,
                 compact: bool, interpret: bool):
    """The full megakernel pallas_call closure:
    (deg, lab, cov, *wire) -> (deg, lab, cov, sums[8]). Memoized per
    shape; `wire` is (s2, d2, v2) [g, tile_e] on the standard wire or
    (nv[1], s16, d16) on the compact wire."""
    key = (eb, vb, kb, tile_e, ck, "c" if compact else "s", interpret)
    got = _CALLS.get(key)
    if got is not None:
        return got
    from . import unionfind as uf

    g = eb // tile_e
    sent = vb
    it = min(tile_e, INTERSECT_TILE, eb)

    def _fold_tile(i, s, d, v, deg_ref, slab_s, slab_d):
        ones = jnp.where(v, 1, 0)
        deg_ref[:] = deg_ref[:].at[s].add(ones).at[d].add(ones)
        slab_s[i, :] = s
        slab_d[i, :] = d

    def _final(deg_ref, lab_ref, cov_ref, sums_ref, slab_s, slab_d):
        sa = slab_s[:].reshape(eb)
        da = slab_d[:].reshape(eb)
        va = sa != sent
        lab = uf.cc_fixpoint(lab_ref[:], sa, da)
        lab_ref[:] = lab
        cov = uf.cc_fixpoint(
            cov_ref[:], jnp.concatenate([sa, sa + (vb + 1)]),
            jnp.concatenate([da + (vb + 1), da]))
        cov_ref[:] = cov
        mdeg, ncomp, odd = _final_summaries(vb, deg_ref[:], lab, cov)
        tri, ovf = _tri_stage(sa, da, va, vb, kb, it, ck)
        sums_ref[:] = _pack_sums(mdeg, ncomp,
                                 jnp.where(odd, 1, 0), tri, ovf)

    def _init(i, deg0, lab0, cov0, deg_ref, lab_ref, cov_ref):
        @pl.when(i == 0)
        def _():
            deg_ref[:] = deg0[:]
            lab_ref[:] = lab0[:]
            cov_ref[:] = cov0[:]

    if compact:
        def kernel(nv_ref, s_ref, d_ref, deg0, lab0, cov0,
                   deg_ref, lab_ref, cov_ref, sums_ref,
                   slab_s, slab_d):
            i = pl.program_id(0)
            _init(i, deg0, lab0, cov0, deg_ref, lab_ref, cov_ref)
            # compact-ingress decode, fused: the window's suffix mask
            # from its valid count + uint16→int32 widening (the
            # widen_stack semantics, per tile in VMEM)
            pos = i * tile_e + jax.lax.broadcasted_iota(
                jnp.int32, (1, tile_e), 1)[0]
            v = pos < nv_ref[0]
            s = jnp.where(v, s_ref[0, :].astype(jnp.int32), sent)
            d = jnp.where(v, d_ref[0, :].astype(jnp.int32), sent)
            _fold_tile(i, s, d, v, deg_ref, slab_s, slab_d)

            @pl.when(i == g - 1)
            def _():
                _final(deg_ref, lab_ref, cov_ref, sums_ref,
                       slab_s, slab_d)

        wire_specs = [
            pl.BlockSpec((1,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tile_e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ]
    else:
        def kernel(s_ref, d_ref, v_ref, deg0, lab0, cov0,
                   deg_ref, lab_ref, cov_ref, sums_ref,
                   slab_s, slab_d):
            i = pl.program_id(0)
            _init(i, deg0, lab0, cov0, deg_ref, lab_ref, cov_ref)
            v = v_ref[0, :]
            s = jnp.where(v, s_ref[0, :], sent)
            d = jnp.where(v, d_ref[0, :], sent)
            _fold_tile(i, s, d, v, deg_ref, slab_s, slab_d)

            @pl.when(i == g - 1)
            def _():
                _final(deg_ref, lab_ref, cov_ref, sums_ref,
                       slab_s, slab_d)

        wire_specs = [
            pl.BlockSpec((1, tile_e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ]

    vb1 = vb + 1
    carry_specs = [
        pl.BlockSpec((vb1,), lambda i: (0,),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((vb1,), lambda i: (0,),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((2 * vb1,), lambda i: (0,),
                     memory_space=pltpu.VMEM),
    ]
    call = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=wire_specs + carry_specs,
        out_specs=carry_specs + [
            pl.BlockSpec((_SUMS,), lambda i: (0,),
                         memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((vb1,), jnp.int32),
            jax.ShapeDtypeStruct((vb1,), jnp.int32),
            jax.ShapeDtypeStruct((2 * vb1,), jnp.int32),
            jax.ShapeDtypeStruct((_SUMS,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((g, tile_e), jnp.int32),
                        pltpu.VMEM((g, tile_e), jnp.int32)],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=window_flops(eb, vb, kb),
            bytes_accessed=window_bytes(eb, vb, compact),
            transcendentals=0),
    )

    def run(deg, lab, cov, *wire):
        return call(*wire, deg, lab, cov)

    _CALLS[key] = run
    return run


def _cohort_call(eb: int, vb: int, kb: int, nb: int, tile_e: int,
                 ck: int, interpret: bool):
    """The tenant-axis megakernel pallas_call closure:
    (deg[nb,vb1], lab[nb,vb1], cov[nb,2vb1], *wire[nb,g,tile_e]) ->
    (deg, lab, cov, sums[nb,8]). The tenant axis is the OUTER grid
    dimension (the edge-tile axis is last, so it iterates innermost:
    each tenant's tiles sweep 0..g-1 before the grid advances to the
    next tenant); the stacked carries are whole-array VMEM blocks
    under constant index maps — the entire cohort stays VMEM-resident
    across the grid, which is exactly the N-row carry term
    cohort_vmem_window_bytes budgets. The slab scratch is reused per
    tenant (tenant n's final stage consumes it at tile g-1, before
    tenant n+1's first tile overwrites it)."""
    key = (eb, vb, kb, nb, tile_e, ck, "n", interpret)
    got = _CALLS.get(key)
    if got is not None:
        return got
    from . import unionfind as uf

    g = eb // tile_e
    sent = vb
    it = min(tile_e, INTERSECT_TILE, eb)
    vb1 = vb + 1

    def _row(ref, n):
        return ref[pl.ds(n, 1), :][0]

    def _set_row(ref, n, val):
        ref[pl.ds(n, 1), :] = val[None]

    def kernel(s_ref, d_ref, v_ref, deg0, lab0, cov0,
               deg_ref, lab_ref, cov_ref, sums_ref, slab_s, slab_d):
        n = pl.program_id(0)
        i = pl.program_id(1)

        @pl.when(jnp.logical_and(n == 0, i == 0))
        def _():
            # one whole-cohort carry copy at the very first grid step
            deg_ref[:] = deg0[:]
            lab_ref[:] = lab0[:]
            cov_ref[:] = cov0[:]

        v = v_ref[0, 0, :]
        s = jnp.where(v, s_ref[0, 0, :], sent)
        d = jnp.where(v, d_ref[0, 0, :], sent)
        ones = jnp.where(v, 1, 0)
        _set_row(deg_ref, n,
                 _row(deg_ref, n).at[s].add(ones).at[d].add(ones))
        slab_s[i, :] = s
        slab_d[i, :] = d

        @pl.when(i == g - 1)
        def _():
            sa = slab_s[:].reshape(eb)
            da = slab_d[:].reshape(eb)
            va = sa != sent
            lab = uf.cc_fixpoint(_row(lab_ref, n), sa, da)
            _set_row(lab_ref, n, lab)
            cov = uf.cc_fixpoint(
                _row(cov_ref, n), jnp.concatenate([sa, sa + vb1]),
                jnp.concatenate([da + vb1, da]))
            _set_row(cov_ref, n, cov)
            mdeg, ncomp, odd = _final_summaries(
                vb, _row(deg_ref, n), lab, cov)
            tri, ovf = _tri_stage(sa, da, va, vb, kb, it, ck)
            _set_row(sums_ref, n,
                     _pack_sums(mdeg, ncomp, jnp.where(odd, 1, 0),
                                tri, ovf))

    tile_spec = pl.BlockSpec((1, 1, tile_e), lambda n, i: (n, i, 0),
                             memory_space=pltpu.VMEM)
    carry_specs = [
        pl.BlockSpec((nb, vb1), lambda n, i: (0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((nb, vb1), lambda n, i: (0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((nb, 2 * vb1), lambda n, i: (0, 0),
                     memory_space=pltpu.VMEM),
    ]
    call = pl.pallas_call(
        kernel,
        grid=(nb, g),
        in_specs=[tile_spec, tile_spec, tile_spec] + carry_specs,
        out_specs=carry_specs + [
            pl.BlockSpec((nb, _SUMS), lambda n, i: (0, 0),
                         memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((nb, vb1), jnp.int32),
            jax.ShapeDtypeStruct((nb, vb1), jnp.int32),
            jax.ShapeDtypeStruct((nb, 2 * vb1), jnp.int32),
            jax.ShapeDtypeStruct((nb, _SUMS), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((g, tile_e), jnp.int32),
                        pltpu.VMEM((g, tile_e), jnp.int32)],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=nb * window_flops(eb, vb, kb),
            bytes_accessed=nb * window_bytes(eb, vb, False),
            transcendentals=0),
    )

    def run(deg, lab, cov, *wire):
        return call(*wire, deg, lab, cov)

    _CALLS[key] = run
    return run


def _counter_call(eb: int, vb: int, kb: int, tile_e: int, ck: int,
                  interpret: bool):
    """Triangle-only megakernel (the stream kernel's per-window body
    carries no analytics state): stage the slab tile by tile, run the
    triangle stage at the last tile, emit (count, overflow)."""
    key = (eb, vb, kb, tile_e, ck, "t", interpret)
    got = _CALLS.get(key)
    if got is not None:
        return got
    g = eb // tile_e
    sent = vb
    it = min(tile_e, INTERSECT_TILE, eb)

    def kernel(s_ref, d_ref, v_ref, sums_ref, slab_s, slab_d):
        i = pl.program_id(0)
        v = v_ref[0, :]
        slab_s[i, :] = jnp.where(v, s_ref[0, :], sent)
        slab_d[i, :] = jnp.where(v, d_ref[0, :], sent)

        @pl.when(i == g - 1)
        def _():
            sa = slab_s[:].reshape(eb)
            da = slab_d[:].reshape(eb)
            tri, ovf = _tri_stage(sa, da, sa != sent, vb, kb, it, ck)
            sums_ref[:] = _pack_sums(tri, ovf)

    tile_spec = pl.BlockSpec((1, tile_e), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    call = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[tile_spec, tile_spec, tile_spec],
        out_specs=pl.BlockSpec((_SUMS,), lambda i: (0,),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((_SUMS,), jnp.int32),
        scratch_shapes=[pltpu.VMEM((g, tile_e), jnp.int32),
                        pltpu.VMEM((g, tile_e), jnp.int32)],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=window_flops(eb, vb, kb),
            bytes_accessed=slab_bytes(eb) + 4 * _SUMS,
            transcendentals=0),
    )
    _CALLS[key] = call
    return call


# ----------------------------------------------------------------------
# scan-body builders (the _build_scan-compatible contract)
# ----------------------------------------------------------------------
def build_window_body(eb: int, vb: int, kb: int, tile_e: int = None,
                      chunk_k: int = None, compact: bool = False,
                      interpret: bool = None):
    """The megakernel as a drop-in scan body for
    scan_analytics._build_scan: body(carry, xs) with the identical
    carry layout ((deg[vb+1], labels[vb+1], cover[2(vb+1)])) and
    per-window outputs (max_degree, num_components, odd, triangles,
    K-overflow). `xs` is (src, dst, valid) rows on the standard wire
    or (s16, d16, nvalid-scalar) on the compact wire — the compact
    body consumes the RAW uint16 stacks, decode fused in-kernel."""
    tile_e, ck = resolve_tiles(eb, kb, vb, tile_e, chunk_k)
    if interpret is None:
        interpret = _need_interpret()
    run = _window_call(eb, vb, kb, tile_e, ck, compact, interpret)
    g = eb // tile_e

    if compact:
        def body(carry, xs):
            deg, lab, cov = carry
            s16, d16, nv = xs
            deg, lab, cov, sums = run(
                deg, lab, cov,
                jnp.reshape(nv, (1,)).astype(jnp.int32),
                s16.reshape(g, tile_e), d16.reshape(g, tile_e))
            return (deg, lab, cov), (sums[0], sums[1], sums[2] != 0,
                                     sums[3], sums[4])
    else:
        def body(carry, xs):
            deg, lab, cov = carry
            src, dst, valid = xs
            deg, lab, cov, sums = run(
                deg, lab, cov, src.reshape(g, tile_e),
                dst.reshape(g, tile_e), valid.reshape(g, tile_e))
            return (deg, lab, cov), (sums[0], sums[1], sums[2] != 0,
                                     sums[3], sums[4])

    body.pallas_window = True
    return body


def maybe_window_body(eb: int, vb: int, kb: int,
                      compact: bool = False):
    """The gated, PROBED entry the engines build through: None (use
    the XLA body) unless the selection gate is on, the shape fits the
    chip budget, AND the probe (_probe) of the built body succeeds. A
    refusal raises when GS_PALLAS_WINDOW pins `on` for the chip, and
    otherwise degrades here with a durable `selection.fallback` event
    (_refuse). On success the analytic cost entry registers with the
    observatory."""
    if not resolve_pallas_window():
        return None
    tile_e, ck = resolve_tiles(eb, kb, vb)
    if not supports(eb, vb, kb, tile_e, ck, compact):
        return _refuse("pallas_window", "GS_PALLAS_WINDOW", "xla_scan",
                       "vmem budget: %d > %d at eb=%d vb=%d kb=%d" % (
                           vmem_window_bytes(eb, vb, kb, tile_e, ck),
                           VMEM_BUDGET, eb, vb, kb))
    try:
        body = build_window_body(eb, vb, kb, tile_e, ck, compact)
        carry = (jax.ShapeDtypeStruct((vb + 1,), jnp.int32),
                 jax.ShapeDtypeStruct((vb + 1,), jnp.int32),
                 jax.ShapeDtypeStruct((2 * (vb + 1),), jnp.int32))
        if compact:
            xs = (jax.ShapeDtypeStruct((eb,), jnp.uint16),
                  jax.ShapeDtypeStruct((eb,), jnp.uint16),
                  jax.ShapeDtypeStruct((), jnp.int32))
        else:
            xs = (jax.ShapeDtypeStruct((eb,), jnp.int32),
                  jax.ShapeDtypeStruct((eb,), jnp.int32),
                  jax.ShapeDtypeStruct((eb,), jnp.bool_))
        _probe(body, carry, xs)
    except Exception as e:  # gslint: disable=except-hygiene (_refuse raises or records a durable selection.fallback)
        return _refuse("pallas_window", "GS_PALLAS_WINDOW", "xla_scan",
                       _why(e))
    register_cost_model(eb, vb, kb, compact)
    return body


def build_cohort_window_body(eb: int, vb: int, kb: int, nb: int,
                             tile_e: int = None,
                             chunk_k: int = None,
                             interpret: bool = None):
    """The tenant-axis megakernel as a drop-in body for
    scan_analytics.build_cohort_scan's window loop: body(carry, xs)
    with the STACKED carry layout ((deg[nb,vb+1], labels[nb,vb+1],
    cover[nb,2(vb+1)])) and per-window-round outputs of shape [nb]
    each (max_degree, num_components, odd, triangles, K-overflow) —
    the same pytree the vmapped XLA body produces, so the two paths
    are interchangeable under lax.scan. Standard wire only (the
    cohort slab is int32 src/dst + bool valid)."""
    tile_e, ck = resolve_tiles(eb, kb, vb, tile_e, chunk_k)
    if interpret is None:
        interpret = _need_interpret()
    run = _cohort_call(eb, vb, kb, nb, tile_e, ck, interpret)
    g = eb // tile_e

    def body(carry, xs):
        deg, lab, cov = carry
        src, dst, valid = xs
        deg, lab, cov, sums = run(
            deg, lab, cov, src.reshape(nb, g, tile_e),
            dst.reshape(nb, g, tile_e),
            valid.reshape(nb, g, tile_e))
        return (deg, lab, cov), (sums[:, 0], sums[:, 1],
                                 sums[:, 2] != 0, sums[:, 3],
                                 sums[:, 4])

    body.pallas_window = True
    return body


def maybe_cohort_body(eb: int, vb: int, kb: int, nb: int):
    """The gated, PROBED entry build_cohort_scan builds through: None
    (vmap the XLA body over tenants) unless resolve_cohort_pallas()
    is on, the N-row shape fits the chip budget, AND the probe of the
    built body succeeds — the same raise-or-fallback contract as
    maybe_window_body, under GS_COHORT_PALLAS / `cohort_pallas`.
    On success the cohort analytic cost entry registers with the
    observatory."""
    if not resolve_cohort_pallas():
        return None
    tile_e, ck = resolve_tiles(eb, kb, vb)
    if not supports_cohort(eb, vb, kb, nb, tile_e, ck):
        return _refuse("cohort_pallas", "GS_COHORT_PALLAS",
                       "xla_cohort_scan",
                       "vmem budget: %d > %d at eb=%d vb=%d kb=%d "
                       "nb=%d" % (cohort_vmem_window_bytes(
                           eb, vb, kb, nb, tile_e, ck),
                           VMEM_BUDGET, eb, vb, kb, nb))
    try:
        body = build_cohort_window_body(eb, vb, kb, nb, tile_e, ck)
        vb1 = vb + 1
        carry = (jax.ShapeDtypeStruct((nb, vb1), jnp.int32),
                 jax.ShapeDtypeStruct((nb, vb1), jnp.int32),
                 jax.ShapeDtypeStruct((nb, 2 * vb1), jnp.int32))
        xs = (jax.ShapeDtypeStruct((nb, eb), jnp.int32),
              jax.ShapeDtypeStruct((nb, eb), jnp.int32),
              jax.ShapeDtypeStruct((nb, eb), jnp.bool_))
        _probe(body, carry, xs)
    except Exception as e:  # gslint: disable=except-hygiene (_refuse raises or records a durable selection.fallback)
        return _refuse("cohort_pallas", "GS_COHORT_PALLAS",
                       "xla_cohort_scan", _why(e))
    costmodel.record_analytic(
        "cohort_pallas", "eb=%d,vb=%d,kb=%d,nb=%d" % (eb, vb, kb, nb),
        flops=nb * window_flops(eb, vb, kb),
        bytes_accessed=nb * window_bytes(eb, vb, False),
        slab_bytes=nb * slab_bytes(eb, False),
        scan_of_gathers_bytes=nb * scan_of_gathers_bytes(eb, vb),
        model="analytic",
        # PER WINDOW ROUND (one window × nb tenants); a super-batch
        # dispatch folds W of them
        unit="window")
    return body


def maybe_compact_scan_fn(eb: int, vb: int, kb: int, label: str,
                          jit_kwargs: dict = None):
    """The compact-fused scan program BOTH summary engines'
    `_ensure_compact_fn` build when the megakernel is selected —
    decode per tile in-kernel, scanned over the raw uint16 stacks —
    factored here so the scan tier and the (donated) resident tier
    can never diverge on the wiring. None when the compact body's
    gate/probe refuses (callers fall back to the widen_stack twin)."""
    cbody = maybe_window_body(eb, vb, kb, compact=True)
    if cbody is None:
        return None
    from ..utils import metrics

    def run_pc(carry, s16, d16, nvalid):
        return jax.lax.scan(cbody, carry, (s16, d16, nvalid))

    return metrics.wrap_jit(label, jax.jit(run_pc,
                                           **(jit_kwargs or {})))


# ----------------------------------------------------------------------
# the GNN window kernel (ops/gnn_window's fused round)
# ----------------------------------------------------------------------
def gnn_h_bytes(vb: int, F: int) -> int:
    """Bytes of one [vb+1, F] float32 feature-slab copy."""
    return 4 * (vb + 1) * F


def gnn_weight_bytes(F: int) -> int:
    """Bytes of the dense layer's W [F, F] + b [F] (float32)."""
    return 4 * F * (F + 1)


def gnn_window_flops(eb: int, vb: int, F: int) -> int:
    """Stated-model FLOP estimate for ONE GNN round (labeled
    `analytic` in the cost registry): the dense update's matmul
    dominates (2·(vb+1)·F²) — THE term no other program here has —
    plus the aggregation's gather-and-add (2·eb·F) and the clamp/act
    elementwise sweeps (~6·(vb+1)·F)."""
    return (2 * (vb + 1) * F * F + 2 * eb * F
            + 6 * (vb + 1) * F)


def gnn_window_bytes(eb: int, vb: int, F: int) -> int:
    """The fused kernel's HBM traffic per round: ONE standard-wire
    slab read (the features ride the same read as the megakernel's
    analytics — the messages never round-trip HBM), the feature slab
    read+write, the weights, the summary row."""
    return (slab_bytes(eb, False) + 2 * gnn_h_bytes(vb, F)
            + gnn_weight_bytes(F) + 4 * _SUMS)


def gnn_scan_bytes(eb: int, vb: int, F: int) -> int:
    """HBM bytes the XLA gather/segment-sum round moves for the SAME
    window: the slab read, the materialized [eb, F] message matrix's
    write+read (gather out, segment-sum in), the [vb+1, F] aggregate's
    write+read, the feature slab's read+write, and the weights. The
    adoption story is the same subtraction as scan_of_gathers_bytes:
    the fused kernel deletes the message-matrix round-trip."""
    msgs = 4 * eb * F
    return (slab_bytes(eb, False) + 2 * msgs
            + 2 * gnn_h_bytes(vb, F) + 2 * gnn_h_bytes(vb, F)
            + gnn_weight_bytes(F))


def gnn_vmem_window_bytes(eb: int, vb: int, F: int,
                          tile_e: int = None) -> int:
    """The GNN kernel's VMEM high-water estimate (DESIGN.md §23
    mirrors §19's walk): decoded slab scratch, the feature slab in
    and out plus the gathered message matrix and the aggregate /
    pre-activation temporaries (~4 slab-sized blocks), and the
    weights."""
    slab = 2 * 4 * eb
    msgs = 4 * eb * F
    return (slab + 4 * gnn_h_bytes(vb, F) + msgs
            + gnn_weight_bytes(F))


def supports_gnn(eb: int, vb: int, F: int,
                 tile_e: int = None) -> bool:
    """Does a GNN round at (eb, vb, F) fit the chip's VMEM budget?
    Same contract as supports(): enforced on TPU backends only —
    interpret mode has no VMEM, and refusing a CPU parity run over a
    budget the backend doesn't have would gate the oracle out of
    existence."""
    if not _on_tpu():
        return True
    return gnn_vmem_window_bytes(eb, vb, F, tile_e) <= VMEM_BUDGET


def register_gnn_cost_model(eb: int, vb: int, F: int,
                            nb: int = None) -> None:
    """Register the GNN programs' analytic cost models with the
    observatory (armed only) under every wrap_jit label the family
    dispatches as: the XLA scan tiers (`gnn_scan`, `gnn_resident`)
    at the gather/segment-sum byte model, the fused kernel
    (`gnn_pallas`) at the single-slab-read model. With `nb` set,
    registers the vmapped tenant-axis program (`gnn_cohort`) at
    nb-scaled numbers instead. These are the repo's first MXU-class
    rows — the flops term carries a matmul, so the stated arithmetic
    intensity finally has a chance against machine balance."""
    flops = gnn_window_flops(eb, vb, F)
    sig = "eb=%d,vb=%d,F=%d" % (eb, vb, F)
    if nb is not None:
        costmodel.record_analytic(
            "gnn_cohort", sig + (",nb=%d" % nb),
            flops=nb * flops,
            bytes_accessed=nb * gnn_scan_bytes(eb, vb, F),
            slab_bytes=nb * slab_bytes(eb, False),
            model="analytic",
            # PER WINDOW ROUND (one window × nb tenants)
            unit="window")
        return
    for program, nbytes in (
            ("gnn_scan", gnn_scan_bytes(eb, vb, F)),
            ("gnn_resident", gnn_scan_bytes(eb, vb, F)),
            ("gnn_pallas", gnn_window_bytes(eb, vb, F))):
        costmodel.record_analytic(
            program, sig,
            flops=flops,
            bytes_accessed=nbytes,
            slab_bytes=slab_bytes(eb, False),
            model="analytic",
            # the model is PER WINDOW; a chunk dispatch folds W of
            # them, so a reader scaling against per-dispatch span
            # seconds multiplies by the sig's leading window count
            unit="window")


def _gnn_call(eb: int, vb: int, F: int, act: str, tile_e: int,
              interpret: bool):
    """The fused GNN-round pallas_call closure:
    (h[vb+1,F], W[F,F], b[F], s2, d2, v2) -> (h', sums[8]). Stages
    the sentinel-mapped slab tile by tile into VMEM scratch; the last
    tile runs the whole round — gather, scatter-accumulate, clamp,
    the MXU dot at Precision.HIGHEST, activation, re-clip — against
    the VMEM-resident feature slab, then packs the four summary
    scalars. Bit-identical to ops/gnn_window._build_gnn_round by the
    lattice argument (every intermediate an exact float32 integer
    < 2^24, so fold order is free). Memoized per shape."""
    key = (eb, vb, F, act, tile_e, "g", interpret)
    got = _CALLS.get(key)
    if got is not None:
        return got
    from . import gnn_window as gw

    g = eb // tile_e
    sent = vb
    sh = gw.agg_shift(eb)
    sc = np.float32(2.0 ** -sh)
    cap = np.float32(gw.UNIT_CAP)
    actf = gw._ACTS_JNP[act]

    def kernel(s_ref, d_ref, v_ref, h0_ref, w_ref, b_ref,
               h_ref, sums_ref, slab_s, slab_d):
        i = pl.program_id(0)
        v = v_ref[0, :]
        slab_s[i, :] = jnp.where(v, s_ref[0, :], sent)
        slab_d[i, :] = jnp.where(v, d_ref[0, :], sent)

        @pl.when(i == g - 1)
        def _():
            sa = slab_s[:].reshape(eb)
            da = slab_d[:].reshape(eb)
            h = h0_ref[:]
            msgs = h[sa]
            if sh:
                msgs = jnp.floor(msgs * sc)
            m = jnp.zeros((vb + 1, F), jnp.float32).at[da].add(msgs)
            p = jnp.minimum(h + jnp.minimum(m, cap), cap)
            z = jax.lax.dot_general(
                p, w_ref[:], (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST) + b_ref[:]
            h2 = jnp.clip(actf(z), 0.0, cap)
            h2 = h2.at[sent].set(0.0)
            # the round's empty-window-holds rule (_build_gnn_round):
            # zero valid edges → the slab carries through untouched
            nmsg = jnp.sum(sa != sent, dtype=jnp.int32)
            h2 = jnp.where(nmsg > 0, h2, h)
            h_ref[:] = h2
            maxf = jnp.max(h2[:vb]).astype(jnp.int32)
            active = jnp.sum(jnp.any(h2[:vb] > 0, axis=1),
                             dtype=jnp.int32)
            checksum = jnp.sum(h2.astype(jnp.int32),
                               dtype=jnp.int32)
            sums_ref[:] = _pack_sums(maxf, active, checksum, nmsg)

    tile_spec = pl.BlockSpec((1, tile_e), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    h_spec = pl.BlockSpec((vb + 1, F), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((F, F), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    b_spec = pl.BlockSpec((F,), lambda i: (0,),
                          memory_space=pltpu.VMEM)
    call = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[tile_spec, tile_spec, tile_spec,
                  h_spec, w_spec, b_spec],
        out_specs=[h_spec,
                   pl.BlockSpec((_SUMS,), lambda i: (0,),
                                memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((vb + 1, F), jnp.float32),
            jax.ShapeDtypeStruct((_SUMS,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((g, tile_e), jnp.int32),
                        pltpu.VMEM((g, tile_e), jnp.int32)],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=gnn_window_flops(eb, vb, F),
            bytes_accessed=gnn_window_bytes(eb, vb, F),
            transcendentals=0),
    )

    def run(h, W, b, *wire):
        return call(*wire, h, W, b)

    _CALLS[key] = run
    return run


def build_gnn_window_body(eb: int, vb: int, F: int, act: str,
                          tile_e: int = None,
                          interpret: bool = None):
    """The fused GNN round as a drop-in body for
    ops/gnn_window._build_gnn_scan: body(h, W, b, xs) with the same
    [vb+1, F] carry and (max_feat, active, checksum, msg_edges)
    outputs as the XLA round — interchangeable under lax.scan by the
    lattice argument. Standard wire only."""
    if tile_e is None:
        tile_e = default_tile(eb)
    if interpret is None:
        interpret = _need_interpret()
    run = _gnn_call(eb, vb, F, act, tile_e, interpret)
    g = eb // tile_e

    def body(h, W, b, xs):
        src, dst, valid = xs
        h, sums = run(h, W, b, src.reshape(g, tile_e),
                      dst.reshape(g, tile_e),
                      valid.reshape(g, tile_e))
        return h, (sums[0], sums[1], sums[2], sums[3])

    body.gnn_pallas = True
    return body


def maybe_gnn_body(eb: int, vb: int, F: int, act: str):
    """The gated, PROBED entry GnnSummaryEngine builds through: None
    (use the XLA gather/segment-sum round) unless
    resolve_gnn_pallas() is on, the [vb+1, F] slab fits the chip
    budget, AND the probe of the built body succeeds — the same
    raise-or-fallback contract as maybe_window_body, under
    GS_GNN_PALLAS / `gnn_pallas`. On success the GNN analytic cost entries
    register with the observatory."""
    if not resolve_gnn_pallas():
        return None
    tile_e = default_tile(eb)
    if not supports_gnn(eb, vb, F, tile_e):
        return _refuse("gnn_pallas", "GS_GNN_PALLAS", "xla_gnn_scan",
                       "vmem budget: %d > %d at eb=%d vb=%d F=%d" % (
                           gnn_vmem_window_bytes(eb, vb, F, tile_e),
                           VMEM_BUDGET, eb, vb, F))
    try:
        body = build_gnn_window_body(eb, vb, F, act, tile_e)
        h = jax.ShapeDtypeStruct((vb + 1, F), jnp.float32)
        W = jax.ShapeDtypeStruct((F, F), jnp.float32)
        b = jax.ShapeDtypeStruct((F,), jnp.float32)
        xs = (jax.ShapeDtypeStruct((eb,), jnp.int32),
              jax.ShapeDtypeStruct((eb,), jnp.int32),
              jax.ShapeDtypeStruct((eb,), jnp.bool_))
        _probe(body, h, W, b, xs)
    except Exception as e:  # gslint: disable=except-hygiene (_refuse raises or records a durable selection.fallback)
        return _refuse("gnn_pallas", "GS_GNN_PALLAS", "xla_gnn_scan",
                       _why(e))
    register_gnn_cost_model(eb, vb, F)
    return body


def maybe_counter(vb: int, kb: int, classic_run):
    """The gated triangle-stream variant for
    triangles.build_window_counter: a selector body that runs the
    triangle-only megakernel where the (trace-static) edge bucket
    fits the budget and the probed kernel built, else `classic_run`.
    The probe runs ONCE per (vb, kb) at a nominal bucket — the same
    raise-or-fallback contract as maybe_window_body."""
    if not resolve_pallas_window():
        return None
    pkey = (vb, kb, "counter")
    verdict = _PROBES.get(pkey)
    if verdict is None:
        try:
            probe_eb = 128
            tile_e, ck = resolve_tiles(probe_eb, kb, vb)
            call = _counter_call(probe_eb, vb, kb, tile_e, ck,
                                 _need_interpret())
            g = probe_eb // tile_e
            sds = jax.ShapeDtypeStruct((g, tile_e), jnp.int32)
            _probe(call, sds, sds,
                   jax.ShapeDtypeStruct((g, tile_e), jnp.bool_))
            verdict = True
        except Exception as e:  # gslint: disable=except-hygiene (_refuse raises or records a durable selection.fallback)
            _refuse("pallas_window", "GS_PALLAS_WINDOW", "xla_counter",
                    _why(e))
            verdict = False
        _PROBES[pkey] = verdict
    if not verdict:
        return None
    # the stream program's stated model: slab in, two scalars out (no
    # carried analytics); joins the pallas_window_stream spans the
    # AOT wrapper tags
    costmodel.record_analytic(
        "pallas_window_stream", "vb=%d,kb=%d" % (vb, kb),
        flops=None, bytes_accessed=None, model="analytic",
        unit="window",
        note="per-window bytes = pallas_window.slab_bytes(eb) + 32; "
             "flops = window_flops(eb, vb, kb) triangle terms")
    pin_tile = knobs.get_int("GS_PALLAS_TILE") or 0
    pin_ck = knobs.get_int("GS_PALLAS_CK") or 0
    interpret = _need_interpret()

    def run(src, dst, valid):
        # tile resolution here is PURE in (eb, the build-time pins):
        # the edge bucket is trace-static, and the knob reads already
        # happened at build — nothing environmental freezes in-trace
        eb = src.shape[0]
        tile_e = max(8, min(pin_tile or default_tile(eb), eb))
        while eb % tile_e:
            tile_e //= 2
        ck = max(8, min(pin_ck or min(128, kb), kb))
        if not supports(eb, vb, kb, tile_e, ck):
            return classic_run(src, dst, valid)
        call = _counter_call(eb, vb, kb, tile_e, ck, interpret)
        g = eb // tile_e
        sums = call(src.reshape(g, tile_e), dst.reshape(g, tile_e),
                    valid.reshape(g, tile_e))
        return sums[0], sums[1]

    run.pallas_window = True
    return run
