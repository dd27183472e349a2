"""Windowed GNN message passing as a first-class engine workload.

One GNN message-passing round per tumbling window — exactly the frame
the SNIPPETS brief puts on this repo's operator, and the first program
here whose arithmetic intensity can clear machine balance (every hot
program the §16 observatory has measured is bytes-bound gathers at
0.25–0.28 FLOPs/byte with the MXU idle). Per window:

  1. aggregation — `segment_sum` of the per-vertex feature slab over
     the window's COO slab (the same gather/scatter machinery the
     degree fold and `windowed_reduce` ride), sentinel-mapped padding
     folding as no-ops;
  2. dense update — a GCN-style layer `H' = act(P · W + b)` where
     `P = min(H + min(M, cap), cap)` is the self-loop-included
     aggregate, on the MXU.

The carry is the `[vb+1, F]` float32 feature slab (sentinel row `vb`
absorbs padded edges and is re-zeroed every round); the weights ride
each dispatch as explicit arguments so a `set_weights` never
recompiles. A window with ZERO valid edges holds the slab untouched —
unlike the analytics monoids a GNN round is not a no-op on empty
input (the dense layer would tick on the carry), and the chunk loop
and cohort both right-pad dispatches with all-invalid windows, so
padding inertness REQUIRES the hold rule.

Exactness policy (the reason the numpy twin is a BIT-exactness oracle
and not a tolerance check): features and weights live on a dyadic
lattice — storage grid 2^-5, feature values in [0, 16) (≤ UNIT_CAP
integer lattice units), weights snapped at set-time to the same grid
with |W| ≤ 16. Every intermediate of the round is then an INTEGER
(in float32) of magnitude < 2^24:

  - aggregation: ≤ eb messages of ≤ UNIT_CAP units each; for
    eb ≤ 2^15 every partial sum (any order) is an exact float32
    integer, so XLA's segment_sum ≡ numpy's add.at ≡ the Pallas
    scatter bit-for-bit. Larger eb pre-shifts messages by the
    deterministic `agg_shift(eb)` (same floor on every tier).
  - dense update: |P·W| ≤ F · UNIT_CAP · WEIGHT_CAP < 2^24 for
    F ≤ 64, so the matmul is exact under ANY accumulation order —
    including the MXU's, forced to float32 accumulation via
    Precision.HIGHEST. Larger F snaps weights to a coarser grid
    (`weight_shift(F)`), preserving the bound.
  - activations are restricted to exact elementwise ops
    (relu/abs/identity — GS_GNN_ACT), and the slab re-clips to
    [0, UNIT_CAP] before carrying.

Per-window summary scalars are exact integers by the same argument:
`max_feat` (lattice units), `active_vertices`, `feat_checksum` (a
wrapping-int32 modular sum of the slab — associative and commutative
mod 2^32, hence order-free), `msg_edges`. Arbitrary float weights
would break all of this; `set_weights` therefore SNAPS its inputs and
DESIGN.md §23 carries the caveat.

Tiers, house style: `GnnSummaryEngine` (fused `lax.scan`, one
dispatch per MAX_WINDOWS windows, optional Pallas body behind
`ops/pallas_window.resolve_gnn_pallas`), `GnnHostEngine` (numpy
parity twin and demotion floor), `GnnResidentEngine` (donated-carry
super-batch rung), and `build_gnn_cohort_scan` (the vmapped
tenant-axis program `core/tenancy.GnnTenantCohort` dispatches).
Checkpoint/WAL/resume ride `SummaryEngineBase` unchanged — the
state_dict carries the feature slab as the carry plus a `gnn` section
(feature width, activation, snapped weights) so gnn→gnn and
gnn→host-twin hand-offs are exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import segment as seg_ops
from ..utils import knobs
from ..utils import latency
from ..utils import metrics
from ..utils import provenance
from .scan_analytics import SummaryEngineBase

Q_BITS = 5                    # storage grid 2^-Q_BITS (units of 1/32)
UNIT_CAP = 511                # max lattice units per slot (< 2^9)
AGG_EXACT_LOG2 = 15           # eb ≤ 2^15 sums exactly at full width
MATMUL_EXACT_F = 64           # F ≤ 64 dots exactly at full width

_ACTS_JNP = {
    "relu": lambda z: jnp.maximum(z, 0.0),
    "abs": jnp.abs,
    "identity": lambda z: z,
}
_ACTS_NP = {
    "relu": lambda z: np.maximum(z, 0.0),
    "abs": np.abs,
    "identity": lambda z: z,
}


def agg_shift(eb: int) -> int:
    """Pre-aggregation message shift: messages floor-divide by
    2^shift so a full eb-edge window's segment sum stays under 2^24
    lattice units (exact float32 integers in any fold order).
    Deterministic from eb alone, so every tier shifts identically."""
    return max(0, int(eb).bit_length() - 1 - AGG_EXACT_LOG2)


def weight_shift(F: int) -> int:
    """Weight-grid coarsening for wide feature dims: F ≤ 64 keeps the
    full ±512-unit weight range exact; each doubling beyond halves
    the weight cap so |P·W| stays under 2^24."""
    return max(0, (int(F) - 1).bit_length() - 6)


def weight_cap(F: int) -> int:
    return max(1, (UNIT_CAP + 1) >> weight_shift(F))


def snap_weights(W, b, F: int):
    """Snap real-valued weights onto the dyadic lattice the exactness
    argument needs: round to the 2^-5 grid, clip to the F-derived cap.
    Returns (W_units, b_units) as integer-valued float32 arrays —
    the representation every tier folds with."""
    cap = float(weight_cap(F))  # gslint: disable=host-sync (pure-python cap, no device value in sight)
    wu = np.clip(np.rint(np.asarray(W, np.float64) * (1 << Q_BITS)),  # gslint: disable=host-sync (host-input normalization: callers pass numpy, never device values)
                 -cap, cap).astype(np.float32)
    bu = np.clip(np.rint(np.asarray(b, np.float64) * (1 << Q_BITS)),  # gslint: disable=host-sync (host-input normalization: callers pass numpy, never device values)
                 -cap, cap).astype(np.float32)
    if wu.shape != (F, F) or bu.shape != (F,):
        raise ValueError(
            "GNN weights must be W [F, F] and b [F] at F=%d; got %s "
            "and %s" % (F, wu.shape, bu.shape))
    return wu, bu


def snap_features(feats, vb: int, F: int) -> np.ndarray:
    """Snap real-valued per-vertex features onto the storage lattice:
    2^-5 grid, clipped to [0, UNIT_CAP] units ([0, ~16) values).
    Accepts [n, F] for n ≤ vb; missing rows stay zero."""
    f = np.asarray(feats, np.float64)  # gslint: disable=host-sync (host-input normalization: callers pass numpy, never device values)
    if f.ndim != 2 or f.shape[1] != F or f.shape[0] > vb:
        raise ValueError(
            "features must be [n ≤ vb=%d, F=%d]; got %s"
            % (vb, F, f.shape))
    units = np.clip(np.rint(f * (1 << Q_BITS)), 0,
                    UNIT_CAP).astype(np.float32)
    slab = np.zeros((vb + 1, F), np.float32)
    slab[:units.shape[0]] = units
    return slab


def default_features(vb: int, F: int, seed: int = 0) -> np.ndarray:
    """Deterministic small-integer feature slab for benches/tests:
    units in [0, 8) so a few rounds of aggregation stay informative
    before the cap saturates."""
    rng = np.random.RandomState(seed)
    slab = np.zeros((vb + 1, F), np.float32)
    slab[:vb] = rng.randint(0, 8, size=(vb, F)).astype(np.float32)
    return slab


def default_weights(F: int):
    """Identity layer at value 1.0 (32 lattice units) with zero bias —
    the out-of-the-box round is pure clipped message accumulation."""
    return np.eye(F, dtype=np.float32), np.zeros(F, np.float32)


def _wrap_i32(total) -> np.ndarray:
    """Two's-complement int32 wrap of an exact int64 sum — the host
    twin's form of the device's native wrapping int32 accumulation
    (modular addition is order-free, which is the whole point of the
    checksum)."""
    return np.asarray(total, np.int64).astype(np.int32)  # gslint: disable=host-sync (host twin arithmetic: numpy-on-numpy, no device value in sight)


def _build_gnn_round(eb: int, vb: int, F: int, act: str):
    """One window's XLA round: (h, W, b, s, d, v) ->
    (h', (max_feat, active, checksum, msg_edges))."""
    sent = vb
    sh = agg_shift(eb)
    sc = np.float32(2.0 ** -sh)
    cap = np.float32(UNIT_CAP)
    actf = _ACTS_JNP[act]

    def round_(h, W, b, s, d, v):
        s = jnp.where(v, s, sent)
        d = jnp.where(v, d, sent)
        msgs = h[s]                      # [eb, F]; sentinel row is 0
        if sh:
            msgs = jnp.floor(msgs * sc)
        m = jax.ops.segment_sum(msgs, d, num_segments=vb + 1)
        p = jnp.minimum(h + jnp.minimum(m, cap), cap)
        z = jax.lax.dot_general(
            p, W, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST) + b
        h2 = jnp.clip(actf(z), 0.0, cap)
        h2 = h2.at[sent].set(0.0)
        # EMPTY windows hold the slab: no messages, no layer tick.
        # This is what makes window-axis padding inert — the chunk
        # loop and the cohort both right-pad dispatches with
        # all-invalid windows, and unlike the analytics monoids a GNN
        # round is NOT a no-op on empty input (the dense layer would
        # still fire on the carry). A select between two exact slabs
        # keeps bit-exactness.
        h2 = jnp.where(jnp.any(v), h2, h)
        maxf = jnp.max(h2[:vb]).astype(jnp.int32)
        active = jnp.sum(jnp.any(h2[:vb] > 0, axis=1),
                         dtype=jnp.int32)
        checksum = jnp.sum(h2.astype(jnp.int32), dtype=jnp.int32)
        nmsg = jnp.sum(v, dtype=jnp.int32)
        return h2, (maxf, active, checksum, nmsg)

    return round_


def _build_gnn_scan(eb: int, vb: int, F: int, act: str,
                    pallas_ok: bool = True):
    """The per-window body the scan engines fold:
    body(h, W, b, (s, d, v)) -> (h', ys). When the fused Pallas GNN
    kernel is selected (ops/pallas_window.resolve_gnn_pallas —
    GS_GNN_PALLAS=on) AND its build/trace probe succeeds, the
    returned body is the kernel instead: one pallas_call per window
    streaming the edge slab through VMEM with the feature slab
    resident — the features
    ride the same single HBM read as the megakernel's analytics.
    `pallas_ok=False` keeps the cohort's vmapped composition pure-XLA
    (same opt-out as scan_analytics.build_cohort_scan)."""
    if pallas_ok:
        from . import pallas_window

        got = pallas_window.maybe_gnn_body(eb, vb, F, act)
        if got is not None:
            return got

    round_ = _build_gnn_round(eb, vb, F, act)

    def body(h, W, b, xs):
        s, d, v = xs
        return round_(h, W, b, s, d, v)

    return body


def build_gnn_cohort_scan(eb: int, vb: int, F: int, act: str):
    """N tenants' GNN windows in ONE vmapped dispatch: carries stack
    [N, vb+1, F], slabs [N, W, eb], the (shared) weights broadcast.
    Both padding axes are inert by the round's empty-window-holds
    rule (all-invalid windows leave the slab untouched — see
    _build_gnn_round), so ragged cohorts right-pad to power-of-two
    (tenants, windows) buckets and reuse O(log N × log W) programs;
    the padded rows' summary outputs are dropped by the dispatcher.
    The single-tenant body builds with pallas_ok=False — a vmapped
    fallback must never smuggle a pallas_call through the XLA path
    (the cohort-Pallas rung is its own future kernel)."""
    body = _build_gnn_scan(eb, vb, F, act, pallas_ok=False)

    def one_tenant(carry, W, b, src_w, dst_w, valid_w):
        def step(h, xs):
            return body(h, W, b, xs)

        return jax.lax.scan(step, carry, (src_w, dst_w, valid_w))

    def run(carries, W, b, src, dst, valid):
        return jax.vmap(
            one_tenant,
            in_axes=(0, None, None, 0, 0, 0))(carries, W, b,
                                              src, dst, valid)

    return run


class GnnEngineBase(SummaryEngineBase):
    """Shared GNN engine scaffolding over SummaryEngineBase: the
    [vb+1, F] feature-slab carry, snapped-weight management, the GNN
    summary assembly, and the checkpoint layout (carry + `gnn`
    section). The chunk loop, WAL/replay, auto-checkpoint and the
    ingress pipeline are the base's, unchanged — a GNN stream gets
    the same durability contracts as the analytics engines."""

    AUTOTUNE = False
    TUNABLE_INGRESS = False
    ingress = "standard"
    METRICS_TIER = "gnn_scan"

    def _configure(self, edge_bucket: int, vertex_bucket: int,
                   feature_dim, activation) -> None:
        self.eb = seg_ops.bucket_size(edge_bucket)
        self.vb = seg_ops.bucket_size(vertex_bucket)
        self.F = int(feature_dim if feature_dim
                     else knobs.get_int("GS_GNN_F"))
        self.act = str(activation if activation
                       else (knobs.get_str("GS_GNN_ACT") or "relu"))
        if self.act not in _ACTS_JNP:
            raise ValueError(
                "unknown GNN activation %r (exact-parity choices: "
                "%s)" % (self.act, sorted(_ACTS_JNP)))
        if not (1 <= self.F <= 256):
            raise ValueError("feature_dim %d out of range [1, 256]"
                             % self.F)
        self._w_units, self._b_units = snap_weights(
            *default_weights(self.F), self.F)

    # -- weights / features -------------------------------------------
    def set_weights(self, W, b=None) -> None:
        """Adopt a dense-update layer, SNAPPED onto the lattice (see
        module docstring — arbitrary float weights would void the
        bit-exactness contract). Never recompiles: weights are
        dispatch arguments, not trace constants."""
        if b is None:
            b = np.zeros(self.F, np.float32)
        self._w_units, self._b_units = snap_weights(W, b, self.F)
        self._weights_changed()

    def _weights_changed(self) -> None:
        """Device engines refresh their on-device weight copies."""

    def weights(self):
        """(W_units, b_units) — the snapped lattice representation."""
        return self._w_units.copy(), self._b_units.copy()

    def load_features(self, feats) -> None:
        """Seed the per-vertex feature slab (real values, snapped).
        Only legal at a window boundary — mid-window the carry covers
        dispatched-but-undelivered state."""
        slab = snap_features(feats, self.vb, self.F)
        self._carry = (self._to_carry(slab),)

    def load_feature_units(self, slab: np.ndarray) -> None:
        """Adopt a prebuilt [vb+1, F] unit slab (e.g.
        default_features) without re-snapping."""
        slab = np.asarray(slab, np.float32)  # gslint: disable=host-sync (host-input normalization: callers pass numpy, never device values)
        if slab.shape != (self.vb + 1, self.F):
            raise ValueError("unit slab must be [vb+1=%d, F=%d]; got "
                             "%s" % (self.vb + 1, self.F, slab.shape))
        self._carry = (self._to_carry(slab),)

    # -- carry / checkpoint -------------------------------------------
    def _init_carry(self):
        return (jnp.zeros((self.vb + 1, self.F), jnp.float32),)

    def state(self) -> np.ndarray:
        """[vb, F] feature snapshot in lattice units."""
        (h,) = self._carry
        return np.asarray(h)[: self.vb].copy()  # gslint: disable=host-sync (sanctioned snapshot boundary: the engine's state() d2h)

    def state_dict(self) -> dict:
        state = super().state_dict()
        state["gnn"] = {
            "feat_dim": self.F,
            "act": self.act,
            "weights": self._w_units.copy(),
            "bias": self._b_units.copy(),
        }
        return state

    def load_state_dict(self, state: dict) -> None:
        g = state.get("gnn") or {}
        if int(g.get("feat_dim", self.F)) != self.F:  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
            raise ValueError(
                "feature-width mismatch: checkpoint carries F=%s, "
                "engine runs F=%d — the [vb+1, F] slab layout would "
                "shift" % (g.get("feat_dim"), self.F))
        act = g.get("act")
        if act is not None and act != self.act:
            raise ValueError(
                "activation mismatch: checkpoint was folded with "
                "act=%r, engine runs act=%r — replayed windows would "
                "diverge from the journal" % (act, self.act))
        super().load_state_dict(state)
        if g.get("weights") is not None:
            self._w_units = np.asarray(g["weights"],  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
                                       np.float32).copy()
            self._b_units = np.asarray(g["bias"], np.float32).copy()  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
            self._weights_changed()

    # -- summary assembly ---------------------------------------------
    def _finalize_summaries(self, item, src, dst, out: list) -> None:
        f_at, f_real, raw = item
        maxf, active, csum, nmsg = (
            x[:f_real] for x in self._materialize(raw))
        for w in range(f_real):
            out.append({
                "max_feat": int(maxf[w]),  # gslint: disable=host-sync (numpy-on-numpy after _materialize)
                "active_vertices": int(active[w]),  # gslint: disable=host-sync (numpy-on-numpy after _materialize)
                "feat_checksum": int(csum[w]),  # gslint: disable=host-sync (numpy-on-numpy after _materialize)
                "msg_edges": int(nmsg[w]),  # gslint: disable=host-sync (numpy-on-numpy after _materialize)
            })
        if latency.enabled():
            st = self._lat_stamps.pop(f_at, None)
            lane = self._lat_lane or self._wal_tenant
            for w in range(f_real):
                lo_w = (f_at + w) * self.eb
                latency.on_window(
                    lane,
                    edges=min(lo_w + self.eb, len(src)) - lo_w,
                    st=st, ordinal=self.windows_done + w,
                    defer=self._lat_defer)
        if provenance.armed():
            # same cursor arithmetic as the scan-family emitter: the
            # recorded span is what replay must stream to re-derive
            # exactly this summary (windows_done × eb contract)
            tenant = self._lat_lane or self._wal_tenant
            for w in range(f_real):
                lo = (self.windows_done + w) * self.eb
                lo_c = (f_at + w) * self.eb
                n_w = min(lo_c + self.eb, len(src)) - lo_c
                provenance.emit(
                    tenant=tenant, window=self.windows_done + w,
                    wal_lo=lo, wal_hi=lo + n_w,
                    tier=self.METRICS_TIER, program="gnn_round",
                    summary=out[len(out) - f_real + w])
        self.windows_done += f_real
        lo_e = f_at * self.eb
        metrics.mark_window(
            f_real, min((f_at + f_real) * self.eb, len(src)) - lo_e,
            engine=type(self).__name__, tier=self.METRICS_TIER)

    def _redo(self, src, dst, b_ovf: int, k_ovf: int) -> int:
        return 0  # no overflow concept: the GNN fold is always exact

    def warm_fallback(self) -> None:
        """No escalation path to warm — the GNN round has no overflow
        recount."""


class GnnSummaryEngine(GnnEngineBase):
    """Single-chip windowed GNN rounds, one dispatch per MAX_WINDOWS
    windows (a `lax.scan` over the chunk's [W, eb] slabs against the
    device-resident feature slab). The body is the XLA
    gather/segment-sum round, or the fused Pallas GNN kernel when
    `ops/pallas_window.resolve_gnn_pallas` selects it — bit-identical
    by the lattice argument either way."""

    def __init__(self, edge_bucket: int, vertex_bucket: int,
                 feature_dim: int = None, activation: str = None):
        self._configure(edge_bucket, vertex_bucket, feature_dim,
                        activation)
        body = _build_gnn_scan(self.eb, self.vb, self.F, self.act)
        self._pallas = bool(getattr(body, "gnn_pallas", False))

        @jax.jit
        def run(carry, W, b, src_w, dst_w, valid_w):
            def step(h, xs):
                return body(h, W, b, xs)

            return jax.lax.scan(step, carry, (src_w, dst_w, valid_w))

        # compile-watch + cost-observatory label: dispatches tag
        # their ledger spans program="gnn_scan" (or "gnn_pallas"),
        # joining the analytic slab model pallas_window registers
        self._run = metrics.wrap_jit(
            "gnn_pallas" if self._pallas else "gnn_scan", run)
        from . import pallas_window

        pallas_window.register_gnn_cost_model(self.eb, self.vb,
                                              self.F)
        self._wdev = None
        self._bdev = None
        self.reset()

    def _weights_changed(self) -> None:
        self._wdev = jnp.asarray(self._w_units)
        self._bdev = jnp.asarray(self._b_units)

    def _dispatch_async(self, s, d, valid):
        if self._wdev is None:
            self._weights_changed()
        (h,) = self._carry
        h, outs = self._run(h, self._wdev, self._bdev,
                            jnp.asarray(s), jnp.asarray(d),
                            jnp.asarray(valid))
        self._carry = (h,)
        return outs

    def _materialize(self, raw):
        return tuple(np.array(x) for x in raw)  # gslint: disable=host-sync (sanctioned finalize boundary: the engine's ONE batched d2h per chunk)


class GnnResidentEngine(GnnSummaryEngine):
    """Resident-tier rung of the GNN workload: the same scan program
    re-jitted with the feature-slab carry DONATED
    (ops/resident_engine.donate_kw — in-place slab updates where the
    backend honors donation, bit-identical undonated elsewhere) and a
    super-batch chunk size (GS_RESIDENT_SPB buckets), so a deep queue
    of windows costs one donated dispatch instead of many."""

    METRICS_TIER = "gnn_resident"

    def __init__(self, edge_bucket: int, vertex_bucket: int,
                 feature_dim: int = None, activation: str = None,
                 superbatch: int = None):
        super().__init__(edge_bucket, vertex_bucket, feature_dim,
                         activation)
        from . import resident_engine

        self.MAX_WINDOWS = seg_ops.bucket_size(
            superbatch if superbatch
            else resident_engine.resident_spb(self.eb))
        body = _build_gnn_scan(self.eb, self.vb, self.F, self.act)
        self._pallas = bool(getattr(body, "gnn_pallas", False))

        def run(carry, W, b, src_w, dst_w, valid_w):
            def step(h, xs):
                return body(h, W, b, xs)

            return jax.lax.scan(step, carry, (src_w, dst_w, valid_w))

        self._run = metrics.wrap_jit(
            "gnn_resident",
            jax.jit(run, **resident_engine.donate_kw()))
        self.reset()

    def _dispatch_async(self, s, d, valid):
        if self._wdev is None:
            self._weights_changed()
        (h,) = self._carry
        # the donated carry is CONSUMED by the dispatch; the returned
        # slab replaces it (same discipline as ResidentSummaryEngine)
        h, outs = self._run(h, self._wdev, self._bdev,
                            jnp.asarray(s), jnp.asarray(d),
                            jnp.asarray(valid))
        self._carry = (h,)
        return outs

    def state_dict(self) -> dict:
        # materializing the donated carry for a checkpoint must not
        # invalidate it: np.array copies d2h, the device slab stays
        # live for the next dispatch
        return super().state_dict()


class GnnHostEngine(GnnEngineBase):
    """Numpy twin of the GNN engines — the bit-exactness oracle and
    demotion floor: the same SummaryEngineBase chunk loop, window
    cuts, checkpoint layout and summary dicts, with the device round
    replayed per window in numpy (`np.add.at` aggregation, BLAS
    float32 matmul — exact by the lattice argument), no compiler and
    no device. Loadable straight from a GnnSummaryEngine (or
    resident) checkpoint of equal buckets and feature width."""

    METRICS_TIER = "host"

    def __init__(self, edge_bucket: int, vertex_bucket: int,
                 feature_dim: int = None, activation: str = None):
        self._configure(edge_bucket, vertex_bucket, feature_dim,
                        activation)
        self.reset()

    @classmethod
    def from_state(cls, state: dict) -> "GnnHostEngine":
        """Build a twin directly from a GNN engine checkpoint and
        adopt it — the gnn→host demotion hand-off."""
        g = state.get("gnn") or {}
        twin = cls(edge_bucket=int(state["edge_bucket"]),  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
                   vertex_bucket=int(state["vertex_bucket"]),  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
                   feature_dim=int(g.get("feat_dim") or 0) or None,
                   activation=g.get("act"))
        twin.load_state_dict(state)
        return twin

    def _init_carry(self):
        return (np.zeros((self.vb + 1, self.F), np.float32),)

    def _to_carry(self, a):
        return np.asarray(a, np.float32).copy()  # gslint: disable=host-sync (host twin: checkpoint carries are host numpy by construction)

    def _h2d(self, args):
        return args

    def _dispatch_async(self, s, d, valid):
        vb, F = self.vb, self.F
        sh = agg_shift(self.eb)
        sc = np.float32(2.0 ** -sh)
        cap = np.float32(UNIT_CAP)
        actf = _ACTS_NP[self.act]
        (h,) = self._carry
        h = h.copy()
        s = np.asarray(s)  # gslint: disable=host-sync (host twin: pipeline payloads are numpy by _h2d identity)
        d = np.asarray(d)  # gslint: disable=host-sync (host twin: pipeline payloads are numpy by _h2d identity)
        valid = np.asarray(valid)  # gslint: disable=host-sync (host twin: pipeline payloads are numpy by _h2d identity)
        num_w = s.shape[0]
        maxf = np.zeros(num_w, np.int32)
        active = np.zeros(num_w, np.int32)
        csum = np.zeros(num_w, np.int32)
        nmsg = np.zeros(num_w, np.int32)
        for i in range(num_w):
            v = valid[i]
            if v.any():
                si = np.where(v, s[i], vb).astype(np.int64)
                di = np.where(v, d[i], vb).astype(np.int64)
                msgs = h[si]
                if sh:
                    msgs = np.floor(msgs * sc)
                m = np.zeros((vb + 1, F), np.float32)
                np.add.at(m, di, msgs)
                p = np.minimum(h + np.minimum(m, cap), cap)
                z = p @ self._w_units + self._b_units
                h = np.clip(actf(z), 0.0, cap).astype(np.float32)
                h[vb] = 0.0
            # else: EMPTY window holds the slab (the device round's
            # rule — padding inertness and parity depend on it)
            maxf[i] = np.int32(h[:vb].max())
            active[i] = np.int32(np.sum(np.any(h[:vb] > 0, axis=1)))
            # exact int64 total, wrapped to the device's native
            # wrapping-int32 accumulation (order-free mod 2^32)
            csum[i] = _wrap_i32(h.astype(np.int64).sum())
            nmsg[i] = np.int32(np.sum(v))
        self._carry = (h,)
        return maxf, active, csum, nmsg

    def _materialize(self, raw):
        return tuple(np.asarray(x) for x in raw)  # gslint: disable=host-sync (host twin: raw outputs are already numpy)
