"""Delta-compacted d2h egress: ship per-window CHANGED slots, not
whole snapshot vectors.

The batched snapshot scan (core/driver._build_snapshot_scan) d2h's a
full [W, vb] stack per analytic per chunk (int32 degrees and labels,
the bool odd flag), and the windowed
reduce's monoid device tier a full [W, vb+1] cells+counts pair — even
though the delta masks the scan already computes (emit_deltas) know
how few entries actually changed, and a reduce window touches at most
one cell per contribution. Where the stream is transfer-bound,
egress bytes sit on the critical path exactly like ingress bytes; this
module is the egress twin of ops/compact_ingress.

Wire format, per window: an int32 changed count, an int32 index row
[cap], and a value row [cap] (dtype per analytic), produced ON DEVICE
by `compact_changed` (jnp.nonzero with a static size — the compaction
fuses into the same scan program). The host reconstructs full
read-only snapshots by applying each window's (idx, vals) pairs to its
carried mirrors — bit-identical to the full-vector extraction, because
a changed-mask applied to the previous snapshot IS the next snapshot.

`cap` bounds the per-window changed set. Degrees can change at most
2·eb slots per window (two endpoints per edge), so cap = min(2·eb, vb)
is exact for them; CC/cover labels can cascade past any cap < vb
(a big component relabeling), so a window whose count EXCEEDS the cap
marks its chunk for the host-fold fallback (ops/host_snapshot — the
bit-exact twin the demotion ladder already trusts), keeping results
exact at every cap. GS_EGRESS_CAP shrinks the cap below the exact
bound when the A/B shows a tighter wire wins net of rare refolds.

Full-vector egress is the default and the fallback everywhere;
`resolve_egress` returns "delta" only when GS_EGRESS pins it. The
sharded engines keep full-vector egress (their snapshots ride
replicated outputs, and the mesh path has no AOT warm cache).
"""

from __future__ import annotations

import numpy as np

from ..utils import knobs


def resolve_egress() -> str:
    """The d2h egress format of the batched snapshot/reduce paths:
    "delta" when GS_EGRESS pins it, "full" otherwise."""
    return "delta" if knobs.get_str("GS_EGRESS") == "delta" else "full"


def egress_cap(eb: int, vb: int) -> int:
    """Per-window changed-slot capacity of the delta wire:
    min(2·eb, vb) — exact for degrees, a fallback-guarded bound for
    label cascades — unless GS_EGRESS_CAP narrows it (never below 1,
    never above vb)."""
    cap = min(2 * eb, vb)
    pinned = knobs.get_int("GS_EGRESS_CAP")
    if pinned is not None:
        cap = min(pinned, vb)
    return cap


def compact_changed(mask, new_vals, cap: int, pad_idx: int):
    """The ONE device-side encode of the delta wire (jax-traceable):
    (changed count, changed indices [cap] ascending, new values
    [cap]). `count` may EXCEED cap — the host detects truncation from
    it and refolds the chunk; padded index slots carry `pad_idx`
    (callers pass a row that exists, e.g. 0 — slots past `count` are
    never read)."""
    import jax.numpy as jnp

    idx = jnp.nonzero(mask, size=cap, fill_value=pad_idx)[0]
    idx = idx.astype(jnp.int32)
    return (jnp.sum(mask, dtype=jnp.int32), idx, new_vals[idx])


def compact_touched(cells, counts, cap: int):
    """Per-row device encode for PER-WINDOW (non-carried) reduce
    rows: (touched count, touched cell ids [cap] ascending, their
    cell values [cap], their edge counts [cap]). A window touches at
    most one cell per contribution, so `cap` = contributions-per-
    window is an EXACT bound — this wire never overflows. vmap it
    over a [wb, vbp] stack."""
    import jax.numpy as jnp

    m = counts > 0
    idx = jnp.nonzero(m, size=cap, fill_value=0)[0].astype(jnp.int32)
    return (jnp.sum(m, dtype=jnp.int32), idx, cells[idx], counts[idx])


def apply_delta(mirror: np.ndarray, cnt: int, idx: np.ndarray,
                vals: np.ndarray) -> None:
    """Host-side decode: scatter one window's (idx, vals) pairs into
    the carried mirror IN PLACE. The mirror then IS that window's
    snapshot over [:len(mirror)]."""
    k = int(cnt)
    mirror[idx[:k]] = vals[:k]


def scatter_full(vbp: int, cnt: int, idx: np.ndarray,
                 vals: np.ndarray, fill, dtype) -> np.ndarray:
    """Reconstruct one PER-WINDOW (non-carried) full row from its
    delta: `fill`-initialized, changed cells scattered — the windowed
    reduce's decode (its cells reset every window, so there is no
    mirror to carry)."""
    row = np.full(vbp, fill, dtype)
    k = int(cnt)
    row[idx[:k]] = vals[:k]
    return row
