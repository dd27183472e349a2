"""Pure-numpy host tier of the driver's batched snapshot analytics —
the LAST rung of the tier-demotion ladder (device scan → native C++ →
host numpy, core/driver._maybe_demote).

Same contract as native.snapshot_windows (which is itself shaped like
the device scan's `outs`): window w is the [offsets[w], offsets[w+1])
slice of the flat COO arrays; the caller-owned carried arrays
(`deg`/`cc`/`cov`, the driver's host-mirror layouts) are updated in
place; per-window snapshot stacks come back as {"deg": [W, vb] int32,
"labels": [W, vb] int32, "odd": [W, vb] bool}, the cover as its odd
flag (v and vb + v in one cover component), plus the chunk's final
cover labels once, as a fresh copy, {"cover_final": [2·vb] int32}.

Bit-exactness across tiers is by CONSTRUCTION, not coincidence: the
carried min-label semantics (ops/unionfind.cc_fixpoint with
carried=True) converge to the canonical labeling — every vertex maps
to the smallest vertex index reachable through this window's edges
plus the carried forest links (v, labels0[v]) — which is unique
whatever the iteration schedule. `_fixpoint` below replays the same
scatter-min + root-hook + pointer-jump rounds in numpy, so checkpoints
and mid-stream demotions carry state across tiers without any
translation (the tier-interchangeability the checkpoint round-trip
suite pins).

This tier exists for availability, not speed: it needs no compiler, no
device, no libgsnative.so — only numpy. A stream that lands here is
degraded and LABELED as such (resilience.record_demotion →
PERF.json's `degradations` section); it is never a measurement tier.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def _fixpoint(labels: np.ndarray, s: np.ndarray,
              d: np.ndarray) -> np.ndarray:
    """Carried min-label fixpoint (numpy twin of
    unionfind.cc_fixpoint(carried=True)): scatter-min each edge's
    smaller label to both endpoints and both endpoints' roots, then
    pointer-jump, until stable. The carried forest's parent links
    participate as edges (see cc_fixpoint's docstring for why dropping
    them can split a component)."""
    n = len(labels)
    src = np.concatenate([s, np.arange(n, dtype=np.int64)])
    dst = np.concatenate([d, labels.astype(np.int64)])
    while True:
        ls = labels[src]
        ld = labels[dst]
        m = np.minimum(ls, ld)
        new = labels.copy()
        np.minimum.at(new, src, m)
        np.minimum.at(new, dst, m)
        np.minimum.at(new, ls, m)
        np.minimum.at(new, ld, m)
        new = new[new]
        if np.array_equal(new, labels):
            return new
        labels = new


def snapshot_windows(src: np.ndarray, dst: np.ndarray,
                     offsets: np.ndarray, vb: int,
                     deg: Optional[np.ndarray] = None,
                     cc: Optional[np.ndarray] = None,
                     cov: Optional[np.ndarray] = None
                     ) -> Dict[str, np.ndarray]:
    """Host-tier carried-state windowed snapshots; see module
    docstring for the contract (identical to native.snapshot_windows).
    """
    src = np.ascontiguousarray(src, np.int64)
    dst = np.ascontiguousarray(dst, np.int64)
    offsets = np.ascontiguousarray(offsets, np.int64)
    num_w = len(offsets) - 1
    if num_w < 0 or int(offsets[-1]) != len(src):
        raise ValueError("offsets must span the flat edge arrays")
    for name, a, ln in (("deg", deg, vb), ("cc", cc, vb),
                        ("cov", cov, 2 * vb)):
        if a is not None and (a.dtype != np.int32 or len(a) != ln):
            raise ValueError("carried %s must be int32[%d]"
                             % (name, ln))
    out: Dict[str, np.ndarray] = {}
    od = np.empty((num_w, vb), np.int32) if deg is not None else None
    oc = np.empty((num_w, vb), np.int32) if cc is not None else None
    oo = np.empty((num_w, vb), bool) if cov is not None else None
    for w in range(num_w):
        lo, hi = int(offsets[w]), int(offsets[w + 1])
        s, d = src[lo:hi], dst[lo:hi]
        if deg is not None:
            np.add.at(deg, s, 1)
            np.add.at(deg, d, 1)
            od[w] = deg
        if cc is not None:
            cc[:] = _fixpoint(cc, s, d)
            oc[w] = cc
        if cov is not None:
            cov[:] = _fixpoint(cov, np.concatenate([s, s + vb]),
                               np.concatenate([d + vb, d]))
            np.equal(cov[:vb], cov[vb:], out=oo[w])
    if od is not None:
        out["deg"] = od
    if oc is not None:
        out["labels"] = oc
    if oo is not None:
        out["odd"] = oo
        out["cover_final"] = cov.copy()
    return out
