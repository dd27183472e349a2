"""The plain reference: numpy and scipy only, nothing of the program.

`ref_triangles` and `_min_member` are verbatim copies of PR 21's
`chip_smoke.py`. Its per-edge Python union-find (`_ParityUnionFind`)
is too slow to follow a run's whole stream inside the window, so the
same semantics are computed here by connected components: the
cumulative components of the graph, and for bipartiteness those of its
double cover (vertex v as v⁺ and v⁻, each edge {a, b} as a⁺b⁻ and
a⁻b⁺), where a component holds an odd cycle exactly when v⁺ and v⁻ meet.
The state is chained from window to window through each vertex's
component label, so a whole stream costs one pass over its edges.
"""

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


def ref_triangles(s: np.ndarray, d: np.ndarray) -> int:
    """Exact triangles of the window's simple undirected graph:
    distinct loop-free edges oriented by (degree, id), each triangle
    found once as a wedge u→v, u→w closed by the edge {v, w}."""
    s = np.asarray(s, np.int64)
    d = np.asarray(d, np.int64)
    keep = s != d
    s, d = s[keep], d[keep]
    if not len(s):
        return 0
    n = int(max(s.max(), d.max())) + 1
    und = np.unique(np.minimum(s, d) * n + np.maximum(s, d))
    lo, hi = und // n, und % n
    deg = np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n)
    rank = deg * n + np.arange(n)
    fwd = rank[lo] < rank[hi]
    a = np.where(fwd, lo, hi)
    b = np.where(fwd, hi, lo)
    order = np.lexsort((b, a))
    a, b = a[order], b[order]
    starts = np.flatnonzero(np.r_[True, a[1:] != a[:-1]])
    sizes = np.diff(np.r_[starts, len(a)])
    total = 0
    for k in np.unique(sizes[sizes > 1]):
        seg = starts[sizes == k]
        i, j = np.triu_indices(int(k), 1)
        v = b[seg[:, None] + i[None, :]].ravel()
        w = b[seg[:, None] + j[None, :]].ravel()
        key = np.minimum(v, w) * n + np.maximum(v, w)
        total += int(np.isin(key, und, assume_unique=False).sum())
    return total


def _min_member(labels: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Each vertex's component named by its smallest member id, so two
    labelings compare as partitions, not as label values."""
    _, inv = np.unique(labels, return_inverse=True)
    low = np.full(inv.max() + 1, np.iinfo(np.int64).max, np.int64)
    np.minimum.at(low, inv, ids)
    return low[inv]


def _components(labels: np.ndarray, s: np.ndarray,
                d: np.ndarray) -> np.ndarray:
    """Components after adding edges (s, d) to a graph whose components
    so far are `labels` (each vertex labelled by its component's
    smallest vertex). Returns the same smallest-vertex labelling."""
    n = len(labels)
    idx = np.arange(n)
    keep = labels != idx
    rows = np.concatenate([s, idx[keep]])
    cols = np.concatenate([d, labels[keep]])
    g = coo_matrix((np.ones(len(rows), np.int8), (rows, cols)),
                   shape=(n, n))
    _, cc = connected_components(g, directed=False)
    low = np.full(cc.max() + 1, n, np.int64)
    np.minimum.at(low, cc, idx)
    return low[cc]


def fold_windows(src, dst, eb: int, nv: int, windows) -> dict:
    """The reference's record of each count window in `windows`
    (indices into the stream's eb-sized windows): touched ids in
    ascending order, their cumulative degrees, component (smallest
    member id) and odd-cycle flag, and the window's own triangles."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    deg = np.zeros(nv, np.int64)
    comp = np.arange(nv)
    cover = np.arange(2 * nv)
    done = 0
    out = {}
    for w in sorted(set(int(x) for x in windows)):
        s, d = src[done:(w + 1) * eb], dst[done:(w + 1) * eb]
        done = (w + 1) * eb
        deg += np.bincount(s, minlength=nv) + np.bincount(d, minlength=nv)
        comp = _components(comp, s, d)
        cover = _components(cover, np.concatenate([s, s + nv]),
                            np.concatenate([d + nv, d]))
        ids = np.flatnonzero(deg)
        out[w] = {"ids": ids, "deg": deg[ids],
                  "comp": _min_member(comp[ids], ids),
                  "odd": cover[ids] == cover[ids + nv],
                  "triangles": ref_triangles(src[w * eb:(w + 1) * eb],
                                             dst[w * eb:(w + 1) * eb])}
    return out


def summaries(src, dst, eb: int, nv: int, windows) -> dict:
    """A served tenant's per-window summaries: max cumulative degree,
    components and odd cycle among touched vertices, and the window's
    triangles (the cohort's summary, PR 21's `ref_summaries`)."""
    return {w: {"max_degree": int(r["deg"].max()),
                "num_components": len(np.unique(r["comp"])),
                "odd_cycle": bool(r["odd"].any()),
                "triangles": r["triangles"]}
            for w, r in fold_windows(src, dst, eb, nv, windows).items()}
