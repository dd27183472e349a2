"""Time the snapshot scan's relabel on the device, at the benchmark's
table sizes: the whole-table gather `lab[labels0]` against the relabel
through the roots that moved (ops/unionfind._relabel_moved) at several
counts of moved roots, on a flat, min-rooted labelling of the
benchmark's own stream (CC 2^20 + 1 slots, double cover 2^21 + 1).
What sets `unionfind.RELABEL_K_MAX`.

    python tools/relabel_probe.py [--calls 8] [--out chiprun_out/relabel_probe.json]

Each reading chains `--chain` relabels (or gathers) in one program and
reports the median milliseconds of one over `--reps` calls, less the
median of an empty call on the same table. Needs a chip: on a CPU it
exits 3 without a reading."""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def labelling(vb: int, calls: int, seed: int):
    """(cc labels [vb+1], cover labels [2vb+1]): the flat, min-rooted
    labellings of the first `calls` 2^18-edge calls of the benchmark's
    stream, slots in arrival order, as the driver carries them."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    from benchmark import streams

    src, dst = streams.make_stream(calls << 18, vb, seed=seed)
    ids, first = np.unique(np.stack([src, dst], 1).ravel(), return_index=True)
    slot = np.empty(vb, np.int64)
    slot[ids[np.argsort(first)]] = np.arange(len(ids))
    s, d = slot[src], slot[dst]

    def labels(n, a, b):
        g = coo_matrix((np.ones(len(a)), (a, b)), shape=(n, n))
        _, comp = connected_components(g, directed=False)
        least = np.full(comp.max() + 1, n, np.int64)
        np.minimum.at(least, comp, np.arange(n))
        return least[comp].astype(np.int32)

    return (labels(vb + 1, s, d),
            labels(2 * vb + 1, np.concatenate([s, s + vb]),
                   np.concatenate([d + vb, d])))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--vb", type=int, default=1 << 20)
    ap.add_argument("--edges", type=int, default=32768,
                    help="a window's edges (the touched list is 2x, 4x)")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=2236067977)
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--counts", default="0,1,128,129,256,512,513")
    ap.add_argument("--out", default="chiprun_out/relabel_probe.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops import unionfind

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("relabel_probe: no TPU (%s); no reading" % dev.platform,
              file=sys.stderr)
        return 3
    rows = []
    for table, labels0 in zip(("cc", "cover"),
                              labelling(args.vb, args.calls, args.seed)):
        e2 = (2 if table == "cc" else 4) * args.edges
        # roots but the least, those with the most members first
        roots, size = np.unique(labels0, return_counts=True)
        movable = roots[1:][np.argsort(-size[1:], kind="stable")]
        lab0 = jnp.asarray(labels0)

        def timed(fn, *xs):
            jax.block_until_ready(fn(*xs))
            ts = []
            for _ in range(args.reps):
                t = time.perf_counter()
                jax.block_until_ready(fn(*xs))
                ts.append(time.perf_counter() - t)
            return statistics.median(ts)

        chain = args.chain
        empty = timed(jax.jit(lambda x: x + 1), lab0)
        gather = jax.jit(lambda x, l0: jax.lax.fori_loop(
            0, chain, lambda i, y: y[l0], x))
        t_gather = (timed(gather, lab0, lab0) - empty) / chain
        rows.append({"table": table, "slots": len(labels0), "what": "gather",
                     "ms": 1e3 * t_gather})
        for count in map(int, args.counts.split(",")):
            pick = movable[:count]
            touched = np.full(e2, labels0[0], np.int32)   # the least root
            touched[:count] = pick
            new = touched.copy()
            new[:count] = 0
            lab = labels0.copy()
            lab[pick] = 0
            relabel = jax.jit(lambda x, l0, t, n: jax.lax.fori_loop(
                0, chain, lambda i, y: unionfind._relabel_moved(
                    l0, y, t, n, n != t)[0], x))
            xs = (jnp.asarray(lab), lab0, jnp.asarray(touched),
                  jnp.asarray(new))
            got = np.asarray(relabel(*xs))
            assert np.array_equal(got, lab[labels0]), (table, count)
            t = (timed(relabel, *xs) - empty) / chain
            rows.append({"table": table, "slots": len(labels0),
                         "what": "relabel", "count": count,
                         "touched": e2, "ms": 1e3 * t})
    out = {"device": dev.device_kind, "k_max": unionfind.RELABEL_K_MAX,
           "block": unionfind.RELABEL_BLOCK, "rows": rows}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    for r in rows:
        print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
