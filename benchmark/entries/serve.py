"""Tenant streams served through `StreamServer` over `TenantCohort`,
run as `python -m gelly_streaming_tpu.core.serve` runs it: WAL and
checkpoints under the run's temporary directory, its own accept loop,
and the sync pump loop of `serve_until_drained` on the main thread
(wrapped here from outside in a trace span). This process holds the
chip; the load generator (benchmark/loadgen.py) is a child process that
never imports JAX.

Set-up admits the tenants over the wire and compiles every cohort
program shape a round can take. The window is the generator's open
loop; `window_p50_ms` and `window_p95_ms` are taken by the generator
from each window's due time to the receipt of its pushed summary.
"""

import json
import os
import subprocess
import sys
import threading
import time

FAULTS = ("state_unchanged", "half_batch", "altered", "control")


def _warm(cohort, vb: int, tenants: int) -> list:
    """Run every (tenants, windows) slab bucket the cohort program can
    take (`ops/segment.bucket_size`: powers of two from 8) once on
    padding, and the triangle recount kernel, so no round inside the
    window builds a program. The cohort keeps no public
    hook for this (PERF.md, Open questions)."""
    import jax.numpy as jnp
    import numpy as np

    from gelly_streaming_tpu.ops import segment as seg_ops
    from gelly_streaming_tpu.ops import triangles as tri_ops

    kb = seg_ops.bucket_size(tri_ops._tuned_kb(cohort.eb))
    shapes = []
    nb = seg_ops.bucket_size(1)     # slab rows: buckets of ready tenants
    while nb <= seg_ops.bucket_size(tenants):
        wb = seg_ops.bucket_size(1)  # windows per row
        while wb <= seg_ops.bucket_size(cohort.wc):
            carry = cohort._fresh_carry(vb)
            stacked = tuple(jnp.stack([leaf] * nb) for leaf in carry)
            s = np.full((nb, wb, cohort.eb), vb, np.int32)
            out = cohort._program(vb, kb, nb, wb)(
                stacked, jnp.asarray(s), jnp.asarray(s),
                jnp.zeros((nb, wb, cohort.eb), bool))
            np.asarray(out[1][0])
            # the per-row carry slices finalize takes
            for leaf in out[0]:
                leaf[nb - 1]
            shapes.append((nb, wb))
            wb *= 2
        nb *= 2
    cohort._redo_kernel(vb, kb).count(np.zeros(8, np.int32),
                                      np.ones(8, np.int32))
    return shapes


def _break(cohort, fault) -> None:
    """Faults for the control and fault tests, planted in the server."""
    import numpy as np

    if fault in ("half_batch", "control"):
        feed = cohort.feed

        def broken_feed(tid, src, dst, ts=None):
            src, dst = np.array(src), np.array(dst)
            if fault == "half_batch":       # half of each feed left out
                h = len(src) // 2
                src[h:2 * h], dst[h:2 * h] = src[:h], dst[:h]
            else:                           # at-least-once: an edge re-sent
                src[-1], dst[-1] = src[-2], dst[-2]
            return feed(tid, src, dst, ts=ts)

        cohort.feed = broken_feed
    else:
        pump = cohort.pump
        last = {}

        def broken_pump(*a, **k):
            out = pump(*a, **k)
            for tid, rows in out.items():
                for i, row in enumerate(rows):
                    if fault == "altered":
                        rows[i] = dict(row, triangles=row["triangles"] + 1)
                    elif tid in last:       # state left unchanged
                        rows[i] = last[tid]
                    last[tid] = row
            return out

        cohort.pump = broken_pump


def run(r) -> None:
    import jax

    from gelly_streaming_tpu.core.serve import StreamServer
    from gelly_streaming_tpu.core.tenancy import TenantCohort

    cfg, tr = r.cell.config, r.cell.traffic
    eb, vb = int(cfg["edge_bucket"]), int(cfg["vertex_bucket"])
    if r.fault is not None and r.fault not in FAULTS:
        raise SystemExit("unknown fault %r" % r.fault)
    cohort = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    cohort.enable_wal(os.path.join(r.tmp, "wal"))
    cohort.enable_auto_checkpoint(os.path.join(r.tmp, "ckpt"),
                                  every_n_windows=int(cfg["checkpoint_every_windows"]))
    shapes = _warm(cohort, vb, int(cfg["tenants"]))
    if r.fault:
        _break(cohort, r.fault)
    server = StreamServer(cohort, port=0).start()
    r.note(server={"port": server.port, "pump": server.pump_mode,
                   "warmed_shapes": shapes})

    spec = os.path.join(r.tmp, "loadgen.json")
    out_path = os.path.join(r.tmp, "loadgen-out.json")
    with open(spec, "w") as f:
        json.dump({"port": server.port, "seed": r.seed, "seconds": r.seconds,
                   "config": cfg, "traffic": tr, "out": out_path}, f)
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
    child = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "loadgen.py"), spec],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    said = {}
    events = {k: threading.Event() for k in ("ready", "t0", "closed", "done")}

    def listen():
        for line in child.stdout:
            line = line.strip()
            if line.startswith("{"):
                said.update(json.loads(line))
                key = next(iter(json.loads(line)))
            else:
                key = line
            if key in events:
                events[key].set()
        for e in events.values():   # the child ended: stop waiting
            e.set()

    reader = threading.Thread(target=listen, daemon=True)
    reader.start()

    backlog = []            # (seconds since go, full windows queued)

    def serve_until(event) -> None:
        # serve.StreamServer.serve_until_drained's sync loop
        while not event.is_set():
            now = time.monotonic()
            if "t0" in said and (not backlog or now - said["t0"]
                                 - backlog[-1][0] >= 0.5):
                backlog.append((now - said["t0"], sum(
                    t.queued // eb for t in list(cohort.tenants.values()))))
            if server._any_ready():
                with jax.profiler.TraceAnnotation("bench.pump"):
                    server.pump_once()
            else:
                time.sleep(0.02)

    try:
        serve_until(events["ready"])
        with r.window():
            w0 = server._stats["windows"]
            child.stdin.write("go\n")
            child.stdin.flush()
            serve_until(events["closed"])
            r.counters["windows_traced"] = server._stats["windows"] - w0
        serve_until(events["done"])
        r.read_memory()
    finally:
        child.stdin.close()
        child.wait(timeout=300)
        server.close()
    if child.returncode != 0 or not os.path.exists(out_path):
        raise RuntimeError("load generator failed (exit %s)" % child.returncode)
    with open(out_path) as f:
        got = json.load(f)
    r.setup_s = got["t0"] - r.t_start
    r.values["window_p50_ms"] = got["window_p50_ms"]
    r.values["window_p95_ms"] = got["window_p95_ms"]
    r.counters["feed_rtt_p95_ms"] = got["feed_rtt_p95_ms"]
    r.note(generator={k: got[k] for k in ("feeds", "refusals", "late_ms",
                                          "windows_timed", "reference_s")},
           server=dict(server._stats),
           backlog_windows=[[round(a, 2), b] for a, b in backlog])
    r.attempted = got["attempted"]
    r.failed = got["missing"] + got["wrong"] + got["duplicates"]
    r.checks.update(missing=(got["missing"], 0), wrong=(got["wrong"], 0),
                    duplicates=(got["duplicates"], 0))
