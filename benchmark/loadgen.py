"""The serve cell's load generator: a child process that never imports
JAX. It speaks the server's loopback wire (JSON lines, the protocol of
`core/serve.ServeClient`, whose module pulls JAX in through the
package), offers an open loop of feeds on a schedule drawn from the
seed, receives the pushed window summaries on a subscribed connection,
and afterwards compares every due window with the reference.

Talks to the parent over stdin/stdout: prints `ready` once connected
and admitted, starts the clock on `go`, prints `closed` when the last
feed has gone and `done` with the result file written.
"""

import json
import os
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference, streams  # noqa: E402


class Wire:
    """One connection: newline-delimited JSON requests and replies;
    pushed `event: window` rows go to `on_push`."""

    def __init__(self, port: int, on_push=None):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=120)
        self.buf = b""
        self.on_push = on_push

    def _line(self) -> dict:
        while True:
            nl = self.buf.find(b"\n")
            if nl >= 0:
                line, self.buf = self.buf[:nl], self.buf[nl + 1:]
                return json.loads(line)
            chunk = self.sock.recv(1 << 20)
            if not chunk:
                raise ConnectionError("server closed the connection")
            self.buf += chunk

    def request(self, **req) -> dict:
        self.sock.sendall((json.dumps(req) + "\n").encode())
        while True:
            resp = self._line()
            if resp.get("event") == "window" and self.on_push:
                self.on_push(resp)
                continue
            return resp

    def pushes(self, stop: threading.Event) -> None:
        self.sock.settimeout(0.5)
        while not stop.is_set():
            try:
                resp = self._line()
            except socket.timeout:
                continue
            except (ConnectionError, OSError):
                return
            if resp.get("event") == "window":
                self.on_push(resp)


def main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    cfg, tr = spec["config"], spec["traffic"]
    seconds, seed = float(spec["seconds"]), int(spec["seed"])
    eb, vb = int(cfg["edge_bucket"]), int(cfg["vertex_bucket"])
    feed = int(tr["feed_edges"])
    per_window = eb // feed
    tenants = int(cfg["tenants"])
    due, tenant = streams.serve_schedule(
        dict(tr, tenants=tenants,
             tenant_zipf_theta=cfg["tenant_zipf_theta"]), seconds, seed)
    counts = np.bincount(tenant, minlength=tenants)
    data = streams.tenant_streams(counts, feed, vb, seed)
    names = ["tenant-%02d" % i for i in range(tenants)]

    received = {}          # (tenant, window) -> [(t_recv, summary), ...]

    def on_push(row):
        key = (row["tenant"], int(row["window"]))
        received.setdefault(key, []).append((time.monotonic(), row["summary"]))

    sub = Wire(spec["port"], on_push)
    assert sub.request(op="subscribe", tenant="*")["ok"]
    conns = [Wire(spec["port"], on_push)
             for _ in range(int(tr["connections"]))]
    for name in names:
        resp = conns[0].request(op="admit", tenant=name)
        if not resp.get("ok"):
            raise RuntimeError("admit refused: %s" % resp)
    # each connection carries a fixed set of tenants, so one tenant's
    # feeds stay in order; a slow reply delays only its own connection
    plans = [[] for _ in conns]
    sent_n = np.zeros(tenants, np.int64)
    for i, (t_due, ti) in enumerate(zip(due, tenant)):
        plans[ti % len(conns)].append((float(t_due), int(ti), int(sent_n[ti])))
        sent_n[ti] += 1
    late, rtt, refusals = [], [], [0]
    feed_due = {}          # (tenant, feed index) -> due time (absolute)
    stop = threading.Event()
    listener = threading.Thread(target=sub.pushes, args=(stop,), daemon=True)
    listener.start()

    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 2
    t0 = time.monotonic()
    print(json.dumps({"t0": t0}), flush=True)

    def feeder(conn, plan):
        for t_due, ti, j in plan:
            at = t0 + t_due
            now = time.monotonic()
            if at > now:
                time.sleep(at - now)
            s, d = data[ti]
            req = {"op": "feed", "tenant": names[ti],
                   "src": s[j * feed:(j + 1) * feed].tolist(),
                   "dst": d[j * feed:(j + 1) * feed].tolist()}
            start = time.monotonic()
            late.append((t_due, start - at))
            while True:
                resp = conn.request(**req)
                if resp.get("ok"):
                    break
                refusals[0] += 1   # retried after the server's hint
                time.sleep(float(resp.get("retry_after_s") or 0.05))
            rtt.append(time.monotonic() - start)
            feed_due[(ti, j)] = at

    threads = [threading.Thread(target=feeder, args=(c, p), daemon=True)
               for c, p in zip(conns, plans)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_closed = time.monotonic()
    print(json.dumps({"closed": t_closed}), flush=True)

    # every window whose last feed went in the window is due; wait for
    # each up to `drain_wait_s` past the close
    due_w = {(names[i], w): feed_due[(i, (w + 1) * per_window - 1)]
             for i in range(tenants) for w in range(counts[i] // per_window)}
    limit = t_closed + float(tr["drain_wait_s"])
    while time.monotonic() < limit and any(k not in received for k in due_w):
        time.sleep(0.05)
    stop.set()
    listener.join()

    t_ref = time.monotonic()
    lat, wrong, missing, dup = [], 0, 0, 0
    for i, name in enumerate(names):
        n_w = counts[i] // per_window
        if not n_w:
            continue
        s, d = data[i]
        want = reference.summaries(s[:n_w * eb], d[:n_w * eb], eb, vb,
                                   range(n_w))
        for w in range(n_w):
            got = received.get((name, w))
            if not got:
                missing += 1
                continue
            dup += len(got) > 1
            lat.append(1e3 * (got[0][0] - due_w[(name, w)]))
            wrong += got[0][1] != want[w]
    late.sort()
    late_ms = 1e3 * np.asarray([x for _t, x in late])
    thirds = [float(np.mean(part)) for part in np.array_split(late_ms, 3)]
    out = {
        "t0": t0, "closed": t_closed,
        "attempted": len(due_w), "missing": missing, "wrong": wrong,
        "duplicates": dup, "refusals": refusals[0], "feeds": len(late),
        "window_p50_ms": float(np.percentile(lat, 50)) if lat else None,
        "window_p95_ms": float(np.percentile(lat, 95)) if lat else None,
        "windows_timed": len(lat),
        "feed_rtt_p95_ms": float(np.percentile(1e3 * np.asarray(rtt), 95)),
        "late_ms": {"p50": float(np.percentile(late_ms, 50)),
                    "p95": float(np.percentile(late_ms, 95)),
                    "max": float(late_ms.max()),
                    "mean_by_third": thirds},
        "reference_s": time.monotonic() - t_ref,
    }
    with open(spec["out"], "w") as f:
        json.dump(out, f)
    print("done", flush=True)
    for c in conns + [sub]:
        c.sock.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
