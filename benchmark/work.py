"""The least work a window needs, from its shapes alone: `eb` edges per
window, `vb` vertex slots, and the analytics set. Never from the
program's own cost analysis, so a kernel's roofline share reads the
same yardstick whatever implements it.

Bytes: each vertex id is read at the width `vb` needs (2 bytes up to
65,536 slots). A table an analytic carries is read and written once
for every slot the window's 2·eb endpoints can touch, min(2·eb, vb),
and each touched slot's result is written once. Ops: one per endpoint
per analytic for the snapshot fold; a sort of the window's edges,
eb·log2(eb) compares, for the triangle count. Both are floors: an
implementation moves at least this much, so the share stays a share.
"""

import json
import math
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

# bytes per touched slot: carried table entries (read + write) and
# the result written out, per analytic
_STATE = {"degrees": (4, 4), "cc": (4, 4), "bipartite": (8, 1)}


def id_bytes(vb: int) -> int:
    return max(1, math.ceil(math.log2(max(vb, 2)) / 8))


def snapshot_scan(eb: int, vb: int, analytics) -> tuple:
    """(ops, bytes) per window of the cumulative degree / CC /
    bipartiteness fold."""
    kept = [a for a in analytics if a in _STATE]
    touched = min(2 * eb, vb)
    nbytes = 2 * eb * id_bytes(vb)
    for a in kept:
        carry, out = _STATE[a]
        nbytes += touched * (2 * carry + out)
    return 2 * eb * len(kept), nbytes


def triangles(eb: int, vb: int) -> tuple:
    """(ops, bytes) per window of the exact per-window triangle count:
    read the edges, write and read back the oriented list once."""
    return eb * max(1, math.log2(eb)), 3 * 2 * eb * id_bytes(vb)


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r (known: %s)"
                       % (device_kind, ", ".join(sorted(table))))
    return table[device_kind]


def roofline_pct(ops: float, nbytes: float, seconds: float,
                 device_kind: str) -> tuple:
    """(share of the roofline in %, the bound that binds) for work
    done in `seconds` of device time."""
    p = peaks(device_kind)
    t_ops = ops / p["ops_per_s"]
    t_bytes = nbytes / p["bytes_per_s"]
    return (100.0 * max(t_ops, t_bytes) / seconds,
            "bytes" if t_bytes >= t_ops else "ops")
