"""Traffic generation, JAX-free (the serve cell's load generator imports
it). Everything here is a function of the seed and a traffic file.

`make_stream` is a verbatim copy of `bench.make_stream` (PR 21) so that
no later change to the program's bench can move the benchmark's data.
"""

import numpy as np


def make_stream(num_edges: int, num_vertices: int, seed: int = 7):
    """Power-law-ish edge stream: endpoints drawn from a Zipf-like
    distribution over the vertex space (heavy hitters like a social
    stream), timestamps strictly increasing."""
    rng = np.random.default_rng(seed)
    # exponent ~1.1 keeps candidate counts representative but bounded
    weights = 1.0 / np.arange(1, num_vertices + 1) ** 1.1
    weights /= weights.sum()
    src = rng.choice(num_vertices, size=num_edges, p=weights)
    dst = rng.choice(num_vertices, size=num_edges, p=weights)
    # no self-loops (match real graph datasets): redraw collisions
    loops = src == dst
    while loops.any():
        dst[loops] = rng.choice(num_vertices, size=int(loops.sum()), p=weights)
        loops = src == dst
    # remap so hot vertices are scattered over the id space
    perm = rng.permutation(num_vertices)
    return perm[src], perm[dst]


def sub_seed(seed: int, *tags: int) -> int:
    """A child seed for one part of a run (a tenant, a chunk), stable
    for any seed up to 2**63."""
    return int(np.random.SeedSequence([int(seed) % (1 << 63), *tags])
               .generate_state(1, np.uint64)[0] >> 1)


def zipf_shares(n: int, theta: float) -> np.ndarray:
    """YCSB's zipfian request shares over n items: p(i) ∝ 1/(i+1)^θ."""
    w = 1.0 / np.arange(1, n + 1) ** theta
    return w / w.sum()


def feed_counts(n_feeds: int, shares: np.ndarray) -> np.ndarray:
    """How many feeds each tenant gets: the largest-remainder rounding
    of n_feeds × shares. The same for every seed, so a seed changes the
    order of the work and never its amount."""
    raw = n_feeds * shares
    counts = np.floor(raw).astype(np.int64)
    rest = np.argsort(-(raw - counts), kind="stable")
    counts[rest[:n_feeds - counts.sum()]] += 1
    return counts


def serve_schedule(traffic: dict, seconds: float, seed: int):
    """Open-loop Poisson arrivals of fixed-size feeds over `tenants`
    streams at an aggregate `rate_edges_per_s`.

    Returns (due_s, tenant): feed i is due `due_s[i]` seconds after the
    window opens and goes to `tenant[i]`. The multiset of inter-arrival
    gaps comes from a fixed draw (seed 0) and the tenant counts from
    `feed_counts`; the run's seed only permutes both, so every seed
    offers the same work."""
    feed = int(traffic["feed_edges"])
    n_feeds = int(round(float(traffic["rate_edges_per_s"]) * seconds
                        / feed))
    mean_gap = seconds / n_feeds
    gaps = np.random.default_rng(0).exponential(mean_gap, n_feeds)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(sub_seed(seed, 1))
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    shares = zipf_shares(int(traffic["tenants"]),
                         float(traffic["tenant_zipf_theta"]))
    counts = feed_counts(n_feeds, shares)
    tenant = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    return due, tenant


def tenant_streams(counts: np.ndarray, feed_edges: int, num_vertices: int,
                   seed: int) -> list:
    """Each tenant's edges: `make_stream` seeded per tenant, as long as
    the feeds it is sent."""
    out = []
    for i, c in enumerate(counts):
        s, d = make_stream(int(c) * feed_edges, num_vertices,
                           seed=sub_seed(seed, 2, i))
        out.append((s.astype(np.int32), d.astype(np.int32)))
    return out
