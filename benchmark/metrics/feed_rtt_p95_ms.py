"""95th percentile of the generator's own span around each `feed`
request and its reply, over the window's feeds."""


def read(run, trace):
    return run.counters.get("feed_rtt_p95_ms")
