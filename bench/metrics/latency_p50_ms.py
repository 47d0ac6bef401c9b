"""Median time from send (closed loop) or due time (open loop) to the
client holding the outputs, over all requests of the window."""
from bench.stats import percentile


def read(run):
    lat = run.latencies_s
    return 1e3 * percentile(lat, 50) if lat else None
