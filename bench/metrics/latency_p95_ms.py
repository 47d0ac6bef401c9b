"""95th percentile of the same samples as ``latency_p50_ms``."""
from bench.stats import percentile


def read(run):
    lat = run.latencies_s
    return 1e3 * percentile(lat, 95) if lat else None
