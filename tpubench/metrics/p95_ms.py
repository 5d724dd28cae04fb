"""Open loop: 95th percentile (nearest rank) of every request's latency,
timed from when it was due; a failed, shed or unanswered request ranks
as the whole window from its due time."""
from tpubench.harness import p95


def read(r):
    lat = r.get("latencies_s")
    return 1e3 * p95(lat) if lat else None
