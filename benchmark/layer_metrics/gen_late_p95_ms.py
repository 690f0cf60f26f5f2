"""Load generator: 95th percentile of hand-over time minus due time. A starved
generator must not be read as a fast server."""
from benchmark.stats import percentile


def read(trace, stats, record):
    late = [r["late_ms"] for r in record.get("requests", [])]
    return percentile(late, 95) if late else None
