"""Engine tick: 95th percentile of the engine's own record of enqueue ->
admission, over the window's requests only."""
from benchmark.stats import percentile


def read(trace, stats, record):
    waits = stats.get("queue_wait_ms") or []
    return percentile(waits, 95) if waits else None
