"""KV-block manager: active rows over ``max_batch``, sampled by the benchmark
every 50 ms of the window."""


def read(trace, stats, record):
    rows = stats.get("occupancy_samples") or []
    if not rows or not stats.get("max_batch"):
        return None
    return 100.0 * sum(rows) / len(rows) / stats["max_batch"]
