"""KV-block manager: rows whose slab of recurrent state is live (the engine's
``state_rows``) over ``max_batch``, sampled with the occupancy every 50 ms of
the traced window. A program without recurrent state reports no samples."""


def read(trace, stats, record):
    rows = stats.get("state_rows_samples") or []
    if not rows or not stats.get("max_batch"):
        return None
    return 100.0 * sum(rows) / len(rows) / stats["max_batch"]
