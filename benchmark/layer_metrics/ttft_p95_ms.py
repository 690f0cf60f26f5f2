"""Engine tick: 95th percentile, over every request of the window, of due time -> first
token. A failed or shed request misses: it counts as the whole window."""
from benchmark.stats import percentile


def read(trace, stats, record):
    miss = record["window_s"] * 1e3
    return percentile([r["ttft_ms"] if r["ok"] else miss for r in record["requests"]], 95)
