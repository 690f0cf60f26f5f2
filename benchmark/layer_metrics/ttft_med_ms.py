"""Engine tick: median, over every request of the window, of due time -> first
token (the ``ttft_p50_ms`` of the benchmark until PR 33). A failed or shed
request misses: it counts as the whole window."""
import statistics


def read(trace, stats, record):
    miss = record["window_s"] * 1e3
    return statistics.median(r["ttft_ms"] if r["ok"] else miss for r in record["requests"])
