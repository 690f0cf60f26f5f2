"""Median, over every request of the window, of due time -> first token: the
reply's ``ttft_ms`` plus the generator's own delay from due time to hand-over.
A failed or shed request misses: it counts as the whole window."""
import statistics


def read(trace, stats, record):
    miss = record["window_s"] * 1e3
    return statistics.median(r["ttft_ms"] if r["ok"] else miss for r in record["requests"])
