"""Median over requests with two output tokens or more of
(latency - ttft) / (output tokens - 1), on the client's clock: the server does
not stream, so this is the gap between tokens a user can observe. It holds the
decode step and every stall a prefill chunk puts into it."""
import statistics


def read(trace, stats, record):
    gaps = [(r["latency_ms"] - r["ttft_ms"]) / (r["n_out"] - 1)
            for r in record["requests"] if r["ok"] and r["n_out"] >= 2]
    return statistics.median(gaps) if gaps else None
