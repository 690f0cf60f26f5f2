"""The gap between tokens over all the window's decoding: the sum, over
requests with two output tokens or more, of latency - ttft on the client's
clock, over the sum of their output tokens - 1. The server does not stream, so
a request's gaps are known only as their sum; this is the mean of every token
gap of the window, each token counting once. It holds the decode step and every
stall a prefill chunk puts into it. (The median over requests of a request's
own mean gap, which a handful of requests at the middle decide, is recorded
per layer as ``tpot_med_ms``.)"""


def read(trace, stats, record):
    multi = [r for r in record["requests"] if r["ok"] and r["n_out"] >= 2]
    tokens = sum(r["n_out"] - 1 for r in multi)
    if not tokens:
        return None
    return sum(r["latency_ms"] - r["ttft_ms"] for r in multi) / tokens
