"""Engine tick: median over requests with two output tokens or more of
(latency - ttft) / (output tokens - 1), on the client's clock (the
``tpot_p50_ms`` of the benchmark until PR 33; ``tpot_mean_ms`` end to end takes
the same gaps over all tokens)."""
import statistics


def read(trace, stats, record):
    gaps = [(r["latency_ms"] - r["ttft_ms"]) / (r["n_out"] - 1)
            for r in record["requests"] if r["ok"] and r["n_out"] >= 2]
    return statistics.median(gaps) if gaps else None
