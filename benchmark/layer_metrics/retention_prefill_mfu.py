"""Jitted steps: the FLOPs the real prompt tokens of the traced window's
prefill dispatches require of a retention model
(``retention_costs.prefill_flops`` from each ``engine.prefill_dispatch`` span's
``tokens``, ``base`` and ``carried``), over the bf16 peak of ``peaks.json``,
over the device time of the ``jit_engine_prefill*`` executions those spans
started. Padding, the last token's head and whatever a program recomputes do
not count. Another family's configuration, a program that computes several
rows at once, or spans that lack these, read None."""
from benchmark import kernel_costs, retention_costs, span_reader
from benchmark.hybrid_costs import paired


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or config.get("family") != "retention":
        return None
    flops = seconds = 0.0
    for s, m in paired(spans, "engine.prefill_dispatch", "jit_engine_prefill"):
        if not all(k in s.stats for k in ("tokens", "carried", "slots")) or int(s.stats["slots"]) != 1:
            return None
        tokens = int(s.stats["tokens"])
        flops += retention_costs.prefill_flops(
            config, tokens, tokens if int(s.stats["carried"]) else 0, tokens * (tokens + 1) // 2)
        seconds += m.end - m.start
    if not seconds:
        return None
    print(f"retention prefill programs paired with their dispatch: {seconds:.4f} s on chip 0, "
          f"{flops / 1e12:.3f} TFLOP required", flush=True)
    return 100.0 * flops / peak["bf16_flops_per_s"] / seconds
