"""Jitted steps: the FLOPs the real prompt tokens of the traced window's
prefill dispatches require (``hybrid_costs.prefill_flops`` from each
``engine.prefill_dispatch`` span's ``tokens`` and ``keys``), over the bf16 peak
of ``peaks.json``, over the device time of the ``jit_engine_prefill*``
executions those spans started. Padding, the last token's head and whatever a
program recomputes do not count."""
from benchmark import hybrid_costs, kernel_costs, span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or "layer_types" not in config:
        return None
    flops = seconds = 0.0
    for s, m in hybrid_costs.paired(spans, "engine.prefill_dispatch", "jit_engine_prefill"):
        if not all(k in s.stats for k in ("tokens", "keys")):
            return None
        flops += hybrid_costs.prefill_flops(config, int(s.stats["tokens"]), int(s.stats["keys"]))
        seconds += m.end - m.start
    if not seconds:
        return None
    print(f"prefill programs paired with their dispatch: {seconds:.4f} s on chip 0, "
          f"{flops / 1e12:.3f} TFLOP required", flush=True)
    return 100.0 * flops / peak["bf16_flops_per_s"] / seconds
