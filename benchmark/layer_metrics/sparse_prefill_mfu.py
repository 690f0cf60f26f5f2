"""Jitted steps: the FLOPs the real prompt tokens of the traced window's prefill
dispatches require of a sparse-expert model with window layers
(``sparse_costs.prefill_flops`` from each ``engine.prefill_dispatch`` span's
``tokens`` and ``keys``: the routed experts only, a window layer's pairs inside
its window), over the bf16 peak of ``peaks.json``, over the device time of the
``jit_engine_prefill*`` executions those spans started. Padding, the last token's
head and whatever a program recomputes do not count. It is this family's share
of the whole prefill step's peak."""
from benchmark import kernel_costs, span_reader, sparse_costs
from benchmark.hybrid_costs import paired


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or "num_experts" not in config:
        return None
    flops = seconds = 0.0
    for s, m in paired(spans, "engine.prefill_dispatch", "jit_engine_prefill"):
        if not all(k in s.stats for k in ("tokens", "keys")) or int(s.stats.get("rows", 1)) != 1:
            return None  # the window's pairs are counted a row at a time
        flops += sparse_costs.prefill_flops(config, int(s.stats["tokens"]), int(s.stats["keys"]))
        seconds += m.end - m.start
    if not seconds:
        return None
    print(f"prefill programs paired with their dispatch: {seconds:.4f} s on chip 0, "
          f"{flops / 1e12:.3f} TFLOP required", flush=True)
    return 100.0 * flops / peak["bf16_flops_per_s"] / seconds
