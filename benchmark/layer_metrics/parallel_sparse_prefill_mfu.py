"""Jitted steps: the FLOPs the real prompt tokens of the traced window's prefill
dispatches require of one chip's share of a parallel-block model
(``parallel_sparse_costs.prefill_flops`` from each ``engine.prefill_dispatch``
span's ``tokens`` and ``keys``: attention with a window layer's pairs inside its
window and a full layer's whole, the router, the shared experts, and the routed
experts by the share of kept assignments that fell on a held expert, the window's
own ``assign_held / assign_all``), over the bf16 peak of ``peaks.json``, over the
device time of the ``jit_engine_prefill*`` executions those spans started.
Padding, the last token's head and whatever a program recomputes do not count. It
is this family's share of the whole prefill step's peak."""
from benchmark import kernel_costs, parallel_sparse_costs, span_reader
from benchmark.hybrid_costs import paired


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or config.get("family") != "parallel_sparse":
        return None
    share = parallel_sparse_costs.held_share(config, stats)
    flops = seconds = 0.0
    for s, m in paired(spans, "engine.prefill_dispatch", "jit_engine_prefill"):
        if not all(k in s.stats for k in ("tokens", "keys")) or int(s.stats.get("rows", 1)) != 1:
            return None  # the window's pairs are counted a row at a time
        flops += parallel_sparse_costs.prefill_flops(
            config, int(s.stats["tokens"]), int(s.stats["keys"]), share)
        seconds += m.end - m.start
    if not seconds:
        return None
    print(f"prefill programs paired with their dispatch: {seconds:.4f} s on chip 0, "
          f"{flops / 1e12:.3f} TFLOP required at a held share of {share:.4f}", flush=True)
    return 100.0 * flops / peak["bf16_flops_per_s"] / seconds
