"""Pallas kernels: the least time ``kernel_costs`` and ``peaks.json`` allow the
traced window's flash-attention calls, over the device time they took.

The kernels are the trace's ``custom-call`` events (``pallas_call`` carries no
name, so they are found by opcode and told apart by the names the trace gives
them). Every distinct kernel instruction of the step sits in a scan over the
layers and so runs once a layer a step: the window's calls over the number of
distinct names is the number of layer-steps traced, and each layer-step needs
one causal forward and one backward over the chip's share of the batch. An
earlier output line of the traced run names the kernels and says which bound
applies."""
from benchmark import kernel_costs, trace_reader


def read(trace, stats, record):
    if trace is None or record.get("kind") != "train":
        return None
    peaks = kernel_costs.load_peaks()
    peak = peaks.get(record["device_kind"])
    kernels = [o for o in trace_reader.window_ops(trace) if trace_reader.is_kernel(o)]
    if not kernels or peak is None:
        return None
    chips = max(1, len(trace.devices))
    measured, _count = trace_reader.op_seconds(trace, trace_reader.is_kernel)
    by_name = {}
    for o in kernels:
        n, t = by_name.get(o.name, (0, 0.0))
        by_name[o.name] = (n + 1, t + o.self_s)
    layer_steps = len(kernels) / chips / len(by_name)
    cfg = record["config"]
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    rows = max(1, stats["global_batch"] // chips)
    shape = (rows, stats["seq_len"], heads, kv, int(cfg["head_dim"]))
    fwd = kernel_costs.least_seconds(kernel_costs.flash_forward(*shape), peak)
    bwd = kernel_costs.least_seconds(kernel_costs.flash_backward(*shape), peak)
    least = layer_steps * (fwd["seconds"] + bwd["seconds"])
    named = ", ".join(f"{k} x{n} {t:.4f}s" for k, (n, t) in sorted(by_name.items()))
    print(f"flash kernels: {named}; {layer_steps:.1f} layer-steps in the traced window, "
          f"{measured:.4f} s measured, {least:.4f} s least (forward {fwd['bound']}-bound, "
          f"backward {bwd['bound']}-bound)", flush=True)
    return 100.0 * least / measured
