"""Operations and bytes that the algorithms need, computed from shapes.

These are the benchmark's own counts: a utilization or a roofline share quoted
anywhere in this repository is one of these over a peak of ``peaks.json``.
Recomputed operations (rematerialisation, the flash backward's second pass over
the scores) do not count: the count is what the mathematics requires, so a
share can only be flattered by a faster program, never by a slower one.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict


def load_peaks() -> Dict[str, Dict[str, float]]:
    """``peaks.json``'s table: device kind -> published peaks."""
    with open(Path(__file__).parent / "peaks.json") as f:
        return json.load(f)["device_kinds"]


def sizes_of(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes a decoder needs, from a configuration file's published keys."""
    heads = int(config["num_attention_heads"])
    return {
        "L": int(config["num_hidden_layers"]), "D": int(config["hidden_size"]), "H": heads,
        "KV": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or config["hidden_size"] // heads),
        "F": int(config["intermediate_size"]), "V": int(config["vocab_size"]),
    }


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that a token is multiplied by: the layers' seven matrices and
    the output head. The embedding is a lookup and the norms are elementwise."""
    s = sizes_of(config)
    layer = (s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"]
             + s["H"] * s["hd"] * s["D"] + 3 * s["D"] * s["F"])
    return s["L"] * layer + s["D"] * s["V"]


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward FLOPs a token of a ``seq_len`` sequence requires.

    Matmuls: 2 FLOPs a parameter forward, 4 backward. Causal attention: a
    token attends to (seq_len + 1) / 2 keys on average; scores and the
    weighted sum are 2 * H * hd FLOPs a key each forward, twice that backward.
    """
    s = sizes_of(config)
    keys = (seq_len + 1) / 2.0
    attention = s["L"] * 3 * (2 * 2 * s["H"] * s["hd"] * keys)
    return 6.0 * matmul_params(config) + attention


def flash_forward(batch: int, seq_len: int, heads: int, kv_heads: int, head_dim: int,
                  itemsize: int = 2) -> Dict[str, float]:
    """One causal flash-attention forward call: Q K^T and P V over the lower
    triangle; q, k, v read once, the output and the float32 log-sum-exp written."""
    pairs = batch * heads * seq_len * (seq_len + 1) / 2.0
    flops = 2 * 2 * pairs * head_dim
    qo = 2 * batch * seq_len * heads * head_dim * itemsize
    kv = 2 * batch * seq_len * kv_heads * head_dim * itemsize
    return {"flops": flops, "bytes": qo + kv + batch * heads * seq_len * 4}


def flash_backward(batch: int, seq_len: int, heads: int, kv_heads: int, head_dim: int,
                   itemsize: int = 2) -> Dict[str, float]:
    """One causal flash-attention backward call: dV = P^T dO, dP = dO V^T,
    dQ = dS K, dK = dS^T Q (the recomputation of the scores is not counted);
    q, k, v, o, dO and the log-sum-exp read, dq, dk, dv written."""
    pairs = batch * heads * seq_len * (seq_len + 1) / 2.0
    flops = 4 * 2 * pairs * head_dim
    q_like = batch * seq_len * heads * head_dim * itemsize
    kv_like = batch * seq_len * kv_heads * head_dim * itemsize
    return {"flops": flops, "bytes": 4 * q_like + 4 * kv_like + batch * heads * seq_len * 4}


def least_seconds(cost: Dict[str, float], peak: Dict[str, float]) -> Dict[str, Any]:
    """The least time a chip with ``peak`` could take, and which bound sets it."""
    compute = cost["flops"] / peak["bf16_flops_per_s"]
    memory = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory), "bound": "compute" if compute >= memory else "memory"}
