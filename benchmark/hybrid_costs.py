"""Operations and bytes the hybrid family's programs need, computed from shapes,
and the pairing of a dispatch span with the program execution it started.

As ``kernel_costs.py``: the count is what the mathematics requires, so a share
can only be flattered by a faster program. Where a span does not say enough
(which step a row's budget ended at, a row's own position), the count takes the
lower bound, never the upper.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple

from benchmark.reference import hybrid_ref


def sizes_of(config: Dict[str, Any]) -> Dict[str, int]:
    """The reference's sizes (``hybrid_ref.sizes_of``, which also refuses a
    configuration it does not describe) with the layers counted by kind."""
    s = hybrid_ref.sizes_of(config)
    kinds = s.pop("kinds")
    return {**s, "Lm": kinds.count("mamba"), "La": kinds.count("attention"), "L": len(kinds)}


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters a token is multiplied by in the layers: the mixers' and the
    MLPs' matrices. The embedding is a lookup; the tied head is counted apart
    (a decode step reads it, a prompt token does not need it)."""
    s = sizes_of(config)
    I, C = s["H"] * s["P"], s["H"] * s["P"] + 2 * s["N"]
    mamba = s["D"] * (I + C + s["H"]) + I * s["D"]
    attention = 2 * s["D"] * s["heads"] * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"]
    return s["Lm"] * mamba + s["La"] * attention + s["L"] * 3 * s["D"] * s["F"]


def weight_bytes(config: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of weights a decode step has to read: every matrix once, the tied
    head among them; norms, conv taps and the recurrence's vectors too."""
    s = sizes_of(config)
    I, C = s["H"] * s["P"], s["H"] * s["P"] + 2 * s["N"]
    small = s["Lm"] * (C * (s["K"] + 1) + I + s["D"]) + s["La"] * s["D"] + (s["L"] + 1) * s["D"]
    return itemsize * (matmul_params(config) + s["V"] * s["D"] + small) + 4 * 3 * s["Lm"] * s["H"]


def state_bytes_per_row(config: Dict[str, Any], itemsize: int = 2) -> int:
    """The float32 recurrent state and the conv window one row owns."""
    s = sizes_of(config)
    C = s["H"] * s["P"] + 2 * s["N"]
    return s["Lm"] * (4 * s["H"] * s["P"] * s["N"] + itemsize * (s["K"] - 1) * C)


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """A key and a value in every attention layer."""
    s = sizes_of(config)
    return 2 * s["La"] * s["KV"] * s["hd"] * itemsize


def decode_segment_bytes(config: Dict[str, Any], k: int, rows: int, take: int,
                         keys: int) -> float:
    """The least bytes a ``k``-step decode segment moves for ``take`` tokens
    kept over ``rows`` scheduled rows that held ``keys`` keys between them when
    it began: the weights once for each step some row still needed (at least
    ``take / rows`` of the ``k``), a kept token's state read and written once,
    its row's keys and values read once (the keys the segment itself adds are
    left out: a lower bound)."""
    if rows <= 0 or take <= 0:
        return 0.0
    steps = min(k, -(-take // rows))
    return (steps * weight_bytes(config) + take * 2 * state_bytes_per_row(config)
            + kv_bytes_per_token(config) * keys * take / rows)


def scan_flops_per_token(config: Dict[str, Any]) -> int:
    """The recurrence a token a mamba layer, as the sequential form needs it:
    the state's decay (H P N), ``dt x B^T`` added (2 H P N), ``S C`` (2 H P N);
    and the depthwise conv (2 K C)."""
    s = sizes_of(config)
    C = s["H"] * s["P"] + 2 * s["N"]
    return 5 * s["H"] * s["P"] * s["N"] + 2 * s["K"] * C


def prefill_flops(config: Dict[str, Any], tokens: int, keys: int) -> float:
    """FLOPs ``tokens`` real prompt tokens require: 2 a matrix parameter, the
    recurrence and conv in every mamba layer, and in every attention layer
    scores and weighted sum over ``keys`` query-key pairs (2 heads hd each)."""
    s = sizes_of(config)
    return (tokens * (2.0 * matmul_params(config) + s["Lm"] * scan_flops_per_token(config))
            + s["La"] * 4.0 * s["heads"] * s["hd"] * keys)


def paired(spans: Any, phase: str, program: str) -> Iterator[Tuple[Any, Any]]:
    """``(dispatch span, execution)`` pairs of the traced window on chip 0:
    each execution of a program named ``program*`` that lies wholly inside the
    window, with the ``phase`` span that dispatched it. The engine dispatches
    in order and the device runs in order, so the pairing is by order: an
    execution takes the earliest span not yet taken that started before it
    did (and, for a decode segment, carries the ``k`` in its name). Executions
    dispatched before the trace began find no span and are left out."""
    lo, hi = spans.window
    waiting: List[Any] = [s for s in spans.spans if s.name == phase]
    for m in (spans.modules[0] if spans.modules else []):
        if not m.name.startswith(program):
            continue
        digits = m.name[len(program):].split("_", 1)[0]
        for n, s in enumerate(waiting):
            if s.start >= m.start:
                break
            if digits.isdigit() and int(s.stats.get("k", -1)) != int(digits):
                continue
            del waiting[: n + 1]
            if m.start >= lo and m.end <= hi:
                yield s, m
            break
