"""Operations and bytes the retention family's programs need, computed from
shapes.

As ``kernel_costs.py``: the count is what the mathematics requires, whatever
implements it, so a share can only be flattered by a faster program. The state
is counted packed: the ``hd (hd + 1) / 2`` distinct products of a key's
symmetric square (8,256 for a head of 128) by ``hd`` values and one
normaliser, in float32; a program that lays it out wider (the 65 x 128
diagonals of ``ops/power_retention.py`` are 0.8% wider) reads a lower share,
never a higher. Where a span does not say enough, the count takes the lower
bound. A dispatch span is paired with the execution it started by
``hybrid_costs.paired``; :func:`whole_segments` keeps the decode segments the
capture holds whole.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple

from benchmark import trace_reader
from benchmark.hybrid_costs import paired
from benchmark.reference import retention_ref

#: the kernel's name in a device profile (``ops/power_retention.py``)
STEP_KERNEL = "retention_step_rows"


def sizes_of(config: Dict[str, Any]) -> Dict[str, int]:
    """The reference's sizes (which also refuses a configuration it does not
    describe) with the packed feature count ``P``."""
    s = retention_ref.sizes_of(config)
    return {**s, "P": s["hd"] * (s["hd"] + 1) // 2}


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters a token is multiplied by in the layers: ``q``, ``k``, ``v``,
    ``o``, the gate's projection and the MLP's three. The embedding is a
    lookup; the head is counted apart (a decode step reads it, a prompt token
    does not need it)."""
    s = sizes_of(config)
    mixer = 2 * s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"] + s["KV"] * s["D"]
    return s["L"] * (mixer + 3 * s["D"] * s["F"])


def weight_bytes(config: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of weights a decode step has to read: every matrix once, the
    untied head among them (not the embedding: a row a token); the norms and
    the gate's bias too."""
    s = sizes_of(config)
    small = s["L"] * (2 * s["D"] + 2 * s["hd"]) + s["D"]
    return itemsize * (matmul_params(config) + s["D"] * s["V"] + small) + 4 * s["L"] * s["KV"]


def slab_bytes(config: Dict[str, Any]) -> int:
    """The float32 state one row owns in ONE layer, packed."""
    s = sizes_of(config)
    return 4 * s["KV"] * s["P"] * (s["hd"] + 1)


def state_bytes_per_row(config: Dict[str, Any]) -> int:
    """The state one row owns: all it owns."""
    return sizes_of(config)["L"] * slab_bytes(config)


def decode_segment_bytes(config: Dict[str, Any], k: int, rows: int, take: int) -> float:
    """The least bytes a ``k``-step decode segment moves for ``take`` tokens
    kept over ``rows`` scheduled rows: the weights once for each step some row
    still needed (at least ``take / rows`` of the ``k``), a kept token's state
    read and written once. Nothing grows with a row's position."""
    if rows <= 0 or take <= 0:
        return 0.0
    steps = min(k, -(-take // rows))
    return steps * weight_bytes(config) + take * 2.0 * state_bytes_per_row(config)


def prefill_flops(config: Dict[str, Any], tokens: int, carried_tokens: int,
                  pairs: int) -> float:
    """FLOPs ``tokens`` real prompt tokens of one prefill program require:
    2 a matrix parameter, and the retention terms a layer by the lesser of the
    two forms. Among themselves the program's tokens make ``pairs``
    query-key pairs (``t (t + 1) / 2``): as pairs they cost a score and a
    weighted sum (2 x 2 H hd each), as a recurrence a read-out a token (2 H P
    (hd + 1)); the lesser counts (the pairs, up to some 8,000 tokens).
    ``carried_tokens`` of them begin from a state that earlier programs left
    and have to read it: one read-out each. Every token is added to the state
    the next program or the decode steps go on from: 2 KV P (hd + 1) each."""
    s = sizes_of(config)
    readout = 2.0 * s["H"] * s["P"] * (s["hd"] + 1)
    update = 2.0 * s["KV"] * s["P"] * (s["hd"] + 1)
    inside = min(4.0 * s["H"] * s["hd"] * pairs, tokens * readout)
    retention = inside + carried_tokens * readout + tokens * update
    return tokens * 2.0 * matmul_params(config) + s["L"] * retention


def whole_segments(trace: Any, spans: Any, config: Dict[str, Any]
                   ) -> Iterator[Tuple[Any, Any, List[Any]]]:
    """``(dispatch span, execution, the state kernel's calls inside it)`` for
    the traced window's decode segments on chip 0 that the trace holds WHOLE:
    a ``k``-step segment of an ``L``-layer model runs the kernel ``L k``
    times, and an execution with another count is one the capture caught in
    the middle (its event begins where the capture did, and it read 102 ms for
    32 steps: PERF.md section 6, PR 42) or a program without the kernel.
    Nothing where the spans lack ``k``."""
    layers = sizes_of(config)["L"]
    kernels = sorted(
        (o for o in (trace.devices[0] if trace.devices else [])
         if trace_reader.is_kernel(o) and o.name.startswith(STEP_KERNEL)),
        key=lambda o: o.start)
    for s, m in paired(spans, "engine.decode_dispatch", "jit_engine_decode_seg"):
        if "k" not in s.stats:
            return
        inside = [o for o in kernels if o.start >= m.start and o.end <= m.end]
        if len(inside) == layers * int(s.stats["k"]):
            yield s, m, inside
