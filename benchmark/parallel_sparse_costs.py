"""Operations and bytes the parallel-sparse family's programs need, computed
from shapes.

As ``sparse_costs.py``: the count is what the mathematics requires of THIS CHIP'S
SHARE (the attention, the router over every published expert, the shared
experts, and the held experts' part), so a share can only be flattered by a
faster program. Where a span does not say enough (which step a row's budget
ended at, a row's own position), the count takes the lower bound or the mean,
never the upper. The pairing of a dispatch span with the program execution it
started is ``hybrid_costs.paired``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple

from benchmark import trace_reader
from benchmark.hybrid_costs import paired
from benchmark.reference import parallel_sparse_ref
from benchmark.sparse_costs import window_pairs

#: the decode kernel's name in a device profile (``paged_attention.DECODE_KERNEL_NAME``)
DECODE_KERNEL = "paged_decode_attention"


def sizes_of(config: Dict[str, Any]) -> Dict[str, int]:
    """The reference's sizes (``parallel_sparse_ref.sizes_of``, which also
    refuses a configuration it does not describe) with the layers counted by kind."""
    s = parallel_sparse_ref.sizes_of(config)
    kinds = s.pop("kinds")
    return {**s, "Lw": kinds.count("sliding_attention"), "Lf": kinds.count("full_attention"),
            "L": len(kinds)}


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's four projections."""
    s = sizes_of(config)
    return 2 * s["D"] * s["heads"] * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"]


def router_params(config: Dict[str, Any]) -> int:
    """The router scores every published expert, held here or not."""
    s = sizes_of(config)
    return s["D"] * s["E"]


def expert_params(config: Dict[str, Any]) -> int:
    """One expert, routed or shared: gate, up and down."""
    s = sizes_of(config)
    return 3 * s["D"] * s["F"]


def shared_params(config: Dict[str, Any]) -> int:
    return sizes_of(config)["n_shared"] * expert_params(config)


def layer_params(config: Dict[str, Any]) -> int:
    """A layer as this chip holds it, with its one norm: what the configuration
    file's memory table counts."""
    s = sizes_of(config)
    return (attention_params(config) + router_params(config) + shared_params(config)
            + s["held"] * expert_params(config) + s["D"])


def model_params(config: Dict[str, Any]) -> int:
    """Every layer, the tied embedding (once) and the final norm."""
    s = sizes_of(config)
    return s["L"] * layer_params(config) + s["V"] * s["D"] + s["D"]


def expert_bytes(config: Dict[str, Any], itemsize: int = 2) -> int:
    return itemsize * expert_params(config)


def step_bytes(config: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes a decode step reads whatever it routes: every layer's attention
    projections, router, norm and shared experts, and the tied head (the
    embedding, read whole as the head) with the final norm."""
    s = sizes_of(config)
    per_layer = (attention_params(config) + router_params(config) + shared_params(config)
                 + s["D"])
    return itemsize * (s["L"] * per_layer + s["V"] * s["D"] + s["D"])


def kv_bytes_per_key(config: Dict[str, Any], itemsize: int = 2) -> int:
    """A key and a value in ONE layer."""
    s = sizes_of(config)
    return 2 * s["KV"] * s["hd"] * itemsize


def decode_segment_bytes(config: Dict[str, Any], k: int, rows: int, take: int, keys: int,
                         wkeys: int, touched: int) -> float:
    """The least bytes a ``k``-step decode segment moves for ``take`` tokens
    kept over ``rows`` scheduled rows (``sparse_costs.decode_segment_bytes`` with
    this family's step): what every step reads, once for each step some row
    still needed (at least ``take / rows`` of the ``k``); a HELD expert's weights
    once for each time a layer's kept tokens touched it (``touched``, summed over
    steps and layers); and a kept token's keys and values once: in each full
    layer the ``keys`` its row held when the segment began, in each window layer
    those of them the window still reaches (``wkeys``)."""
    if rows <= 0 or take <= 0:
        return 0.0
    s = sizes_of(config)
    steps = min(k, -(-take // rows))
    return (steps * step_bytes(config) + touched * expert_bytes(config)
            + kv_bytes_per_key(config) * (s["Lf"] * keys + s["Lw"] * wkeys) * take / rows)


def held_share(config: Dict[str, Any], stats: Dict[str, Any]) -> float:
    """Of the kept assignments, the share that fell on a held expert: the
    window's own count where the program gives one (``assign_held`` over
    ``assign_all``), else what an even router gives, held over published."""
    s = sizes_of(config)
    every = float(stats.get("assign_all") or 0)
    return float(stats.get("assign_held", 0)) / every if every > 0 else s["held"] / s["E"]


def prefill_flops(config: Dict[str, Any], tokens: int, keys: int, share: float) -> float:
    """FLOPs ``tokens`` real prompt tokens of ONE row require of this chip: 2 a
    parameter a token multiplies (attention, the router's every output, the
    shared experts, and ``top_k x share`` routed experts: the assignments that
    fall on an expert held here), in every layer, and scores and weighted sum
    (2 heads hd each a pair) over ``keys`` query-key pairs in every full layer
    and over the pairs inside the window in every window layer. Padding, the
    head and what a program recomputes do not count."""
    s = sizes_of(config)
    per_token = 2.0 * s["L"] * (attention_params(config) + router_params(config)
                                + shared_params(config)
                                + s["top_k"] * share * expert_params(config))
    pairs = s["Lf"] * float(keys) + s["Lw"] * window_pairs(tokens, keys, s["window"])
    return tokens * per_token + 4.0 * s["heads"] * s["hd"] * pairs


def folded_keys(config: Dict[str, Any], tokens: int, keys: int, bucket: int,
                tile: int = 512, block: int = 16) -> Dict[str, int]:
    """Keys the blocked prefill folds for one row's chunk, a layer of each
    kind: ``full`` the tiles up to the chunk's last token, ``window`` the tiles
    of the fixed run of blocks (the window, the bucket and one block). From a
    ``engine.prefill_dispatch`` span's ``tokens``, ``keys`` and ``bucket``
    (``keys = tokens x base + tokens (tokens + 1) / 2`` gives back ``base``)."""
    if tokens <= 0:
        return {"full": 0, "window": 0}
    base = int(round((keys - tokens * (tokens + 1) / 2) / tokens))
    run = sizes_of(config)["window"] // block + -(-bucket // block) + 1
    per_tile = tile // block
    return {"full": ((base + tokens - 1) // tile + 1) * tile,
            "window": -(-run // per_tile) * tile}


def kernel_segment_bytes(config: Dict[str, Any], k: int, rows: int, take: int, read: int,
                         wkeys: int) -> float:
    """Bytes the decode kernel's calls of one segment fetch, at least: a full
    layer's call the keys of its scheduled rows in whole compute blocks
    (``read``: the engine's count for ``k`` steps of every scheduled row, here
    times the share of those steps a row still kept, ``take / (rows k)``), a
    window layer's the keys the window still reaches (``wkeys`` a step, not
    rounded up to compute blocks: a lower bound)."""
    if rows <= 0 or take <= 0:
        return 0.0
    s = sizes_of(config)
    kept = take / float(rows * k)
    return kv_bytes_per_key(config) * (s["Lf"] * read * kept + s["Lw"] * wkeys * k * kept)


def whole_segments(trace: Any, spans: Any, config: Dict[str, Any]
                   ) -> Iterator[Tuple[Any, Any, List[Any]]]:
    """``(dispatch span, execution, the decode kernel's calls inside it)`` for
    the traced window's decode segments on chip 0 that the trace holds WHOLE
    (``retention_costs.whole_segments``, for this kernel): a ``k``-step segment
    of an ``L``-layer model calls the kernel ``L k`` times. Nothing where the
    spans lack ``k`` or the program has no such kernel."""
    layers = sizes_of(config)["L"]
    kernels = sorted(
        (o for o in (trace.devices[0] if trace.devices else [])
         if trace_reader.is_kernel(o) and o.name.startswith(DECODE_KERNEL)),
        key=lambda o: o.start)
    if not kernels:
        return
    for s, m in paired(spans, "engine.decode_dispatch", "jit_engine_decode_seg"):
        if "k" not in s.stats:
            return
        inside = [o for o in kernels if o.start >= m.start and o.end <= m.end]
        if len(inside) == layers * int(s.stats["k"]):
            yield s, m, inside
