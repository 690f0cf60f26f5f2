"""Operations and bytes the sparse-window family's programs need, computed from
shapes.

As ``hybrid_costs.py``: the count is what the mathematics requires, so a share
can only be flattered by a faster program. Where a span does not say enough
(which step a row's budget ended at, a row's own position), the count takes the
lower bound, never the upper. The pairing of a dispatch span with the program
execution it started is ``hybrid_costs.paired``.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.reference import sparse_window_ref


def sizes_of(config: Dict[str, Any]) -> Dict[str, int]:
    """The reference's sizes (``sparse_window_ref.sizes_of``, which also refuses
    a configuration it does not describe) with the layers counted by kind."""
    s = sparse_window_ref.sizes_of(config)
    kinds = s.pop("kinds")
    return {**s, "Lw": kinds.count("sliding_attention"), "Lf": kinds.count("full_attention"),
            "L": len(kinds)}


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's four projections."""
    s = sizes_of(config)
    return 2 * s["D"] * s["heads"] * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"]


def router_params(config: Dict[str, Any]) -> int:
    s = sizes_of(config)
    return s["D"] * s["E"]


def expert_params(config: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    s = sizes_of(config)
    return 3 * s["D"] * s["F"]


def layer_params(config: Dict[str, Any]) -> int:
    """A whole layer with its two norms: what the configuration file's memory
    table counts."""
    s = sizes_of(config)
    return (attention_params(config) + router_params(config)
            + s["E"] * expert_params(config) + 2 * s["D"])


def expert_bytes(config: Dict[str, Any], itemsize: int = 2) -> int:
    return itemsize * expert_params(config)


def step_bytes(config: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes a decode step reads whatever it routes: every layer's attention
    projections, router and norms, and the untied head with the final norm. The
    embedding is a lookup of the step's rows."""
    s = sizes_of(config)
    per_layer = attention_params(config) + router_params(config) + 2 * s["D"]
    return itemsize * (s["L"] * per_layer + s["V"] * s["D"] + s["D"])


def kv_bytes_per_key(config: Dict[str, Any], itemsize: int = 2) -> int:
    """A key and a value in ONE layer."""
    s = sizes_of(config)
    return 2 * s["KV"] * s["hd"] * itemsize


def decode_segment_bytes(config: Dict[str, Any], k: int, rows: int, take: int, keys: int,
                         wkeys: int, touched: int) -> float:
    """The least bytes a ``k``-step decode segment moves for ``take`` tokens
    kept over ``rows`` scheduled rows: what every step reads, once for each step
    some row still needed (at least ``take / rows`` of the ``k``); an expert's
    weights once for each time a layer's kept tokens touched it (``touched``,
    summed over steps and layers: a program that reads only the experts it
    needs cannot pass 100%); and a kept token's keys and values once: in each
    full layer the ``keys`` its row held when the segment began, in each window
    layer those of them the window still reaches (``wkeys``). The keys the
    segment itself adds are left out: a lower bound."""
    if rows <= 0 or take <= 0:
        return 0.0
    s = sizes_of(config)
    steps = min(k, -(-take // rows))
    return (steps * step_bytes(config) + touched * expert_bytes(config)
            + kv_bytes_per_key(config) * (s["Lf"] * keys + s["Lw"] * wkeys) * take / rows)


def window_pairs(tokens: int, keys: int, window: int) -> float:
    """Query-key pairs inside a window for one row's ``tokens`` prompt tokens,
    of which causal attention over everything needs ``keys`` (= ``tokens x base +
    tokens (tokens + 1) / 2``, which gives back the ``base`` they start at): the
    token at position ``p`` sees ``min(p + 1, window)`` keys."""
    if tokens <= 0:
        return 0.0
    base = max(0.0, (keys - tokens * (tokens + 1) / 2) / tokens)
    # positions base .. base + tokens - 1: those below window - 1 see p + 1 keys
    short = int(min(tokens, max(0.0, window - 1 - base)))
    return short * base + short * (short + 1) / 2 + (tokens - short) * float(window)


def prefill_flops(config: Dict[str, Any], tokens: int, keys: int) -> float:
    """FLOPs ``tokens`` real prompt tokens of ONE row require: 2 a parameter a
    token multiplies (attention, router and its ``top_k`` experts, in every
    layer), and scores and weighted sum (2 heads hd each a pair) over ``keys``
    query-key pairs in every full layer and over the pairs inside the window in
    every window layer. Padding, the head and what a program recomputes do not
    count."""
    s = sizes_of(config)
    per_token = 2.0 * s["L"] * (attention_params(config) + router_params(config)
                                + s["top_k"] * expert_params(config))
    pairs = s["Lf"] * float(keys) + s["Lw"] * window_pairs(tokens, keys, s["window"])
    return tokens * per_token + 4.0 * s["heads"] * s["hd"] * pairs
