"""Plain reference: a decoder of parallel attention-and-experts blocks, one
chip's share of its routed experts.

The forward pass that ``command-a-plus-05-2026``'s ``config.json``
(``model_type`` ``cohere2_moe``) describes for its language model, in
straightforward ``jax.numpy``: float32 throughout, every matrix multiplication
at ``precision="highest"``, attention dense under a mask, the experts a loop
over experts under a mask. No cache, no sort, no grouped product, no kernels,
no batching. It imports nothing of the program under test and takes nothing
the program made. With ``h`` the residual stream and layer ``l`` of kind
``layer_types[l]``::

    h0     = embed[ids]                                        (no multiplier)
    n      = (h - mean(h)) / sqrt(var(h) + layer_norm_eps) * w_l   (a weight, no bias)
    q,k,v  = n Wq [heads x head_dim], n Wk, n Wv [kv heads x head_dim]   (attention_bias false)
    sliding_attention: q, k rotated in interleaved pairs (2i, 2i + 1) by
             pos * rope_theta^(-2i / head_dim)   (position_embedding_type rope_gptj, rotary_pct 1);
             query i sees keys  i - sliding_window < j <= i
    full_attention:    q, k not rotated (no positions at all); query i sees keys j <= i
    a      = softmax(q k^T / sqrt(head_dim)) v Wo               (use_qk_norm false)
    s      = sigmoid(n Wr) over all the published experts       (expert_selection_fn sigmoid)
    g      = s[top-k] / sum(s[top-k])                           (num_experts_per_tok, norm_topk_prob)
    routed = sum_{e in top-k, e held here} g_e Wout_e (silu(Wgate_e n) * Wup_e n)
    shared = 1 / num_shared_experts * sum_j Wout'_j (silu(Wgate'_j n) * Wup'_j n)
    h      = h + a + routed + shared                            (use_parallel_block)
    logits = logit_scale * LayerNorm(h_L; w_f) @ embed^T        (tie_word_embeddings)

Departures from the published model, each with its reason:

- ``assumed`` (the configuration file lists the three): an expert's width is
  ``intermediate_size`` (the config has no key of its own for it); each shared
  expert has that width too, and ``shared_expert_combination_strategy``
  ``"average"`` is the mean of the shared experts' outputs, added to the routed
  sum; ``prefix_dense_intermediate_size`` and
  ``prefix_dense_sliding_window_pattern`` name leading dense layers of which
  this model has none (``first_k_dense_replace`` 0) and are carried unused.
- The vision tower is outside the language model's ``config`` and is left out.
- **The share.** ``num_experts`` in the file counts the experts HELD here
  (``expert_first`` and the count; the published count stands under
  ``published``): the router scores all the published experts and keeps its
  top-k among them, and only an assignment that falls on a held expert adds to
  ``routed``. What the absent chips' experts would have added is left out, here
  and in the program alike, and that partial result is what goes on to the next
  layer (``model-configs`` guide, section 4). :func:`hidden` takes ``first``
  and ``count`` to compute another share of the same weights, or the whole.
- ``vocab_size`` in the file is this chip's slice: a smaller vocabulary, the
  logits over the slice.
- ``layer_types`` may list more layers than ``num_hidden_layers`` (a depth cut
  keeps the published list whole); the first ``num_hidden_layers`` are the model's.
- Computed in blocks so that a 24,576-token prompt fits beside the weights on
  one chip, each exact: attention a block of :data:`QUERY_BLOCK` query rows at
  a time (their q, scores, weighted sum and output product; a sliding layer
  against the ``sliding_window + QUERY_BLOCK`` keys that end at the block), the
  experts one expert at a time over all tokens.
- Weights arrive in bfloat16 (``benchmark/parallel_sparse_weights.py``),
  stacked by kind, and are upcast where they are used. An expert's gate and up
  arrive side by side in one leaf (``gate_up_proj [D, 2F]``: gate then up). The
  shared experts arrive side by side too, ``shared_gate_up_proj [D, 2 n F]``
  (every expert's gate, then every expert's up) and ``shared_down_proj [n F,
  D]``; expert ``j`` is columns ``j F .. (j + 1) F`` of each half and those rows
  of the down projection, sliced out here and computed alone.

``precision`` selects the arithmetic, for the control that has to come out as
not correct: ``"float32"`` is the reference; ``"bfloat16"`` rounds every matmul
input to bfloat16, the router's among them (its product and sigmoid stay
float32, as the configuration states for the served path); ``"int8"`` also
rounds each weight matrix, the router's and the embedding under the head too,
to 8 bits with one scale per output column.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "int8")
#: query rows scored at once: a block's float32 scores are heads x 128 x keys
QUERY_BLOCK = 128
KINDS = ("sliding_attention", "full_attention")


def _int8_round(w: jax.Array) -> jax.Array:
    """Symmetric 8-bit rounding of a [in, out] matrix, one scale per column."""
    a = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=0, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _matmul(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    if precision == "float32":
        return jnp.matmul(x, w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    if precision == "int8":
        w = _int8_round(w)
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def layernorm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Mean-centred, a weight and no bias."""
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(
        jnp.float32)


def rope_table(theta: float, head_dim: int, positions: np.ndarray):
    """``(cos, sin) [S, head_dim / 2]`` float32: pair ``i`` turns by ``pos *
    theta^(-2i / head_dim)``."""
    half = head_dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang), jnp.float32), jnp.asarray(np.sin(ang), jnp.float32)


def rotate_pairs(t: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Interleaved rope: ``t [S, H, hd]``, pair ``i`` is dimensions ``2i`` and
    ``2i + 1``."""
    even, odd = t[..., 0::2], t[..., 1::2]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(t.shape)


@partial(jax.jit, static_argnames=("H", "KV", "hd", "window", "eps", "rotated", "precision"))
def _attention_branch(x, lw, cos, sin, *, H, KV, hd, window, eps, rotated, precision):
    """``attn(norm(x))`` of one layer, ``[S, D]``: k and v whole, then a block
    of query rows at a time. ``window`` 0: every earlier key."""
    S = x.shape[0]
    n = layernorm(x, lw["input_norm"], eps)
    k = _matmul(n, lw["k_proj"], precision).reshape(S, KV, hd)
    v = _matmul(n, lw["v_proj"], precision).reshape(S, KV, hd)
    if rotated:
        k = rotate_pairs(k, cos, sin)
    group = H // KV
    span = S if not window else min(S, window + QUERY_BLOCK)
    prec = jax.lax.Precision.HIGHEST if precision == "float32" else None
    if precision != "float32":
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)

    def block(lo):
        q = _matmul(jax.lax.dynamic_slice_in_dim(n, lo, QUERY_BLOCK), lw["q_proj"],
                    precision).reshape(QUERY_BLOCK, H, hd)
        if rotated:
            q = rotate_pairs(q, jax.lax.dynamic_slice_in_dim(cos, lo, QUERY_BLOCK),
                             jax.lax.dynamic_slice_in_dim(sin, lo, QUERY_BLOCK))
        start = jnp.clip(lo + QUERY_BLOCK - span, 0, S - span)
        kb = jnp.repeat(jax.lax.dynamic_slice_in_dim(k, start, span), group, axis=1)
        vb = jnp.repeat(jax.lax.dynamic_slice_in_dim(v, start, span), group, axis=1)
        if precision != "float32":
            q = q.astype(jnp.bfloat16)
        scores = jnp.einsum("shd,thd->hst", q, kb, precision=prec,
                            preferred_element_type=jnp.float32) / math.sqrt(hd)
        i = lo + jnp.arange(QUERY_BLOCK)[:, None]
        j = start + jnp.arange(span)[None, :]
        seen = (j <= i) & ((j > i - window) if window else True)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        if precision != "float32":
            probs = probs.astype(jnp.bfloat16)
        a = jnp.einsum("hst,thd->shd", probs, vb, precision=prec,
                       preferred_element_type=jnp.float32)
        return _matmul(a.reshape(QUERY_BLOCK, H * hd), lw["o_proj"], precision)

    out = jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK))
    return out.reshape(S, -1)


def routing(n: jax.Array, router: jax.Array, top_k: int, precision: str):
    """``(experts [S, top_k], gates [S, top_k])``: each expert's sigmoid in
    float32, the ``top_k`` largest, renormalised to sum 1."""
    if precision != "float32":
        n = n.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "int8":
        router = _int8_round(router)
    s = jax.nn.sigmoid(jnp.matmul(n, router.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST))
    top_s, top_e = jax.lax.top_k(s, top_k)
    return top_e, top_s / jnp.sum(top_s, axis=-1, keepdims=True)


def _gated(n, w_in, w_out, F, precision):
    gu = _matmul(n, w_in, precision)
    return _matmul(jax.nn.silu(gu[:, :F]) * gu[:, F:], w_out, precision)


@partial(jax.jit, static_argnames=("F", "top_k", "n_shared", "eps", "precision", "held_first",
                                   "first", "count", "shared"))
def _expert_branch(x, norm, lw, *, F, top_k, n_shared, eps, precision, held_first, first, count,
                   shared=True):
    """``(routed + shared, experts [S, top_k])`` of one layer over ``norm(x)``.
    ``lw``'s expert leaves hold experts ``held_first ..``; experts ``[first,
    first + count)`` (numbered as the router numbers them) add their part."""
    n = layernorm(x, norm, eps)
    top_e, gates = routing(n, lw["router"], top_k, precision)

    def one(y, inp):  # one expert: every token computed, the unrouted times 0
        e, w_in, w_out = inp
        g = jnp.sum(jnp.where(top_e == e, gates, 0.0), axis=-1)
        return y + g[:, None] * _gated(n, w_in, w_out, F, precision), None

    here = slice(first - held_first, first - held_first + count)
    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        jnp.arange(first, first + count), lw["gate_up_proj"][here], lw["down_proj"][here]))
    if shared and n_shared:
        W = n_shared * F
        both, down = lw["shared_gate_up_proj"], lw["shared_down_proj"]
        for j in range(n_shared):  # expert j's gate, its up, its rows of the down projection
            cols = slice(j * F, (j + 1) * F)
            w_in = jnp.concatenate([both[:, cols], both[:, W:][:, cols]], axis=1)
            y = y + _gated(n, w_in, down[cols], F, precision) / n_shared
    return y, top_e


@partial(jax.jit, static_argnames=("eps", "scale", "precision"))
def _head(x, final_norm, embed, *, eps, scale, precision):
    return scale * _matmul(layernorm(x, final_norm, eps), embed.T, precision)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference (and the weights) need, from the published keys;
    a configuration this file does not describe is refused by name."""
    L = int(config["num_hidden_layers"])
    kinds: List[str] = list(config["layer_types"])[:L]
    if len(kinds) != L:
        raise ValueError("layer_types lists fewer than num_hidden_layers layers")
    if set(kinds) - set(KINDS):
        raise ValueError(f"layer_types {sorted(set(kinds) - set(KINDS))}: sliding and full attention only")
    checks = {"attention_bias": False, "hidden_act": "silu", "norm_topk_prob": True,
              "tie_word_embeddings": True, "use_parallel_block": True, "use_qk_norm": False,
              "expert_selection_fn": "sigmoid", "use_gated_activation": True,
              "position_embedding_type": "rope_gptj", "rotary_pct": 1,
              "shared_expert_combination_strategy": "average", "first_k_dense_replace": 0}
    for key, want in checks.items():
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: the reference describes {want!r} only")
    held = int(config["num_experts"])
    return {
        "kinds": kinds, "D": int(config["hidden_size"]), "V": int(config["vocab_size"]),
        "heads": int(config["num_attention_heads"]), "KV": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]), "held": held,
        "held_first": int(config.get("expert_first", 0)),
        "E": int(config.get("published", {}).get("num_experts", held)),
        "top_k": int(config["num_experts_per_tok"]), "F": int(config["intermediate_size"]),
        "n_shared": int(config["num_shared_experts"]), "window": int(config["sliding_window"]),
    }


def hidden(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
           precision: str = "float32", routed: Optional[list] = None,
           first: Optional[int] = None, count: Optional[int] = None) -> jax.Array:
    """tokens [S] (a multiple of :data:`QUERY_BLOCK`) -> the residual stream
    before the final norm, [S, D] float32. ``routed``, a list, is given each
    layer's ``experts [S, top_k]``. ``first``/``count``: the experts that add
    their part (the held ones by default)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: have {PRECISIONS}")
    s = sizes_of(config)
    first = s["held_first"] if first is None else first
    count = s["held_first"] + s["held"] - first if count is None else count
    S = tokens.shape[0]
    if S % QUERY_BLOCK:
        raise ValueError(f"{S} tokens: the reference takes whole blocks of {QUERY_BLOCK}")
    eps = float(config["layer_norm_eps"])
    cos, sin = rope_table(float(config["rope_theta"]), s["hd"], np.arange(S))
    x = weights["embed"][tokens].astype(jnp.float32)
    seen = {kind: 0 for kind in KINDS}
    for i, kind in enumerate(s["kinds"]):
        lw = jax.tree_util.tree_map(lambda leaf: leaf[seen[kind]], weights[kind])
        seen[kind] += 1
        sliding = kind == "sliding_attention"
        a = _attention_branch(
            x, lw, cos, sin, H=s["heads"], KV=s["KV"], hd=s["hd"],
            window=s["window"] if sliding else 0, eps=eps, rotated=sliding,
            precision=precision)
        y, top_e = _expert_branch(
            x, lw["input_norm"], jax.tree_util.tree_map(lambda leaf: leaf[i], weights["moe"]),
            F=s["F"], top_k=s["top_k"], n_shared=s["n_shared"], eps=eps, precision=precision,
            held_first=s["held_first"], first=first, count=count)
        x = x + a + y
        if routed is not None:
            routed.append(top_e)
    return x


def logits_at(weights: Dict[str, Any], tokens: jax.Array, positions: jax.Array,
              config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    """Logits [len(positions), V] of one sequence ``tokens [S]`` at ``positions``."""
    x = hidden(weights, tokens, config, precision)[positions]
    return _head(x, weights["final_norm"], weights["embed"],
                 eps=float(config["layer_norm_eps"]), scale=float(config["logit_scale"]),
                 precision=precision)


def forward(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
            precision: str = "float32") -> jax.Array:
    """tokens [S] -> logits [S, V] float32."""
    return logits_at(weights, tokens, jnp.arange(tokens.shape[0]), config, precision)
