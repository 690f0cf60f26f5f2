"""Plain reference: a sparse-expert decoder with window and full attention layers.

The forward pass that ``Mellum2-12B-A2.5B-Instruct``'s ``config.json``
(``model_type`` ``mellum``) describes, in straightforward ``jax.numpy``:
float32 throughout, every matrix multiplication at ``precision="highest"``,
attention dense with the window as a mask, the experts as a loop over experts
under a mask. No cache, no sort, no grouped product, no kernels, no batching.
It imports nothing of the program under test and takes nothing the program
made. With ``h`` the residual stream::

    h0     = embed[ids]                                  (no multiplier)
    h      = h + attn_l(rmsnorm(h, input_norm_l))
    h      = h + moe_l(rmsnorm(h, post_attention_norm_l))
    logits = rmsnorm(h, final_norm) @ lm_head            (tie_word_embeddings false)
    rmsnorm(x, w) = x * rsqrt(mean(x^2) + rms_norm_eps) * w

- Attention, every layer: ``q = x Wq`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = x Wk``, ``v = x Wv`` (``num_key_value_heads`` heads), no
  bias, rotate-half rope on q and k, scores ``q.k / sqrt(head_dim)``, causal,
  grouped queries, ``o = (softmax . v) Wo``. ``layer_types[l]`` says which keys
  and which table: ``sliding_attention`` sees keys ``i - sliding_window < j <=
  i`` and rotates by ``rope_parameters.sliding_attention`` (``default``:
  ``inv_freq_d = theta^(-2d / head_dim)``); ``full_attention`` sees every
  earlier key and rotates by ``rope_parameters.full_attention`` (``yarn``:
  ``inv_freq = inv_extrap / factor * (1 - m) + inv_extrap * m``, ``m_d = 1 -
  clip((d - low) / (high - low), 0, 1)``, ``low`` and ``high`` the floor and
  the ceiling of ``(head_dim / 2) ln(original_max / (beta 2 pi)) / ln(theta)``
  at ``beta_fast`` and ``beta_slow``; cos and sin times ``attention_factor``).
- Expert layer, every layer (``mlp_layer_types`` all ``sparse``): ``p =
  softmax(x Wr)`` over all ``num_experts``, ``S`` the ``num_experts_per_tok``
  largest, ``g_e = p_e / sum_{S} p`` (``norm_topk_prob``), ``y = sum_{e in S}
  g_e W_down,e (silu(W_gate,e x) * W_up,e x)``. No shared expert, no router
  bias, nothing dropped.

Departures from the published description, each with its reason:

- ``intermediate_size`` names a dense MLP that no layer of this model has
  (every ``mlp_layer_types`` entry is ``sparse``); a ``dense`` entry raises.
- Not in the config and taken as absent: a norm on q or k, attention sinks,
  logit soft-capping. ``truncate`` of the yarn parameters is not in the config
  and taken as true (``low`` and ``high`` are rounded), the convention's
  default. The model's family is described with a multi-token-prediction head;
  the config carries none and it would not change the served distribution.
- ``layer_types`` and ``mlp_layer_types`` may list more layers than
  ``num_hidden_layers`` (a depth cut keeps the published lists whole); the
  first ``num_hidden_layers`` entries are the model's.
- Attention is computed in blocks of query rows, so that a 7.5K-token
  sequence fits beside the weights on one chip; exact.
- Weights arrive in bfloat16 (``benchmark/sparse_weights.py``), stacked by
  kind, and are upcast a layer, and in the expert layer an expert, at a time.
  Gate and up arrive side by side in one leaf (``gate_up_proj [D, 2F]``: gate
  then up) and are multiplied as its two halves, which is the same products.

``precision`` selects the arithmetic, for the control that has to come out as
not correct: ``"float32"`` is the reference; ``"bfloat16"`` rounds every
matmul input to bfloat16, the router's among them (its product and softmax
stay float32, as the configuration states for the served path); ``"int8"``
also rounds each weight matrix, the router's too, to 8 bits with one scale
per output column.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "int8")
#: query rows scored at once: a block's float32 scores are heads x 1024 x S
QUERY_BLOCK = 1024
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


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)


def inv_freq(rope: Dict[str, Any], head_dim: int) -> np.ndarray:
    """The ``head_dim / 2`` rotary frequencies of one ``rope_parameters`` entry."""
    half = head_dim // 2
    theta = float(rope["rope_theta"])
    extrap = theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope["rope_type"] == "default":
        return extrap
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}: default and yarn only")

    def turns(beta: float) -> float:
        return half * math.log(float(rope["original_max_position_embeddings"])
                               / (beta * 2 * math.pi)) / math.log(theta)

    low = max(math.floor(turns(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(rope["beta_slow"]))), head_dim - 1)
    m = 1.0 - np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extrap / float(rope["factor"]) * (1.0 - m) + extrap * m


def rope_table(rope: Dict[str, Any], head_dim: int, positions: np.ndarray):
    """``(cos, sin) [S, head_dim / 2]`` float32, times the entry's ``attention_factor``."""
    ang = np.asarray(positions, np.float64)[:, None] * inv_freq(rope, head_dim)[None, :]
    factor = float(rope.get("attention_factor", 1.0))
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32))


def rotate(t: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate-half: ``t [S, H, hd]``, the pair of dimension ``d`` is ``d + hd / 2``."""
    t1, t2 = jnp.split(t, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int,
              precision: str) -> jax.Array:
    """Causal grouped-query attention; q [S, H, hd], k and v [S, KV, hd]. A
    query at ``i`` sees keys ``i - window < j <= i`` (``window`` 0: all)."""
    S, H, hd = q.shape
    group = H // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    prec = jax.lax.Precision.HIGHEST if precision == "float32" else None
    if precision != "float32":
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)
        scores = jnp.einsum("shd,thd->hst", q[lo:hi], k[:hi], precision=prec,
                            preferred_element_type=jnp.float32) / math.sqrt(hd)
        i, j = jnp.arange(lo, hi)[:, None], jnp.arange(hi)[None, :]
        seen = (j <= i) & ((j > i - window) if window else True)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        if precision != "float32":
            probs = probs.astype(jnp.bfloat16)
        out.append(jnp.einsum("hst,thd->shd", probs, v[:hi], precision=prec,
                              preferred_element_type=jnp.float32))
    return jnp.concatenate(out, axis=0)


@partial(jax.jit, static_argnames=("H", "KV", "hd", "window", "eps", "precision"))
def _attention_layer(x, lw, cos, sin, *, H, KV, hd, window, eps, precision):
    S = x.shape[0]
    h = rmsnorm(x, lw["input_norm"], eps)
    q = rotate(_matmul(h, lw["q_proj"], precision).reshape(S, H, hd), cos, sin)
    k = rotate(_matmul(h, lw["k_proj"], precision).reshape(S, KV, hd), cos, sin)
    v = _matmul(h, lw["v_proj"], precision).reshape(S, KV, hd)
    a = attention(q, k, v, window, precision).reshape(S, H * hd)
    return x + _matmul(a, lw["o_proj"], precision)


def routing(h: jax.Array, router: jax.Array, top_k: int, precision: str):
    """``(experts [S, top_k], gates [S, top_k])``: softmax over every expert in
    float32, the ``top_k`` largest, renormalised."""
    if precision != "float32":
        h = h.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "int8":
        router = _int8_round(router)
    p = jax.nn.softmax(jnp.matmul(h, router.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST), axis=-1)
    top_p, top_e = jax.lax.top_k(p, top_k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


@partial(jax.jit, static_argnames=("F", "top_k", "eps", "precision", "first", "count"))
def _expert_layer(x, lw, *, F, top_k, eps, precision, first=0, count=None):
    """``(x + moe(rmsnorm(x)), experts [S, top_k])``; with ``first``/``count``
    only experts ``[first, first + count)`` add their part."""
    h = rmsnorm(x, lw["post_attention_norm"], eps)
    top_e, gates = routing(h, lw["router"], top_k, precision)
    E = lw["gate_up_proj"].shape[0]
    count = E - first if count is None else count

    def one(y, inp):  # one expert: every token computed, the unrouted times 0
        e, w_in, w_out = inp
        g = jnp.sum(jnp.where(top_e == e, gates, 0.0), axis=-1)
        gu = _matmul(h, w_in, precision)
        return y + g[:, None] * _matmul(jax.nn.silu(gu[:, :F]) * gu[:, F:], w_out, precision), None

    held = slice(first, first + count)
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(first, first + count), lw["gate_up_proj"][held], lw["down_proj"][held]))
    return x + y, top_e


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, final_norm, lm_head, *, eps, precision):
    return _matmul(rmsnorm(x, final_norm, eps), lm_head, precision)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference (and the weights) need, from the published keys."""
    L = int(config["num_hidden_layers"])
    kinds: List[str] = list(config["layer_types"])[:L]
    mlps = list(config["mlp_layer_types"])[:L]
    if len(kinds) != L or len(mlps) != L:
        raise ValueError("layer_types or mlp_layer_types lists fewer than num_hidden_layers layers")
    if set(kinds) - set(KINDS):
        raise ValueError(f"layer_types {sorted(set(kinds) - set(KINDS))}: sliding and full attention only")
    if set(mlps) != {"sparse"}:
        raise ValueError("mlp_layer_types: every layer of this family has a sparse expert layer")
    checks = {"attention_bias": False, "hidden_act": "silu", "norm_topk_prob": True,
              "tie_word_embeddings": False, "use_sliding_window": True}
    for key, want in checks.items():
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: the reference describes {want!r} only")
    return {
        "kinds": kinds, "D": int(config["hidden_size"]), "V": int(config["vocab_size"]),
        "heads": int(config["num_attention_heads"]), "KV": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]), "E": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]), "F": int(config["moe_intermediate_size"]),
        "window": int(config["sliding_window"]),
    }


def hidden(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
           precision: str = "float32", routed: Optional[list] = None) -> jax.Array:
    """tokens [S] -> the residual stream before the final norm, [S, D] float32.
    ``routed``, a list, is given each layer's ``experts [S, top_k]``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: have {PRECISIONS}")
    s = sizes_of(config)
    eps = float(config["rms_norm_eps"])
    positions = np.arange(tokens.shape[0])
    tables = {kind: rope_table(config["rope_parameters"][kind], s["hd"], positions)
              for kind in KINDS}
    x = weights["embed"][tokens].astype(jnp.float32)
    seen = {kind: 0 for kind in KINDS}
    for i, kind in enumerate(s["kinds"]):
        lw = jax.tree_util.tree_map(lambda leaf: leaf[seen[kind]], weights[kind])
        seen[kind] += 1
        x = _attention_layer(
            x, lw, *tables[kind], H=s["heads"], KV=s["KV"], hd=s["hd"],
            window=s["window"] if kind == "sliding_attention" else 0,
            eps=eps, precision=precision)
        x, top_e = _expert_layer(
            x, jax.tree_util.tree_map(lambda leaf: leaf[i], weights["moe"]),
            F=s["F"], top_k=s["top_k"], eps=eps, precision=precision)
        if routed is not None:
            routed.append(top_e)
    return x


def logits_at(weights: Dict[str, Any], tokens: jax.Array, positions: jax.Array,
              config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    """Logits [len(positions), V] of one sequence ``tokens [S]`` at ``positions``."""
    x = hidden(weights, tokens, config, precision)[positions]
    return _head(x, weights["final_norm"], weights["lm_head"],
                 eps=float(config["rms_norm_eps"]), precision=precision)


def forward(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
            precision: str = "float32") -> jax.Array:
    """tokens [S] -> logits [S, V] float32."""
    return logits_at(weights, tokens, jnp.arange(tokens.shape[0]), config, precision)
