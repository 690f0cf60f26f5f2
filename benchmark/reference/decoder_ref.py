"""Plain reference: a pre-norm decoder in straightforward ``jax.numpy``.

RMSNorm, rotary positions (the published split-halves ``rotate_half`` form),
grouped-query causal attention, SwiGLU, untied output head: the block that
Mistral-7B-v0.3's ``config.json`` and ``modeling_mistral.py`` describe. No
cache, no kernels, no batching tricks; float32 throughout, and on a TPU every
matrix multiplication at ``precision="highest"`` (a float32 matmul there runs
in bfloat16 passes unless told otherwise).

It imports nothing of the program under test and takes nothing the program
made. Weights come from ``benchmark/weights.py`` in bfloat16 and are upcast one
layer at a time, so a 16-layer model's reference fits beside nothing else on
one chip: run it before the program's state is made or after it is freed.

``precision`` selects the arithmetic, for the control that has to come out as
not correct: ``"float32"`` is the reference; ``"bfloat16"`` rounds every matmul
input to bfloat16; ``"int8"`` also rounds each weight matrix to 8 bits with one
scale per output column, the step below the configurations' stated bfloat16.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "int8")


def _int8_round(w: jax.Array) -> jax.Array:
    """Symmetric 8-bit rounding of a [in, out] matrix, one scale per column."""
    a = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=0, keepdims=True) / 127.0, 1e-8)
    rounded = jnp.clip(jnp.round(a / scale), -127, 127) * scale
    # straight through: the gradient passes as if the weight had not been rounded
    return a + jax.lax.stop_gradient(rounded - a)


def _matmul(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    if precision == "float32":
        return jnp.matmul(x, w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    if precision == "int8":
        w = _int8_round(w)
    return jnp.matmul(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x [B, S, heads, hd] at positions 0..S-1: out = x*cos + rotate_half(x)*sin."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


#: query rows scored at once: the float32 scores of a block are heads x 1024 x S
QUERY_BLOCK = 1024


def attention(q: jax.Array, k: jax.Array, v: jax.Array, precision: str) -> jax.Array:
    """Causal grouped-query attention; q [B,S,H,hd], k and v [B,S,KV,hd].

    Computed in blocks of query rows against the whole context, so that a long
    prompt's scores never exist at once."""
    B, S, H, hd = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    prec = jax.lax.Precision.HIGHEST if precision == "float32" else None
    if precision != "float32":
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)
        scores = jnp.einsum(
            "bshd,bthd->bhst", q[:, lo:hi], k[:, :hi], precision=prec,
            preferred_element_type=jnp.float32,
        ) / math.sqrt(hd)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        if precision != "float32":
            probs = probs.astype(jnp.bfloat16)
        out.append(jnp.einsum(
            "bhst,bthd->bshd", probs, v[:, :hi], precision=prec,
            preferred_element_type=jnp.float32,
        ))
    return jnp.concatenate(out, axis=1)


@partial(jax.jit, static_argnames=("H", "KV", "hd", "theta", "eps", "precision"))
def _layer(x, lw, *, H, KV, hd, theta, eps, precision):
    B, S, _ = x.shape
    h = rmsnorm(x, lw["attn_norm"], eps)
    q = rope(_matmul(h, lw["wq"], precision).reshape(B, S, H, hd), theta)
    k = rope(_matmul(h, lw["wk"], precision).reshape(B, S, KV, hd), theta)
    v = _matmul(h, lw["wv"], precision).reshape(B, S, KV, hd)
    a = attention(q, k, v, precision).reshape(B, S, H * hd)
    x = x + _matmul(a, lw["wo"], precision)
    h = rmsnorm(x, lw["mlp_norm"], eps)
    gate = jax.nn.silu(_matmul(h, lw["w_gate"], precision))
    return x + _matmul(gate * _matmul(h, lw["w_up"], precision), lw["w_down"], precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, final_norm, lm_head, *, eps, precision):
    return _matmul(rmsnorm(x, final_norm, eps), lm_head, precision)


def _statics(config: Dict[str, Any]) -> Dict[str, Any]:
    heads = int(config["num_attention_heads"])
    return {
        "H": heads,
        "KV": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or config["hidden_size"] // heads),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def hidden(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
           precision: str = "float32") -> jax.Array:
    """tokens [B, S] -> hidden states before the final norm, [B, S, D] float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: have {PRECISIONS}")
    st = _statics(config)
    x = weights["embed"][tokens].astype(jnp.float32)
    for i in range(int(config["num_hidden_layers"])):
        lw = jax.tree_util.tree_map(lambda leaf: leaf[i], weights["layers"])
        x = _layer(x, lw, precision=precision, **st)
    return x


def forward(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
            precision: str = "float32") -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    x = hidden(weights, tokens, config, precision)
    return _head(x, weights["final_norm"], weights["lm_head"],
                 eps=float(config["rms_norm_eps"]), precision=precision)


def logits_at(weights: Dict[str, Any], tokens: jax.Array, positions: jax.Array,
              config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    """Logits [len(positions), V] of one sequence ``tokens [S]`` at ``positions``."""
    x = hidden(weights, tokens[None, :], config, precision)[0, positions]
    return _head(x, weights["final_norm"], weights["lm_head"],
                 eps=float(config["rms_norm_eps"]), precision=precision)


def loss(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
         precision: str = "float32") -> jax.Array:
    """Mean next-token cross entropy of tokens[:, 1:] given tokens[:, :-1]."""
    logits = forward(weights, tokens, config, precision)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()
