"""Plain reference: a decoder whose every mixer is gated power retention.

The forward pass of ``Brumby-14B-Base`` as its ``config.json`` (``model_type``
``brumby``: the Qwen3-14B block key for key) and the power retention paper
(Buckman, Gelada, Zhang et al., "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239) describe it, in straightforward ``jax.numpy``:
float32 throughout, every matrix multiplication at ``precision="highest"``,
the retention weights as a dense masked matrix. No state, no chunk
recurrence, no feature map, no kernel, no cache, no batching. It imports
nothing of the program under test and takes nothing the program made. With
``h`` the residual stream::

    h0     = embed[ids]
    h      = h + mixer(rmsnorm(h, input_layernorm))
    h      = h + down_proj(silu(gate_proj x) * up_proj x),  x = rmsnorm(h, post_attention_layernorm)
    logits = rmsnorm(h, final_norm) @ lm_head            (untied)
    rmsnorm(x, w) = x * rsqrt(mean(x^2) + rms_norm_eps) * w

The mixer, for token ``t``, query head ``h`` of key group ``g = h // 5``::

    q = x q_proj (40 heads of 128);  k = x k_proj;  v = x v_proj (8 heads of 128)
    q, k = rmsnorm over the 128 of each head (q_norm, k_norm), then rotate-half
           rope at position t, theta 1e6, no scaling
    log gamma_{t,g} = log sigmoid(x . g_proj[g] + g_bias[g])
    w_{t,s} = exp(sum_{r = s+1 .. t} log gamma_{r,g}) (q_{t,h} . k_{s,g})^2      (s <= t)
    y_{t,h} = sum_s w_{t,s} v_{s,g} / (sum_s w_{t,s} + 1e-6)
    mixer   = concat_h(y_{t,h}) o_proj

What the published ``config.json`` does not carry, and this file sets (each is
listed under ``assumed`` in the configuration's file): the degree 2; the
per-head norms of ``q`` and ``k`` and the rope, both kept from the block the
model was retrained from; the gate (one a key group, log-sigmoid of a
projection with a bias: a gate a query head would make 40 states where the
five heads of a group read one); the normaliser (the sum of the weights, so
that any scale on ``q . k`` cancels and none is applied) and its epsilon.

Departures from the published code, each with its reason:

- The published layer computes the same sum by chunks with a carried state
  of symmetric powers of the keys; the chunking and the state are how, not
  what, so the reference has neither.
- The weights ``w`` are computed in blocks of query rows, so that 5,120
  positions of 40 heads fit beside the weights on one chip; that is exact.
- Weights arrive in bfloat16 (``benchmark/retention_weights.py``) and are
  upcast a layer at a time; ``g_proj`` arrives as ``[8, 5120]``, a group a
  row, and is multiplied as its transpose, which is the same product.

``precision`` selects the arithmetic, for the control that has to come out as
not correct: ``"float32"`` is the reference; ``"bfloat16"`` rounds every
matmul input (the scores' and the weighted sum's among them) to bfloat16;
``"int8"`` also rounds each weight matrix to 8 bits with one scale per output
column.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "int8")
#: query rows weighted at once: a block's float32 weights are heads x 512 x S
QUERY_BLOCK = 512
RETENTION_EPS = 1e-6


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


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rope of ``x [S, heads, hd]`` at positions 0 .. S-1."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def retention(q: jax.Array, k: jax.Array, v: jax.Array, log_g: jax.Array,
              precision: str) -> jax.Array:
    """Power retention of degree 2, state-free: ``q [S, H, hd]``, ``k`` and
    ``v [S, KV, hd]``, ``log_g [S, KV]``; returns ``[S, H, hd]``."""
    S, H, _ = q.shape
    group = H // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    a = jnp.repeat(jnp.cumsum(log_g, axis=0), group, axis=1).T  # [H, S]
    prec = jax.lax.Precision.HIGHEST if precision == "float32" else None
    if precision != "float32":
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)
        scores = jnp.einsum("shd,thd->hst", q[lo:hi], k[:hi], precision=prec,
                            preferred_element_type=jnp.float32)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        # the decay from s to t: masked before the exponential
        decay = jnp.exp(jnp.where(causal[None], a[:, lo:hi, None] - a[:, None, :hi], -jnp.inf))
        w = decay * scores * scores
        den = jnp.sum(w, axis=-1)  # [H, s]
        if precision != "float32":
            w = w.astype(jnp.bfloat16)
        num = jnp.einsum("hst,thd->shd", w, v[:hi], precision=prec,
                         preferred_element_type=jnp.float32)
        out.append(num / (den.T[:, :, None] + RETENTION_EPS))
    return jnp.concatenate(out, axis=0)


@partial(jax.jit, static_argnames=("H", "KV", "hd", "theta", "eps", "precision"))
def _mixer(x, lw, *, H, KV, hd, theta, eps, precision):
    S = x.shape[0]
    h = rmsnorm(x, lw["input_layernorm"], eps)
    q = _matmul(h, lw["q_proj"], precision).reshape(S, H, hd)
    k = _matmul(h, lw["k_proj"], precision).reshape(S, KV, hd)
    v = _matmul(h, lw["v_proj"], precision).reshape(S, KV, hd)
    q = rope(rmsnorm(q, lw["q_norm"], eps), theta)
    k = rope(rmsnorm(k, lw["k_norm"], eps), theta)
    gate = _matmul(h, lw["g_proj"].T, precision) + lw["g_bias"].astype(jnp.float32)
    y = retention(q, k, v, jax.nn.log_sigmoid(gate), precision).reshape(S, H * hd)
    return x + _matmul(y, lw["o_proj"], precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _mlp(x, lw, *, eps, precision):
    h = rmsnorm(x, lw["post_attention_layernorm"], eps)
    return x + _matmul(jax.nn.silu(_matmul(h, lw["gate_proj"], precision))
                       * _matmul(h, lw["up_proj"], precision), lw["down_proj"], precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, final_norm, lm_head, *, eps, precision):
    return _matmul(rmsnorm(x, final_norm, eps), lm_head, precision)


def sizes_of(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the reference (and the weights) need, from the published
    keys; raises on a configuration this file does not describe."""
    checks = {"attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False,
              "rope_scaling": None, "use_sliding_window": False}
    for key, want in checks.items():
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: the retention reference describes {want!r} only")
    if int(config.get("retention_degree", 2)) != 2:
        raise ValueError("power retention of degree 2 only")
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    if heads % kv:
        raise ValueError("num_attention_heads is not a multiple of num_key_value_heads")
    return {
        "L": int(config["num_hidden_layers"]), "D": int(config["hidden_size"]),
        "V": int(config["vocab_size"]), "F": int(config["intermediate_size"]),
        "H": heads, "KV": kv,
        "hd": int(config.get("head_dim") or config["hidden_size"] // heads),
    }


def hidden(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
           precision: str = "float32") -> jax.Array:
    """tokens [S] -> the residual stream before the final norm, [S, D] float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: have {PRECISIONS}")
    s = sizes_of(config)
    eps = float(config["rms_norm_eps"])
    x = weights["embed"][tokens].astype(jnp.float32)
    for i in range(s["L"]):
        lw = jax.tree_util.tree_map(lambda leaf: leaf[i], weights["layers"])
        x = _mixer(x, lw, H=s["H"], KV=s["KV"], hd=s["hd"],
                   theta=float(config["rope_theta"]), eps=eps, precision=precision)
        x = _mlp(x, lw, eps=eps, precision=precision)
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
