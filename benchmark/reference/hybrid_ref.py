"""Plain reference: a hybrid decoder of Mamba-2 mixers and a few attention layers.

The forward pass that ``granite-4.0-h-micro``'s ``config.json``
(``model_type`` ``granitemoehybrid``, ``num_local_experts`` 0) and the
Mamba-2 paper describe, in straightforward ``jax.numpy``: float32 throughout,
every matrix multiplication at ``precision="highest"``, the recurrence as a
``lax.scan`` over tokens, attention dense. No cache, no chunked scan, no
kernels, no batching. It imports nothing of the program under test and takes
nothing the program made. With ``h`` the residual stream::

    h0     = embed[ids] * embedding_multiplier
    h      = h + residual_multiplier * mixer(rmsnorm(h, mixer_norm))
    h      = h + residual_multiplier * mlp(rmsnorm(h, mlp_norm))
    logits = (rmsnorm(h, final_norm) @ embed.T) / logits_scaling
    mlp(x) = output_linear(silu(g) * u),  [g | u] = input_linear(x)

``layer_types`` says which mixer a layer has.

- ``attention``: causal grouped-query attention, ``num_attention_heads`` /
  ``num_key_value_heads`` heads of ``head_dim``, no bias, **no positional
  term at all** (``position_embedding_type`` ``nope``), scores times
  ``attention_multiplier`` (1/64 here, not 1/sqrt(64)).
- ``mamba``: ``[z | xBC | dt] = in_proj(x)`` with widths ``I``, ``I + 2 G N``,
  ``H`` (``I = mamba_expand * hidden_size = H * P``); ``xBC = silu(conv1d(xBC))``,
  depthwise, causal, kernel ``mamba_d_conv``, with bias; ``[x | B | C] = xBC``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; for each head ``h`` with
  ``x_t`` in R^P: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D_h x_t``; ``y = rmsnorm(y * silu(z)) * w`` over the ``I``
  channels (the gate first, then the norm, one group); ``out_proj(y)``.

Departures from the published code, each with its reason:

- ``mamba_n_groups`` is 1 in the configuration this was written for, and the
  code takes ``B`` and ``C`` as one group shared by every head; more groups
  raise.
- The published mixer computes the same recurrence by chunks of
  ``mamba_chunk_size``; the chunking is how, not what, so the reference has
  none (``mamba_chunk_size`` is read by the program alone).
- Attention is computed in blocks of query rows and the recurrence holds one
  token's state at a time, so that a 6.5K-token sequence fits beside the
  weights on one chip; both are exact.
- Weights arrive in bfloat16 (``benchmark/hybrid_weights.py``) and are upcast
  a layer at a time; ``in_proj`` arrives as its three column blocks, and is
  multiplied block by block, which is the same product. The recurrent state
  is float32 in every precision.

``precision`` selects the arithmetic, for the control that has to come out as
not correct: ``"float32"`` is the reference; ``"bfloat16"`` rounds every
matmul input (and what enters the recurrence) to bfloat16; ``"int8"`` also
rounds each weight matrix to 8 bits with one scale per output column.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "int8")
#: query rows scored at once: a block's float32 scores are heads x 1024 x S
QUERY_BLOCK = 1024


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


def attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
              precision: str) -> jax.Array:
    """Causal grouped-query attention without positions; q [S, H, hd], k and v
    [S, KV, hd]; scores times ``scale``."""
    S, H, _ = q.shape
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
                            preferred_element_type=jnp.float32) * scale
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        if precision != "float32":
            probs = probs.astype(jnp.bfloat16)
        out.append(jnp.einsum("hst,thd->shd", probs, v[:hi], precision=prec,
                              preferred_element_type=jnp.float32))
    return jnp.concatenate(out, axis=0)


def recurrence(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array) -> jax.Array:
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` from
    ``S = 0``, a token at a time; x [S, H, P], dt [S, H], A [H], Bm, Cm [S, N]."""
    H, P = x.shape[1:]

    def step(S, inp):
        xt, dtt, bt, ct = inp
        S = jnp.exp(dtt * A)[:, None, None] * S + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return S, jnp.sum(S * ct[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, Bm.shape[-1]), jnp.float32), (x, dt, Bm, Cm))
    return y


@partial(jax.jit, static_argnames=("F", "eps", "rm", "precision"))
def _mlp(x, lw, *, F, eps, rm, precision):
    gu = _matmul(rmsnorm(x, lw["mlp_norm"], eps), lw["input_linear"], precision)
    return x + rm * _matmul(jax.nn.silu(gu[:, :F]) * gu[:, F:], lw["output_linear"], precision)


@partial(jax.jit, static_argnames=("H", "KV", "hd", "scale", "eps", "rm", "precision"))
def _attention_mixer(x, lw, *, H, KV, hd, scale, eps, rm, precision):
    S = x.shape[0]
    h = rmsnorm(x, lw["mixer_norm"], eps)
    q = _matmul(h, lw["q_proj"], precision).reshape(S, H, hd)
    k = _matmul(h, lw["k_proj"], precision).reshape(S, KV, hd)
    v = _matmul(h, lw["v_proj"], precision).reshape(S, KV, hd)
    a = attention(q, k, v, scale, precision).reshape(S, H * hd)
    return x + rm * _matmul(a, lw["o_proj"], precision)


@partial(jax.jit, static_argnames=("H", "P", "N", "K", "eps", "rm", "precision"))
def _mamba_mixer(x, lw, *, H, P, N, K, eps, rm, precision):
    S, I = x.shape[0], H * P
    h = rmsnorm(x, lw["mixer_norm"], eps)
    z, xbc, dt = (_matmul(h, lw[f"in_proj_{part}"], precision) for part in ("z", "xbc", "dt"))
    if precision != "float32":
        xbc = xbc.astype(jnp.bfloat16).astype(jnp.float32)
    # depthwise causal conv: output t sees inputs t-K+1 .. t, zeros before 0
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
    w = lw["conv_w"].astype(jnp.float32)  # [K, C]: tap k multiplies input t-K+1+k
    xbc = jax.nn.silu(sum(w[k] * padded[k:k + S] for k in range(K))
                      + lw["conv_b"].astype(jnp.float32))
    if precision != "float32":
        xbc = xbc.astype(jnp.bfloat16).astype(jnp.float32)
    xs = xbc[:, :I].reshape(S, H, P)
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(jnp.float32))
    y = recurrence(xs, dt, -jnp.exp(lw["A_log"].astype(jnp.float32)),
                   xbc[:, I:I + N], xbc[:, I + N:])
    y = y + lw["D"].astype(jnp.float32)[None, :, None] * xs
    y = rmsnorm(y.reshape(S, I) * jax.nn.silu(z), lw["gate_norm"], eps)
    return x + rm * _matmul(y, lw["out_proj"], precision)


@partial(jax.jit, static_argnames=("eps", "scaling", "precision"))
def _head(x, final_norm, embed, *, eps, scaling, precision):
    return _matmul(rmsnorm(x, final_norm, eps), embed.T, precision) / scaling


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference (and the weights) need, from the published keys."""
    kinds: List[str] = list(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types does not list num_hidden_layers layers")
    if int(config.get("mamba_n_groups", 1)) != 1:
        raise ValueError("one group of B and C only (mamba_n_groups 1)")
    if int(config.get("num_local_experts", 0)):
        raise ValueError("no expert branch (num_local_experts 0)")
    heads = int(config["num_attention_heads"])
    H, P = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if H * P != int(config["mamba_expand"]) * int(config["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    return {
        "kinds": kinds, "D": int(config["hidden_size"]), "V": int(config["vocab_size"]),
        "F": int(config["shared_intermediate_size"]), "heads": heads,
        "KV": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or config["hidden_size"] // heads),
        "H": H, "P": P, "N": int(config["mamba_d_state"]), "K": int(config["mamba_d_conv"]),
    }


def hidden(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
           precision: str = "float32") -> jax.Array:
    """tokens [S] -> the residual stream before the final norm, [S, D] float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: have {PRECISIONS}")
    s = sizes_of(config)
    eps, rm = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    x = weights["embed"][tokens].astype(jnp.float32) * float(config["embedding_multiplier"])
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(s["kinds"]):
        lw = jax.tree_util.tree_map(lambda leaf: leaf[seen[kind]], weights[kind])
        seen[kind] += 1
        if kind == "attention":
            x = _attention_mixer(x, lw, H=s["heads"], KV=s["KV"], hd=s["hd"],
                                 scale=float(config["attention_multiplier"]),
                                 eps=eps, rm=rm, precision=precision)
        else:
            x = _mamba_mixer(x, lw, H=s["H"], P=s["P"], N=s["N"], K=s["K"],
                             eps=eps, rm=rm, precision=precision)
        x = _mlp(x, jax.tree_util.tree_map(lambda leaf: leaf[i], weights["mlp"]),
                 F=s["F"], eps=eps, rm=rm, precision=precision)
    return x


def logits_at(weights: Dict[str, Any], tokens: jax.Array, positions: jax.Array,
              config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    """Logits [len(positions), V] of one sequence ``tokens [S]`` at ``positions``."""
    x = hidden(weights, tokens, config, precision)[positions]
    return _head(x, weights["final_norm"], weights["embed"],
                 eps=float(config["rms_norm_eps"]),
                 scaling=float(config["logits_scaling"]), precision=precision)


def forward(weights: Dict[str, Any], tokens: jax.Array, config: Dict[str, Any],
            precision: str = "float32") -> jax.Array:
    """tokens [S] -> logits [S, V] float32."""
    return logits_at(weights, tokens, jnp.arange(tokens.shape[0]), config, precision)
