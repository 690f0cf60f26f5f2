"""The parallel-sparse family's weights: a decoder of parallel
attention-and-experts blocks, one chip's share of its routed experts, made on
the device from a seed.

One jitted call makes the whole tree in the type it is served in (bfloat16), the
large leaves one layer at a time (``lax.map`` over per-layer keys), so the
float32 normals of one layer's held experts are the largest temporary. The
program under test and the plain reference are both given trees made by this
function from the same seed.

Layout (what ``benchmark/parallel_sparse_program.py`` hands the program under
its own names, without a copy, and ``reference/parallel_sparse_ref.py`` reads),
every leaf stacked over the layers of its kind in layer order: ``embed [V, D]``
(the head is its transpose: tied), ``final_norm [D]``; ``sliding_attention`` and
``full_attention`` (``input_norm [n, D]``: the layer's ONE norm, ``q_proj [n, D,
H * hd]``, ``k_proj``, ``v_proj [n, D, KV * hd]``, ``o_proj [n, H * hd, D]``);
``moe`` over all layers (``router [L, D, E]`` over every PUBLISHED expert,
``gate_up_proj [L, held, D, 2F]``: a held expert's gate then its up, side by
side, ``down_proj [L, held, F, D]``, and the shared experts side by side,
``shared_gate_up_proj [L, D, 2 n F]``: every one's gate, then every one's up,
``shared_down_proj [L, n F, D]``).

Matrices are normal with deviation 1/sqrt(fan_in); what is not is the
configuration's ``init`` group, each entry with its reason under ``assumed``:

- ``stream_deviation`` ``s``: the embedding's entries (a table of rows, each
  read whole: fan-in 1) and the scale of the three output projections (``o_proj``,
  ``down_proj``, ``shared_down_proj``: ``s / sqrt(fan_in)`` times their gain), so
  that a branch adds to the stream a part of it. The head is tied and
  ``logit_scale`` is 1, so logits have deviation ``s sqrt(D)``.
- ``final_norm`` ``"signs"``: the final norm's weight is +1 or -1 by a coin a
  dimension. With ones a tied head scores the token that stands at a position
  by the square of its own embedding's norm, and every position's best
  continuation is the token it holds.
- ``router_logit_deviation``, ``score_gain`` (on ``q_proj``: the deviation of a
  score before the softmax), ``attention_out_gain``, ``routed_out_gain``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.parallel_sparse_ref import KINDS, sizes_of
from benchmark.weights import seed_key

INIT = {"stream_deviation": 1.0, "router_logit_deviation": 1.0, "score_gain": 1.0,
        "attention_out_gain": 1.0, "routed_out_gain": 1.0, "final_norm": "ones"}


@partial(jax.jit, static_argnames=("counts", "L", "D", "V", "heads", "KV", "hd", "E", "held", "F",
                                   "n_shared", "init"))
def _make(key, *, counts, L, D, V, heads, KV, hd, E, held, F, n_shared, init):
    dtype = jnp.bfloat16
    init = dict(init)
    s = init["stream_deviation"]

    def dense(k, shape, fan_in, scale=1.0):
        w = jax.random.normal(k, shape, jnp.float32) * (scale / math.sqrt(fan_in))
        return w.astype(dtype)

    k_embed, k_final, k_moe, *k_kinds = jax.random.split(key, 3 + len(counts))

    def one_attention(k):
        ks = jax.random.split(k, 4)
        return {
            "q_proj": dense(ks[0], (D, heads * hd), D, init["score_gain"]),
            "k_proj": dense(ks[1], (D, KV * hd), D),
            "v_proj": dense(ks[2], (D, KV * hd), D),
            "o_proj": dense(ks[3], (heads * hd, D), heads * hd, s * init["attention_out_gain"]),
        }

    def one_moe(k):
        ks = jax.random.split(k, 5)
        W = n_shared * F
        return {"router": dense(ks[0], (D, E), D, init["router_logit_deviation"]),
                "gate_up_proj": dense(ks[1], (held, D, 2 * F), D),
                "down_proj": dense(ks[2], (held, F, D), F, s * init["routed_out_gain"]),
                "shared_gate_up_proj": dense(ks[3], (D, 2 * W), D),
                "shared_down_proj": dense(ks[4], (W, D), F, s)}

    final = jnp.ones((D,), dtype)
    if init["final_norm"] == "signs":
        final = jnp.where(jax.random.bernoulli(k_final, 0.5, (D,)), 1.0, -1.0).astype(dtype)
    tree = {"embed": dense(k_embed, (V, D), 1, s), "final_norm": final}
    for (kind, n), k in zip(counts, k_kinds):
        tree[kind] = jax.lax.map(one_attention, jax.random.split(k, n))
        tree[kind]["input_norm"] = jnp.ones((n, D), dtype)
    tree["moe"] = jax.lax.map(one_moe, jax.random.split(k_moe, L))
    return tree


def parallel_sparse_weights(seed: int, config: Dict[str, Any]) -> Dict[str, Any]:
    """The whole tree for ``config`` (published keys), from ``seed``."""
    s = sizes_of(config)
    kinds = s.pop("kinds")
    init = {**INIT, **config.get("init", {})}
    if set(init) != set(INIT) or init["final_norm"] not in ("ones", "signs"):
        raise ValueError(f"init {sorted(init)}: this family's weights know {sorted(INIT)}")
    return _make(seed_key(seed), counts=tuple((k, kinds.count(k)) for k in KINDS), L=len(kinds),
                 D=s["D"], V=s["V"], heads=s["heads"], KV=s["KV"], hd=s["hd"], E=s["E"],
                 held=s["held"], F=s["F"], n_shared=s["n_shared"],
                 init=tuple(sorted((k, v if isinstance(v, str) else float(v))
                                   for k, v in init.items())))
