"""The sparse-window family's weights: a sparse-expert decoder's parameters made
on the device from a seed.

One jitted call makes the whole tree in the type it is served in (bfloat16), the
large leaves one layer at a time (``lax.map`` over per-layer keys), so the
float32 normals of one layer's experts are the largest temporary. The program
under test and the plain reference are both given trees made by this function
from the same seed.

Layout (what ``benchmark/sparse_program.py`` adapts to the program's own and
``reference/sparse_window_ref.py`` reads), every leaf stacked over the layers of
its kind in layer order: ``embed [V, D]``, ``lm_head [D, V]`` (untied),
``final_norm [D]``; ``sliding_attention`` and ``full_attention`` (``input_norm``,
``q_proj [n, D, H * hd]``, ``k_proj``, ``v_proj [n, D, KV * hd]``, ``o_proj [n, H *
hd, D]``); ``moe`` over all layers (``post_attention_norm [L, D]``, ``router [L, D,
E]``, ``gate_up_proj [L, E, D, 2F]``: an expert's gate then its up, side by side,
``down_proj [L, E, F, D]``).

Matrices are normal with standard deviation 1/sqrt(fan_in), norms are ones. The
embedding is a table of rows, each read whole, so its fan-in is 1 and its
entries have the deviation the normed stream's have: a layer then adds to the
stream a part of it (at 1/sqrt(D), a row of norm 1, the first expert layer's
output IS the stream and a rounding error grows twentyfold by the head; the
configuration's ``assumed`` has the counts). The router's deviation is
``router_logit_deviation / sqrt(D)`` (the configuration's ``init`` group, listed
under its ``assumed``): its logits over a normed hidden state then have that
deviation, and the configuration file says why it is what it is.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.sparse_window_ref import KINDS, sizes_of
from benchmark.weights import seed_key


@partial(jax.jit, static_argnames=("counts", "L", "D", "V", "heads", "KV", "hd", "E", "F",
                                   "router_deviation"))
def _make(key, *, counts, L, D, V, heads, KV, hd, E, F, router_deviation):
    dtype = jnp.bfloat16

    def dense(k, shape, fan_in, scale=1.0):
        w = jax.random.normal(k, shape, jnp.float32) * (scale / math.sqrt(fan_in))
        return w.astype(dtype)

    k_embed, k_head, k_moe, *k_kinds = jax.random.split(key, 3 + len(counts))

    def one_attention(k):
        ks = jax.random.split(k, 4)
        return {
            "q_proj": dense(ks[0], (D, heads * hd), D),
            "k_proj": dense(ks[1], (D, KV * hd), D),
            "v_proj": dense(ks[2], (D, KV * hd), D),
            "o_proj": dense(ks[3], (heads * hd, D), heads * hd),
        }

    def one_moe(k):
        ks = jax.random.split(k, 3)
        return {"router": dense(ks[0], (D, E), D, router_deviation),
                "gate_up_proj": dense(ks[1], (E, D, 2 * F), D),
                "down_proj": dense(ks[2], (E, F, D), F)}

    tree = {"embed": dense(k_embed, (V, D), 1), "lm_head": dense(k_head, (D, V), D),
            "final_norm": jnp.ones((D,), dtype)}
    for (kind, n), k in zip(counts, k_kinds):
        tree[kind] = jax.lax.map(one_attention, jax.random.split(k, n))
        tree[kind]["input_norm"] = jnp.ones((n, D), dtype)
    tree["moe"] = jax.lax.map(one_moe, jax.random.split(k_moe, L))
    tree["moe"]["post_attention_norm"] = jnp.ones((L, D), dtype)
    return tree


def sparse_weights(seed: int, config: Dict[str, Any]) -> Dict[str, Any]:
    """The whole tree for ``config`` (published keys), from ``seed``."""
    s = sizes_of(config)
    kinds = s.pop("kinds")
    del s["top_k"], s["window"]
    return _make(seed_key(seed), counts=tuple((k, kinds.count(k)) for k in KINDS), L=len(kinds),
                 router_deviation=float(config.get("init", {}).get("router_logit_deviation", 1.0)),
                 **s)
