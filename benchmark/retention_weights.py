"""The retention family's weights: an attention-free decoder's parameters made
on the device from a seed.

One jitted call makes the whole tree in the type it is served in (bfloat16;
the gate's bias in float32), the large leaves one layer at a time (``lax.map``
over per-layer keys), so the float32 normals of one layer are the largest
temporary. The program under test and the plain reference are both given
trees made by this function from the same seed.

Layout (what ``benchmark/retention_program.py`` adapts to the program's own
and ``reference/retention_ref.py`` reads): ``embed [V, D]``, ``lm_head [D,
V]`` (untied), ``final_norm [D]``, and ``layers`` with every leaf stacked on a
leading layer axis: ``input_layernorm``, ``q_proj [L, D, H * hd]``,
``k_proj``, ``v_proj [L, D, KV * hd]``, ``o_proj [L, H * hd, D]``, ``q_norm``,
``k_norm [L, hd]``, the gate's ``g_proj [L, KV, D]`` (a key group a row: ``[D,
8]`` is padded to 128 lanes on the chip) and ``g_bias [L, KV]``,
``post_attention_layernorm``, ``gate_proj``, ``up_proj [L, D, F]``,
``down_proj [L, F, D]``.

Matrices are normal with standard deviation 1/sqrt(fan_in), norms are ones.
The gate's bias is ``logit(gamma)`` with ``1 - gamma`` log-uniform over
[5e-4, 2e-2] (``gamma`` in 0.98-0.9995, as a trained retention layer's): with
a zero bias ``gamma`` is about one half, nothing older than a few tokens
reaches an output, and a state dropped between two chunks or read from
another row's slab would pass every comparison.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.retention_ref import sizes_of
from benchmark.weights import seed_key

#: 1 - gamma of the gate at a zero input, log-uniform between these
GAMMA_AWAY = (5e-4, 2e-2)


@partial(jax.jit, static_argnames=("L", "D", "V", "F", "H", "KV", "hd"))
def _make(key, *, L, D, V, F, H, KV, hd):
    dtype = jnp.bfloat16

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype)

    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def one_layer(k):
        ks = jax.random.split(k, 9)
        away = jnp.exp(jax.random.uniform(
            ks[8], (KV,), jnp.float32, math.log(GAMMA_AWAY[0]), math.log(GAMMA_AWAY[1])))
        return {
            "q_proj": dense(ks[0], (D, H * hd), D),
            "k_proj": dense(ks[1], (D, KV * hd), D),
            "v_proj": dense(ks[2], (D, KV * hd), D),
            "o_proj": dense(ks[3], (H * hd, D), H * hd),
            "g_proj": dense(ks[4], (KV, D), D),
            "g_bias": jnp.log1p(-away) - jnp.log(away),
            "gate_proj": dense(ks[5], (D, F), D),
            "up_proj": dense(ks[6], (D, F), D),
            "down_proj": dense(ks[7], (F, D), F),
        }

    layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
    layers.update(input_layernorm=jnp.ones((L, D), dtype),
                  post_attention_layernorm=jnp.ones((L, D), dtype),
                  q_norm=jnp.ones((L, hd), dtype), k_norm=jnp.ones((L, hd), dtype))
    return {"embed": dense(k_embed, (V, D), D), "lm_head": dense(k_head, (D, V), D),
            "final_norm": jnp.ones((D,), dtype), "layers": layers}


def retention_weights(seed: int, config: Dict[str, Any]) -> Dict[str, Any]:
    """The whole tree for ``config`` (published keys), from ``seed``."""
    return _make(seed_key(seed), **sizes_of(config))
