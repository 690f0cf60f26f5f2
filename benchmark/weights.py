"""The benchmark's weights: a decoder's parameters made on the device from a seed.

One jitted call makes the whole tree in the type it is served or trained in
(bfloat16), one layer at a time (``lax.map`` over per-layer keys), so the
float32 normals of one layer are the largest temporary. The program under test
and the plain reference are both given trees made by this function from the
same seed; neither takes the other's.

Layout (the interface ``program.py`` adapts to the program's own):
``embed [V, D]``, ``layers`` with every leaf stacked on a leading layer axis
(``attn_norm``, ``wq [L, D, H*hd]``, ``wk``, ``wv [L, D, KV*hd]``,
``wo [L, H*hd, D]``, ``mlp_norm``, ``w_gate``, ``w_up [L, D, F]``,
``w_down [L, F, D]``), ``final_norm [D]``, ``lm_head [D, V]``. Matrices are
normal with standard deviation 1/sqrt(fan_in), norms are ones.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.kernel_costs import sizes_of


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number up to 64 bits."""
    seed = int(seed)
    data = jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], jnp.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


@partial(jax.jit, static_argnames=("L", "D", "H", "KV", "hd", "F", "V"))
def _make(key, *, L, D, H, KV, hd, F, V):
    dtype = jnp.bfloat16

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype)

    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def one_layer(k):
        ks = jax.random.split(k, 7)
        return {
            "wq": dense(ks[0], (D, H * hd), D),
            "wk": dense(ks[1], (D, KV * hd), D),
            "wv": dense(ks[2], (D, KV * hd), D),
            "wo": dense(ks[3], (H * hd, D), H * hd),
            "w_gate": dense(ks[4], (D, F), D),
            "w_up": dense(ks[5], (D, F), D),
            "w_down": dense(ks[6], (F, D), F),
        }

    layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
    layers["attn_norm"] = jnp.ones((L, D), dtype)
    layers["mlp_norm"] = jnp.ones((L, D), dtype)
    return {
        "embed": dense(k_embed, (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": dense(k_head, (D, V), D),
    }


def decoder_weights(seed: int, config: Dict[str, Any]) -> Dict[str, Any]:
    """The whole tree for ``config`` (published keys), from ``seed``."""
    if config.get("tie_word_embeddings"):
        raise ValueError("tied embeddings: no configuration of the benchmark has them yet")
    return _make(seed_key(seed), **sizes_of(config))
