"""The hybrid family's weights: a Mamba-2 / attention decoder's parameters made
on the device from a seed.

One jitted call makes the whole tree in the type it is served in (bfloat16;
the recurrence's own three leaves in float32), the large leaves one layer at a
time (``lax.map`` over per-layer keys), so the float32 normals of one layer
are the largest temporary. The program under test and the plain reference are
both given trees made by this function from the same seed.

Layout (what ``benchmark/hybrid_program.py`` adapts to the program's own and
``reference/hybrid_ref.py`` reads), every leaf stacked over the layers of its
kind in layer order: ``embed [V, D]`` (tied: it is the head too),
``final_norm [D]``; ``mamba`` (``mixer_norm``, ``in_proj``'s three column blocks
``[z | xBC | dt]`` as ``in_proj_z [Lm, D, I]``, ``in_proj_xbc [Lm, D, I + 2N]``,
``in_proj_dt [Lm, D, H]`` (one ``[D, 8512]`` leaf is no whole number of 128
lanes: the chip stores it transposed and a decode program copies all of it),
``conv_w [Lm, K, C]``: tap ``k`` multiplies the input ``K-1-k`` positions back,
``conv_b [Lm, C]``, ``dt_bias``, ``A_log``, ``D [Lm, H]``, ``gate_norm [Lm, I]``,
``out_proj [Lm, I, D]``); ``attention`` (``mixer_norm``, ``q_proj``, ``k_proj``,
``v_proj``, ``o_proj``); ``mlp`` over all layers (``mlp_norm``,
``input_linear [L, D, 2F]``: gate then up, ``output_linear [L, F, D]``).

Matrices are normal with standard deviation 1/sqrt(fan_in) (the conv's taps
1/sqrt(K), its bias a tenth of a unit normal), norms are ones. The embedding's
deviation is 1/(embedding_multiplier sqrt(D)), so that a row times the
multiplier has unit norm: at 1/sqrt(D) the tied head scores the token just
read 7 deviations above every other (its own embedding, times 12, is a
seventh of the last hidden state), every served token repeats the one before,
and no comparison of logits could tell a precision from another. The
recurrence's leaves are Mamba-2's own initialisation, so that the state decays
as a trained one does: ``A_log = log U(1, 16)``, ``dt_bias`` the inverse
softplus of a log-uniform ``dt`` in [1e-3, 1e-1], ``D = 1``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.hybrid_ref import sizes_of
from benchmark.weights import seed_key


@partial(jax.jit, static_argnames=("Lm", "La", "D", "V", "F", "heads", "KV", "hd",
                                   "H", "P", "N", "K", "embed_mult"))
def _make(key, *, Lm, La, D, V, F, heads, KV, hd, H, P, N, K, embed_mult):
    dtype = jnp.bfloat16
    I, C = H * P, H * P + 2 * N

    def dense(k, shape, fan_in, scale=1.0):
        w = jax.random.normal(k, shape, jnp.float32) * (scale / math.sqrt(fan_in))
        return w.astype(dtype)

    k_embed, k_mamba, k_attn, k_mlp = jax.random.split(key, 4)

    def one_mamba(k):
        ks = jax.random.split(k, 8)
        dt = jnp.exp(jax.random.uniform(ks[4], (H,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {
            "in_proj_z": dense(ks[0], (D, I), D),
            "in_proj_xbc": dense(ks[6], (D, C), D),
            "in_proj_dt": dense(ks[7], (D, H), D),
            "out_proj": dense(ks[1], (I, D), I),
            "conv_w": dense(ks[2], (K, C), K),
            "conv_b": dense(ks[3], (C,), 1, 0.1),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(ks[5], (H,), jnp.float32, 1.0, 16.0)),
        }

    def one_attention(k):
        ks = jax.random.split(k, 4)
        return {
            "q_proj": dense(ks[0], (D, heads * hd), D),
            "k_proj": dense(ks[1], (D, KV * hd), D),
            "v_proj": dense(ks[2], (D, KV * hd), D),
            "o_proj": dense(ks[3], (heads * hd, D), heads * hd),
        }

    def one_mlp(k):
        ks = jax.random.split(k, 2)
        return {"input_linear": dense(ks[0], (D, 2 * F), D),
                "output_linear": dense(ks[1], (F, D), F)}

    mamba = jax.lax.map(one_mamba, jax.random.split(k_mamba, Lm))
    mamba.update(mixer_norm=jnp.ones((Lm, D), dtype), gate_norm=jnp.ones((Lm, I), dtype),
                 D=jnp.ones((Lm, H), jnp.float32))
    attention = jax.lax.map(one_attention, jax.random.split(k_attn, La))
    attention["mixer_norm"] = jnp.ones((La, D), dtype)
    mlp = jax.lax.map(one_mlp, jax.random.split(k_mlp, Lm + La))
    mlp["mlp_norm"] = jnp.ones((Lm + La, D), dtype)
    return {"embed": dense(k_embed, (V, D), D, 1.0 / embed_mult), "final_norm": jnp.ones((D,), dtype),
            "mamba": mamba, "attention": attention, "mlp": mlp}


def hybrid_weights(seed: int, config: Dict[str, Any]) -> Dict[str, Any]:
    """The whole tree for ``config`` (published keys), from ``seed``."""
    if not config.get("tie_word_embeddings"):
        raise ValueError("untied head: the hybrid family's one configuration ties it")
    s = sizes_of(config)
    kinds = s.pop("kinds")
    return _make(seed_key(seed), Lm=kinds.count("mamba"), La=kinds.count("attention"),
                 embed_mult=float(config["embedding_multiplier"]), **s)
