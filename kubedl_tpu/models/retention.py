"""An attention-free decoder: every mixer is gated power retention.

The block is the pre-norm rope/GQA/SwiGLU decoder's (``models/llama.py``) with
the softmax taken out of the mixer::

    h0     = embed[ids]
    h      = h + mixer(rmsnorm(h))
    h      = h + W_down(silu(W_gate x) * W_up x),  x = rmsnorm(h)
    logits = rmsnorm(h) @ lm_head                     (the head is its own leaf)

The mixer projects ``q`` (``n_heads`` of ``head_dim``), ``k`` and ``v``
(``n_kv_heads``), norms ``q`` and ``k`` over each head (RMSNorm, weights
``[head_dim]``), rotates both (rotate-half rope at the token's position),
takes a decay a key group ``log gamma = log sigmoid(x . W_g + b_g)``, and
mixes by power retention of degree 2 (``ops/power_retention.py``): weights
``gamma^(t - s) (q_t . k_s)^2`` over the sum of weights, no softmax, no scale;
then ``W_o``. The query heads of a group read one state.

What a served row owns (``init_cache``): ``pos`` and a fixed slab of float32
state a layer, ``S [B, L, KV, hd / 2 + 1, hd, hd]`` and its normaliser ``z [B,
L, KV, hd / 2 + 1, hd]``. There is no pool and no block table: nothing a row
holds grows with its context, and a step costs the same at any position. The
slab's rules are ``models/hybrid_ssm.py``'s: a prefill program whose row
starts at position 0 begins from a zero slab, whatever the slab held; one that
starts later carries it on; a padded position has ``log gamma = 0`` and a zero
key, and so leaves the state alone; a decode step computes every row but
advances the slab of the rows named ``live`` only. Prefill runs the chunked
form (``retention_chunked``, ``chunk`` positions at a time), decode the
one-step form.

Parameters are stacked ``[n_layers, ...]``; one ``lax.scan`` runs over the
layers, the slabs its carries, written in place under donation.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from kubedl_tpu.models import llama
from kubedl_tpu.ops import power_retention as pr
from kubedl_tpu.ops import ssd_scan

Params = Dict[str, Any]


@dataclass(frozen=True)
class RetentionConfig:
    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_dim: int = 17408
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    #: added to the sum of weights a token is divided by
    retention_eps: float = pr.EPS
    #: positions a chunk of the chunked form holds: the program's, not the
    #: model's. Inside a chunk the work is quadratic (2 x 2 heads hd a pair),
    #: across chunks it is the state's (4 heads x 8,320 x hd a token), so a
    #: chunk as long as the engine's own is the cheaper until 4,000 positions
    chunk: int = 1024
    #: gamma of a trained layer: the gate's bias is drawn so that gamma lies
    #: here at a zero input (log-uniform in 1 - gamma)
    gamma_range: Tuple[float, float] = (0.98, 0.9995)
    max_seq: int = 32768
    dtype: Any = jnp.bfloat16

    @property
    def diagonals(self) -> int:
        return pr.diagonals(self.head_dim)

    def num_params(self) -> int:
        hd = self.head_dim
        mixer = (self.dim * hd * (2 * self.n_heads + 2 * self.n_kv_heads)
                 + self.n_kv_heads * (self.dim + 1) + 2 * hd)
        mlp = 3 * self.dim * self.ffn_dim
        return (self.n_layers * (mixer + mlp + 2 * self.dim)
                + 2 * self.vocab_size * self.dim + self.dim)


#: Brumby-14B-Base as published (huggingface.co/manifestai/Brumby-14B-Base,
#: config.json): the Qwen3-14B block, retention in every layer
BRUMBY_14B_BASE = RetentionConfig()
#: CPU-test size: two layers, heads of 16 (9 diagonals), chunks of 8
TINY_RETENTION = RetentionConfig(
    vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
    ffn_dim=128, rope_theta=10000.0, chunk=8, max_seq=128, dtype=jnp.float32,
)

PRESETS = {"brumby-14b-base": BRUMBY_14B_BASE, "tiny-retention": TINY_RETENTION}


def preset(name: str) -> RetentionConfig:
    return PRESETS[name]


# ---- init ------------------------------------------------------------------

def gate_bias(key: jax.Array, shape, gamma_range: Tuple[float, float]) -> jax.Array:
    """The gate's bias: ``logit(gamma)`` with ``1 - gamma`` log-uniform over
    the range. With a zero bias ``gamma`` is about one half: nothing older
    than a few tokens reaches an output, and a state dropped between two
    chunks would pass any comparison."""
    lo, hi = (1.0 - g for g in sorted(gamma_range, reverse=True))
    away = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(lo), math.log(hi)))
    return jnp.log1p(-away) - jnp.log(away)


def retention_init(key: jax.Array, cfg: RetentionConfig) -> Params:
    """Normal weights of deviation 1/sqrt(fan_in), norms ones, the gate's bias
    by :func:`gate_bias`."""
    L, D, F, V, dt_ = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size, cfg.dtype
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k = iter(jax.random.split(key, 11))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dt_)

    return {
        "embed": dense(next(k), (V, D), D),
        "lm_head": dense(next(k), (D, V), D),
        "final_norm": jnp.ones((D,), dt_),
        "layers": {
            "mixer_norm": jnp.ones((L, D), dt_),
            "wq": dense(next(k), (L, D, H * hd), D),
            "wk": dense(next(k), (L, D, KV * hd), D),
            "wv": dense(next(k), (L, D, KV * hd), D),
            "wo": dense(next(k), (L, H * hd, D), H * hd),
            "q_norm": jnp.ones((L, hd), dt_),
            "k_norm": jnp.ones((L, hd), dt_),
            # the gate's D -> KV projection, a group a ROW: [D, 8] would be
            # padded to 128 lanes on the chip
            "w_g": dense(next(k), (L, KV, D), D),
            "b_g": gate_bias(next(k), (L, KV), cfg.gamma_range),
            "mlp_norm": jnp.ones((L, D), dt_),
            "w_gate": dense(next(k), (L, D, F), D),
            "w_up": dense(next(k), (L, D, F), D),
            "w_down": dense(next(k), (L, F, D), F),
        },
    }


def init_cache(cfg: RetentionConfig, batch: int) -> Params:
    """``pos`` and every row's slab, zeroed. No pool, no block table."""
    state = (batch, cfg.n_layers, cfg.n_kv_heads, cfg.diagonals, cfg.head_dim)
    return {
        "pos": jnp.zeros((batch,), jnp.int32),
        "S": jnp.zeros(state + (cfg.head_dim,), jnp.float32),
        "z": jnp.zeros(state, jnp.float32),
    }


def state_bytes_per_row(cfg: RetentionConfig) -> int:
    """Bytes of state one row owns, whatever its context: all it owns."""
    return int(4 * cfg.n_layers * cfg.n_kv_heads * pr.features(cfg.head_dim)
               * (cfg.head_dim + 1))


# ---- the layers ------------------------------------------------------------

def _norm(x: jax.Array, w: jax.Array, cfg: RetentionConfig) -> jax.Array:
    return llama.rmsnorm(x, w, cfg.norm_eps)


def _rope(x: jax.Array, pos: jax.Array, cfg: RetentionConfig) -> jax.Array:
    """Rotate-half rope (``llama.apply_rope``'s pairing) of ``x [..., heads,
    hd]`` at positions ``pos [...]``, a row's own."""
    half = cfg.head_dim // 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None, None] * inv
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _mixer_inputs(h: jax.Array, lp: Params, pos: jax.Array, real: jax.Array,
                  cfg: RetentionConfig):
    """``h [..., D]`` (normed) at positions ``pos [...]`` -> ``q [..., H, hd]``,
    ``k``, ``v [..., KV, hd]``, ``log_g [..., KV]`` float32. Where ``real`` is
    false the key is zeros and ``log gamma`` 0: the state is left alone."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = h.shape[:-1]
    q = _norm((h @ lp["wq"]).reshape(*lead, H, hd), lp["q_norm"], cfg)
    k = _norm((h @ lp["wk"]).reshape(*lead, KV, hd), lp["k_norm"], cfg)
    v = (h @ lp["wv"]).reshape(*lead, KV, hd)
    gate = jnp.einsum("...d,gd->...g", h, lp["w_g"], preferred_element_type=jnp.float32)
    log_g = jax.nn.log_sigmoid(gate + lp["b_g"])
    q, k = _rope(q, pos, cfg), _rope(k, pos, cfg)
    return (q, jnp.where(real[..., None, None], k, jnp.zeros((), k.dtype)), v,
            jnp.where(real[..., None], log_g, 0.0))


def _mlp(x: jax.Array, lp: Params, cfg: RetentionConfig) -> jax.Array:
    h = _norm(x, lp["mlp_norm"], cfg)
    act = jax.nn.silu((h @ lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return x + (act * (h @ lp["w_up"])) @ lp["w_down"]


def _run_layers(params: Params, x, S, z, mixer_fn, cfg: RetentionConfig):
    """Every layer in order, one ``lax.scan`` with the slabs as carries.
    ``mixer_fn(h, lp, S, z, m) -> (out, S, z)`` reads and writes layer ``m``'s
    part of the slabs."""
    def one(carry, inp):
        x, S, z = carry
        lp, m = inp
        out, S, z = mixer_fn(_norm(x, lp["mixer_norm"], cfg), lp, S, z, m)
        x = x + out.astype(x.dtype) @ lp["wo"]
        return (_mlp(x, lp, cfg), S, z), None

    layers = (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32))
    return lax.scan(one, (x, S, z), layers)[0]


def _logits(params: Params, x: jax.Array, cfg: RetentionConfig) -> jax.Array:
    """``x [B, D]`` (before the final norm) -> float32 logits."""
    x = _norm(x, params["final_norm"], cfg)
    return jnp.einsum("bd,dv->bv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ---- prefill ---------------------------------------------------------------

def prefill(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, T] right-padded prompt tokens of the rows
    lengths: jax.Array,  # [B]; 0 = row untouched
    cfg: RetentionConfig,
    rows: jax.Array,  # [B] cache rows of this compact batch
    starts: Optional[jax.Array] = None,  # [B] where each row's tokens begin
) -> Tuple[jax.Array, Params]:
    """``lengths`` prompt tokens of each of ``rows`` from ``starts`` (None:
    from position 0): last-token logits ``[B, V]`` and the cache. A row whose
    tokens begin at 0 begins from a zero slab, any other from the slab its
    last chunk left; where every row of the program begins at 0 its first
    chunk takes no product with the state. Pad positions and inactive rows
    leave the state alone."""
    B, T = tokens.shape
    active = lengths > 0
    begin = jnp.zeros((B,), jnp.int32) if starts is None else starts
    posq = begin[:, None] + jnp.arange(T)[None, :]
    real = active[:, None] & (jnp.arange(T)[None, :] < lengths[:, None])
    fresh = active & (begin == 0)
    no_state = jnp.all(fresh)

    def mixer_fn(h, lp, S, z, m):
        q, k, v, log_g = _mixer_inputs(h, lp, posq, real, cfg)
        s0 = jnp.where(fresh[:, None, None, None, None], 0.0, S[rows, m])
        z0 = jnp.where(fresh[:, None, None, None], 0.0, z[rows, m])
        y, s1, z1 = pr.retention_chunked(
            q, k, v, log_g, s0, z0, cfg.chunk, first_reads_no_state=no_state,
            eps=cfg.retention_eps)
        return y.reshape(B, T, -1), S.at[rows, m].set(s1), z.at[rows, m].set(z1)

    x, S, z = _run_layers(
        params, llama.gather_embed(params["embed"], tokens), cache["S"], cache["z"],
        mixer_fn, cfg)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    pos = cache["pos"]
    pos = pos.at[rows].set(jnp.where(active, begin + lengths, pos[rows]).astype(jnp.int32))
    return _logits(params, last, cfg), {"pos": pos, "S": S, "z": z}


# ---- decode ----------------------------------------------------------------

def decode_step(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, 1]
    live: jax.Array,  # [B] bool: rows whose slab this step may advance
    cfg: RetentionConfig,
) -> Tuple[jax.Array, Params]:
    """One token for every cache row at its ``pos``, the one-step recurrence
    on the slabs of ``live``. A row not in ``live`` (vacant, or between two
    chunks of its prompt) computes garbage nobody reads and keeps its slab.

    How the slabs advance is chosen here, from what can be observed
    (:func:`steps_listed_rows`): on a TPU, a float32 state of whole tiles goes
    whole to the kernel ``power_retention.retention_step_rows``, one call a
    layer, which reads and writes the ``live`` rows' slabs once and names no
    other row's; anywhere else ``retention_step`` sweeps every row's slab of
    the layer with ``gamma = 1`` and a zero key for the rows not ``live`` (a
    CPU, and a shape the kernel refuses: a head that is not whole lanes)."""
    pos = cache["pos"]
    listed = ssd_scan.scheduled_rows(live) if steps_listed_rows(cache) else None

    def mixer_fn(h, lp, S, z, m):
        q, k, v, log_g = _mixer_inputs(h, lp, pos, live, cfg)
        if listed is None:
            s1, z1, y = pr.retention_step(
                q, k, v, log_g, lax.dynamic_index_in_dim(S, m, 1, keepdims=False),
                lax.dynamic_index_in_dim(z, m, 1, keepdims=False), cfg.retention_eps)
            S = lax.dynamic_update_index_in_dim(S, s1, m, 1)
            z = lax.dynamic_update_index_in_dim(z, z1, m, 1)
        else:  # the state whole: no plane of it is sliced out or put back
            S, z, y = pr.retention_step_rows(
                q, k, v, log_g, S, z, m, *listed, eps=cfg.retention_eps)
        return y.reshape(y.shape[0], -1), S, z

    x, S, z = _run_layers(
        params, llama.gather_embed(params["embed"], tokens[:, 0]), cache["S"],
        cache["z"], mixer_fn, cfg)
    return _logits(params, x, cfg), {"pos": pos + 1, "S": S, "z": z}


def steps_listed_rows(cache: Params) -> bool:
    """Whether a decode step of this process advances the scheduled rows'
    slabs alone, by the kernel (else it sweeps every row's): a TPU, and a
    state the kernel can take as it stands."""
    return jax.default_backend() == "tpu" and pr.step_kernel_fits(cache["S"])


def decode_segment(
    params: Params, cache: Params, tokens: jax.Array, temps: jax.Array,
    key: jax.Array, live: jax.Array, cfg: RetentionConfig, n_steps: int,
    greedy: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, Params, Dict[str, jax.Array]]:
    """``n_steps`` of :func:`decode_step` with the decoder's own on-device
    sample-and-feed chain (``llama.sampled_segment``): ``(toks [B, n_steps],
    last [B, 1], next_key, cache, counters)``. The counters are
    ``hybrid_ssm.decode_segment``'s: ``slabs_stepped``, the sum over the
    steps of the rows whose slab the step fetched (the ``live`` rows through
    the kernel, every row where ``retention_step`` sweeps them all), and
    ``slabs_held``, rows times steps."""
    step = partial(decode_step, live=live, cfg=cfg)
    B = live.shape[0]
    stepped = jnp.sum(live, dtype=jnp.int32) if steps_listed_rows(cache) else jnp.int32(B)
    counters = {"slabs_stepped": n_steps * stepped, "slabs_held": jnp.int32(n_steps * B)}
    return (*llama.sampled_segment(
        lambda cache, toks: step(params, cache, toks), cache, tokens, temps, key,
        n_steps, greedy), counters)
