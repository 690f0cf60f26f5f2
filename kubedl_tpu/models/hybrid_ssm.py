"""A hybrid state-space decoder: Mamba-2 mixers beside a few attention layers.

The stack is ``periods`` repeats of (``mamba_before`` mamba layers, one
attention layer, ``mamba_after`` mamba layers); every layer is a mixer and a
shared SwiGLU MLP, each behind an RMSNorm and a scaled residual::

    h0     = embed[ids] * embedding_multiplier
    h      = h + residual_multiplier * mixer(rmsnorm(h))
    h      = h + residual_multiplier * mlp(rmsnorm(h))
    logits = (rmsnorm(h) @ embed.T) / logits_scaling

The attention mixer is causal GQA with NO positional term and scores scaled
by ``attention_multiplier`` (not 1/sqrt(head_dim)). The mamba mixer is
``[z | xBC | dt] = in_proj(x)``, a depthwise causal conv over ``xBC`` then
silu, the state-space recurrence of ``ops/ssd_scan.py`` per head, ``D x`` added,
the gate ``y * silu(z)`` taken BEFORE the RMSNorm over the inner width, and
``out_proj``.

What a served row owns (``init_cache``): its K/V blocks in the pools of the
attention layers alone (``[periods, NB, BS, KV * hd]``: the paged layout of
``models/llama.py`` with a token's heads side by side, because a last axis of
``hd`` = 64 is padded to 128 lanes on the chip and the pool would take twice
its bytes; the block table, the trash block, the gathered view and its span
ladder are the decoder's), and beside them a fixed slab of recurrent state: the float32
``ssm [B, n_mamba, H, P, N]`` and the conv window ``conv [B, n_mamba, K-1, C]``
(the last ``K-1`` inputs of the conv; channels last, so that they lie along
the lanes). A prefill program whose row starts at position 0 begins from a
zero slab, whatever the slab held; one that starts later carries it on.
Prefill runs the chunked form of the recurrence (``ssd_chunked``), never a
loop over tokens; decode the one-step form. A padded position has ``dt = 0``
and so leaves the state alone; the conv window is taken at the last real
token. A decode step computes every row but advances the slab of the rows
named ``live`` only: a row between two chunks of its prompt keeps its state.

Parameters are stacked by kind, in layer order: mamba leaves ``[n_mamba,
...]`` (period ``p``'s are ``[p * mamba_per_period, (p + 1) *
mamba_per_period)``: the bytes of ``[periods, mamba_per_period, ...]``),
attention leaves ``[periods, ...]``, MLP leaves ``[n_layers, ...]``. One
``lax.scan`` runs over the periods, the pools and the slabs its carries,
written in place under donation.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from kubedl_tpu.models import llama
from kubedl_tpu.ops import ssd_scan

Params = Dict[str, Any]


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    #: the layer pattern: ``periods`` x (before mamba, 1 attention, after mamba)
    periods: int = 4
    mamba_before: int = 5
    mamba_after: int = 4
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    ffn_dim: int = 8192
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    conv_kernel: int = 4
    #: positions a chunk of the chunked scan holds
    ssm_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    attention_multiplier: float = 1.0 / 64
    norm_eps: float = 1e-5
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16

    @property
    def mamba_per_period(self) -> int:
        return self.mamba_before + self.mamba_after

    @property
    def layers_per_period(self) -> int:
        return self.mamba_per_period + 1

    @property
    def n_mamba(self) -> int:
        return self.periods * self.mamba_per_period

    @property
    def n_layers(self) -> int:
        return self.periods * self.layers_per_period

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: x and one group's B and C."""
        return self.ssm_inner + 2 * self.ssm_state

    @property
    def in_proj_dim(self) -> int:
        return self.ssm_inner + self.conv_dim + self.ssm_heads

    def num_params(self) -> int:
        mamba = (self.dim * self.in_proj_dim + self.ssm_inner * self.dim
                 + self.conv_dim * (self.conv_kernel + 1)
                 + 3 * self.ssm_heads + self.ssm_inner)
        attn = 2 * self.dim * self.n_heads * self.head_dim \
            + 2 * self.dim * self.n_kv_heads * self.head_dim
        mlp = 3 * self.dim * self.ffn_dim
        return (self.n_mamba * mamba + self.periods * attn
                + self.n_layers * (mlp + 2 * self.dim)
                + self.vocab_size * self.dim + self.dim)


def pattern_of(layer_types: Sequence[str]) -> Tuple[int, int, int]:
    """``(periods, mamba_before, mamba_after)`` of a published
    ``layer_types`` list; raises unless it is whole repeats of (mamba...,
    one attention, mamba...)."""
    kinds = list(layer_types)
    n_attn = kinds.count("attention")
    if not n_attn or len(kinds) % n_attn or set(kinds) != {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds} is not periods of mamba and attention")
    span = len(kinds) // n_attn
    period = kinds[:span]
    if kinds != period * n_attn or period.count("attention") != 1:
        raise ValueError(f"layer_types {kinds} does not repeat one period")
    before = period.index("attention")
    return n_attn, before, span - 1 - before


#: granite-4.0-h-micro as published (huggingface.co/ibm-granite/
#: granite-4.0-h-micro, config.json): 40 layers, attention at 5, 15, 25, 35
GRANITE_4_H_MICRO = HybridConfig()
#: CPU-test size: two periods of (2 mamba, attention, 1 mamba), chunks of 8
TINY_HYBRID = HybridConfig(
    vocab_size=256, dim=64, periods=2, mamba_before=2, mamba_after=1,
    n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128, ssm_heads=4,
    ssm_head_dim=32, ssm_state=16, ssm_chunk=8, attention_multiplier=1.0 / 16,
    max_seq=128, dtype=jnp.float32,
)

PRESETS = {"granite-4.0-h-micro": GRANITE_4_H_MICRO, "tiny-hybrid": TINY_HYBRID}


def preset(name: str) -> HybridConfig:
    return PRESETS[name]


# ---- init ------------------------------------------------------------------

def hybrid_init(key: jax.Array, cfg: HybridConfig) -> Params:
    """Normal weights of deviation 1/sqrt(fan_in); the recurrence's own leaves
    as Mamba-2 initialises them (``A_log = log U(1, 16)``, ``dt_bias`` the
    inverse softplus of a log-uniform ``dt`` in [1e-3, 1e-1], ``D = 1``), so
    that the state decays as a trained one does."""
    Pd, NM, NL = cfg.periods, cfg.n_mamba, cfg.n_layers
    D, F, V, dt_ = cfg.dim, cfg.ffn_dim, cfg.vocab_size, cfg.dtype
    I, C, H, K = cfg.ssm_inner, cfg.conv_dim, cfg.ssm_heads, cfg.conv_kernel
    k = iter(jax.random.split(key, 14))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dt_)

    dt0 = jnp.exp(jax.random.uniform(
        next(k), (NM, H), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        # a row times embedding_multiplier has unit norm; at 1/sqrt(D) the
        # tied head would put the token just read 7 deviations above all
        "embed": dense(next(k), (V, D), D * cfg.embedding_multiplier ** 2),
        "final_norm": jnp.ones((D,), dt_),
        "mamba": {
            "norm": jnp.ones((NM, D), dt_),
            # in_proj's three column blocks [z | xBC | dt], a leaf each: one
            # [D, 8512] leaf is no whole number of 128 lanes, the chip then
            # stores it transposed and a decode program copies all of it
            "in_z": dense(next(k), (NM, D, I), D),
            "in_xbc": dense(next(k), (NM, D, C), D),
            "in_dt": dense(next(k), (NM, D, H), D),
            "conv_w": dense(next(k), (NM, K, C), K),
            "conv_b": jnp.zeros((NM, C), dt_),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "A_log": jnp.log(jax.random.uniform(next(k), (NM, H), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((NM, H), jnp.float32),
            "gate_norm": jnp.ones((NM, I), dt_),
            "out_proj": dense(next(k), (NM, I, D), I),
        },
        "attn": {
            "norm": jnp.ones((Pd, D), dt_),
            "wq": dense(next(k), (Pd, D, cfg.n_heads * cfg.head_dim), D),
            "wk": dense(next(k), (Pd, D, cfg.n_kv_heads * cfg.head_dim), D),
            "wv": dense(next(k), (Pd, D, cfg.n_kv_heads * cfg.head_dim), D),
            "wo": dense(next(k), (Pd, cfg.n_heads * cfg.head_dim, D),
                        cfg.n_heads * cfg.head_dim),
        },
        "mlp": {
            "norm": jnp.ones((NL, D), dt_),
            "w_in": dense(next(k), (NL, D, 2 * F), D),
            "w_out": dense(next(k), (NL, F, D), F),
        },
    }


def init_cache(cfg: HybridConfig, batch: int, max_seq: int, num_blocks: int,
               block_size: int) -> Params:
    """The paged pools of the attention layers, ``pos`` and ``bt`` as
    ``llama.init_paged_cache`` makes them, and the rows' slabs of state."""
    if max_seq % block_size != 0:
        raise ValueError(f"max_seq {max_seq} not a multiple of block_size {block_size}")
    pool = (cfg.periods, num_blocks, block_size, cfg.n_kv_heads * cfg.head_dim)
    return {
        "k": jnp.zeros(pool, cfg.dtype),
        "v": jnp.zeros(pool, cfg.dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
        "bt": jnp.zeros((batch, max_seq // block_size), jnp.int32),
        "ssm": jnp.zeros((batch, cfg.n_mamba, cfg.ssm_heads, cfg.ssm_head_dim,
                          cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.n_mamba, cfg.conv_kernel - 1, cfg.conv_dim),
                          cfg.dtype),
    }


def state_bytes_per_row(cfg: HybridConfig) -> int:
    """Bytes of recurrent state and conv window one row owns, whatever its
    context."""
    ssm = cfg.n_mamba * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
    conv = cfg.n_mamba * (cfg.conv_kernel - 1) * cfg.conv_dim * jnp.dtype(cfg.dtype).itemsize
    return int(ssm + conv)


# ---- the layers ------------------------------------------------------------

def _norm(x: jax.Array, w: jax.Array, cfg: HybridConfig) -> jax.Array:
    return llama.rmsnorm(x, w, cfg.norm_eps)


def _mlp(x: jax.Array, lp: Params, cfg: HybridConfig) -> jax.Array:
    """``x + residual_multiplier * output_linear(silu(g) * u)``."""
    gu = _norm(x, lp["norm"], cfg) @ lp["w_in"]
    g, u = jnp.split(gu, 2, axis=-1)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
    return x + cfg.residual_multiplier * ((act * u) @ lp["w_out"])


def _in_proj(h: jax.Array, lp: Params):
    """``[z | xBC | dt] = in_proj(h)``, by its three column blocks."""
    return h @ lp["in_z"], h @ lp["in_xbc"], h @ lp["in_dt"]


def _ssm_inputs(xbc: jax.Array, dt_raw: jax.Array, lp: Params, cfg: HybridConfig):
    """The conv's output split into the scan's ``x``, ``B``, ``C``; ``dt``
    through its softplus; ``A``. ``xbc [..., C]``, ``dt_raw [..., H]``."""
    I, N = cfg.ssm_inner, cfg.ssm_state
    x = xbc[..., :I].reshape(*xbc.shape[:-1], cfg.ssm_heads, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])
    return x, xbc[..., I:I + N], xbc[..., I + N:], dt, -jnp.exp(lp["A_log"])


def _gate_out(y: jax.Array, x: jax.Array, z: jax.Array, lp: Params,
              cfg: HybridConfig) -> jax.Array:
    """``out_proj(rmsnorm((y + D x) * silu(z)))``: the gate first."""
    y = y + lp["D"][:, None] * x.astype(jnp.float32)
    y = y.reshape(*y.shape[:-2], cfg.ssm_inner) * jax.nn.silu(z.astype(jnp.float32))
    return _norm(y, lp["gate_norm"], cfg).astype(z.dtype) @ lp["out_proj"]


def mamba_prefill(h: jax.Array, lp: Params, ssm: jax.Array, conv: jax.Array,
                  lengths: jax.Array, cfg: HybridConfig):
    """The mixer over ``h [B, S, D]`` (normed input), from the state ``ssm
    [B, H, P, N]`` and window ``conv [B, K-1, C]``; positions at and past
    ``lengths`` are padding. Returns ``(out [B, S, D], ssm, conv)``."""
    B, S, _ = h.shape
    K = cfg.conv_kernel
    z, xbc, dt_raw = _in_proj(h, lp)
    full = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=1)  # [B, K-1+S, C]
    w = lp["conv_w"].astype(jnp.float32)
    acc = lp["conv_b"].astype(jnp.float32)
    for k in range(K):
        acc = acc + w[k] * full[:, k:k + S].astype(jnp.float32)
    # the window after the last REAL token: the K-1 inputs that end there
    at = lengths[:, None] + jnp.arange(K - 1)[None, :]
    conv = jnp.take_along_axis(full, at[:, :, None], axis=1).astype(conv.dtype)
    x, Bm, Cm, dt, A = _ssm_inputs(jax.nn.silu(acc).astype(h.dtype), dt_raw, lp, cfg)
    real = jnp.arange(S)[None, :] < lengths[:, None]
    dt = jnp.where(real[:, :, None], dt, 0.0)
    y, ssm = ssd_scan.ssd_chunked(x, dt, A, Bm, Cm, ssm, cfg.ssm_chunk)
    return _gate_out(y, x, z, lp, cfg), ssm, conv


def mamba_step(h: jax.Array, lp: Params, ssm: jax.Array, conv: jax.Array,
               live: jax.Array, cfg: HybridConfig, at=None):
    """One token a row: ``h [B, D]``; rows not ``live`` compute and leave
    their state and window as they were. Returns ``(out [B, D], ssm, conv)``.
    ``ssm`` is the layer's ``[B, H, P, N]``, every row's slab swept by
    ``ssd_scan.ssd_step``; with ``at = (layer, rows, count)`` it is the whole
    ``[B, L, H, P, N]`` state, of which the kernel ``ssd_scan.ssd_step_rows``
    touches that layer's slabs of the listed rows and nothing else."""
    K = cfg.conv_kernel
    z, xbc, dt_raw = _in_proj(h, lp)
    w = lp["conv_w"].astype(jnp.float32)
    acc = lp["conv_b"].astype(jnp.float32) + w[K - 1] * xbc.astype(jnp.float32)
    for k in range(K - 1):
        acc = acc + w[k] * conv[:, k].astype(jnp.float32)
    moved = jnp.concatenate([conv[:, 1:], xbc[:, None].astype(conv.dtype)], axis=1)
    conv = jnp.where(live[:, None, None], moved, conv)
    x, Bm, Cm, dt, A = _ssm_inputs(jax.nn.silu(acc).astype(h.dtype), dt_raw, lp, cfg)
    if at is None:
        dt = jnp.where(live[:, None], dt, 0.0)  # decay 1, nothing added
        ssm, y = ssd_scan.ssd_step(x, dt, A, Bm, Cm, ssm)
    else:
        ssm, y = ssd_scan.ssd_step_rows(x, dt, A, Bm, Cm, ssm, *at)
    return _gate_out(y, x, z, lp, cfg), ssm, conv


def _at(tree: Params, index) -> Params:
    """One layer's leaves of a stacked tree, by a traced index."""
    return jax.tree_util.tree_map(
        lambda leaf: lax.dynamic_index_in_dim(leaf, index, 0, keepdims=False), tree)


def _run_layers(params: Params, cfg: HybridConfig, x, kp, vp, ssm, conv,
                mamba_fn, attn_fn):
    """Every layer in order, one ``lax.scan`` over the periods with the pools
    and the slabs as carries. ``mamba_fn(h, lp, ssm, conv, m) -> (out, ssm,
    conv)`` reads and writes layer ``m``'s part of the slabs;
    ``attn_fn(h, lp, kp, vp, p) -> (out, kp, vp)`` period ``p``'s pools."""
    M, T, rm = cfg.mamba_per_period, cfg.layers_per_period, cfg.residual_multiplier

    def mamba_layers(carry, p, first, count, mlp_from):
        def one(carry, j):
            x, ssm, conv = carry
            m = p * M + first + j
            lp = _at(params["mamba"], m)
            out, ssm, conv = mamba_fn(_norm(x, lp["norm"], cfg), lp, ssm, conv, m)
            x = _mlp(x + rm * out, _at(params["mlp"], p * T + mlp_from + j), cfg)
            return (x, ssm, conv), None
        return lax.scan(one, carry, jnp.arange(count, dtype=jnp.int32))[0]

    def period(carry, p):
        x, kp, vp, ssm, conv = carry
        x, ssm, conv = mamba_layers((x, ssm, conv), p, 0, cfg.mamba_before, 0)
        lp = _at(params["attn"], p)
        out, kp, vp = attn_fn(_norm(x, lp["norm"], cfg), lp, kp, vp, p)
        x = _mlp(x + rm * out, _at(params["mlp"], p * T + cfg.mamba_before), cfg)
        x, ssm, conv = mamba_layers((x, ssm, conv), p, cfg.mamba_before,
                                    cfg.mamba_after, cfg.mamba_before + 1)
        return (x, kp, vp, ssm, conv), None

    return lax.scan(period, (x, kp, vp, ssm, conv),
                    jnp.arange(cfg.periods, dtype=jnp.int32))[0]


def _embed(params: Params, tokens: jax.Array, cfg: HybridConfig) -> jax.Array:
    x = llama.gather_embed(params["embed"], tokens).astype(jnp.float32)
    return (x * cfg.embedding_multiplier).astype(cfg.dtype)


def _logits(params: Params, x: jax.Array, cfg: HybridConfig) -> jax.Array:
    """``x [B, D]`` (before the final norm) -> float32 logits, the tied head."""
    x = _norm(x, params["final_norm"], cfg)
    logits = jnp.einsum("bd,vd->bv", x, params["embed"]).astype(jnp.float32)
    return logits / cfg.logits_scaling


def _q_scale(cfg: HybridConfig) -> float:
    """``llama.attention`` divides scores by sqrt(head_dim); the queries are
    scaled so that the scores come out times ``attention_multiplier``."""
    return cfg.attention_multiplier * math.sqrt(cfg.head_dim)


def _qkv(h: jax.Array, lp: Params, cfg: HybridConfig):
    """``q [B, S, H, hd]`` scaled; ``k``, ``v`` ``[B, S, KV * hd]`` as the pool
    stores them."""
    B, S, _ = h.shape
    q = ((h @ lp["wq"]) * _q_scale(cfg)).astype(h.dtype)
    return q.reshape(B, S, cfg.n_heads, cfg.head_dim), h @ lp["wk"], h @ lp["wv"]


def _heads(kv: jax.Array, cfg: HybridConfig) -> jax.Array:
    return kv.reshape(*kv.shape[:-1], cfg.n_kv_heads, cfg.head_dim)


def _view(pool: jax.Array, p: jax.Array, bt: jax.Array) -> jax.Array:
    """Period ``p``'s blocks of ``pool [periods, NB, BS, KV * hd]`` through
    the block table ``[B, MB]``: the logical ``[B, MB * BS, KV * hd]`` view, by
    one gather with the period in the index (``llama._paged_view``)."""
    Pd, NB, BS, W = pool.shape
    B, MB = bt.shape
    return pool.reshape(Pd * NB, BS, W)[p * NB + bt].reshape(B, MB * BS, W)


def _attention_one_query(q: jax.Array, k: jax.Array, v: jax.Array,
                         mask: jax.Array, scores_type=None) -> jax.Array:
    """``llama.attention`` for one query a row (a decode step), computed on
    the view as the pool stores it, ``[B, T, KV * hd]``: each query head is
    laid into its key head's ``hd`` columns of a ``KV * hd`` row of zeros, so
    scores and the weighted sum are two products over the whole row and the
    head's columns are picked out at the end. ``KV`` times the FLOPs of the
    head-by-head form, which for one query are nothing; what it saves is the
    copy that splits ``KV * hd`` into heads, which pads ``hd`` = 64 to 128
    lanes and moves the whole view a second time. Same scaling, mask and
    float32 softmax as ``llama.attention``. ``q [B, 1, H, hd]``; ``k``, ``v``
    ``[B, T, KV * hd]``; ``mask [B, T]``: the keys a row's query may see.
    ``scores_type`` float32 keeps the scores as the product accumulates them
    (None: rounded to the inputs' type first, as ``llama.attention``'s)."""
    B, _, H, hd = q.shape
    KV = k.shape[2] // hd
    own = (jnp.arange(H)[:, None] // (H // KV) == jnp.arange(KV)[None, :])[None, :, :, None]
    wide = jnp.where(own, q[:, 0][:, :, None, :], 0).reshape(B, H, KV * hd)
    scores = jnp.einsum("bhw,btw->bht", wide, k, preferred_element_type=scores_type)
    scores = scores.astype(jnp.float32) / math.sqrt(hd)
    scores = jnp.where(mask[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bht,btw->bhw", probs, v).reshape(B, H, KV, hd)
    return jnp.sum(jnp.where(own, out, 0), axis=2)[:, None]


def _attend(q: jax.Array, kp: jax.Array, vp: jax.Array, p: jax.Array,
            bt: jax.Array, posq: jax.Array, cfg: HybridConfig,
            spans: Optional[Tuple[int, ...]], span_at) -> jax.Array:
    """Gather attention of ``q [B, S, H, hd]`` at positions ``posq [B, S]``
    over the first ``spans[span_at]`` keys of each row's table (the whole
    table without spans): ``llama._attend_over_span`` on this pool's layout,
    one branch of a ``lax.switch`` a span."""
    BS = kp.shape[2]

    def over(span, q, kp, vp, p, bt, posq):
        view_bt = bt[:, :span // BS]
        mask = jnp.arange(span)[None, None, :] <= posq[:, :, None]
        k, v = _view(kp, p, view_bt), _view(vp, p, view_bt)
        if q.shape[1] == 1:
            return _attention_one_query(q, k, v, mask[:, 0])
        return llama.attention(q, _heads(k, cfg), _heads(v, cfg), causal=False,
                               mask=mask[:, None, None])

    if spans is None:
        return over(bt.shape[1] * BS, q, kp, vp, p, bt, posq)
    return lax.switch(span_at, [partial(over, span) for span in spans],
                      q, kp, vp, p, bt, posq)


# ---- prefill ---------------------------------------------------------------

def prefill(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, S] right-padded prompt tokens of the rows
    lengths: jax.Array,  # [B]; 0 = row untouched
    cfg: HybridConfig,
    rows: jax.Array,  # [B] cache rows of this compact batch
    starts: Optional[jax.Array] = None,  # [B] where each row's tokens begin
    spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """``lengths`` prompt tokens of each of ``rows`` from ``starts``:
    last-token logits ``[B, V]`` and the cache.

    ``starts`` None: whole prompts from position 0, attention local and
    causal (no pool read), every row's state from zero. Given: suffixes that
    attend through the pool over the span holding ``live_to`` (as
    ``llama.paged_prefill_from``); a row whose ``starts`` is 0 begins from a
    zero slab, any other from the slab its last chunk left. Pad positions
    and inactive rows write their K/V to the trash block and leave state and
    window alone."""
    B, S = tokens.shape
    bt = cache["bt"][rows]
    BS = cache["k"].shape[2]
    max_s = bt.shape[1] * BS
    active = lengths > 0
    begin = jnp.zeros((B,), jnp.int32) if starts is None else starts
    posq = jnp.minimum(begin[:, None] + jnp.arange(S)[None, :], max_s - 1)
    writable = active[:, None] & (jnp.arange(S)[None, :] < lengths[:, None])
    blk = jnp.where(writable, bt[jnp.arange(B)[:, None], posq // BS], 0)
    off = posq % BS
    span_at = None
    if starts is not None and spans is not None:
        llama._check_spans(spans, bt, BS, "gather", live_to)
        span_at = llama._span_index(spans, live_to)
    fresh = (active & (begin == 0))[:, None, None]

    def mamba_fn(h, lp, ssm, conv, m):
        s0 = jnp.where(fresh[..., None], 0.0, ssm[rows, m])
        c0 = jnp.where(fresh, jnp.zeros((), conv.dtype), conv[rows, m])
        out, s1, c1 = mamba_prefill(h, lp, s0, c0, lengths, cfg)
        return out, ssm.at[rows, m].set(s1), conv.at[rows, m].set(c1)

    def attn_fn(h, lp, kp, vp, p):
        q, k, v = _qkv(h, lp, cfg)
        kp = kp.at[p, blk, off].set(k)
        vp = vp.at[p, blk, off].set(v)
        if starts is None:
            a = llama.attention(q, _heads(k, cfg), _heads(v, cfg), causal=True)
        else:
            a = _attend(q, kp, vp, p, bt, posq, cfg, spans, span_at)
        return a.reshape(B, S, -1) @ lp["wo"], kp, vp

    x, kp, vp, ssm, conv = _run_layers(
        params, cfg, _embed(params, tokens, cfg), cache["k"], cache["v"],
        cache["ssm"], cache["conv"], mamba_fn, attn_fn)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return _logits(params, last, cfg), {
        "k": kp, "v": vp, "bt": cache["bt"], "ssm": ssm, "conv": conv,
        "pos": llama._advance_pos(cache["pos"], rows, active, begin + lengths, max_s),
    }


# ---- decode ----------------------------------------------------------------

def decode_step(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, 1]
    live: jax.Array,  # [B] bool: rows whose slab this step may advance
    cfg: HybridConfig,
    spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """One token for every cache row (as ``llama.paged_decode_step_batched``:
    the new K/V scattered into the row's current block, attention over the
    gathered view's span), the one-step recurrence on the slabs of ``live``.
    A row not in ``live`` (vacant, between two chunks of its prompt, left out
    by the block reserve) computes garbage nobody reads, writes its K/V to
    the trash block and keeps its slab.

    How the slabs advance is chosen here, from what can be observed
    (:func:`steps_listed_rows`): on a TPU, a float32 state of whole tiles
    goes whole to the kernel ``ssd_scan.ssd_step_rows``, one call a mamba
    layer, which reads and writes the ``live`` rows' slabs once and names no
    other row's; anywhere else ``ssd_scan.ssd_step`` sweeps every row's slab
    of the layer with a decay of 1 for the rows not ``live`` (a CPU, and a
    shape the kernel refuses: ``ssm_state`` not a multiple of 128,
    ``ssm_head_dim`` not of 8, a state that is not float32)."""
    B = tokens.shape[0]
    pos, bt = cache["pos"], cache["bt"]
    BS = cache["k"].shape[2]
    max_s = bt.shape[1] * BS
    blk = jnp.where(live, bt[jnp.arange(B), pos // BS], 0)
    span_at = None
    if spans is not None:
        llama._check_spans(spans, bt, BS, "gather", live_to)
        span_at = llama._span_index(spans, live_to)
        blk = jnp.where(pos < jnp.asarray(spans, jnp.int32)[span_at], blk, 0)
    off = pos % BS

    listed = ssd_scan.scheduled_rows(live) if steps_listed_rows(cache) else None

    def mamba_fn(h, lp, ssm, conv, m):
        window = lax.dynamic_index_in_dim(conv, m, 1, keepdims=False)
        if listed is None:
            out, s1, c1 = mamba_step(
                h[:, 0], lp, lax.dynamic_index_in_dim(ssm, m, 1, keepdims=False),
                window, live, cfg)
            ssm = lax.dynamic_update_index_in_dim(ssm, s1, m, 1)
        else:  # the state whole: no plane of it is sliced out or put back
            out, ssm, c1 = mamba_step(h[:, 0], lp, ssm, window, live, cfg,
                                      at=(m, *listed))
        return out[:, None], ssm, lax.dynamic_update_index_in_dim(conv, c1, m, 1)

    def attn_fn(h, lp, kp, vp, p):
        q, k, v = _qkv(h, lp, cfg)
        kp = kp.at[p, blk, off].set(k[:, 0])
        vp = vp.at[p, blk, off].set(v[:, 0])
        a = _attend(q, kp, vp, p, bt, pos[:, None], cfg, spans, span_at)
        return a.reshape(B, 1, -1) @ lp["wo"], kp, vp

    x, kp, vp, ssm, conv = _run_layers(
        params, cfg, _embed(params, tokens, cfg), cache["k"], cache["v"],
        cache["ssm"], cache["conv"], mamba_fn, attn_fn)
    return _logits(params, x[:, 0], cfg), {
        "k": kp, "v": vp, "bt": bt, "ssm": ssm, "conv": conv,
        "pos": jnp.minimum(pos + 1, max_s - 1),
    }


def steps_listed_rows(cache: Params) -> bool:
    """Whether a decode step of this process advances the scheduled rows'
    slabs alone, by the kernel (else it sweeps every row's): a TPU, and a
    state the kernel can take as it stands."""
    return jax.default_backend() == "tpu" and ssd_scan.step_kernel_fits(cache["ssm"])


def decode_segment(
    params: Params, cache: Params, tokens: jax.Array, temps: jax.Array,
    key: jax.Array, live: jax.Array, cfg: HybridConfig, n_steps: int,
    greedy: bool = False, spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Params, Dict[str, jax.Array]]:
    """``n_steps`` of :func:`decode_step` with the decoder's own on-device
    sample-and-feed chain (``llama.sampled_segment``): ``(toks [B, n_steps],
    last [B, 1], next_key, cache, counters)``. The counters say how much of
    the state the segment's steps moved: ``slabs_stepped``, the sum over its
    steps of the rows whose slab the step fetched (the ``live`` rows through
    the kernel, every row where ``ssd_step`` sweeps them all), and
    ``slabs_held``, rows times steps."""
    step = partial(decode_step, live=live, cfg=cfg, spans=spans, live_to=live_to)
    B = live.shape[0]
    stepped = jnp.sum(live, dtype=jnp.int32) if steps_listed_rows(cache) else jnp.int32(B)
    counters = {"slabs_stepped": n_steps * stepped, "slabs_held": jnp.int32(n_steps * B)}
    return (*llama.sampled_segment(
        lambda cache, toks: step(params, cache, toks), cache, tokens, temps, key,
        n_steps, greedy), counters)
