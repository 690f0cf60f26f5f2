"""A sparse-expert decoder whose attention layers keep different amounts of
context: window layers beside full layers, a routed expert layer in each.

The stack is ``periods`` repeats of ``period``, a tuple of attention kinds
(``("window", "window", "window", "full")``); every layer is pre-norm
attention and a pre-norm expert layer, each on a plain residual::

    h0     = embed[ids]
    h      = h + attn_l(rmsnorm(h))
    h      = h + moe_l(rmsnorm(h))
    logits = rmsnorm(h) @ lm_head                     (an untied head)

Attention is causal GQA with rotate-half rope on q and k and scores over
``sqrt(head_dim)``, no bias, no q/k norm. A ``window`` layer's query at
position ``i`` sees keys ``i - window < j <= i`` and rotates by
``rope_window``; a ``full`` layer's sees every earlier key and rotates by
``rope_full`` (:func:`inv_freq`: plain, or YaRN's blend of kept and
interpolated frequencies with its ``attention_factor`` on cos and sin).

The expert layer (:func:`expert_layer`) is dropless top-k: a float32 softmax
over all ``n_experts`` router outputs, the ``top_k`` largest kept and
renormalised, every kept token computed by every one of its experts. It is
TOLD which experts it holds (a contiguous range), routes over all of them,
and returns the part of the result its own give; the parts of all ranges add
up to the layer. The assignments are ordered by expert and the two products
run grouped over that order, so the FLOPs are the routed ones: on a TPU as
ONE Pallas kernel (``ops/expert_gmm.py``) whose work list names an expert
only where a row tile of its group exists, so a step reads the experts its
kept tokens touch and no other, and a share runs no tile past the
assignments that fell on it; on any other backend as two ``lax.ragged_dot``
(:func:`_grouped`), the plain twin the tests hold the kernel to. A decode
step's 16 rows and a chunk's 1024 take the same path. A token not ``kept``
(padding, a row the dispatch did not schedule, a step past a row's budget)
routes nowhere: it touches no expert and counts in no load.

What a served row owns (``init_cache``): blocks in TWO pools, each with its
own block table, block 0 of each its trash block. The full pool ``k``/``v``
``[n_full, NB, BS, KV * hd]`` holds the whole context through ``bt``, read as
the gathered view with its span ladder (:func:`_attend_full`). The window
pool ``wk``/``wv`` ``[n_window, NBw, BS, KV * hd]`` holds what the window still
reaches: ``wbt`` is indexed by a position's block like ``bt``, the engine
points the entries that have fallen behind the window back at trash
(``serving/kv_blocks.py`` ``WindowTable``), and a window layer's view is a
FIXED number of blocks ending at the row's position (``window`` and the chunk
in a prefill program, ``window`` and one block in a decode step), whatever the
row's length.

Parameters are stacked by kind in layer order: ``window`` leaves
``[n_window, ...]`` (period ``p``'s are ``[p * w, (p + 1) * w)``: the bytes of
``[periods, w, ...]``), ``full`` leaves ``[n_full, ...]``, ``moe`` leaves
``[n_layers, ...]`` with the experts' ``[n_layers, n_experts, ...]``. One
``lax.scan`` runs over the periods with the four pools as carries, written in
place under donation. The grouped products take the WHOLE stack of expert
weights, seen as ``n_layers * n_experts`` groups: the layer is in the index
of the kernel's fetches (a scalar it prefetches; in the group sizes of the
plain twin, of which all but one layer's are empty), as ``llama._paged_view``
has it in the gather's index, so no program slices a layer's experts out.

The block is a setting, not a copy (every default is the block above):
``norm`` ``"layer"`` centres on the mean before it scales (a weight, no bias);
``parallel_block`` feeds ONE norm to attention and to the expert layer and
adds both to the stream at once, ``h = h + attn(n) + moe(n) + shared(n)``;
``router_score`` ``"sigmoid"`` scores each expert on its own before the top-k
and its renormalisation; ``n_shared`` experts of width ``shared_ffn`` see every
token, side by side as one product, their outputs averaged
(``shared_average``) or summed; a :class:`Rope`'s ``form`` is ``"half"``,
``"interleaved"`` (pairs ``(2i, 2i + 1)``) or ``"none"`` (no positions at all);
``tied_head`` multiplies by the embedding's transpose, times ``logit_scale``.
``experts_held`` says that the weight stacks hold only experts
``[expert_first, expert_first + experts_held)`` of the ``n_experts`` the router
scores: one chip's share of a layer that several chips divide by experts. An
assignment to an expert that is not here adds nothing, here or in the
reference; no code stands in for the chips that hold the others.

The blocked arm (``init_cache(..., blocked=True)``; the programs tell it by
the pools' five axes, ``[n, NB, BS, KV, hd]``, the layout ``paged_attention``
reads as it stands): no program gathers a view. A prefill program folds the
keys in tiles of :data:`PREFILL_TILE` with an online softmax (a full layer
over the row's live blocks, a window layer over the fixed run of blocks that
ends at the chunk), so scores are never whole in memory; a decode step
attends through ``paged_attention`` in both kinds of layer, a window layer
over the run of blocks that ends at the row's position. On a TPU that is the
Pallas kernel, which fetches each scheduled row's own blocks.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kubedl_tpu.models import llama, paged_attention
from kubedl_tpu.models.hybrid_ssm import _at, _attention_one_query, _heads, _view
from kubedl_tpu.ops import expert_gmm

Params = Dict[str, Any]


@dataclass(frozen=True)
class Rope:
    """One kind's rotary table. ``factor`` 1 is the plain table; above 1 it is
    YaRN over ``original_max`` positions. ``form``: which dimensions make a
    pair, ``"half"`` (``d`` and ``d + hd / 2``) or ``"interleaved"`` (``2i`` and
    ``2i + 1``); ``"none"``: the kind's q and k are not rotated at all."""

    theta: float = 500000.0
    factor: float = 1.0
    original_max: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    form: str = "half"


@dataclass(frozen=True)
class SparseWindowConfig:
    vocab_size: int = 98304
    dim: int = 2304
    periods: int = 7
    #: the attention kinds of one period, in layer order
    period: Tuple[str, ...] = ("window", "window", "window", "full")
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    #: keys a window layer's query sees, its own among them
    window: int = 1024
    n_experts: int = 64
    top_k: int = 8
    expert_ffn: int = 896
    rope_window: Rope = Rope()
    rope_full: Rope = Rope(factor=16.0, attention_factor=0.1 * math.log(16.0) + 1.0)
    norm_eps: float = 1e-6
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16
    #: ``"rms"``, or ``"layer"``: mean-centred, a weight and no bias
    norm: str = "rms"
    #: one norm a layer feeds attention and the expert layer, one add
    parallel_block: bool = False
    #: ``"softmax"`` over all experts, or ``"sigmoid"`` of each, before top-k
    router_score: str = "softmax"
    #: experts every token passes through, each ``shared_ffn`` wide, their
    #: outputs averaged (``shared_average``) or summed
    n_shared: int = 0
    shared_ffn: int = 0
    shared_average: bool = True
    #: logits are ``logit_scale * norm(h) @ embed^T``: no ``lm_head`` leaf
    tied_head: bool = False
    logit_scale: float = 1.0
    #: the weight stacks hold experts ``[expert_first, expert_first +
    #: experts_held)`` of the ``n_experts`` the router scores; 0: all of them
    expert_first: int = 0
    experts_held: int = 0

    @property
    def held(self) -> int:
        """Experts the weight stacks hold."""
        return self.experts_held or self.n_experts

    @property
    def shared_width(self) -> int:
        """The shared experts side by side: the width of their one product."""
        return self.n_shared * self.shared_ffn

    @property
    def windows_per_period(self) -> int:
        return self.period.count("window")

    @property
    def fulls_per_period(self) -> int:
        return self.period.count("full")

    @property
    def n_window(self) -> int:
        return self.periods * self.windows_per_period

    @property
    def n_full(self) -> int:
        return self.periods * self.fulls_per_period

    @property
    def n_layers(self) -> int:
        return self.periods * len(self.period)

    def num_params(self) -> int:
        attn = 2 * self.dim * self.n_heads * self.head_dim \
            + 2 * self.dim * self.n_kv_heads * self.head_dim
        moe = (self.dim * self.n_experts + self.held * 3 * self.dim * self.expert_ffn
               + 3 * self.dim * self.shared_width)
        norms = 1 if self.parallel_block else 2
        heads = 1 if self.tied_head else 2
        return (self.n_layers * (attn + moe + norms * self.dim)
                + heads * self.vocab_size * self.dim + self.dim)


def pattern_of(layer_types: Sequence[str]) -> Tuple[int, Tuple[str, ...]]:
    """``(periods, period)`` of a published ``layer_types`` list, its kinds
    renamed ``window`` and ``full``: the shortest period the list repeats."""
    names = {"sliding_attention": "window", "full_attention": "full"}
    try:
        kinds = [names[k] for k in layer_types]
    except KeyError as e:
        raise ValueError(f"layer_types holds {e.args[0]!r}: window and full attention only")
    for span in range(1, len(kinds) + 1):
        if len(kinds) % span == 0 and kinds == kinds[:span] * (len(kinds) // span):
            return len(kinds) // span, tuple(kinds[:span])
    raise ValueError("layer_types is empty")


#: Mellum2-12B-A2.5B-Instruct as published (huggingface.co/JetBrains/
#: Mellum2-12B-A2.5B-Instruct, config.json): 28 layers, full attention at 3, 7, ...
MELLUM2_12B = SparseWindowConfig()
#: CPU-test size: two periods of (window, window, full), a window of 32 keys
TINY_SPARSE = SparseWindowConfig(
    vocab_size=256, dim=64, periods=2, period=("window", "window", "full"),
    n_heads=4, n_kv_heads=2, head_dim=16, window=32, n_experts=8, top_k=2,
    expert_ffn=32, rope_window=Rope(theta=10000.0),
    rope_full=Rope(theta=10000.0, factor=4.0, original_max=64,
                   attention_factor=0.1 * math.log(4.0) + 1.0),
    max_seq=256, dtype=jnp.float32,
)

#: command-a-plus-05-2026 (huggingface.co/CohereLabs/command-a-plus-05-2026,
#: config.json, ``cohere2_moe``) at published widths, ONE chip's share of the
#: deployment in which 8 chips divide each layer by experts: one period of the
#: pattern (4 of 32 layers), experts 0-15 of 128, an eighth of the vocabulary
#: (32,768 of 262,144 rows). A parallel block on a mean-centring norm, 128
#: query heads, sigmoid routing, four averaged shared experts; the window
#: layers rotate interleaved pairs, the full layers know no positions.
COMMAND_A_PLUS_L4 = SparseWindowConfig(
    vocab_size=32768, dim=4096, periods=1, n_heads=128, n_kv_heads=8, head_dim=128,
    window=4096, n_experts=128, top_k=8, expert_ffn=4096,
    rope_window=Rope(theta=50000.0, form="interleaved"), rope_full=Rope(form="none"),
    norm_eps=1e-5, max_seq=200000, norm="layer", parallel_block=True,
    router_score="sigmoid", n_shared=4, shared_ffn=4096, tied_head=True,
    experts_held=16,
)
#: CPU-test size of the same block: a window of 32 keys, 2 of 8 experts held
#: (the second pair: ``expert_first`` 2), two shared experts
TINY_PARALLEL = SparseWindowConfig(
    vocab_size=256, dim=64, periods=2, period=("window", "window", "full"),
    n_heads=4, n_kv_heads=2, head_dim=16, window=32, n_experts=8, top_k=2,
    expert_ffn=32, rope_window=Rope(theta=10000.0, form="interleaved"),
    rope_full=Rope(form="none"), norm_eps=1e-5, max_seq=256, dtype=jnp.float32,
    norm="layer", parallel_block=True, router_score="sigmoid", n_shared=2,
    shared_ffn=32, tied_head=True, logit_scale=0.5, expert_first=2, experts_held=2,
)

PRESETS = {"mellum2-12b-a2.5b": MELLUM2_12B, "tiny-sparse": TINY_SPARSE,
           "command-a-plus-05-2026-l4": COMMAND_A_PLUS_L4, "tiny-parallel": TINY_PARALLEL}


def preset(name: str) -> SparseWindowConfig:
    return PRESETS[name]


# ---- init ------------------------------------------------------------------

def sparse_init(key: jax.Array, cfg: SparseWindowConfig) -> Params:
    """Normal weights of deviation 1/sqrt(fan_in), norms ones."""
    D, E, F, V, dt_ = cfg.dim, cfg.n_experts, cfg.expert_ffn, cfg.vocab_size, cfg.dtype
    Hq, Hk = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    k = iter(jax.random.split(key, 13))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dt_)

    def attn(n):
        return {"norm": jnp.ones((n, D), dt_), "wq": dense(next(k), (n, D, Hq), D),
                "wk": dense(next(k), (n, D, Hk), D), "wv": dense(next(k), (n, D, Hk), D),
                "wo": dense(next(k), (n, Hq, D), Hq)}

    L = cfg.n_layers
    params = {
        "embed": dense(next(k), (V, D), D),
        "lm_head": dense(next(k), (D, V), D),
        "final_norm": jnp.ones((D,), dt_),
        "window": attn(cfg.n_window),
        "full": attn(cfg.n_full),
        "moe": {
            "norm": jnp.ones((L, D), dt_),
            "router": dense(next(k), (L, D, E), D),
            # gate then up, side by side: one grouped product makes both
            "w_in": dense(next(k), (L, cfg.held, D, 2 * F), D),
            "w_out": dense(next(k), (L, cfg.held, F, D), F),
        },
    }
    if cfg.tied_head:
        del params["lm_head"]
    if cfg.parallel_block:
        del params["moe"]["norm"]  # the attention leaves' norm is the layer's one
    if cfg.n_shared:
        W = cfg.shared_width  # every shared expert's gate, then every one's up
        k_in, k_out = jax.random.split(jax.random.fold_in(key, 1))
        params["moe"]["shared_in"] = dense(k_in, (L, D, 2 * W), D)
        params["moe"]["shared_out"] = dense(k_out, (L, W, D), cfg.shared_ffn)
    return params


def init_cache(cfg: SparseWindowConfig, batch: int, max_seq: int, num_blocks: int,
               window_blocks: int, block_size: int, blocked: bool = False) -> Params:
    """The two pools, zeroed, ``pos``, the two block tables (every entry at
    its pool's trash block) and ``expert_tokens [n_layers, held]``: the
    kept tokens each held expert has computed in the prefill programs since
    the last decode segment, which takes the count over and hands it out with
    its own (:func:`decode_segment`); where the stacks hold a share of the
    experts, ``assign_all`` beside it: every kept assignment those programs
    routed, to an expert here or not. ``blocked``: the pools of the blocked
    arm, a block ``[BS, KV, hd]``."""
    if max_seq % block_size or cfg.window % block_size:
        raise ValueError(f"max_seq {max_seq} and window {cfg.window} must be whole "
                         f"blocks of {block_size}")
    W = (cfg.n_kv_heads, cfg.head_dim) if blocked else (cfg.n_kv_heads * cfg.head_dim,)
    table = (batch, max_seq // block_size)
    cache = {
        "k": jnp.zeros((cfg.n_full, num_blocks, block_size, *W), cfg.dtype),
        "v": jnp.zeros((cfg.n_full, num_blocks, block_size, *W), cfg.dtype),
        "wk": jnp.zeros((cfg.n_window, window_blocks, block_size, *W), cfg.dtype),
        "wv": jnp.zeros((cfg.n_window, window_blocks, block_size, *W), cfg.dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
        "bt": jnp.zeros(table, jnp.int32),
        "wbt": jnp.zeros(table, jnp.int32),
        "expert_tokens": jnp.zeros((cfg.n_layers, cfg.held), jnp.int32),
    }
    if cfg.experts_held:
        cache["assign_all"] = jnp.zeros((), jnp.int32)
    return cache


# ---- rotary tables ---------------------------------------------------------

def inv_freq(rope: Rope, head_dim: int) -> np.ndarray:
    """The ``head_dim / 2`` frequencies of a kind's table, float32. Plain:
    ``theta^(-2d / head_dim)``. YaRN: dimensions below ``low`` keep that
    frequency, those above ``high`` are divided by ``factor``, those between
    are blended linearly; ``low`` and ``high`` are the floor and the ceiling
    of the dimension that turns ``beta_fast`` and ``beta_slow`` times over
    ``original_max`` positions."""
    half = head_dim // 2
    extrap = rope.theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope.factor == 1.0:
        return extrap.astype(np.float32)

    def turns(beta: float) -> float:
        return half * math.log(rope.original_max / (beta * 2 * math.pi)) / math.log(rope.theta)

    low = max(math.floor(turns(rope.beta_fast)), 0)
    high = min(math.ceil(turns(rope.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    kept = 1.0 - ramp
    return (extrap / rope.factor * (1.0 - kept) + extrap * kept).astype(np.float32)


def _rope_at(rope: Rope, head_dim: int, posq: jax.Array):
    """``(cos, sin)`` ``[B, S, 1, head_dim / 2]`` at positions ``posq [B, S]``."""
    ang = posq[:, :, None, None].astype(jnp.float32) * jnp.asarray(inv_freq(rope, head_dim))
    return jnp.cos(ang) * rope.attention_factor, jnp.sin(ang) * rope.attention_factor


def _rotate(t: jax.Array, cos: jax.Array, sin: jax.Array, form: str = "half") -> jax.Array:
    """``llama.apply_rope`` (rotate-half) with a table a row: ``t [B, S, H, hd]``.
    ``form`` ``"interleaved"`` pairs dimension ``2i`` with ``2i + 1`` instead
    of ``d`` with ``d + hd / 2``, frequency ``i`` for pair ``i`` either way."""
    if form == "interleaved":
        # each lane's partner is its neighbour, brought over by a roll: the
        # head dimension is never split into (pair, 2), a reshape that XLA
        # moves through the projection into its weight, which it then lays
        # out anew every step (PERF.md section 6, PR 45)
        x = t.astype(jnp.float32)
        even = jnp.arange(t.shape[-1]) % 2 == 0
        partner = jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
        return (x * jnp.repeat(cos, 2, axis=-1)
                + partner * jnp.repeat(sin, 2, axis=-1)).astype(t.dtype)
    t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1).astype(t.dtype)


# ---- the expert layer ------------------------------------------------------

def route(x: jax.Array, router: jax.Array, cfg: SparseWindowConfig):
    """``(experts [T, top_k], gates [T, top_k])`` of ``x [T, D]``: softmax in
    float32 over every expert (or each expert's own sigmoid:
    ``cfg.router_score``), the ``top_k`` largest, renormalised to sum 1."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    score = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" else jax.nn.softmax(
        logits, axis=-1)
    top_p, top_e = lax.top_k(score, cfg.top_k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def grouped_by_kernel(n: int, moe: Params, cfg: SparseWindowConfig) -> int:
    """The row tile in which this process multiplies ``n`` assignments by
    their experts in the Pallas kernel (``ops/expert_gmm.py``): a TPU, and
    stacks whose tiles the kernel can take as they stand. 0 where it does not
    (:func:`_grouped` multiplies them)."""
    if jax.default_backend() != "tpu":
        return 0
    return expert_gmm.rows_for(n, moe["w_in"], moe["w_out"], cfg.n_experts)


def _grouped(xs, w_in, w_out, load, place):
    """The plain twin of ``expert_gmm.expert_gmm``: the two products of the
    assignments ``xs [n, D]``, ordered by expert, as ``lax.ragged_dot`` over
    the WHOLE stacks ``[L * E, ...]``, whose groups are all empty but the
    ``load [count]`` that begin at ``place``. ``[n, D]`` float32; a row past
    ``sum(load)`` is nobody's and holds nothing that is read."""
    F = w_out.shape[1]
    sizes = lax.dynamic_update_slice(
        jnp.zeros((w_in.shape[0],), jnp.int32), load, (place,))
    h = lax.ragged_dot(xs, w_in, sizes)
    act = jax.nn.silu(h[:, :F].astype(jnp.float32)).astype(xs.dtype) * h[:, F:]
    return lax.ragged_dot(act, w_out, sizes, preferred_element_type=jnp.float32)


def expert_layer(x: jax.Array, moe: Params, layer, kept: jax.Array,
                 cfg: SparseWindowConfig, first: Optional[int] = None,
                 count: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """The part of layer ``layer``'s expert output that experts ``[first,
    first + count)`` give (by default all that the stacks hold: every expert,
    or the share ``cfg.experts_held`` names), for ``x [T, D]`` (normed):
    ``(y [T, D] float32, load [count])``, ``load`` the kept tokens each of
    those experts computed. ``moe`` is the WHOLE stacked tree, its expert
    axis the held ones; ``layer`` may be traced. ``first`` counts among all
    ``n_experts``, as the router does.

    Every token routes over all ``n_experts``; an assignment counts where its
    token is ``kept`` and its expert is held. The assignments are ordered by
    expert, those that do not count last, beyond the groups' rows, and the
    two products run grouped over that order: on a TPU in one kernel that
    fetches an expert where a row tile of its group exists and runs no tile
    past the groups (:func:`grouped_by_kernel`), elsewhere as two
    ``lax.ragged_dot``. A decode step's few rows and a chunk's many take the
    same path."""
    T, D = x.shape
    E, K, F = cfg.held, cfg.top_k, cfg.expert_ffn
    first = cfg.expert_first if first is None else first
    count = cfg.expert_first + E - first if count is None else count
    router = lax.dynamic_index_in_dim(moe["router"], layer, 0, keepdims=False)
    top_e, gates = route(x, router, cfg)
    held = kept[:, None] & (top_e >= first) & (top_e < first + count)
    group = jnp.where(held, top_e - first, count).reshape(T * K)
    order = jnp.argsort(group)  # stable: an expert's tokens stay in order
    load = jnp.sum(group[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    xs = x[order // K]  # [T * K, D]: each assignment's token, by expert
    # the layer's groups among the whole stack's: no layer's experts sliced out
    w_in, w_out = moe["w_in"].reshape(-1, D, 2 * F), moe["w_out"].reshape(-1, F, D)
    place = layer * E + first - cfg.expert_first
    if grouped_by_kernel(T * K, moe, cfg):
        out = expert_gmm.expert_gmm(xs, w_in, w_out, load, place, experts=cfg.n_experts)
    else:
        out = _grouped(xs, w_in, w_out, load, place)
    weight = jnp.where(held, gates, 0.0).reshape(T * K)[order]
    out = jnp.where(weight[:, None] > 0, out * weight[:, None], 0.0)
    back = jnp.zeros((T * K,), jnp.int32).at[order].set(jnp.arange(T * K, dtype=jnp.int32))
    return jnp.sum(out[back].reshape(T, K, D), axis=1), load


# ---- the layers ------------------------------------------------------------

def _norm(x: jax.Array, w: jax.Array, cfg: SparseWindowConfig) -> jax.Array:
    """``rmsnorm`` of the float32 stream (``cfg.norm`` ``"layer"``: centred on
    its mean first, a weight and no bias), in the type the weights multiply."""
    if cfg.norm == "layer":
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.norm_eps)
        return (x * w.astype(jnp.float32)).astype(cfg.dtype)
    return llama.rmsnorm(x, w, cfg.norm_eps).astype(cfg.dtype)


def shared_experts(x: jax.Array, moe: Params, layer, cfg: SparseWindowConfig) -> jax.Array:
    """What layer ``layer``'s ``n_shared`` experts give every token of ``x [T,
    D]`` (normed), float32: side by side they are ONE gated product of width
    ``shared_width``, whose output product sums over experts and width at
    once. Their mean is that sum over ``n_shared``, taken on the activations
    in float32 (a weight scaled instead would be rounded anew)."""
    W = cfg.shared_width
    w_in = lax.dynamic_index_in_dim(moe["shared_in"], layer, 0, keepdims=False)
    w_out = lax.dynamic_index_in_dim(moe["shared_out"], layer, 0, keepdims=False)
    h = x @ w_in
    act = jax.nn.silu(h[:, :W].astype(jnp.float32)) * h[:, W:].astype(jnp.float32)
    if cfg.shared_average:
        act = act / cfg.n_shared
    return _out(act.astype(x.dtype), w_out)


def _out(a: jax.Array, w: jax.Array) -> jax.Array:
    """``a @ w`` as the product accumulates it, float32."""
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)


def _qkv(h: jax.Array, lp: Params, rope: Rope, posq: jax.Array, cfg: SparseWindowConfig):
    """``q [B, S, H, hd]`` and ``k``, ``v`` ``[B, S, KV * hd]`` as the pools
    store them, q and k rotated by ``rope`` at ``posq``."""
    B, S, _ = h.shape
    if rope.form != "half":
        # the projection's output stands whole before it is split into heads:
        # XLA otherwise moves the split through the product into ``wq``, and a
        # decode step's 16 rows then pay for the weight laid out anew, 134 MB
        # read and written a layer (PERF.md section 6, PR 45)
        q = lax.optimization_barrier(h @ lp["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
        if rope.form == "none":  # the kind knows no positions
            return q, h @ lp["wk"], h @ lp["wv"]
        cos, sin = _rope_at(rope, cfg.head_dim, posq)
        k = _rotate(_heads(h @ lp["wk"], cfg), cos, sin, rope.form)
        return _rotate(q, cos, sin, rope.form), k.reshape(B, S, -1), h @ lp["wv"]
    cos, sin = _rope_at(rope, cfg.head_dim, posq)
    q = _rotate((h @ lp["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim), cos, sin)
    k = _rotate(_heads(h @ lp["wk"], cfg), cos, sin)
    return q, k.reshape(B, S, -1), h @ lp["wv"]


def _window_mask(posq: jax.Array, keys: jax.Array, window: int) -> jax.Array:
    """``[B, S, T]``: key positions ``keys [B, T]`` a query at ``posq [B, S]``
    sees through the window."""
    q, t = posq[:, :, None], keys[:, None, :]
    return (t >= 0) & (t <= q) & (t > q - window)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
               cfg: SparseWindowConfig) -> jax.Array:
    """Grouped-query attention of ``q [B, S, H, hd]`` over ``k``, ``v`` ``[B,
    T, KV * hd]`` (as the pools store them) under ``mask [B, S, T]``: as
    ``llama.attention`` but for the scores, which stay float32 as the product
    accumulates them. YaRN's factor makes them 1.6 times larger, and a score
    of 8 rounded to bfloat16 moves its key's weight by 3%. One query a row
    (a decode step) is computed on the view as the pool stores it
    (``hybrid_ssm._attention_one_query``): splitting the view into heads
    would lay all of it out anew."""
    B, S, H, hd = q.shape
    if S == 1:
        return _attention_one_query(q, k, v, mask[:, 0], scores_type=jnp.float32)
    KV = cfg.n_kv_heads
    q = q.reshape(B, S, KV, H // KV, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, _heads(k, cfg),
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkh->bskgh", probs, _heads(v, cfg)).reshape(B, S, H, hd)


def _attend_full(q: jax.Array, kp: jax.Array, vp: jax.Array, f, bt: jax.Array,
                 posq: jax.Array, cfg: SparseWindowConfig,
                 spans: Optional[Tuple[int, ...]], span_at) -> jax.Array:
    """Attention of ``q`` at ``posq [B, S]`` over the first ``spans[span_at]``
    keys of each row's table in full layer ``f`` (the whole table without
    spans): the gathered view of ``llama._attend_over_span`` on this pool's
    layout, one branch of a ``lax.switch`` a span."""
    BS = kp.shape[2]

    def over(span, q, kp, vp, f, bt, posq):
        view_bt = bt[:, :span // BS]
        mask = jnp.arange(span)[None, None, :] <= posq[:, :, None]
        return _attention(q, _view(kp, f, view_bt), _view(vp, f, view_bt), mask, cfg)

    if spans is None:
        return over(bt.shape[1] * BS, q, kp, vp, f, bt, posq)
    return lax.switch(span_at, [partial(over, span) for span in spans],
                      q, kp, vp, f, bt, posq)


def _attend_window(q: jax.Array, wkp: jax.Array, wvp: jax.Array, w, wbt: jax.Array,
                   posq: jax.Array, first: jax.Array, n_blocks: int,
                   cfg: SparseWindowConfig) -> jax.Array:
    """Attention of ``q [B, S, H, hd]`` at ``posq`` over the ``n_blocks``
    blocks of window layer ``w`` that begin at each row's block ``first [B]``
    (a block before the table's start is the trash block, masked)."""
    BS = wkp.shape[2]
    B = q.shape[0]
    at = first[:, None] + jnp.arange(n_blocks)[None, :]
    inside = (at >= 0) & (at < wbt.shape[1])
    blocks = jnp.where(inside, jnp.take_along_axis(
        wbt, jnp.clip(at, 0, wbt.shape[1] - 1), axis=1), 0)
    keys = (at[:, :, None] * BS + jnp.arange(BS)[None, None, :]).reshape(B, n_blocks * BS)
    mask = _window_mask(posq, keys, cfg.window)
    return _attention(q, _view(wkp, w, blocks), _view(wvp, w, blocks), mask, cfg)


#: keys a step of the blocked arm's prefill fold takes: the float32 scores
#: that exist at once are ``heads x queries x PREFILL_TILE``, 256 MiB at 128
#: heads and a chunk of 1024 where the gathered view's are ``x span``
PREFILL_TILE = 512


def _fold_keys(q: jax.Array, kp: jax.Array, vp: jax.Array, index, table: jax.Array,
               posq: jax.Array, first: jax.Array, n_tiles, window: int,
               cfg: SparseWindowConfig) -> jax.Array:
    """Attention of ``q [B, S, H, hd]`` at ``posq [B, S]`` over ``n_tiles``
    (traced, or a Python int) tiles of :data:`PREFILL_TILE` keys, read from
    layer ``index`` of the blocked pools ``[n, NB, BS, KV, hd]`` through each
    row's ``table [B, MB]`` from its block ``first [B]`` on, and folded into
    an online softmax: scores exist a tile at a time, float32 as the product
    accumulates them. A query sees keys ``j <= i`` and, with ``window``, ``j >
    i - window``; a block before the table's start or past its end is the
    trash block, masked. The recurrence of ``paged_attention``'s lax arm,
    with a run that may begin anywhere and end early."""
    B, S, H, hd = q.shape
    n, NB, BS, KV, _ = kp.shape
    MB = table.shape[1]
    C = PREFILL_TILE // BS
    G = H // KV
    kf = kp.reshape(n * NB, BS, KV, hd)
    vf = vp.reshape(n * NB, BS, KV, hd)
    qg = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)  # [B, KV, G, S, hd]
    scale = 1.0 / math.sqrt(hd)

    def tile(j, carry):
        at = first[:, None] + j * C + jnp.arange(C)[None, :]  # [B, C] table entries
        inside = (at >= 0) & (at < MB)
        blocks = index * NB + jnp.where(inside, jnp.take_along_axis(
            table, jnp.clip(at, 0, MB - 1), axis=1), 0)
        kb = kf[blocks].reshape(B, C * BS, KV, hd)
        vb = vf[blocks].reshape(B, C * BS, KV, hd)
        t = (at[:, :, None] * BS + jnp.arange(BS)[None, None, :]).reshape(B, 1, C * BS)
        seen = jnp.repeat(inside, BS, axis=1)[:, None, :] & (t <= posq[:, :, None])
        if window:
            seen &= t > posq[:, :, None] - window
        s = jnp.einsum("bkgsh,btkh->bkgst", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen[:, None, None], s, paged_attention.NEG_INF)
        m, l, acc = carry
        m_new = jnp.maximum(jnp.maximum(m, s.max(axis=-1)), -1e29)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        pv = jnp.einsum("bkgst,btkh->bkgsh", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        return m_new, l * corr + p.sum(axis=-1), acc * corr[..., None] + pv

    m0 = jnp.full((B, KV, G, S), -1e29, jnp.float32)
    m, l, acc = lax.fori_loop(0, n_tiles, tile, (
        m0, jnp.zeros_like(m0), jnp.zeros((B, KV, G, S, hd), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


def _run_of_blocks(wbt: jax.Array, pos: jax.Array, BS: int, window: int):
    """For a window layer's decode step through ``paged_attention``: each
    row's RUN of table entries that ends at its position, ``(run [B, n],
    at [B])``, ``at`` the row's position counted from the run's first key. The
    run begins ``window / BS`` blocks back (at the table's start for a young
    row) and is 16 entries longer, a whole number of the kernel's compute
    blocks; entries past the table's end are the trash block, and past the
    position nothing is read."""
    MB = wbt.shape[1]
    first = jnp.maximum(pos // BS - window // BS, 0)
    at = first[:, None] + jnp.arange(window // BS + 16)[None, :]
    run = jnp.where(at < MB, jnp.take_along_axis(wbt, jnp.minimum(at, MB - 1), axis=1), 0)
    return run, pos - first * BS


def _run_layers(params: Params, cfg: SparseWindowConfig, x, pools, kept, attn_fn):
    """Every layer in order, one ``lax.scan`` over the periods with the four
    pools as carries. ``attn_fn(kind, h, lp, pools, index) -> (out, pools)``
    reads and writes layer ``index`` of its kind's pools and returns ``out``
    in float32. ``x [B, S, D]``, ``kept [B, S]``. The residual stream is
    float32 from the embedding to the head: a layer reads it through its
    norm, in the weights' type, and adds to it what its output product
    accumulated, unrounded. Returns ``(x, pools, load [n_layers, held])``."""
    B, S, D = x.shape
    T = len(cfg.period)
    per = {"window": cfg.windows_per_period, "full": cfg.fulls_per_period}
    flat = kept.reshape(B * S)

    def period(carry, p):
        x, pools, load = carry
        seen = {"window": 0, "full": 0}
        for j, kind in enumerate(cfg.period):
            index = p * per[kind] + seen[kind]
            seen[kind] += 1
            lp = _at(params[kind], index)
            if cfg.parallel_block:
                # one norm feeds both branches; one add takes both
                layer = p * T + j
                h = _norm(x, lp["norm"], cfg)
                out, pools = attn_fn(kind, h, lp, pools, index)
            else:
                out, pools = attn_fn(kind, _norm(x, lp["norm"], cfg), lp, pools, index)
                x = x + out
                layer = p * T + j
                h = _norm(x, lax.dynamic_index_in_dim(
                    params["moe"]["norm"], layer, 0, keepdims=False), cfg)
            h = h.reshape(B * S, D)
            y, n = expert_layer(h, params["moe"], layer, flat, cfg)
            if cfg.n_shared:
                y = y + shared_experts(h, params["moe"], layer, cfg)
            y = y.reshape(B, S, D)
            x = x + out + y if cfg.parallel_block else x + y  # float32 to float32
            load = lax.dynamic_update_index_in_dim(load, n, layer, 0)
        return (x, pools, load), None

    load = jnp.zeros((cfg.n_layers, cfg.held), jnp.int32)
    return lax.scan(period, (x, pools, load), jnp.arange(cfg.periods, dtype=jnp.int32))[0]


def _embed(params: Params, tokens: jax.Array, cfg: SparseWindowConfig) -> jax.Array:
    return llama.gather_embed(params["embed"], tokens).astype(jnp.float32)


def _logits(params: Params, x: jax.Array, cfg: SparseWindowConfig) -> jax.Array:
    """``x [B, D]`` (before the final norm) -> float32 logits, not rounded to
    the weights' type on the way (a bfloat16 logit near 4 is a multiple of
    1/32, and near-ties would be broken by the rounding). A tied head is the
    embedding's transpose, the logits times ``logit_scale``."""
    h = _norm(x, params["final_norm"], cfg)
    if not cfg.tied_head:
        return _out(h, params["lm_head"])
    logits = jnp.einsum("bd,vd->bv", h, params["embed"], preferred_element_type=jnp.float32)
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


_POOLS = ("k", "v", "wk", "wv")


# ---- prefill ---------------------------------------------------------------

def prefill(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, S] right-padded prompt tokens of the rows
    lengths: jax.Array,  # [B]; 0 = row untouched
    cfg: SparseWindowConfig,
    rows: jax.Array,  # [B] cache rows of this compact batch
    starts: Optional[jax.Array] = None,  # [B] where each row's tokens begin
    spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """``lengths`` prompt tokens of each of ``rows`` from ``starts``:
    last-token logits ``[B, V]`` and the cache.

    On the blocked arm (pools of five axes) every program attends through
    the pools, in tiles (:func:`_fold_keys`), and takes no span. Else:
    ``starts`` None: whole prompts from position 0, attention local (no pool
    read), a window layer's by its mask. Given: suffixes that attend through
    the pools, a full layer over the span holding ``live_to`` (as
    ``llama.paged_prefill_from``), a window layer over the blocks from
    ``starts - window`` on. Pad positions and inactive rows write their K/V to
    the trash blocks and route to no expert; what the real tokens' experts
    computed is added to the cache's ``expert_tokens``."""
    B, S = tokens.shape
    bt, wbt = cache["bt"][rows], cache["wbt"][rows]
    BS = cache["k"].shape[2]
    blocked = cache["k"].ndim == 5
    max_s = bt.shape[1] * BS
    active = lengths > 0
    begin = jnp.zeros((B,), jnp.int32) if starts is None else starts
    posq = jnp.minimum(begin[:, None] + jnp.arange(S)[None, :], max_s - 1)
    writable = active[:, None] & (jnp.arange(S)[None, :] < lengths[:, None])
    at = (jnp.arange(B)[:, None], posq // BS)
    blk = {"full": jnp.where(writable, bt[at], 0), "window": jnp.where(writable, wbt[at], 0)}
    off = posq % BS
    span_at = None
    if starts is not None and spans is not None and not blocked:
        llama._check_spans(spans, bt, BS, "gather", live_to)
        span_at = llama._span_index(spans, live_to)
    # a chunk's first query reaches back a window; one block more where the
    # chunk does not start on a block's edge
    first = begin // BS - cfg.window // BS
    n_blocks = cfg.window // BS + -(-S // BS) + 1
    causal = jnp.arange(S)[None, :, None] >= jnp.arange(S)[None, None, :]
    local = {"full": causal, "window": causal & _window_mask(posq, posq, cfg.window)}
    # the blocked arm: a full layer folds the tiles that hold the batch's
    # last position, a window layer those of its fixed run of blocks
    if blocked:
        per_tile = PREFILL_TILE // BS
        full_tiles = jnp.max(begin + jnp.maximum(lengths, 1) - 1) // PREFILL_TILE + 1
        zero = jnp.zeros((B,), jnp.int32)

    def attn_fn(kind, h, lp, pools, index):
        window = kind == "window"
        q, k, v = _qkv(h, lp, cfg.rope_window if window else cfg.rope_full, posq, cfg)
        kn, vn = ("wk", "wv") if window else ("k", "v")
        pools = dict(pools)
        if blocked:
            pools[kn] = pools[kn].at[index, blk[kind], off].set(_heads(k, cfg))
            pools[vn] = pools[vn].at[index, blk[kind], off].set(_heads(v, cfg))
            if window:
                a = _fold_keys(q, pools[kn], pools[vn], index, wbt, posq, first,
                               -(-n_blocks // per_tile), cfg.window, cfg)
            else:
                a = _fold_keys(q, pools[kn], pools[vn], index, bt, posq, zero,
                               full_tiles, 0, cfg)
            return _out(a.reshape(B, S, -1), lp["wo"]), pools
        pools[kn] = pools[kn].at[index, blk[kind], off].set(k)
        pools[vn] = pools[vn].at[index, blk[kind], off].set(v)
        if starts is None:
            a = _attention(q, k, v, local[kind], cfg)
        elif window:
            a = _attend_window(q, pools[kn], pools[vn], index, wbt, posq, first,
                               n_blocks, cfg)
        else:
            a = _attend_full(q, pools[kn], pools[vn], index, bt, posq, cfg, spans, span_at)
        return _out(a.reshape(B, S, -1), lp["wo"]), pools

    x, pools, load = _run_layers(
        params, cfg, _embed(params, tokens, cfg), {n: cache[n] for n in _POOLS},
        writable, attn_fn)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = _logits(params, last, cfg)
    out = {
        **pools, "bt": cache["bt"], "wbt": cache["wbt"],
        "pos": llama._advance_pos(cache["pos"], rows, active, begin + lengths, max_s),
        "expert_tokens": cache["expert_tokens"] + load,
    }
    if cfg.experts_held:
        out["assign_all"] = cache["assign_all"] + (
            cfg.n_layers * cfg.top_k * jnp.sum(writable, dtype=jnp.int32))
    return logits, out


# ---- decode ----------------------------------------------------------------

def decode_step(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, 1]
    kept: jax.Array,  # [B] bool: rows whose token this step somebody reads
    cfg: SparseWindowConfig,
    spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params, jax.Array]:
    """One token for every cache row: the new K/V scattered into the row's
    current block of each pool, a full layer's attention over the gathered
    view's span, a window layer's over the ``window / BS + 1`` blocks that end
    at the row's position (the blocked arm: both through ``paged_attention``
    over the row's own blocks, no span). A row not ``kept`` (vacant, between two chunks of
    its prompt, left out by the block reserve, past its budget) computes
    garbage nobody reads, writes its K/V to the trash blocks and routes to no
    expert. Returns ``(logits [B, V], cache, load [n_layers, n_experts])``."""
    B = tokens.shape[0]
    pos, bt, wbt = cache["pos"], cache["bt"], cache["wbt"]
    BS = cache["k"].shape[2]
    blocked = cache["k"].ndim == 5
    max_s = bt.shape[1] * BS
    at = (jnp.arange(B), pos // BS)
    blk = {"full": jnp.where(kept, bt[at], 0), "window": jnp.where(kept, wbt[at], 0)}
    span_at = None
    if blocked:
        run, run_pos = _run_of_blocks(wbt, pos, BS, cfg.window)
    elif spans is not None:
        llama._check_spans(spans, bt, BS, "gather", live_to)
        span_at = llama._span_index(spans, live_to)
        blk["full"] = jnp.where(pos < jnp.asarray(spans, jnp.int32)[span_at], blk["full"], 0)
    off = pos % BS
    posq = pos[:, None]
    first = pos // BS - cfg.window // BS

    def attn_fn(kind, h, lp, pools, index):
        window = kind == "window"
        q, k, v = _qkv(h, lp, cfg.rope_window if window else cfg.rope_full, posq, cfg)
        kn, vn = ("wk", "wv") if window else ("k", "v")
        pools = dict(pools)
        if blocked:
            # the step's K/V written and the row's own blocks read by one
            # call: every key it holds in a full layer, the run that ends at
            # its position in a window layer; a row not kept reads nothing
            where = {"window": cfg.window} if window else {}
            a, pools[kn], pools[vn] = paged_attention.paged_attention(
                q, pools[kn], pools[vn], run if window else bt,
                run_pos if window else pos, layer=index, live=kept,
                new_k=_heads(k[:, 0], cfg), new_v=_heads(v[:, 0], cfg), **where)
            return _out(a.reshape(B, 1, -1), lp["wo"]), pools
        pools[kn] = pools[kn].at[index, blk[kind], off].set(k[:, 0])
        pools[vn] = pools[vn].at[index, blk[kind], off].set(v[:, 0])
        if window:
            a = _attend_window(q, pools[kn], pools[vn], index, wbt, posq, first,
                               cfg.window // BS + 1, cfg)
        else:
            a = _attend_full(q, pools[kn], pools[vn], index, bt, posq, cfg, spans, span_at)
        return _out(a.reshape(B, 1, -1), lp["wo"]), pools

    x, pools, load = _run_layers(
        params, cfg, _embed(params, tokens, cfg), {n: cache[n] for n in _POOLS},
        kept[:, None], attn_fn)
    return _logits(params, x[:, 0], cfg), {
        **pools, "bt": bt, "wbt": wbt, "pos": jnp.minimum(pos + 1, max_s - 1),
    }, load


def decode_segment(
    params: Params, cache: Params, tokens: jax.Array, temps: jax.Array,
    key: jax.Array, take: jax.Array, cfg: SparseWindowConfig, n_steps: int,
    greedy: bool = False, spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
):
    """``n_steps`` of :func:`decode_step` with the decoder's own on-device
    sample-and-feed chain (``llama.sampled_segment``). ``take [B]`` is how many
    of the segment's tokens each row keeps (0: a row the dispatch did not
    schedule); a row's step past its ``take`` is not kept. Returns ``(toks
    [B, n_steps], last [B, 1], next_key, cache, counters)``; the counters ride
    in the chain's carry beside the cache: ``expert_tokens [n_layers,
    n_experts]`` (kept tokens each expert computed, in this segment's steps
    and in the prefill programs since the segment before: the cache's count,
    which goes back zeroed), ``experts_touched`` (sum over the segment's steps
    and layers of experts with at least one) and ``expert_steps`` (layers
    times the steps some row still needed). Where the stacks hold a share of
    the experts (``cfg.experts_held``), also ``assign_all``, every kept
    assignment the routers made in the same programs, and ``assign_held``,
    those of them that fell on an expert held here (``expert_tokens``
    summed): an eighth of them is an even router over eight shares."""
    one = partial(decode_step, cfg=cfg, spans=spans, live_to=live_to)
    zero = jnp.zeros((), jnp.int32)
    counted = ("expert_tokens", "assign_all") if cfg.experts_held else ("expert_tokens",)
    cache_names = tuple(n for n in cache if n not in counted)
    tile = grouped_by_kernel(tokens.shape[0] * cfg.top_k, params["moe"], cfg)

    def step(carry, toks):
        kept = carry["step"] < take
        logits, cache, load = one(params, {n: carry[n] for n in cache_names}, toks, kept)
        more = {"assign_all": carry["assign_all"] + cfg.n_layers * cfg.top_k * jnp.sum(
            kept, dtype=jnp.int32)} if cfg.experts_held else {}
        return logits, {
            **cache, "step": carry["step"] + 1, **more,
            "expert_tokens": carry["expert_tokens"] + load,
            "experts_touched": carry["experts_touched"] + jnp.sum(load > 0, dtype=jnp.int32),
            "expert_steps": carry["expert_steps"] + cfg.n_layers * jnp.any(kept).astype(jnp.int32),
            "expert_tiles": carry["expert_tiles"] + (
                expert_gmm.row_tiles(load, tile) if tile else zero),
        }

    carry = {**cache, "step": zero, "experts_touched": zero, "expert_steps": zero,
             "expert_tiles": zero}
    toks, last, next_key, carry = llama.sampled_segment(
        step, carry, tokens, temps, key, n_steps, greedy)
    counters = {n: carry[n] for n in (
        *counted, "experts_touched", "expert_steps", "expert_tiles")}
    cache = {n: carry[n] for n in cache_names}
    for n in counted:  # taken over: the next prefill programs count from zero
        cache[n] = jnp.zeros_like(counters[n])
    if cfg.experts_held:  # of all kept assignments, those an expert here took
        counters["assign_held"] = jnp.sum(counters["expert_tokens"], dtype=jnp.int32)
    return toks, last, next_key, cache, counters
