"""Llama-3-family decoder, TPU-first.

Design choices (and why they're TPU-idiomatic, not a torch translation):

- **Functional**: params are a plain pytree; the forward is a pure function
  under `jit` — no modules, no state.
- **Scanned layers**: per-layer weights are stacked on a leading axis and the
  decoder runs as one `lax.scan` over layers. XLA compiles ONE layer body
  (compile time O(1) in depth) and the weight layout is uniform, which is
  what makes fsdp/tp shardings trivially specifiable for all layers at once.
- **Remat**: the scan body is `jax.checkpoint`ed so activations are
  recomputed in backward — HBM is the bottleneck, MXU flops are cheap.
- **bf16 params/activations, fp32 softmax + loss** — MXU-native precision.
- **GQA** (n_kv_heads < n_heads) exactly as Llama-3 uses it.
- **Sharding by rules**: :func:`param_pspecs` returns a PartitionSpec tree
  (megatron tensor split + fsdp) consumed by `pjit`/NamedSharding; XLA
  inserts the collectives.

North-star config (BASELINE.md #4): Llama-3-8B on a gang-scheduled v5e-32.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from kubedl_tpu.models import paged_attention as blocked_attention


def remat_policy_for(name: str):
    """Map a config string to a jax.checkpoint policy (None = save
    nothing, i.e. full recompute)."""
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    if name == "attn":
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    if name == "dots_attn":
        # matmul outputs AND the attention output: backward recomputes
        # neither the dots nor the flash forward kernel
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_out"),
        )
    if name == "flash":
        # ONLY the flash kernel's residuals: backward re-runs the
        # projection/ffn dots (cheap, MXU-bound) but never the attention
        # kernel; saves ~8GB of stacked dot outputs vs "dots" at b8 —
        # for memory-capacity-bound shapes
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse"
        )
    if name == "flash_rope":
        # flash residuals + the kernel's INPUTS (post-rope q/k and v):
        # backward then reconstructs nothing on the attention path —
        # no norm/projection/rope re-run to feed the bwd kernel. The
        # round-4 full-step winner at bench shapes (582ms vs 601 flash,
        # 605 dots, 643 r3-shipped) for ~4GB of saved activations.
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse", "rope_out", "attn_v"
        )
    if name == "attn_flash":
        # attention output + kernel residuals, dots recomputed
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "flash_out", "flash_lse"
        )
    if name == "dots_flash":
        # dots PLUS the flash kernel's own residuals (out + lse, tagged in
        # ops/flash_attention._flash_fwd). "dots_attn" was not enough: it
        # saves the post-transpose attention output but the custom-vjp
        # backward also needs lse, which no policy could name — so the
        # forward kernel still re-ran under remat (~43ms/step profiled on
        # the bench model). Costs lse (f32 [B,H,S]) + out per layer.
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"
            ),
        )
    raise ValueError(f"unknown remat_policy {name!r}")

Params = Dict[str, Any]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    #: remat the scan body (trade flops for HBM)
    remat: bool = True
    #: what the remat saves: "dots_flash" (matmul outputs AND the flash
    #: kernel's out/lse residuals — the default, because without the
    #: residuals the backward must re-run the forward attention kernel
    #: every layer), "flash_rope" (kernel residuals + its post-rope
    #: q/k + v inputs: backward reconstructs nothing on the attention
    #: path — the measured bench winner), "flash" (only the kernel
    #: residuals: re-run the cheap dots, ~8GB less saved at bench
    #: shapes), "dots", "dots_attn", "nothing", "attn", "attn_flash"
    remat_policy: str = "dots_flash"
    #: compute the LM loss over sequence chunks of this many positions
    #: (0 = whole sequence at once). The full [B, S, V] fp32 logits are
    #: the single biggest activation (b8 x s2048 x v32k = 2.1 GB before
    #: softmax temporaries); chunking + remat caps loss memory at
    #: [B, chunk, V] and recomputes each chunk's logits in backward.
    loss_chunk: int = 0
    #: tie lm_head to the embedding table (smaller models do)
    tie_embeddings: bool = False
    #: fuse the QKV (and gate/up) projections into single matmuls at use
    #: (concat-at-use: param tree and checkpoints unchanged). Wrong for
    #: tensor-parallel meshes (the trainer force-disables it there); off
    #: for quantized weights automatically.
    fuse_projections: bool = False
    # -- Gemma-family knobs (same decoder skeleton, different details) -----
    #: MLP activation: "silu" (Llama SwiGLU) or "gelu" (Gemma GeGLU)
    act: str = "silu"
    #: RMSNorm uses (1 + weight) (Gemma)
    norm_plus_one: bool = False
    #: scale embeddings by sqrt(dim) at input (Gemma)
    embed_scale: bool = False
    #: fixed head dim decoupled from dim/n_heads (Gemma: 256); 0 = dim/heads
    head_dim_fixed: int = 0
    #: zero-init the residual OUTPUT projections (wo, w_down) of layers
    #: with index >= this value (0 = off). ReZero/GPT-2-style depth init:
    #: the deep layers start as exact identity residuals, so an early-exit
    #: draft sliced at this depth (serving.speculative.ModelDraft
    #: .from_target) agrees with the full target at init — the tiny-deep
    #: draft/target pairing the speculative bench measures honestly.
    zero_init_deep_from: int = 0

    @property
    def head_dim(self) -> int:
        return self.head_dim_fixed or self.dim // self.n_heads

    def num_params(self) -> int:
        hd = self.head_dim
        per_layer = (
            self.dim * (self.n_heads * hd)  # wq
            + 2 * self.dim * (self.n_kv_heads * hd)  # wk, wv
            + (self.n_heads * hd) * self.dim  # wo
            + 3 * self.dim * self.ffn_dim  # gate, up, down
            + 2 * self.dim  # norms
        )
        embed = self.vocab_size * self.dim
        head = 0 if self.tie_embeddings else self.dim * self.vocab_size
        return embed + self.n_layers * per_layer + head + self.dim

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (fwd+bwd ~= 6*N)."""
        return 6.0 * self.num_params()


# ---- presets ---------------------------------------------------------------

LLAMA3_8B = LlamaConfig()
LLAMA3_1B = LlamaConfig(
    vocab_size=128256, dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
    ffn_dim=8192, tie_embeddings=True,
)
#: bench-scale model that fits one v5e chip (16 GiB) with room for a real
#: batch. loss_chunk keeps the fp32 logits out of HBM (2.1 GB at b8 s2048
#: — measured equal-speed and strictly more headroom, docs/performance.md)
BENCH_350M = LlamaConfig(
    vocab_size=32768, dim=1024, n_layers=24, n_heads=16, n_kv_heads=8,
    ffn_dim=4096, max_seq=2048, loss_chunk=0,
    # "flash_rope" saves the kernel residuals AND its inputs (post-rope
    # q/k, v): backward reconstructs nothing on the attention path while
    # the ~8GB of stacked dot outputs "dots" would have saved stay free —
    # which is also what lets loss_chunk=0 (unchunked logits) win.
    # Full-step sweep on v5e b8 s2048: flash_rope 582ms vs flash 597-601
    # vs dots 605-614 vs dots_flash 639-647 vs 643 shipped in r3.
    remat_policy="flash_rope",
)
TINY = LlamaConfig(
    vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
    max_seq=128, dtype=jnp.float32, remat=False,
)
#: Gemma-2B (BASELINE.md target 5: inference on v5e): MQA, head_dim 256,
#: GeGLU, (1+w) norms, sqrt(dim)-scaled tied embeddings.
GEMMA_2B = LlamaConfig(
    vocab_size=256000, dim=2048, n_layers=18, n_heads=8, n_kv_heads=1,
    ffn_dim=16384, max_seq=8192, rope_theta=10000.0, tie_embeddings=True,
    act="gelu", norm_plus_one=True, embed_scale=True, head_dim_fixed=256,
)
#: tiny's 4-layer sibling for the draft/target MODEL_ZOO pairing: layers
#: >= 2 start as identity residuals (zero_init_deep_from), so the 2-layer
#: early-exit draft carved out of its own weights proposes what the full
#: target would emit — a CPU-scale proxy for a trained draft/target pair.
TINY_DEEP = dataclasses.replace(TINY, n_layers=4, zero_init_deep_from=2)
TINY_GEMMA = LlamaConfig(
    vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=1, ffn_dim=128,
    max_seq=128, dtype=jnp.float32, remat=False, tie_embeddings=True,
    act="gelu", norm_plus_one=True, embed_scale=True, head_dim_fixed=32,
)


def preset(name: str) -> LlamaConfig:
    table = {
        "llama3-8b": LLAMA3_8B,
        "llama3-1b": LLAMA3_1B,
        "bench-350m": BENCH_350M,
        "gemma-2b": GEMMA_2B,
        "tiny-gemma": TINY_GEMMA,
        "tiny": TINY,
        "tiny-deep": TINY_DEEP,
    }
    return table[name]


# ---- init ------------------------------------------------------------------

def llama_init(key: jax.Array, cfg: LlamaConfig) -> Params:
    hd = cfg.head_dim
    k = iter(jax.random.split(key, 12))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(
            cfg.dtype
        )

    L, D, F, V = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size
    norm_init = jnp.zeros if cfg.norm_plus_one else jnp.ones
    params: Params = {
        "embed": dense(next(k), (V, D), D),
        "layers": {
            "attn_norm": norm_init((L, D), cfg.dtype),
            "wq": dense(next(k), (L, D, cfg.n_heads * hd), D),
            "wk": dense(next(k), (L, D, cfg.n_kv_heads * hd), D),
            "wv": dense(next(k), (L, D, cfg.n_kv_heads * hd), D),
            "wo": dense(next(k), (L, cfg.n_heads * hd, D), cfg.n_heads * hd),
            "mlp_norm": norm_init((L, D), cfg.dtype),
            "w_gate": dense(next(k), (L, D, F), D),
            "w_up": dense(next(k), (L, D, F), D),
            "w_down": dense(next(k), (L, F, D), F),
        },
        "final_norm": norm_init((D,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (D, V), D)
    if cfg.zero_init_deep_from:
        deep = jnp.arange(L) >= cfg.zero_init_deep_from
        lyr = params["layers"]
        for name in ("wo", "w_down"):
            lyr[name] = jnp.where(
                deep[:, None, None], 0.0, lyr[name]
            ).astype(cfg.dtype)
    return params


def param_pspecs(cfg: LlamaConfig) -> Params:
    """Megatron tensor split + fsdp, stacked-layer aware.

    Column-parallel (output dim on "tensor"): wq/wk/wv, w_gate/w_up.
    Row-parallel (input dim on "tensor"): wo, w_down. fsdp shards the other
    matmul dim. Embedding: vocab on tensor, dim on fsdp.
    """
    specs: Params = {
        "embed": P("tensor", "fsdp"),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, "fsdp", "tensor"),
            "wk": P(None, "fsdp", "tensor"),
            "wv": P(None, "fsdp", "tensor"),
            "wo": P(None, "tensor", "fsdp"),
            "mlp_norm": P(None, None),
            "w_gate": P(None, "fsdp", "tensor"),
            "w_up": P(None, "fsdp", "tensor"),
            "w_down": P(None, "tensor", "fsdp"),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("fsdp", "tensor")
    return specs


# ---- weight-only int8 (serving) --------------------------------------------

#: weights quantized for serving (norms stay float: tiny and sensitive)
_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _quant_leaf(w: jax.Array, axis: int = -2) -> Dict[str, jax.Array]:
    """Symmetric int8 with the scale reduced over ``axis``. For matmul
    weights that is the CONTRACTION axis (-2): `deq(w)` folds into the
    consuming matmul as a per-output-column scale and XLA fuses
    convert+scale into the dot — HBM reads the int8 bytes, half the bf16
    traffic. The embedding table instead scales PER ROW (axis=-1): one
    outlier token's norm must not inflate the int8 step for every token,
    and its consumers (a row gather; a dim-contraction when tied as the
    lm_head) factor a per-row scale just as well."""
    a = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(a / s), -127, 127).astype(jnp.int8)
    return {"q8": q, "s8": s.astype(jnp.bfloat16)}


def quantize_params(params: Params, cfg: LlamaConfig) -> Params:
    """Weight-only int8 for the decode/prefill paths (serving: decode is
    HBM-bandwidth-bound, and weights dominate the bytes — int8 halves
    them). Embedding/lm_head and all layer matmuls quantize; norms stay
    in their float dtype. Training never sees quantized params."""
    out: Params = dict(params)
    out["embed"] = _quant_leaf(params["embed"], axis=-1)  # per-token rows
    if "lm_head" in params:
        out["lm_head"] = _quant_leaf(params["lm_head"])
    layers = dict(params["layers"])
    for key in _QUANT_KEYS:
        layers[key] = _quant_leaf(layers[key])
    out["layers"] = layers
    return out


def deq(w) -> jax.Array:
    """Dequantize an int8 weight leaf ({"q8","s8"} -> bf16); identity for
    raw arrays, so every consumer works with either representation."""
    if isinstance(w, dict) and "q8" in w:
        return w["q8"].astype(w["s8"].dtype) * w["s8"]
    return w


def _wdim(w, axis: int) -> int:
    return (w["q8"] if isinstance(w, dict) and "q8" in w else w).shape[axis]


# ---- building blocks -------------------------------------------------------

def rmsnorm(
    x: jax.Array, weight: jax.Array, eps: float, plus_one: bool = False
) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if plus_one:  # Gemma convention: weight is a residual around 1
        w = w + 1.0
    return (x * w).astype(dtype)


def _act(cfg: LlamaConfig):
    return jax.nn.silu if cfg.act == "silu" else partial(
        jax.nn.gelu, approximate=True
    )


def rope_table(
    head_dim: int, theta: float, seq_len: int, offset: int = 0
) -> Tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    ang = jnp.outer(t, inv)  # [S, hd/2]
    return jnp.cos(ang), jnp.sin(ang)


def rope_freqs(cfg: LlamaConfig, seq_len: int, offset: int = 0) -> Tuple[jax.Array, jax.Array]:
    return rope_table(cfg.head_dim, cfg.rope_theta, seq_len, offset)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, H, hd]; rotate pairs (even, odd)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    # interleaved convention folded to split-halves (equivalent under a
    # fixed permutation of head dims; consistent between q and k)
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, S, KV, hd]
    v: jax.Array,  # [B, S, KV, hd]
    causal: bool = True,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference attention: fp32 softmax, GQA via head grouping. The pallas
    flash kernel (kubedl_tpu.ops.flash_attention) is the fused drop-in; this
    is the numerics oracle and CPU fallback."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    q = q.reshape(B, S, KV, group, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if causal:
        idx = jnp.arange(S)
        cmask = idx[:, None] >= idx[None, :]  # [S, T]
        scores = jnp.where(cmask[None, None, None], scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, hd)


def _block(
    x: jax.Array, lp: Params, cfg: LlamaConfig, cos, sin, attn_fn=None,
    tp_axis: Optional[str] = None,
) -> jax.Array:
    """One decoder block. Head/ffn counts are inferred from the WEIGHT
    shapes, not the config, so the same body runs tensor-parallel inside a
    shard_map (megatron split: wq/wk/wv/w_gate/w_up column-parallel, wo/
    w_down row-parallel with a psum over ``tp_axis``) — this is what lets
    pipe x tensor compose in the GPipe stage body."""
    B, S, D = x.shape
    hd = cfg.head_dim
    po = cfg.norm_plus_one
    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, po)
    n_heads = _wdim(lp["wq"], -1) // hd  # local (tensor-split) head count
    n_kv = _wdim(lp["wk"], -1) // hd
    # fuse_projections: one [D, (H+2KV)*hd] matmul instead of three.
    # Concat-at-use keeps the param tree (and checkpoints) unchanged;
    # autodiff slices the fused grad back apart. Only for unsharded/
    # data-parallel meshes (the trainer force-disables it under tensor
    # parallelism: concat along the column-split dim would make GSPMD
    # all-gather the shards) and unquantized weights. Measured on v5e
    # bench shapes: -19ms/step in an isolated forward but +16ms on the
    # FULL remat'd train step (the concats rematerialize in backward and
    # the extra weight-bytes traffic beats the MXU gain) — hence default
    # OFF; the knob exists for inference-style forward-heavy workloads.
    fuse = cfg.fuse_projections and not isinstance(lp["wq"], dict)
    if fuse:
        qkv = h @ jnp.concatenate(
            [lp["wq"], lp["wk"], lp["wv"]], axis=1
        )
        dq_w, dkv_w = n_heads * hd, n_kv * hd
        q = qkv[..., :dq_w].reshape(B, S, n_heads, hd)
        k = qkv[..., dq_w:dq_w + dkv_w].reshape(B, S, n_kv, hd)
        v = qkv[..., dq_w + dkv_w:].reshape(B, S, n_kv, hd)
    else:
        q = (h @ deq(lp["wq"])).reshape(B, S, n_heads, hd)
        k = (h @ deq(lp["wk"])).reshape(B, S, n_kv, hd)
        v = (h @ deq(lp["wv"])).reshape(B, S, n_kv, hd)
    # named so "flash_rope" can SAVE the attention kernel's exact inputs:
    # without these, the backward scan re-runs norm + the q/k/v
    # projections + rope just to reconstruct the custom-vjp residuals
    # (the kernel's q/k/v) — measured 601 -> 582 ms/step on the bench
    # model for ~3.2GB of saved activations
    if getattr(attn_fn, "fused_rope", False):
        # rotary fused into the pallas kernel (rotation on VMEM tiles;
        # backward emits pre-rope grads): q/k go in UN-rotated, and the
        # saved kernel inputs are the raw projection outputs — the
        # XLA-side rope (rotate + concat + relayouts over [B,S,H,hd],
        # again in backward) profiled at ~37ms/step on the bench model
        q = checkpoint_name(q, "rope_out")
        k = checkpoint_name(k, "rope_out")
        v = checkpoint_name(v, "attn_v")
        attn = attn_fn(q, k, v, rope_cos=cos, rope_sin=sin)
    else:
        q = checkpoint_name(apply_rope(q, cos, sin), "rope_out")
        k = checkpoint_name(apply_rope(k, cos, sin), "rope_out")
        v = checkpoint_name(v, "attn_v")
        attn = (attn_fn or attention)(q, k, v)
    attn = attn.reshape(B, S, n_heads * hd)
    # named for remat_policy="attn": save the attention output so backward
    # never re-runs the (flash) attention kernel, recompute everything else
    attn = checkpoint_name(attn, "attn_out")
    attn_out = attn @ deq(lp["wo"])  # row-parallel: partial sums under tp
    if tp_axis:
        attn_out = lax.psum(attn_out, tp_axis)
    x = x + attn_out
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, po)
    if fuse:
        F = _wdim(lp["w_gate"], -1)
        g_u = h @ jnp.concatenate([lp["w_gate"], lp["w_up"]], axis=1)
        gate = _act(cfg)(g_u[..., :F].astype(jnp.float32)).astype(h.dtype)
        mlp = (gate * g_u[..., F:]) @ deq(lp["w_down"])
    else:
        gate = _act(cfg)(
            (h @ deq(lp["w_gate"])).astype(jnp.float32)
        ).astype(h.dtype)
        mlp = (gate * (h @ deq(lp["w_up"]))) @ deq(lp["w_down"])
    if tp_axis:
        mlp = lax.psum(mlp, tp_axis)
    return x + mlp


def llama_hidden(
    params: Params, tokens: jax.Array, cfg: LlamaConfig, attn_fn=None
) -> jax.Array:
    """tokens [B, S] int32 -> final-norm hidden states [B, S, D]."""
    B, S = tokens.shape
    x = gather_embed(params["embed"], tokens).astype(cfg.dtype)
    if cfg.embed_scale:  # Gemma scales inputs by sqrt(dim)
        x = x * math.sqrt(cfg.dim)
    cos, sin = rope_freqs(cfg, S)

    def body(carry, lp):
        return _block(carry, lp, cfg, cos, sin, attn_fn), None

    if cfg.remat:
        body = jax.checkpoint(body, policy=remat_policy_for(cfg.remat_policy))
    x, _ = lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)


def gather_embed(embed, tokens: jax.Array) -> jax.Array:
    """Token embedding lookup; int8 embeds gather q8 rows + their per-row
    scales (embed quantizes per row — see `_quant_leaf`)."""
    if isinstance(embed, dict) and "q8" in embed:
        return embed["q8"][tokens].astype(embed["s8"].dtype) * embed["s8"][tokens]
    return embed[tokens]


def lm_head_of(params: Params, cfg: LlamaConfig) -> jax.Array:
    return deq(params["embed"]).T if cfg.tie_embeddings else deq(params["lm_head"])


def llama_forward(
    params: Params, tokens: jax.Array, cfg: LlamaConfig, attn_fn=None
) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V] (fp32).

    ``attn_fn`` swaps the attention implementation: dense oracle (default),
    pallas flash kernel, or sequence-parallel ring/ulysses attention built
    by `kubedl_tpu.parallel.ring.make_context_attention` — RoPE is applied
    here with global positions, so sequence-sharded attention composes
    without position bookkeeping.
    """
    x = llama_hidden(params, tokens, cfg, attn_fn)
    return (x @ lm_head_of(params, cfg)).astype(jnp.float32)


def llama_loss(
    params: Params, tokens: jax.Array, cfg: LlamaConfig, attn_fn=None
) -> jax.Array:
    """Next-token cross entropy over tokens[:, 1:].

    The forward runs on the FULL sequence (last position's logits unused)
    so the seq dim keeps its length — slicing to S-1 before the forward
    would break even sequence sharding under context parallelism.

    With ``cfg.loss_chunk`` set, the head matmul + softmax run chunk by
    chunk so the [B, S, V] fp32 logits never materialize.
    """
    if cfg.loss_chunk:
        x = llama_hidden(params, tokens, cfg, attn_fn)
        return chunked_next_token_nll(
            x, lm_head_of(params, cfg), tokens, cfg.loss_chunk
        )
    logits = llama_forward(params, tokens, cfg, attn_fn)
    return next_token_nll(logits, tokens)


def next_token_nll(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean next-token NLL: logits [B, S, V] (full sequence) scored against
    tokens shifted by one. Shared by every LM family."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    targets = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def chunked_next_token_nll(
    x: jax.Array,  # [B, S, D] final hidden states
    head: jax.Array,  # [D, V]
    tokens: jax.Array,  # [B, S]
    chunk: int,
) -> jax.Array:
    """Same mean NLL as :func:`next_token_nll`, computed over sequence
    chunks so the fp32 [B, S, V] logits (+ softmax temporaries) never
    exist at once — peak loss memory is [B, chunk, V], and the chunk body
    is rematerialized so backward recomputes each chunk's logits instead
    of saving softmax residuals for every chunk (which would be the full
    array again)."""
    B, S = tokens.shape
    n_pos = S - 1  # scored positions
    n_chunks = -(-n_pos // chunk)
    pad = n_chunks * chunk - n_pos
    xs = jnp.pad(x[:, :-1], ((0, 0), (0, pad), (0, 0)))
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad)))
    xs = xs.reshape(B, n_chunks, chunk, -1).transpose(1, 0, 2, 3)
    targets = targets.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    valid = (jnp.arange(n_chunks * chunk) < n_pos).reshape(n_chunks, chunk)

    def body(total, inp):
        xc, tc, vc = inp  # [B, chunk, D], [B, chunk], [chunk]
        logits = (xc @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        return total + (nll * vc[None, :]).sum(), None

    body = jax.checkpoint(
        body, policy=jax.checkpoint_policies.nothing_saveable
    )
    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, targets, valid))
    return total / (B * n_pos)


# ---- sharded serving -------------------------------------------------------

def serving_shardings(params: Params, cfg: LlamaConfig, mesh) -> Params:
    """NamedSharding tree for (possibly int8-quantized) params on a
    serving mesh — BASELINE target 5 runs Gemma-2B on a v5e-4, so the
    decode/prefill weights shard over a "tensor" axis (megatron split,
    `param_pspecs`) and XLA inserts the collectives. Quantized leaves
    shard q8 like the weight; scale dims of size 1 (the reduced axis)
    stay unsharded."""
    from jax.sharding import NamedSharding

    pspecs = param_pspecs(cfg)  # omits lm_head for tied configs already
    names = set(mesh.axis_names)

    def prune(spec: P) -> P:
        return P(*(a if a in names else None for a in spec))

    def leaf_sharding(leaf, spec: P):
        spec = prune(spec)
        if isinstance(leaf, dict) and "q8" in leaf:
            s_spec = P(*(
                a if leaf["s8"].shape[i] != 1 else None
                for i, a in enumerate(spec)
            ))
            return {
                "q8": NamedSharding(mesh, spec),
                "s8": NamedSharding(mesh, s_spec),
            }
        return NamedSharding(mesh, spec)

    out: Params = {
        "embed": leaf_sharding(params["embed"], pspecs["embed"]),
        "final_norm": NamedSharding(mesh, prune(pspecs["final_norm"])),
        "layers": {
            k: leaf_sharding(params["layers"][k], pspecs["layers"][k])
            for k in params["layers"]
        },
    }
    if "lm_head" in params:
        out["lm_head"] = leaf_sharding(params["lm_head"], pspecs["lm_head"])
    return out


def shard_serving_params(params: Params, cfg: LlamaConfig, mesh) -> Params:
    """device_put the params onto their serving shardings (one transfer at
    engine start; decode then runs fully sharded). The shardings tree
    mirrors the params structure, so a single pytree device_put covers
    raw and quantized leaves alike."""
    return jax.device_put(params, serving_shardings(params, cfg, mesh))


# ---- pipeline hooks --------------------------------------------------------

def pipeline_hooks(cfg: LlamaConfig):
    """Family adapter for the GPipe pipeline (trainer._make_pipeline_loss):
    embed / rope / stage body / head+loss, with optional tensor parallelism
    INSIDE the stage (tp_axis psums in `_block`)."""
    from kubedl_tpu.parallel.pipeline import PipelineHooks

    def embed(params, tokens):
        x = params["embed"][tokens].astype(cfg.dtype)
        if cfg.embed_scale:
            x = x * math.sqrt(cfg.dim)
        return x

    def make_stage(attn_fn, cos, sin, tp_axis=None, ep_axis=None):
        def stage_fn(layer_params, x):
            def body(carry, lp):
                return _block(carry, lp, cfg, cos, sin, attn_fn, tp_axis), None

            if cfg.remat:
                body = jax.checkpoint(
                    body, policy=remat_policy_for(cfg.remat_policy)
                )
            x, _ = lax.scan(body, x, layer_params)
            return x, jnp.zeros((), jnp.float32)

        return stage_fn

    def head_loss(params, h, tokens, aux_mean):
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
        logits = (h @ lm_head_of(params, cfg)).astype(jnp.float32)
        return next_token_nll(logits, tokens)

    return PipelineHooks(
        embed=embed,
        rope=lambda S: rope_freqs(cfg, S),
        make_stage=make_stage,
        head_loss=head_loss,
        n_layers=cfg.n_layers,
    )


# ---- KV-cache decode (serving path) ---------------------------------------

def init_cache(cfg: LlamaConfig, batch: int, max_seq: int) -> Params:
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def init_batched_cache(cfg: LlamaConfig, batch: int, max_seq: int) -> Params:
    """Continuous-batching cache: PER-SLOT positions so every batch row can
    be a different sequence at a different decode depth (the serving
    engine's slot model). Shapes are static — one compile serves any mix
    of in-flight requests."""
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def _row_update(cache_layer: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write ``new`` [B, S, KV, hd] into ``cache_layer`` [B, T, KV, hd] at
    per-row offset ``pos`` [B] via vmapped `dynamic_update_slice` — O(S)
    HBM traffic per row instead of the one-hot full-cache rewrite the
    round-2 decode paid (O(T) per generated token, VERDICT.md weak #2)."""
    return jax.vmap(
        lambda c, n, p: lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
    )(cache_layer, new, pos)


def decode_step_batched(
    params: Params, cache: Params, tokens: jax.Array, cfg: LlamaConfig
) -> Tuple[jax.Array, Params]:
    """One decode step with per-row positions: tokens [B, 1] ->
    (logits [B, V], updated cache). Each row attends to its own prefix
    (per-row causal mask) and writes its KV at its own position with a
    per-row `dynamic_update_slice` (in-place under donation). The layer
    stack runs as one `lax.scan` so XLA compiles ONE layer body — compile
    time O(1) in depth, matching the training forward. Static shapes: the
    step compiles once and serves any interleaving of requests
    (continuous batching)."""
    B = tokens.shape[0]
    hd = cfg.head_dim
    pos = cache["pos"]  # [B]
    max_s = cache["k"].shape[2]
    x = gather_embed(params["embed"], tokens).astype(cfg.dtype)  # [B, 1, D]
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.dim)
    cos, sin = rope_freqs(cfg, max_s)
    cos_t = cos[pos][:, None, None, :]  # [B,1,1,hd/2] per-row rotation
    sin_t = sin[pos][:, None, None, :]
    # per-row validity: row b sees positions 0..pos[b]
    valid = (jnp.arange(max_s)[None, :] <= pos[:, None])  # [B, T]
    mask = valid[:, None, None, None, :]  # broadcast over (KV, G, S=1)

    def rot(t):  # apply_rope with per-row tables
        t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [t1 * cos_t - t2 * sin_t, t1 * sin_t + t2 * cos_t], axis=-1
        ).astype(t.dtype)

    def body(x, inp):
        lp, ck, cv = inp  # ck/cv: [B, T, KV, hd] this layer's cache
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q = rot((h @ deq(lp["wq"])).reshape(B, 1, cfg.n_heads, hd))
        k = rot((h @ deq(lp["wk"])).reshape(B, 1, cfg.n_kv_heads, hd))
        v = (h @ deq(lp["wv"])).reshape(B, 1, cfg.n_kv_heads, hd)
        ck = _row_update(ck, k, pos)
        cv = _row_update(cv, v, pos)
        attn = attention(q, ck, cv, causal=False, mask=mask)
        x = x + attn.reshape(B, 1, cfg.n_heads * hd) @ deq(lp["wo"])
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
        gate = _act(cfg)((h @ deq(lp["w_gate"])).astype(jnp.float32)).astype(h.dtype)
        x = x + (gate * (h @ deq(lp["w_up"]))) @ deq(lp["w_down"])
        return x, (ck, cv)

    x, (new_k, new_v) = lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"])
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    logits = (x[:, 0] @ lm_head_of(params, cfg)).astype(jnp.float32)
    cache = {
        "k": new_k,
        "v": new_v,
        "pos": jnp.minimum(pos + 1, max_s - 1),
    }
    return logits, cache


def decode_segment(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, 1] first input token per row
    temps: jax.Array,  # [B] sampling temperature; <= 0 = greedy
    key: jax.Array,  # PRNG key for the whole segment
    cfg: LlamaConfig,
    n_steps: int,
    greedy: bool = False,  # static: all rows argmax — skips the gumbel
) -> Tuple[jax.Array, jax.Array, jax.Array, Params]:
    """``n_steps`` decode steps with ON-DEVICE sampling, one dispatch.

    The serving engine's per-token tick paid a full-logits device_get
    ([B, V] — 8MB for Gemma-2B at B=8) plus a host round trip EVERY
    token; that transfer dwarfed the compute. Here the sample->feed
    chain runs inside one jitted `lax.scan` (gumbel-max ==
    categorical; temperature <= 0 degrades to pure argmax) and only the
    sampled ids ([B, n_steps] int32) cross to the host, once per
    segment. Completion in the engine is token-COUNT based, so the
    scheduler can size segments to the earliest completion without
    seeing any token value. One compile per distinct n_steps (the engine
    buckets to powers of two).

    Returns ``(toks [B, n_steps], last [B, 1], next_key, cache)``:
    ``last`` and ``next_key`` stay on device, so the engine chains
    straight into the next segment with zero host->device transfers and
    no extra split dispatch while the slot set is unchanged. ``toks`` is
    shaped for DEFERRED harvest: the engine dispatches segment N+1
    against ``last`` before calling `device_get` on segment N's ``toks``,
    so the copy-out (and all host bookkeeping behind it) overlaps the
    next segment's device compute instead of idling the chip."""
    return sampled_segment(
        lambda cache, toks: decode_step_batched(params, cache, toks, cfg),
        cache, tokens, temps, key, n_steps, greedy,
    )


def sampled_segment(step, cache: Params, tokens: jax.Array, temps: jax.Array,
                    key: jax.Array, n_steps: int, greedy: bool):
    """The sample->feed chain of a decode segment over any one-token step
    ``step(cache, toks [B, 1]) -> (logits [B, V], cache)``: the contiguous
    and the paged decoder's, and a model kind's of its own
    (models/hybrid_ssm.py). The gumbel chain is keyed off ``key`` alone:
    per step, one split shared by every row. Returns ``(toks [B, n_steps],
    last [B, 1], next_key, cache)``."""
    keys = jax.random.split(key, n_steps + 1)
    next_key, gumbel_keys = keys[0], keys[1:]

    def body(carry, step_key):
        cache, toks = carry
        logits, cache = step(cache, toks)
        if greedy:
            z = logits  # all-argmax batch: the [B, V] gumbel would cost
            # ~1.3ms/step at Gemma-2B's vocab for nothing
        else:
            g = jax.random.gumbel(step_key, logits.shape, dtype=logits.dtype)
            z = jnp.where(
                temps[:, None] > 0.0,
                logits / jnp.maximum(temps[:, None], 1e-4) + g,
                logits,
            )
        nxt = jnp.argmax(z, axis=-1).astype(jnp.int32)[:, None]  # [B, 1]
        return (cache, nxt), nxt[:, 0]

    (cache, last), toks = lax.scan(body, (cache, tokens), gumbel_keys)
    return toks.T, last, next_key, cache  # [B, n_steps], [B, 1]


def merge_chain_tokens(
    last: jax.Array,  # [B, 1] device token chain (prior segment's output)
    ids: jax.Array,  # [B] freshly sampled first tokens (prefill output)
    mask: jax.Array,  # [B] bool: True where a row was just prefilled
) -> jax.Array:
    """Graft prefill-sampled first tokens into the device token chain.

    An interleaved prefill used to invalidate the WHOLE chain, forcing
    the next segment's feed back through the host for every row. The
    prefill's first tokens are already on device (`_sample_logits` keeps
    the [B, V] logits there and returns [B] int32 ids), so scattering
    them into ``last`` keeps the chain device-resident across admissions:
    rows untouched by the prefill keep their in-flight segment's output,
    prefilled rows pick up their sampled id — zero host->device traffic
    either way."""
    return jnp.where(mask[:, None], ids[:, None], last)


def prefill_batched(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, S] right-padded prompts
    lengths: jax.Array,  # [B] prompt lengths; 0 = row untouched
    cfg: LlamaConfig,
) -> Tuple[jax.Array, Params]:
    """Consume whole prompts in ONE forward: fills rows' KV cache at
    positions [0, S), sets each active row's pos to its prompt length, and
    returns the logits at each row's LAST prompt token (the first sampled
    token comes from here) — so TTFT is one batched matmul-heavy forward
    instead of `prompt_len` sequential decode steps (round-2 measured
    633ms for a 64-token prompt; the reference only models batching,
    inference_types.go:96-104).

    Rows with ``lengths[b] == 0`` keep their cache and pos untouched, so
    new requests prefill while other rows are mid-decode (continuous
    batching). Padded query positions >= lengths[b] compute garbage that
    is never read: causal attention keeps them out of valid queries, later
    decode steps overwrite their cache slots before pos reaches them.
    """
    B, S = tokens.shape
    hd = cfg.head_dim
    max_s = cache["k"].shape[2]
    active = lengths > 0
    x = gather_embed(params["embed"], tokens).astype(cfg.dtype)  # [B, S, D]
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.dim)
    cos, sin = rope_freqs(cfg, S)
    sel = active[:, None, None, None]

    def body(x, inp):
        lp, ck, cv = inp
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q = apply_rope((h @ deq(lp["wq"])).reshape(B, S, cfg.n_heads, hd), cos, sin)
        k = apply_rope((h @ deq(lp["wk"])).reshape(B, S, cfg.n_kv_heads, hd), cos, sin)
        v = (h @ deq(lp["wv"])).reshape(B, S, cfg.n_kv_heads, hd)
        attn = attention(q, k, v, causal=True)
        x = x + attn.reshape(B, S, cfg.n_heads * hd) @ deq(lp["wo"])
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
        gate = _act(cfg)((h @ deq(lp["w_gate"])).astype(jnp.float32)).astype(h.dtype)
        x = x + (gate * (h @ deq(lp["w_up"]))) @ deq(lp["w_down"])
        # prompts start at position 0 (rows are reset on admission)
        ck = jnp.where(sel, lax.dynamic_update_slice_in_dim(ck, k, 0, axis=1), ck)
        cv = jnp.where(sel, lax.dynamic_update_slice_in_dim(cv, v, 0, axis=1), cv)
        return x, (ck, cv)

    x, (new_k, new_v) = lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"])
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    # head matmul only at each row's last valid position (V is large)
    idx = jnp.maximum(lengths - 1, 0)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]  # [B, D]
    logits = (x_last @ lm_head_of(params, cfg)).astype(jnp.float32)
    pos = jnp.where(active, jnp.minimum(lengths, max_s - 1), cache["pos"])
    return logits, {"k": new_k, "v": new_v, "pos": pos.astype(jnp.int32)}


# ---- prefix KV reuse (serving path) ----------------------------------------

def copy_prefix_into_row(
    cache: Params,
    k: jax.Array,  # [L, P, KV, hd] cached prefix keys (P = padded bucket)
    v: jax.Array,  # [L, P, KV, hd] cached prefix values
    row,  # scalar int: batch row to graft into
    length,  # scalar int: true prefix length (<= P)
) -> Params:
    """Graft a cached prefix's K/V into one batch row at offset 0.

    The serving prefix cache stores device-resident per-layer K/V for
    shared prompt prefixes; on a trie hit the engine copies them into the
    freshly admitted row instead of recomputing them, and prefill then
    consumes only the uncached SUFFIX. A per-row `dynamic_update_slice`
    keeps this O(prefix) HBM traffic (the same idiom as `_row_update`);
    under donation it is an in-place write. The entry is bucket-padded
    (P >= length): the pad tail lands at positions >= pos and is masked
    by the per-row validity until decode overwrites it — the exact
    garbage-beyond-pos contract batched prefill already relies on.
    ``pos`` is set to ``length`` so a decode step between graft and
    suffix prefill cannot write inside the protected prefix span."""
    ck = lax.dynamic_update_slice(cache["k"], k[:, None], (0, row, 0, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v[:, None], (0, row, 0, 0, 0))
    length = jnp.asarray(length, jnp.int32)
    pos = lax.dynamic_update_slice(cache["pos"], length[None], (row,))
    return {"k": ck, "v": cv, "pos": pos}


def extract_prefix_from_row(
    cache: Params, row, p_len: int
) -> Tuple[jax.Array, jax.Array]:
    """Read the first ``p_len`` cached K/V positions of one batch row
    (a new prefix-cache entry, taken after that row's prefill filled
    them). ``p_len`` is STATIC (the engine buckets entry lengths to
    powers of two, so compiles stay bounded); ``row`` is traced. NOT
    donated — the live batched cache must survive the copy."""
    L, _, _, KV, hd = cache["k"].shape
    k = lax.dynamic_slice(
        cache["k"], (0, row, 0, 0, 0), (L, 1, p_len, KV, hd)
    )[:, 0]
    v = lax.dynamic_slice(
        cache["v"], (0, row, 0, 0, 0), (L, 1, p_len, KV, hd)
    )[:, 0]
    return k, v


def prefill_batched_from(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, S] right-padded SUFFIX tokens
    lengths: jax.Array,  # [B] suffix lengths; 0 = row untouched
    starts: jax.Array,  # [B] per-row start offset (cached prefix length)
    cfg: LlamaConfig,
) -> Tuple[jax.Array, Params]:
    """Suffix-only prefill: like :func:`prefill_batched`, but each row's
    prompt tokens occupy GLOBAL positions [starts[b], starts[b]+lengths[b])
    and attend over the K/V already resident in the cache below
    ``starts[b]`` (a prefix grafted by :func:`copy_prefix_into_row`).
    With ``starts == 0`` this is exactly whole-prompt prefill; with a
    cached prefix the prompt cost drops from O(prompt) to O(suffix) —
    the prefix-reuse win for shared-system-prompt serving traffic.

    Differences from the root-prefill path, all per-row:
    - rope runs at global positions ``starts[b] + s`` (gathered tables);
    - K/V write via vmapped `dynamic_update_slice` at ``starts[b]``
      (the `_row_update` idiom decode uses);
    - attention queries the FULL cache row with an offset causal mask
      ``t <= starts[b] + s``, so suffix queries see the grafted prefix.

    Callers must keep ``starts[b] + S <= T`` for active rows (the engine
    drops a graft rather than let the padded write clamp out of place).
    Inactive rows (``lengths[b] == 0``) keep cache and pos untouched.
    """
    B, S = tokens.shape
    hd = cfg.head_dim
    max_s = cache["k"].shape[2]
    active = lengths > 0
    x = gather_embed(params["embed"], tokens).astype(cfg.dtype)  # [B, S, D]
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.dim)
    cos_full, sin_full = rope_freqs(cfg, max_s)  # [T, hd/2]
    # global query positions, clamped so padded rows stay in-table
    posq = jnp.minimum(
        starts[:, None] + jnp.arange(S)[None, :], max_s - 1
    )  # [B, S]
    cos_t = cos_full[posq][:, :, None, :]  # [B, S, 1, hd/2]
    sin_t = sin_full[posq][:, :, None, :]
    # offset causal mask: suffix query s sees cache positions t <= start+s
    mask = (
        jnp.arange(max_s)[None, None, :] <= posq[:, :, None]
    )[:, None, None]  # [B, 1, 1, S, T]
    sel = active[:, None, None, None]

    def rot(t):  # apply_rope with per-row-position tables
        t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [t1 * cos_t - t2 * sin_t, t1 * sin_t + t2 * cos_t], axis=-1
        ).astype(t.dtype)

    def body(x, inp):
        lp, ck, cv = inp  # ck/cv: [B, T, KV, hd] this layer's cache
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q = rot((h @ deq(lp["wq"])).reshape(B, S, cfg.n_heads, hd))
        k = rot((h @ deq(lp["wk"])).reshape(B, S, cfg.n_kv_heads, hd))
        v = (h @ deq(lp["wv"])).reshape(B, S, cfg.n_kv_heads, hd)
        # write the suffix K/V at each row's start (inactive rows keep
        # their cache bit-identical: mid-decode neighbours are sacred)
        ck = jnp.where(sel, _row_update(ck, k, starts), ck)
        cv = jnp.where(sel, _row_update(cv, v, starts), cv)
        attn = attention(q, ck, cv, causal=False, mask=mask)
        x = x + attn.reshape(B, S, cfg.n_heads * hd) @ deq(lp["wo"])
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
        gate = _act(cfg)((h @ deq(lp["w_gate"])).astype(jnp.float32)).astype(h.dtype)
        x = x + (gate * (h @ deq(lp["w_up"]))) @ deq(lp["w_down"])
        return x, (ck, cv)

    x, (new_k, new_v) = lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"])
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    # head matmul only at each row's LAST suffix token (V is large)
    idx = jnp.maximum(lengths - 1, 0)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]  # [B, D]
    logits = (x_last @ lm_head_of(params, cfg)).astype(jnp.float32)
    pos = jnp.where(
        active, jnp.minimum(starts + lengths, max_s - 1), cache["pos"]
    )
    return logits, {"k": new_k, "v": new_v, "pos": pos.astype(jnp.int32)}


# ---- paged KV (block-table serving path) -----------------------------------
#
# The contiguous batched cache stores row b's position t at cache[l, b, t].
# The PAGED cache stores it at pool[l, bt[b, t // BS], t % BS]: the cache is
# a pool of NB fixed-size blocks of BS tokens and each row owns an ordered
# block list (the [B, MB] block table, MB = max_seq // BS). Rows grow block
# by block, so resident HBM tracks tokens actually cached instead of
# max_seq * batch; blocks are refcounted host-side
# (kubedl_tpu.serving.kv_blocks) so prefix-cache entries share blocks by
# reference instead of copying whole prefixes into rows.
#
# Exactness contract (the tier-1 gate): every paged function below computes
# the SAME attention math as its contiguous twin over a gathered
# [B, T, KV, hd] view of the pool, where view position t is logical
# position t. Valid positions (t < pos) hold bit-identical K/V by
# induction; masked positions hold garbage that contributes an exact 0.0
# through the -1e30 mask — the same garbage-beyond-pos contract the
# contiguous path already relies on. Block-table entries a row does not own
# point at block 0 (the trash block): writes from vacant rows, padded
# prefill positions, and budget overshoot land there and are never read.


def init_paged_cache(
    cfg: LlamaConfig, batch: int, max_seq: int, num_blocks: int,
    block_size: int,
) -> Params:
    """Paged serving cache: K/V pools ``[L, NB, BS, KV, hd]`` + per-row
    positions + the ``[B, MB]`` block table (all entries start at the
    trash block 0). ``max_seq`` must be a multiple of ``block_size`` so
    the gathered view is exactly [B, max_seq, KV, hd]. Every paged program
    carries the whole pools through its layer scan and, under donation,
    writes them in place (:func:`_scan_layers_over_pools`)."""
    if max_seq % block_size != 0:
        raise ValueError(
            f"max_seq {max_seq} not a multiple of block_size {block_size}"
        )
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
        "bt": jnp.zeros((batch, max_seq // block_size), jnp.int32),
    }


def _paged_view(pool: jax.Array, layer: jax.Array, bt: jax.Array) -> jax.Array:
    """Gather layer ``layer`` of the pool [L, NB, BS, KV, hd] through the
    block table [B, MB] into the logical [B, MB*BS, KV, hd] view the
    contiguous attention math runs over unchanged. ONE gather with the
    layer in the index: ``pool[layer][bt]`` would first slice the layer's
    whole pool out into a buffer of its own. The index is the block's
    number in the pool seen as ``L * NB`` blocks (a reshape that moves no
    byte), a gather over one axis."""
    L, NB, BS, KV, hd = pool.shape
    B, MB = bt.shape
    blocks = pool.reshape(L * NB, BS, KV, hd)[layer * NB + bt]
    return blocks.reshape(B, MB * BS, KV, hd)


def _attend_over_span(spans: Tuple[int, ...], index: jax.Array,
                      q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      layer: jax.Array, bt: jax.Array,
                      pos: jax.Array) -> jax.Array:
    """Gather attention over the first ``spans[index]`` keys of each row's
    block table. ``spans`` are ascending key counts, whole blocks, the
    last the whole table; ``index`` is :func:`_span_index` of the caller's
    ``live_to``, a traced scalar one past the highest position it will
    READ a result for. One branch of a ``lax.switch`` a span, so one program serves
    every span and only the branch taken runs: the view, the validity
    mask, the scores and the value product follow its length, a
    ``span / max_seq`` share of the whole table's gather and attention.
    Nothing that reaches a result is dropped (a key beyond a row's
    position is masked to an exact 0.0 either way), and a row that stands
    below the span gets the whole table's result to the bit. ``pos`` is
    the position of each query, ``[B]`` (decode) or ``[B, S]``; key ``t``
    is valid for a query at ``pos`` when ``t <= pos``. The row's real
    length (the rope table, the clamp of ``pos``) stays the whole
    table's. Who picks: ``ModelRunner`` (serving/model_runner.py) owns
    the ladder, the engine passes ``live_to`` from its position mirror."""
    BS = k_pool.shape[2]
    posq = pos.reshape(pos.shape[0], -1)  # [B, S]

    def attend(span, q, k_pool, v_pool, layer, bt, posq):
        view_bt = bt[:, :span // BS]
        mask = (jnp.arange(span)[None, None, :] <= posq[:, :, None])
        return attention(
            q, _paged_view(k_pool, layer, view_bt),
            _paged_view(v_pool, layer, view_bt),
            causal=False, mask=mask[:, None, None],
        )

    return lax.switch(
        index, [partial(attend, span) for span in spans],
        q, k_pool, v_pool, layer, bt, posq,
    )


def _span_index(spans: Tuple[int, ...], live_to: jax.Array) -> jax.Array:
    """Which of ``spans`` holds positions ``[0, live_to)``: the smallest
    that does, the last for anything longer."""
    return jnp.sum(live_to > jnp.asarray(spans[:-1], jnp.int32)).astype(jnp.int32)


def _check_spans(spans: Optional[Tuple[int, ...]], bt: jax.Array, BS: int,
                 kv_attention: str, live_to) -> None:
    if spans is None:
        return
    if kv_attention != "gather":
        raise ValueError("spans cut the gathered view: kv_attention='gather'")
    if live_to is None:
        raise ValueError("spans need live_to")
    if (list(spans) != sorted(set(spans)) or any(s <= 0 or s % BS for s in spans)
            or spans[-1] != bt.shape[1] * BS):
        raise ValueError(
            f"spans {spans} must ascend in whole blocks of {BS} up to the "
            f"table's {bt.shape[1] * BS} keys"
        )


def _scan_layers_over_pools(body, x, layers: Params, k_pool, v_pool):
    """The paged programs' layer scan: ``body(x, k_pool, v_pool, lp,
    layer) -> (x, k_pool, v_pool)`` runs once a layer with the WHOLE
    ``[L, NB, BS, KV, hd]`` pools as loop carries beside ``x`` and the
    layer's index as the scanned input. A body writes new K/V by
    ``pool.at[layer, blk, off].set(new)``: on a loop carry (and, at the
    program's edge, a donated argument) XLA scatters in place, so the
    bytes moved are ``new``'s, not the pool's. Scanning the pools in as
    ``xs`` and stacking them out as ``ys`` instead (the contiguous
    cache's scans still do) makes every layer slice its pool out into a
    fresh buffer and write the whole layer back into a new stacked
    array, to store one token a row. Returns ``(x, k_pool, v_pool)``."""
    carry, _ = lax.scan(
        lambda carry, inp: (body(*carry, *inp), None),
        (x, k_pool, v_pool),
        (layers, jnp.arange(k_pool.shape[0], dtype=jnp.int32)),
    )
    return carry


def _check_kv_attention(kv_attention: str) -> None:
    if kv_attention not in ("gather", "blocked"):
        raise ValueError(
            f"kv_attention must be 'gather' or 'blocked', got "
            f"{kv_attention!r}"
        )


def paged_decode_step_batched(
    params: Params, cache: Params, tokens: jax.Array, cfg: LlamaConfig,
    kv_attention: str = "gather",
    spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """Block-table twin of :func:`decode_step_batched`: scatter the new
    K/V into each row's current block at ``(bt[b, pos//BS], pos%BS)``,
    then attend over the gathered view with the identical per-row
    validity mask. Rows whose table entry is unmapped write to the trash
    block (vacant rows keep advancing pos exactly like the contiguous
    path — their writes just land in garbage).

    The pools are carried through the layer scan and written in place
    under donation (:func:`_scan_layers_over_pools`).

    ``kv_attention`` picks the attention implementation: ``"gather"``
    (the bit-exactness oracle and the CPU's path — materialize the logical
    view, dense masked attention) or ``"blocked"``
    (:func:`kubedl_tpu.models.paged_attention.paged_attention` over the
    WHOLE pools with the layer in the index: the same scatter, then an
    online softmax that walks the block table; fp-close,
    greedy-token-identical). On a TPU that is one Pallas kernel a layer
    (``paged_decode_attention``) which fetches, for each row, the blocks
    the row holds and no others, so a step costs the keys read and not a
    span; ``ModelRunner`` runs its decode segments through this arm there
    whatever the option says.

    ``live`` (``[B]`` bool; None: every row) names the rows the dispatch
    scheduled. Any other row writes its K/V to the trash block, is not
    attended by the blocked arm (the kernel fetches nothing for it) and
    computes garbage nobody reads; its ``pos`` advances all the same.

    ``spans`` (static; gather only) with ``live_to`` (traced scalar):
    attend over the smallest span that holds ``live_to``, see
    :func:`_attend_over_span`. The caller promises that every row it will
    READ stands below ``live_to``; any row at or beyond the chosen span
    (one the dispatch did not schedule) computes garbage nobody reads and
    writes its K/V to the trash block, because a view that stops short of
    it says nothing about where it may write. None: the whole table, the
    program this was before it took a span."""
    _check_kv_attention(kv_attention)
    B = tokens.shape[0]
    hd = cfg.head_dim
    pos = cache["pos"]  # [B]
    bt = cache["bt"]  # [B, MB]
    BS = cache["k"].shape[2]
    max_s = bt.shape[1] * BS
    _check_spans(spans, bt, BS, kv_attention, live_to)
    x = gather_embed(params["embed"], tokens).astype(cfg.dtype)  # [B, 1, D]
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.dim)
    cos, sin = rope_freqs(cfg, max_s)
    cos_t = cos[pos][:, None, None, :]
    sin_t = sin[pos][:, None, None, :]
    valid = (jnp.arange(max_s)[None, :] <= pos[:, None])  # [B, T]
    mask = valid[:, None, None, None, :]
    blk = bt[jnp.arange(B), pos // BS]  # [B] current block per row
    if spans is not None:
        span_at = _span_index(spans, live_to)
        blk = jnp.where(pos < jnp.asarray(spans, jnp.int32)[span_at], blk, 0)
    if live is not None:
        blk = jnp.where(live, blk, 0)
    off = pos % BS

    def rot(t):
        t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [t1 * cos_t - t2 * sin_t, t1 * sin_t + t2 * cos_t], axis=-1
        ).astype(t.dtype)

    def body(x, kp, vp, lp, layer):  # kp/vp: [L, NB, BS, KV, hd], whole
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q = rot((h @ deq(lp["wq"])).reshape(B, 1, cfg.n_heads, hd))
        k = rot((h @ deq(lp["wk"])).reshape(B, 1, cfg.n_kv_heads, hd))
        v = (h @ deq(lp["wv"])).reshape(B, 1, cfg.n_kv_heads, hd)
        if kv_attention == "blocked":
            # the same in-place scatter as below, then attention straight
            # from the whole pools at this layer: no view, no slice
            attn, kp, vp = blocked_attention.paged_attention(
                q, kp, vp, bt, pos, layer=layer, live=live,
                new_k=k[:, 0], new_v=v[:, 0],
            )
        else:
            kp = kp.at[layer, blk, off].set(k[:, 0])
            vp = vp.at[layer, blk, off].set(v[:, 0])
            if spans is None:
                attn = attention(
                    q, _paged_view(kp, layer, bt), _paged_view(vp, layer, bt),
                    causal=False, mask=mask,
                )
            else:
                attn = _attend_over_span(
                    spans, span_at, q, kp, vp, layer, bt, pos)
        x = x + attn.reshape(B, 1, cfg.n_heads * hd) @ deq(lp["wo"])
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
        gate = _act(cfg)((h @ deq(lp["w_gate"])).astype(jnp.float32)).astype(h.dtype)
        x = x + (gate * (h @ deq(lp["w_up"]))) @ deq(lp["w_down"])
        return x, kp, vp

    x, new_k, new_v = _scan_layers_over_pools(
        body, x, params["layers"], cache["k"], cache["v"]
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    logits = (x[:, 0] @ lm_head_of(params, cfg)).astype(jnp.float32)
    return logits, {
        "k": new_k,
        "v": new_v,
        "pos": jnp.minimum(pos + 1, max_s - 1),
        "bt": bt,
    }


def paged_decode_segment(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, 1] first input token per row
    temps: jax.Array,  # [B] sampling temperature; <= 0 = greedy
    key: jax.Array,
    cfg: LlamaConfig,
    n_steps: int,
    greedy: bool = False,
    kv_attention: str = "gather",
    spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Params]:
    """Block-table twin of :func:`decode_segment` — same on-device
    sample->feed chain and return contract, over the paged step. The
    engine reserves blocks covering ``pos + n_steps`` for every decoding
    row BEFORE dispatch, so in-segment writes never need a host trip.
    ``spans``, ``live_to`` and ``live`` as in
    :func:`paged_decode_step_batched`: ``live_to`` holds ``pos + n_steps``
    of every row whose tokens are read, ``live`` marks those rows.

    The gumbel sample chain is keyed off ``key`` alone — per step, one
    split shared by every row — so for a fixed seed the sampled path is
    deterministic and IDENTICAL across ``kv_attention`` kernels (the
    regression gate for the blocked kernel: kernel choice may only
    perturb logits at fp tolerance, never the randomness)."""
    return sampled_segment(
        lambda cache, toks: paged_decode_step_batched(
            params, cache, toks, cfg, kv_attention=kv_attention,
            spans=spans, live_to=live_to, live=live,
        ),
        cache, tokens, temps, key, n_steps, greedy,
    )


def _advance_pos(
    pos: jax.Array, rows: Optional[jax.Array], active: jax.Array,
    new: jax.Array, max_s: int,
) -> jax.Array:
    """Per-row positions after a prefill: ``new`` (clamped) at the active
    rows of the batch, unchanged everywhere else. ``rows`` None: the
    batch is every cache row; else only ``pos[rows]`` can move."""
    new = jnp.minimum(new, max_s - 1).astype(jnp.int32)
    if rows is None:
        return jnp.where(active, new, pos)
    return pos.at[rows].set(jnp.where(active, new, pos[rows]))


def _paged_suffix_forward(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, S] right-padded suffix tokens
    lengths: jax.Array,  # [B] suffix lengths; 0 = row untouched
    starts: jax.Array,  # [B] per-row global start offset
    cfg: LlamaConfig,
    kv_attention: str = "gather",
    self_contained: bool = False,
    positions: Optional[jax.Array] = None,  # [B, S] per-token positions
    self_mask: Optional[jax.Array] = None,  # [B, S, S] in-suffix mask
    rows: Optional[jax.Array] = None,  # [B] cache rows of a compact batch
    spans: Optional[Tuple[int, ...]] = None,  # the view's spans, in keys
    live_to: Optional[jax.Array] = None,  # scalar: picks one of ``spans``
) -> Tuple[jax.Array, Params]:
    """Shared body of paged prefill and speculative verify: run suffix
    tokens at global positions ``starts[b] + s`` against the gathered
    cache view (offset causal mask, same math as
    :func:`prefill_batched_from`), scattering their K/V into each row's
    blocks. Pad positions (``s >= lengths[b]``) and inactive rows route
    their writes to the trash block — which retires the contiguous
    path's dispatch-time graft-overflow fixup for paged engines: a
    clamped write can only ever land in garbage, never inside a row.
    Returns (final-norm hidden states [B, S, D], updated cache).

    The pools are carried through the layer scan and written in place
    under donation (:func:`_scan_layers_over_pools`); the read-only mode
    carries them through untouched.

    ``self_contained=True`` is the READ-ONLY scoring mode behind
    :func:`paged_verify_multi`: the pool is never written (so several
    candidate suffixes can share one row's blocks in a single forward)
    — each query attends committed pool history (``t < starts``) merged
    with the suffix's own fresh K/V under an in-suffix causal mask,
    which is the same key set the write path would have seen. The
    returned cache is the input cache, untouched.

    ``positions`` overrides the default consecutive position layout
    ``starts[b] + s`` — the tree-verify hook, where several trie nodes
    share a depth (and so a RoPE angle). ``self_mask[b, s, t]`` replaces
    the in-suffix causal block with an arbitrary visibility mask (the
    trie's ancestor mask). Both are read-only-mode-only: the write path
    demands consecutive causal suffixes.

    ``rows`` makes the batch COMPACT: ``tokens``/``lengths``/``starts``
    describe only the cache rows listed (distinct indices), each read and
    written through its own block table ``bt[rows[b]]``; every other
    row's blocks and ``pos`` are left as they were. The same mathematics
    on fewer rows — prefill computes the rows that hold a prompt. None
    (the verify entry points) means every cache row, in order.

    ``spans`` (static; the gather write path only) with ``live_to``
    (traced scalar): attend over the smallest span that holds ``live_to``,
    which must hold ``starts + S`` of every active row
    (:func:`_attend_over_span`). Writes go through the whole table as
    ever (pad and inactive positions to the trash block)."""
    _check_kv_attention(kv_attention)
    if (positions is not None or self_mask is not None) \
            and not self_contained:
        raise ValueError(
            "positions/self_mask require self_contained=True"
        )
    if spans is not None and self_contained:
        raise ValueError("spans are the write path's: self_contained=False")
    B, S = tokens.shape
    hd = cfg.head_dim
    bt = cache["bt"] if rows is None else cache["bt"][rows]  # [B, MB]
    BS = cache["k"].shape[2]
    max_s = bt.shape[1] * BS
    _check_spans(spans, bt, BS, kv_attention, live_to)
    span_at = None if spans is None else _span_index(spans, live_to)
    active = lengths > 0
    x = gather_embed(params["embed"], tokens).astype(cfg.dtype)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.dim)
    cos_full, sin_full = rope_freqs(cfg, max_s)
    if positions is None:
        posq = jnp.minimum(
            starts[:, None] + jnp.arange(S)[None, :], max_s - 1
        )  # [B, S]
    else:
        posq = jnp.minimum(positions, max_s - 1)
    cos_t = cos_full[posq][:, :, None, :]
    sin_t = sin_full[posq][:, :, None, :]
    if self_contained:
        # pool history (t < starts) ++ in-suffix causal block: the same
        # key set the write path exposes, without the writes
        hist = jnp.broadcast_to(
            jnp.arange(max_s)[None, None, :] < starts[:, None, None],
            (B, S, max_s),
        )
        if self_mask is None:
            causal_self = jnp.broadcast_to(
                (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None],
                (B, S, S),
            )
        else:
            causal_self = self_mask
        mask = jnp.concatenate([hist, causal_self], axis=-1)[:, None, None]
    else:
        mask = (
            jnp.arange(max_s)[None, None, :] <= posq[:, :, None]
        )[:, None, None]  # [B, 1, 1, S, T]
    # scatter targets: pad/inactive positions write to the trash block
    writable = active[:, None] & (jnp.arange(S)[None, :] < lengths[:, None])
    blk = jnp.where(writable, bt[jnp.arange(B)[:, None], posq // BS], 0)
    off = posq % BS

    def rot(t):
        t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [t1 * cos_t - t2 * sin_t, t1 * sin_t + t2 * cos_t], axis=-1
        ).astype(t.dtype)

    def body(x, kp, vp, lp, layer):  # kp/vp: [L, NB, BS, KV, hd], whole
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q = rot((h @ deq(lp["wq"])).reshape(B, S, cfg.n_heads, hd))
        k = rot((h @ deq(lp["wk"])).reshape(B, S, cfg.n_kv_heads, hd))
        v = (h @ deq(lp["wv"])).reshape(B, S, cfg.n_kv_heads, hd)
        if not self_contained:
            kp = kp.at[layer, blk, off].set(k)
            vp = vp.at[layer, blk, off].set(v)
        if kv_attention == "blocked":
            # the blocked attention walks ONE layer's pool
            attn = blocked_attention.paged_attention(
                q, lax.dynamic_index_in_dim(kp, layer, keepdims=False),
                lax.dynamic_index_in_dim(vp, layer, keepdims=False),
                bt, starts,
                self_k=k if self_contained else None,
                self_v=v if self_contained else None,
                self_mask=self_mask,
            )
        elif self_contained:
            attn = attention(
                q,
                jnp.concatenate([_paged_view(kp, layer, bt), k], axis=1),
                jnp.concatenate([_paged_view(vp, layer, bt), v], axis=1),
                causal=False, mask=mask,
            )
        elif spans is None:
            attn = attention(
                q, _paged_view(kp, layer, bt), _paged_view(vp, layer, bt),
                causal=False, mask=mask,
            )
        else:
            attn = _attend_over_span(
                spans, span_at, q, kp, vp, layer, bt, posq)
        x = x + attn.reshape(B, S, cfg.n_heads * hd) @ deq(lp["wo"])
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
        gate = _act(cfg)((h @ deq(lp["w_gate"])).astype(jnp.float32)).astype(h.dtype)
        x = x + (gate * (h @ deq(lp["w_up"]))) @ deq(lp["w_down"])
        return x, kp, vp

    x, new_k, new_v = _scan_layers_over_pools(
        body, x, params["layers"], cache["k"], cache["v"]
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    if self_contained:
        return x, cache
    return x, {
        "k": new_k, "v": new_v, "bt": cache["bt"],
        "pos": _advance_pos(cache["pos"], rows, active, starts + lengths,
                            max_s),
    }


def paged_prefill_batched(
    params: Params,
    cache: Params,
    tokens: jax.Array,
    lengths: jax.Array,
    cfg: LlamaConfig,
    rows: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """Block-table twin of :func:`prefill_batched` (whole prompts from
    position 0): last-token logits + updated cache. ``rows`` as in
    :func:`_paged_suffix_forward`: a compact batch of those cache rows.

    NOT routed through the suffix forward: prompts starting at 0 attend
    only to their own fresh K/V, so this mirrors `prefill_batched`'s
    LOCAL causal attention — identical ops on identical inputs, which is
    what makes the tier-1 bit-identity gate hold for the prefill leg —
    and only the cache WRITE differs (scatter into blocks instead of a
    contiguous row update)."""
    B, S = tokens.shape
    hd = cfg.head_dim
    bt = cache["bt"] if rows is None else cache["bt"][rows]
    BS = cache["k"].shape[2]
    max_s = bt.shape[1] * BS
    active = lengths > 0
    x = gather_embed(params["embed"], tokens).astype(cfg.dtype)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.dim)
    cos, sin = rope_freqs(cfg, S)
    posw = jnp.minimum(jnp.arange(S), max_s - 1)
    writable = active[:, None] & (jnp.arange(S)[None, :] < lengths[:, None])
    blk = jnp.where(writable, bt[:, posw // BS], 0)  # [B, S]
    off = jnp.broadcast_to((posw % BS)[None, :], (B, S))

    def body(x, kp, vp, lp, layer):  # kp/vp: [L, NB, BS, KV, hd], whole
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q = apply_rope((h @ deq(lp["wq"])).reshape(B, S, cfg.n_heads, hd), cos, sin)
        k = apply_rope((h @ deq(lp["wk"])).reshape(B, S, cfg.n_kv_heads, hd), cos, sin)
        v = (h @ deq(lp["wv"])).reshape(B, S, cfg.n_kv_heads, hd)
        attn = attention(q, k, v, causal=True)
        x = x + attn.reshape(B, S, cfg.n_heads * hd) @ deq(lp["wo"])
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
        gate = _act(cfg)((h @ deq(lp["w_gate"])).astype(jnp.float32)).astype(h.dtype)
        x = x + (gate * (h @ deq(lp["w_up"]))) @ deq(lp["w_down"])
        return (x, kp.at[layer, blk, off].set(k),
                vp.at[layer, blk, off].set(v))

    x, new_k, new_v = _scan_layers_over_pools(
        body, x, params["layers"], cache["k"], cache["v"]
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    idx = jnp.maximum(lengths - 1, 0)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    logits = (x_last @ lm_head_of(params, cfg)).astype(jnp.float32)
    return logits, {
        "k": new_k, "v": new_v, "bt": cache["bt"],
        "pos": _advance_pos(cache["pos"], rows, active, lengths, max_s),
    }


def paged_prefill_from(
    params: Params,
    cache: Params,
    tokens: jax.Array,
    lengths: jax.Array,
    starts: jax.Array,
    cfg: LlamaConfig,
    kv_attention: str = "gather",
    rows: Optional[jax.Array] = None,
    spans: Optional[Tuple[int, ...]] = None,
    live_to: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """Block-table twin of :func:`prefill_batched_from` (suffix-only
    prefill over a grafted prefix): last-token logits + updated cache.
    ``rows``, ``spans`` and ``live_to`` as in
    :func:`_paged_suffix_forward`: a compact batch, over a view that
    stops at the span holding ``live_to``."""
    x, cache = _paged_suffix_forward(
        params, cache, tokens, lengths, starts, cfg,
        kv_attention=kv_attention, rows=rows, spans=spans, live_to=live_to,
    )
    idx = jnp.maximum(lengths - 1, 0)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    logits = (x_last @ lm_head_of(params, cfg)).astype(jnp.float32)
    return logits, cache


def paged_verify(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, S]: [last accepted token, draft_1..draft_k]
    lengths: jax.Array,  # [B] k+1 for verifying rows, 0 = untouched
    starts: jax.Array,  # [B] row position before the verify
    cfg: LlamaConfig,
    kv_attention: str = "gather",
) -> Tuple[jax.Array, Params]:
    """Speculative verify: score a draft-extended suffix in ONE forward
    and return the target model's GREEDY token after every position —
    ``ids[b, j]`` is the argmax continuation after consuming
    ``tokens[b, j]``. The host accepts the longest prefix where
    ``draft_j == ids[:, j-1]`` plus the bonus token ``ids[:, a]``; greedy
    acceptance is exact by construction because every emitted token is
    the target's own argmax given only accepted history. Rejected-suffix
    KV stays in the row's blocks as garbage beyond the rolled-back pos
    (the engine rewinds its host pos mirror and frees now-unneeded
    blocks)."""
    x, cache = _paged_suffix_forward(
        params, cache, tokens, lengths, starts, cfg,
        kv_attention=kv_attention,
    )
    logits = (x @ lm_head_of(params, cfg)).astype(jnp.float32)  # [B, S, V]
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return ids, cache


def paged_verify_multi(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, N, S]: N candidate suffixes per row
    lengths: jax.Array,  # [B] suffix length (shared by a row's candidates)
    starts: jax.Array,  # [B] row position before the verify
    cfg: LlamaConfig,
    kv_attention: str = "gather",
) -> jax.Array:
    """Score N candidate continuations per row in ONE read-only forward:
    returns the target's greedy ids ``[B, N, S]`` (``ids[b, n, j]`` =
    argmax after consuming ``tokens[b, n, j]``). Candidates are flattened
    to ``B*N`` rows SHARING each row's block table and start — legal only
    because the self-contained suffix forward never writes the pool, so
    candidate n cannot leak K/V into candidate m's view. The host picks
    the candidate with the longest agreeing prefix and re-runs the
    standard write-path :func:`paged_verify` on the winner alone, which
    keeps every emitted token the target's own argmax over committed
    history (bit-exact vs the single-candidate path). No cache is
    returned: with nothing donated, XLA drops all cache updates."""
    B, N, S = tokens.shape
    rep = lambda a: jnp.repeat(a, N, axis=0)  # noqa: E731
    flat_cache = {
        "k": cache["k"], "v": cache["v"],
        "pos": rep(cache["pos"]), "bt": rep(cache["bt"]),
    }
    x, _ = _paged_suffix_forward(
        params, flat_cache, tokens.reshape(B * N, S), rep(lengths),
        rep(starts), cfg, kv_attention=kv_attention, self_contained=True,
    )
    logits = (x @ lm_head_of(params, cfg)).astype(jnp.float32)
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return ids.reshape(B, N, S)


def paged_verify_tree(
    params: Params,
    cache: Params,
    tokens: jax.Array,  # [B, M] trie-node tokens (node 0 = last accepted)
    positions: jax.Array,  # [B, M] global position of each node
    tree_mask: jax.Array,  # [B, M, M] bool: node m sees node t
    lengths: jax.Array,  # [B] live node count; 0 = row untouched
    starts: jax.Array,  # [B] row position before the verify
    cfg: LlamaConfig,
    kv_attention: str = "gather",
) -> jax.Array:
    """Score a prefix-trie of draft continuations in ONE read-only
    forward: returns the target's greedy ids ``[B, M]`` — ``ids[b, m]``
    is the argmax continuation after consuming trie node m along its
    root path. The trie generalizes :func:`paged_verify_multi`'s flat
    candidate list: candidates sharing a prefix share nodes, so the
    verify window is the trie size M, not candidates x depth.

    ``tree_mask[b, m, t]`` must be True exactly when t is m itself or an
    ancestor of m, and ``positions[b, m] = starts[b] + depth(m)`` (node
    0, the last accepted token, sits at depth 0). Under that mask each
    node attends committed pool history plus its own root path — the
    identical key set a chain verify of that path would see, so a
    single-chain trie reproduces :func:`paged_verify` bit-exactly. The
    host walks the deepest accepted path and re-runs the write-path
    verify on it alone; like multi-verify, nothing here writes the pool
    and no cache is returned."""
    x, _ = _paged_suffix_forward(
        params, cache, tokens, lengths, starts, cfg,
        kv_attention=kv_attention, self_contained=True,
        positions=positions, self_mask=tree_mask,
    )
    logits = (x @ lm_head_of(params, cfg)).astype(jnp.float32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, M]


def copy_kv_block(cache: Params, src, dst) -> Params:
    """Copy one block's K/V across all layers (``src`` -> ``dst``, traced
    scalars: one compile total). The copy-on-write primitive: the engine
    calls it when a row must append inside a SHARED block — the partial
    tail of a grafted prefix — so the write lands in a private copy and
    the prefix entry's block stays immutable for its other readers."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return {
        "k": cache["k"].at[:, dst].set(cache["k"][:, src]),
        "v": cache["v"].at[:, dst].set(cache["v"][:, src]),
        "pos": cache["pos"],
        "bt": cache["bt"],
    }


def paged_graft_prefix(
    cache: Params,
    k: jax.Array,  # [L, P, KV, hd] array-payload prefix entry (padded)
    v: jax.Array,
    row,  # scalar int: batch row to graft into
    length,  # scalar int: true prefix length (<= P)
) -> Params:
    """Array-payload twin of :func:`copy_prefix_into_row` for paged rows:
    scatter a prefix entry's K/V into ``row``'s blocks at positions
    [0, P) and set pos to ``length``. Block-ref entries never need this
    (the engine splices the block table host-side at zero device cost);
    it exists for entries holding materialized arrays — e.g. inserted by
    tests or migrated from a contiguous engine. Pad positions beyond the
    row's allocated blocks hit trash-block table entries and vanish."""
    L, P, KV, hd = k.shape
    bt = cache["bt"]
    BS = cache["k"].shape[2]
    posw = jnp.minimum(jnp.arange(P), bt.shape[1] * BS - 1)
    blk = bt[row][posw // BS]  # [P]
    off = posw % BS
    length = jnp.asarray(length, jnp.int32)
    pos = lax.dynamic_update_slice(cache["pos"], length[None], (row,))
    return {
        "k": cache["k"].at[:, blk, off].set(k),
        "v": cache["v"].at[:, blk, off].set(v),
        "pos": pos,
        "bt": bt,
    }


def export_kv_blocks(
    cache: Params, blocks
) -> Tuple[jax.Array, jax.Array]:
    """Gather ``blocks``' K/V payloads out of the pool for a disaggregated
    handoff: (k, v) each [L, n_blocks, BS, KV, hd]. A fresh gather, not a
    view — the result stays valid after the source cache is donated into
    later dispatches or the blocks are freed back to the allocator."""
    idx = jnp.asarray(blocks, jnp.int32)
    return cache["k"][:, idx], cache["v"][:, idx]


def import_kv_blocks(cache: Params, k, v, blocks) -> Params:
    """Scatter a handoff's K/V payloads ([L, n, BS, KV, hd]) into ``blocks``
    of the adopting engine's pool. Inverse of :func:`export_kv_blocks`; the
    block ids come from the adopter's OWN allocator — block numbering never
    survives the transfer, only payloads and the logical table order do."""
    idx = jnp.asarray(blocks, jnp.int32)
    return {
        "k": cache["k"].at[:, idx].set(jnp.asarray(k, cache["k"].dtype)),
        "v": cache["v"].at[:, idx].set(jnp.asarray(v, cache["v"].dtype)),
        "pos": cache["pos"],
        "bt": cache["bt"],
    }


def decode_step(
    params: Params, cache: Params, tokens: jax.Array, cfg: LlamaConfig
) -> Tuple[jax.Array, Params]:
    """One decode step: tokens [B, 1] -> (logits [B, V], updated cache).

    Static shapes throughout (cache is pre-allocated to max_seq) so the step
    compiles once and never re-traces — the XLA serving requirement.
    """
    B = tokens.shape[0]
    hd = cfg.head_dim
    pos = cache["pos"]
    x = gather_embed(params["embed"], tokens).astype(cfg.dtype)  # [B, 1, D]
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.dim)
    cos, sin = rope_freqs(cfg, cfg.max_seq)
    cos_t = lax.dynamic_slice_in_dim(cos, pos, 1)
    sin_t = lax.dynamic_slice_in_dim(sin, pos, 1)
    max_s = cache["k"].shape[2]
    valid = (jnp.arange(max_s) <= pos)[None, None, None, :]  # [1,1,1,T]

    new_k, new_v = [], []
    for layer in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[layer], params["layers"])
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q = (h @ deq(lp["wq"])).reshape(B, 1, cfg.n_heads, hd)
        k = (h @ deq(lp["wk"])).reshape(B, 1, cfg.n_kv_heads, hd)
        v = (h @ deq(lp["wv"])).reshape(B, 1, cfg.n_kv_heads, hd)
        q = apply_rope(q, cos_t, sin_t)
        k = apply_rope(k, cos_t, sin_t)
        ck = lax.dynamic_update_slice_in_dim(cache["k"][layer], k, pos, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"][layer], v, pos, axis=1)
        new_k.append(ck)
        new_v.append(cv)
        attn = attention(q, ck, cv, causal=False, mask=valid)
        x = x + attn.reshape(B, 1, cfg.n_heads * hd) @ deq(lp["wo"])
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
        gate = _act(cfg)((h @ deq(lp["w_gate"])).astype(jnp.float32)).astype(h.dtype)
        x = x + (gate * (h @ deq(lp["w_up"]))) @ deq(lp["w_down"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    logits = (x[:, 0] @ lm_head_of(params, cfg)).astype(jnp.float32)
    cache = {
        "k": jnp.stack(new_k),
        "v": jnp.stack(new_v),
        "pos": pos + 1,
    }
    return logits, cache
