"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context training support the reference lacks entirely (SURVEY.md §5
"Long-context / sequence parallelism: absent") but is first-class here: the
sequence dim is sharded over an ``sp`` mesh axis, each device holds one
query block, and K/V blocks rotate around the ring via `lax.ppermute` while
an online-softmax accumulator (the flash-attention recurrence) folds each
visiting block in. Peak memory per device is O(S/n * S/n) scores instead of
O(S^2), and the K/V transfer rides ICI neighbor links — the collective
pattern ring attention was designed around (PAPERS.md: Ring Attention with
Blockwise Transformers; blockwise parallel transformer recurrence).

Numerics: fp32 scores/accumulator, bf16 inputs — matches the dense oracle
`kubedl_tpu.models.llama.attention` to ~1e-2 in bf16, ~1e-5 in fp32.

Use inside `shard_map` (the trainer wires this via
`make_context_attention`); RoPE must already be applied with *global*
positions — under jit the caller's rope sees global S, so this holds for
free.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_scores(
    q: jax.Array,  # [B, Sq, H, hd] (already grouped-up for GQA)
    k: jax.Array,  # [B, Sk, H, hd]
    scale: float,
) -> jax.Array:
    return jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale


def ring_attention(
    q: jax.Array,  # [B, S_local, H, hd]
    k: jax.Array,  # [B, S_local, KV, hd]
    v: jax.Array,  # [B, S_local, KV, hd]
    axis_name: str,
    causal: bool = True,
) -> jax.Array:
    """Blockwise ring attention over ``axis_name`` (call under shard_map).

    GQA K/V are repeated up to H heads per block before the score matmul;
    the pallas flash kernel is the fused single-chip analogue
    (kubedl_tpu.ops), this is the cross-chip layer above it.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Sl, H, hd = q.shape
    KV = k.shape[2]
    if H != KV:
        group = H // KV
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scale = 1.0 / math.sqrt(hd)
    rows = idx * Sl + jnp.arange(Sl)  # global query positions

    acc0 = jnp.zeros((B, H, Sl, hd), jnp.float32)
    m0 = jnp.full((B, H, Sl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sl), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        acc, m, l, k_blk, v_blk = carry
        j = (idx - t) % n  # which global block this k/v shard is
        s = _block_scores(q, k_blk, scale)  # [B, H, Sl, Sl]
        if causal:
            cols = j * Sl + jnp.arange(Sl)
            mask = rows[:, None] >= cols[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # fully-masked-so-far rows keep m at NEG_INF; exp() stays finite
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhst,bthd->bhsd", p.astype(v_blk.dtype), v_blk)
        acc = acc * corr[..., None] + pv.astype(jnp.float32)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (acc, m_new, l, k_blk, v_blk), None

    (acc, _, l, _, _), _ = lax.scan(
        tick, (acc0, m0, l0, k, v), jnp.arange(n)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, H, Sl, hd]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,  # [B, S_local, H, hd]
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
) -> jax.Array:
    """DeepSpeed-Ulysses-style sequence parallelism: one `all_to_all`
    re-shards seq-sharded/head-replicated tensors into seq-replicated/
    head-sharded, dense attention runs locally per head group, and a second
    all_to_all restores sequence sharding. One collective round-trip instead
    of a ring of n-1 ppermutes — better when heads >= axis size and the
    sequence still fits per-device (PAPERS.md: Ulysses). Requires H and KV
    divisible by the axis size.
    """
    from kubedl_tpu.models.llama import attention

    a2a = partial(
        lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    q, k, v = a2a(q), a2a(k), a2a(v)  # [B, S, H/n, hd]
    out = attention(q, k, v, causal=causal)
    # restore: split S back out, concatenate heads
    return lax.all_to_all(
        out, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def make_context_attention(
    mesh: Mesh,
    sp_axis: str = "sp",
    batch_axes: Tuple[str, ...] = ("replica", "data", "fsdp"),
    head_axis: str = "tensor",
    impl: str = "ring",
    causal: bool = True,
):
    """Wrap ring/ulysses attention in shard_map for use inside a jitted
    forward (the trainer passes the result as ``attn_fn`` to llama_forward).

    Returns None if the mesh has no ``sp_axis`` (caller falls back to dense
    attention — XLA shards that fine without sequence parallelism).
    """
    if impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown context-parallel impl {impl!r}; "
                         "expected 'ring' or 'ulysses'")
    if sp_axis not in mesh.axis_names or mesh.shape[sp_axis] <= 1:
        return None
    from jax import shard_map

    bt = tuple(a for a in batch_axes if a in mesh.axis_names)
    ht = head_axis if head_axis in mesh.axis_names else None
    spec = P(bt if bt else None, sp_axis, ht, None)
    fn = ring_attention if impl == "ring" else ulysses_attention
    inner = shard_map(
        partial(fn, axis_name=sp_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    sharding = NamedSharding(mesh, spec)

    make_causal = causal

    def attn_fn(q, k, v, causal=None, mask=None):  # llama.attention signature
        # the ring recurrence is specialized at build time — reject silent
        # divergence from the requested semantics (None = build-time value)
        if mask is not None:
            raise ValueError(
                "ring/ulysses attention does not support arbitrary masks; "
                "use the dense oracle or flash_attention for masked paths"
            )
        if causal is not None and causal != make_causal:
            raise ValueError(
                f"context attention was built with causal={make_causal}; "
                f"got causal={causal} at call time"
            )
        q = lax.with_sharding_constraint(q, sharding)
        k = lax.with_sharding_constraint(k, sharding)
        v = lax.with_sharding_constraint(v, sharding)
        return inner(q, k, v)

    return attn_fn
