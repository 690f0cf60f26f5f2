"""Blocked paged attention: attend against the KV block pool directly.

The gather path (`llama._paged_view`) materializes the full logical
``[B, MB*BS, KV, hd]`` view of every row's cache via ``pool[bt]`` before
running dense attention — at high decode concurrency that gather is pure
data movement and dominates step time (ROADMAP item 2). This module walks
the block table instead: a flash-style online-softmax recurrence folds the
pool in ``tile``-sized chunks of blocks, so the logical view never exists
and the garbage in unowned/trash blocks contributes an exact 0.0 through
the same -1e30 mask contract the gather path relies on.

Two implementations behind ONE interface (:func:`paged_attention`):

- ``lax``: a `lax.scan` over chunks of C blocks (C = the largest divisor
  of MB with C*BS <= tile keys). Chunking is what makes this a win — a
  one-block-per-step scan loses to the gather at production block sizes
  (BS=16/32) because scan-iteration overhead swamps the per-block math;
  at tile=256 the chunked scan beats the gather at every benched shape.
  Runs everywhere (tier-1 exercises it on CPU).
- ``pallas``: a TPU kernel on grid (B, row tiles, MB) with the block
  table and per-row starts as scalar-prefetch operands, so the BlockSpec
  index map streams exactly each row's own pool blocks through VMEM — no
  gather, no logical view, O(tile) live keys. A grid step takes one
  block for all kv heads as a lane-dense [BS, KV*hd] tile. Interpret mode
  (``interpret=True``) covers CPU parity tests.

Numerics: the online softmax reorders the reduction, so outputs are
fp-close (observed ~4e-7 f32) but NOT bit-identical to the gather+dense
oracle. The engine therefore defaults to ``kv_attention="gather"`` (the
tier-1 bit-exactness oracle) and selects ``"blocked"`` as the opt-in fast
path; greedy decode chains are token-identical in tier-1 either way.

Masking contract (matches ``llama._paged_suffix_forward``): query s of
row b sits at global position ``posq = min(starts[b] + s, max_s - 1)``
and attends pool keys at positions ``t <= posq``. With ``self_k``/
``self_v`` (the read-only multi-candidate verify), pool keys are history
only (``t < starts[b]``) and the fresh suffix K/V are folded as one extra
online-softmax step under an in-suffix mask — the pool is never
written, which is what lets XLA drop the scatter entirely. The default
in-suffix mask is causal; ``self_mask`` (a [B, S, S] bool, True = key
visible) overrides it for tree-structured verification where node s may
only see its trie ancestors.

Fused KV-write (decode, S=1): passing ``new_k``/``new_v`` ([B, KV, hd],
this step's K/V) makes :func:`paged_attention` write them into each
row's current pool block at ``(bt[b, starts//BS], starts % BS)`` inside
the same call and return ``(out, k_pool, v_pool)`` — retiring the
separate per-layer scatter dispatch the decode step used to pay. The
lax path folds the scatter in front of the chunk scan (identical ops to
the old scatter-then-attend call-site sequence, so bit-identical); the
pallas kernel aliases the pools in/out and patches the written row in
VMEM at the write block, so the fresh token is attended from the
patched tile and only the ONE dirty block per row is copied back to
HBM.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30
#: exp2 domain in the pallas kernel (same rationale as ops.flash_attention:
#: the VPU's transcendental unit is a 2^x evaluator).
LOG2E = math.log2(math.e)

#: default key-tile width (keys folded per lax-scan step). 256 is the
#: measured CPU sweet spot for BS=16/32; the pallas kernel tiles by BS.
DEFAULT_TILE = 256

#: trace-time counters per implementation — bench asserts the blocked
#: path is actually in the compiled hot graph, not silently the oracle.
#: "fused" counts paged_attention calls that carried the decode step's
#: K/V write (either implementation).
TRACE_COUNT = {"lax": 0, "pallas": 0, "fused": 0}


def blocks_per_chunk(num_blocks: int, block_size: int,
                     tile: int = DEFAULT_TILE) -> int:
    """Largest divisor C of ``num_blocks`` with C*block_size <= tile
    (>= 1 even when a single block exceeds the tile)."""
    best = 1
    for c in range(1, num_blocks + 1):
        if num_blocks % c == 0 and c * block_size <= tile:
            best = c
    return best


def _online_fold(m, l, acc, s, vb, einsum_pv: str):
    """One online-softmax step: fold masked scores ``s`` (-1e30 where
    invalid) and values ``vb`` into the running (max, sum, acc) triple.
    The -1e29 clamp makes a FULLY-masked chunk contribute exact zeros
    (p = exp(-1e30 + 1e29) underflows to 0.0) instead of the classic
    exp(-1e30 - (-1e30)) = 1 poisoning — reachable in self_k mode where
    a row with starts=0 has no pool history at all."""
    m_new = jnp.maximum(jnp.maximum(m, s.max(axis=-1)), -1e29)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(einsum_pv, p, vb)
    return m_new, l_new, acc_new


def _lax_paged_attention(
    q: jax.Array,  # [B, S, H, hd]
    k_pool: jax.Array,  # [NB, BS, KV, hd]
    v_pool: jax.Array,
    bt: jax.Array,  # [B, MB] int32
    starts: jax.Array,  # [B] int32 (decode: pos; suffix: row start)
    self_k: Optional[jax.Array],  # [B, S, KV, hd] fresh suffix K (or None)
    self_v: Optional[jax.Array],
    tile: int,
    self_mask: Optional[jax.Array] = None,  # [B, S, S] bool (tree verify)
) -> jax.Array:
    TRACE_COUNT["lax"] += 1
    B, S, H, hd = q.shape
    BS, KV = k_pool.shape[1], k_pool.shape[2]
    MB = bt.shape[1]
    max_s = MB * BS
    group = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, group, hd).astype(jnp.float32)
    posq = jnp.minimum(starts[:, None] + jnp.arange(S)[None, :], max_s - 1)
    C = blocks_per_chunk(MB, BS, tile)
    NC = MB // C
    btc = bt.reshape(B, NC, C)

    def body(carry, inp):
        btj, c = inp  # btj [B, C], c scalar chunk index
        kb = k_pool[btj].reshape(B, C * BS, KV, hd).astype(jnp.float32)
        vb = v_pool[btj].reshape(B, C * BS, KV, hd).astype(jnp.float32)
        s = jnp.einsum("bskgh,btkh->bkgst", qg, kb) * scale
        t = c * (C * BS) + jnp.arange(C * BS)
        if self_k is None:
            valid = t[None, None, :] <= posq[:, :, None]  # [B, S, C*BS]
        else:
            # read-only mode: pool keys are committed history only
            valid = jnp.broadcast_to(
                t[None, None, :] < starts[:, None, None], (B, S, C * BS)
            )
        s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
        return _online_fold(*carry, s, vb, "bkgst,btkh->bkgsh"), None

    m0 = jnp.full((B, KV, group, S), -1e29, jnp.float32)
    l0 = jnp.zeros_like(m0)
    a0 = jnp.zeros((B, KV, group, S, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, a0), (btc.transpose(1, 0, 2), jnp.arange(NC))
    )
    if self_k is not None:
        kb = self_k.reshape(B, S, KV, hd).astype(jnp.float32)
        vb = self_v.reshape(B, S, KV, hd).astype(jnp.float32)
        s = jnp.einsum("bskgh,btkh->bkgst", qg, kb) * scale  # [B,KV,G,S,S]
        if self_mask is None:
            causal = (
                jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
            )  # [Sq, Sk]
            s = jnp.where(causal[None, None, None], s, NEG_INF)
        else:
            # tree verify: node s sees exactly its trie ancestors + itself
            s = jnp.where(self_mask[:, None, None], s, NEG_INF)
        m, l, acc = _online_fold(m, l, acc, s, vb, "bkgst,btkh->bkgsh")
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


#: query rows (S * group) one grid step holds per kv head. The running
#: (acc, m, l) scratch is [KV, rows, ...] f32, so a long suffix is walked
#: in row tiles instead of growing VMEM with S.
_ROW_TILE = 256


def _row_tile(rows: int) -> int:
    """Rows per grid step: all of them when they fit ``_ROW_TILE``, else
    the largest 16-multiple divisor (a bf16 tile is 16 sublanes)."""
    if rows <= _ROW_TILE:
        return rows
    for t in range(_ROW_TILE, 15, -16):
        if rows % t == 0:
            return t
    return rows


def _blocked_kernel(
    bt_ref, st_ref,  # scalar-prefetch: [B, MB] block table, [B] starts
    q_ref, k_ref, v_ref, *rest,
    scale: float, group: int, block_size: int, n_blocks: int, max_s: int,
    n_kv: int, hd: int, fused: bool,
):
    """One (row b, row tile i, pool block j) step for ALL kv heads: the
    pool block arrives as a lane-dense [BS, KV*hd] tile (one contiguous
    DMA) and a static loop folds each head's [BS, hd] lane slice.

    ``fused`` (decode, S=1): at the block holding ``starts[b]`` the step
    patches row ``starts % BS`` with this step's K/V in VMEM, attends the
    patched tile, and writes the patched block through the aliased pool
    output — the only block whose copy-out the revolving out buffer
    performs (the out index map is constant in j). Untouched pool blocks
    survive via the aliasing."""
    if fused:
        nk_ref, nv_ref, o_ref, ok_ref, ov_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    TR = q_ref.shape[2]  # query rows of this tile, row r = s*group + u

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    start = st_ref[b]
    sidx = (
        i * TR + lax.broadcasted_iota(jnp.int32, (TR, block_size), 0)
    ) // group
    qpos = jnp.minimum(start + sidx, max_s - 1)
    t = j * block_size + lax.broadcasted_iota(
        jnp.int32, (TR, block_size), 1
    )
    visible = t <= qpos
    if fused:
        jw = start // block_size
        sel = (
            lax.broadcasted_iota(jnp.int32, (block_size, hd), 0)
            == start % block_size
        ) & (j == jw)

    for g in range(n_kv):
        lanes = slice(g * hd, (g + 1) * hd)
        k = k_ref[0, :, lanes]  # [BS, hd] — head g of row b's j-th block
        v = v_ref[0, :, lanes]
        if fused:
            k = jnp.where(sel, nk_ref[0, :, lanes], k)
            v = jnp.where(sel, nv_ref[0, :, lanes], v)

            @pl.when(j == jw)
            def _write(k=k, v=v, lanes=lanes):
                ok_ref[0, :, lanes] = k
                ov_ref[0, :, lanes] = v

        s = lax.dot_general(
            q_ref[0, g], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * LOG2E)  # [TR, BS], base-2 domain
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[g, :, :1]  # [TR, 1]
        m_new = jnp.maximum(
            jnp.maximum(m_prev, s.max(axis=-1, keepdims=True)), -1e29
        )
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[g] = acc_ref[g] * corr + pv
        l_ref[g, :, :1] = l_ref[g, :, :1] * corr + p.sum(
            axis=-1, keepdims=True
        )
        m_ref[g, :, :1] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        for g in range(n_kv):
            l = jnp.maximum(l_ref[g, :, :1], 1e-30)
            o_ref[0, g] = (acc_ref[g] / l).astype(o_ref.dtype)


def _pallas_paged_attention(
    q: jax.Array,  # [B, S, H, hd]
    k_pool: jax.Array,  # [NB, BS, KV, hd]
    v_pool: jax.Array,
    bt: jax.Array,  # [B, MB] int32
    starts: jax.Array,  # [B] int32 (fused: = the written position)
    new_k: Optional[jax.Array] = None,  # [B, KV, hd] this step's K (S=1)
    new_v: Optional[jax.Array] = None,
    interpret: bool = False,
):
    from jax.experimental.pallas import tpu as pltpu

    TRACE_COUNT["pallas"] += 1
    fused = new_k is not None
    B, S, H, hd = q.shape
    NB, BS, KV, _ = k_pool.shape
    MB = bt.shape[1]
    group = H // KV
    R = S * group
    TR = _row_tile(R)
    # [B, KV, R, hd] with row r = s*group + u: one contiguous query tile
    # per kv head, GQA folded into the tile rows
    qr = q.reshape(B, S, KV, group, hd).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(B, KV, R, hd)
    # the pool as [NB, BS, KV*hd] (a free reshape): a (1, BS, 1, hd) block
    # of the 4-D pool is a sublane slice mosaic refuses unless KV == 1
    pools = [p.reshape(NB, BS, KV * hd) for p in (k_pool, v_pool)]
    kernel = functools.partial(
        _blocked_kernel, scale=1.0 / math.sqrt(hd), group=group,
        block_size=BS, n_blocks=MB, max_s=MB * BS, n_kv=KV, hd=hd,
        fused=fused,
    )
    q_spec = pl.BlockSpec(
        (1, KV, TR, hd), lambda b, i, j, bt, st: (b, 0, i, 0)
    )
    # the whole point: stream row b's OWN j-th block from the pool
    pool_spec = pl.BlockSpec(
        (1, BS, KV * hd), lambda b, i, j, bt, st: (bt[b, j], 0, 0)
    )
    in_specs = [q_spec, pool_spec, pool_spec]
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B, KV, R, hd), q.dtype)]
    args = [qr, *pools]
    aliases = {}
    if fused:
        new_spec = pl.BlockSpec(
            (1, 1, KV * hd), lambda b, i, j, bt, st: (b, 0, 0)
        )
        # write-block spec: CONSTANT in j, so the revolving out buffer
        # only copies the one dirty block back per row. Rows own their
        # blocks exclusively (unowned entries all point at the trash
        # block, where colliding writes are garbage by contract).
        wb_spec = pl.BlockSpec(
            (1, BS, KV * hd),
            lambda b, i, j, bt, st: (bt[b, st[b] // BS], 0, 0),
        )
        in_specs += [new_spec, new_spec]
        out_specs += [wb_spec, wb_spec]
        out_shape += [
            jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools
        ]
        args += [n.reshape(B, 1, KV * hd) for n in (new_k, new_v)]
        # inputs count the 2 scalar-prefetch operands: 3/4 = the pools
        aliases = {3: 1, 4: 2}
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, R // TR, MB),  # j innermost: scratch carries across
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((KV, TR, hd), jnp.float32),
                pltpu.VMEM((KV, TR, 128), jnp.float32),
                pltpu.VMEM((KV, TR, 128), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3
        ),
        interpret=interpret,
    )(bt.astype(jnp.int32), starts.astype(jnp.int32), *args)
    out = outs[0].reshape(B, KV, S, group, hd).transpose(0, 2, 1, 3, 4)
    out = out.reshape(B, S, H, hd)
    if fused:
        return out, outs[1].reshape(k_pool.shape), outs[2].reshape(v_pool.shape)
    return out


def _fused_write_lax(k_pool, v_pool, bt, starts, new_k, new_v):
    """The scatter the decode call site used to dispatch separately,
    folded behind the fused-call interface: write row b's step K/V at
    ``(bt[b, starts//BS], starts % BS)``. Identical ops in identical
    order to the old external scatter — bit-identical by construction."""
    B = starts.shape[0]
    BS = k_pool.shape[1]
    blk = bt[jnp.arange(B), starts // BS]
    off = starts % BS
    return (
        k_pool.at[blk, off].set(new_k),
        v_pool.at[blk, off].set(new_v),
    )


def paged_attention(
    q: jax.Array,  # [B, S, H, hd]
    k_pool: jax.Array,  # [NB, BS, KV, hd] (one layer's pool)
    v_pool: jax.Array,
    bt: jax.Array,  # [B, MB] block table
    starts: jax.Array,  # [B] first query's global position per row
    *,
    self_k: Optional[jax.Array] = None,  # [B, S, KV, hd] (read-only mode)
    self_v: Optional[jax.Array] = None,
    self_mask: Optional[jax.Array] = None,  # [B, S, S] bool (tree verify)
    new_k: Optional[jax.Array] = None,  # [B, KV, hd] (fused decode write)
    new_v: Optional[jax.Array] = None,
    kernel: str = "auto",  # "auto" | "lax" | "pallas"
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
):
    """Blocked paged attention over the pool — returns [B, S, H, hd],
    or ``(out, k_pool, v_pool)`` when ``new_k``/``new_v`` carry a fused
    decode-step KV write (S must be 1; the write lands at ``starts``).

    Query s of row b sits at global position ``min(starts[b]+s, max_s-1)``
    and sees pool keys at ``t <= posq`` — identical math to the gather
    oracle's masked dense attention, without ever building the gathered
    view. With ``self_k``/``self_v``, pool keys are restricted to
    ``t < starts`` and the fresh suffix attends itself under the causal
    (default) or ``self_mask`` tree mask (the read-only verify modes;
    lax path only — the pallas kernel serves the write-path decode hot
    loop).

    ``kernel="auto"`` is the compiled pallas kernel on a TPU and the lax
    scan elsewhere; a kernel the TPU compiler refuses raises, nothing
    falls back. ``interpret=True`` (tests) runs the pallas kernel through
    the interpreter on any backend.
    """
    if kernel == "auto":
        kernel = "pallas" if jax.default_backend() == "tpu" else "lax"
    if kernel not in ("lax", "pallas"):
        raise ValueError(f"unknown paged-attention kernel {kernel!r}")
    if self_mask is not None and self_k is None:
        raise ValueError("self_mask requires self_k/self_v")
    if new_k is not None:
        if self_k is not None:
            raise ValueError("fused KV write excludes self_k/self_v")
        if q.shape[1] != 1:
            raise ValueError(
                f"fused KV write is decode-only (S=1), got S={q.shape[1]}"
            )
        TRACE_COUNT["fused"] += 1
        if kernel == "pallas":
            return _pallas_paged_attention(
                q, k_pool, v_pool, bt, starts, new_k, new_v,
                interpret=interpret,
            )
        k_pool, v_pool = _fused_write_lax(
            k_pool, v_pool, bt, starts, new_k, new_v
        )
        out = _lax_paged_attention(
            q, k_pool, v_pool, bt, starts, None, None, tile
        )
        return out, k_pool, v_pool
    if kernel == "pallas" and self_k is None:
        return _pallas_paged_attention(
            q, k_pool, v_pool, bt, starts, interpret=interpret
        )
    return _lax_paged_attention(
        q, k_pool, v_pool, bt, starts, self_k, self_v, tile,
        self_mask=self_mask,
    )


__all__ = [
    "paged_attention",
    "blocks_per_chunk",
    "DEFAULT_TILE",
    "TRACE_COUNT",
]
