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
- ``pallas``: the decode kernel (``paged_decode_attention``), ONE
  invocation a layer for the whole batch. The pools stay in HBM, whole
  (``[L, NB, BS, KV, hd]`` with the layer in the index, seen as
  ``[L*NB, BS*KV, hd]``: a reshape that moves no byte on a TPU, whose
  tiles span the last two axes). From each row's length the kernel lists
  (row, compute block) pairs, a compute block being C table entries;
  it fetches a pair's blocks by the row's own table entries (one DMA a
  block) into one of two VMEM buffers while it folds the pair before
  into a float32 online softmax, all heads in one product. A row of
  length 0 (not scheduled) has no pair: nothing is fetched for it and
  its output is zeros. So the time follows the keys the scheduled rows
  hold, not the table's span, and the kernel writes nothing into a pool.
  For one query a row and a few (``S * H`` query rows at most
  ``_MAX_QUERY_ROWS``); a pool whose heads are not whole 128-lane tiles
  is refused by name (:func:`decode_kernel_fits`). It replaced a kernel
  on grid (B, row tiles, MB) that took one 16-key block a grid step over
  every table entry of one layer's pool sliced out. Interpret mode
  (``interpret=True``) covers CPU parity tests.

Numerics: the online softmax reorders the reduction, so outputs are
fp-close (observed ~4e-7 f32) but NOT bit-identical to the gather+dense
oracle. The engine's option defaults to ``kv_attention="gather"`` (the
tier-1 bit-exactness oracle, and what a CPU runs); on a TPU the
decoder's decode steps take the pallas kernel whatever the option says
(``ModelRunner``), and greedy decode chains are token-identical in
tier-1 either way.

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
row's current pool block at ``(bt[b, starts//BS], starts % BS)`` in
front of the attention and return ``(out, k_pool, v_pool)``: the decode
call site's scatter behind the same call, on either kernel (identical
ops to the scatter-then-attend sequence, so bit-identical; on a carried,
donated pool XLA scatters in place).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30
#: exp2 domain in the pallas kernel (same rationale as ops.flash_attention:
#: the VPU's transcendental unit is a 2^x evaluator).
LOG2E = math.log2(math.e)

#: default key-tile width: keys folded per lax-scan step, and keys of the
#: pallas kernel's compute block. 256 is the measured CPU sweet spot for
#: BS=16/32, and on the chip read as fast as 512 and faster than 128 at the
#: decoder cells' lengths (PERF.md section 6, PR 38).
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
    layer: Optional[jax.Array] = None,  # with whole [L, NB, BS, KV, hd] pools
    window: int = 0,  # > 0: a query sees only keys t > posq - window
) -> jax.Array:
    TRACE_COUNT["lax"] += 1
    B, S, H, hd = q.shape
    if layer is not None:
        # the pool seen as L * NB blocks with the layer in the index, as
        # llama._paged_view has it: no layer is sliced out
        L, NB = k_pool.shape[:2]
        k_pool = k_pool.reshape(L * NB, *k_pool.shape[2:])
        v_pool = v_pool.reshape(L * NB, *v_pool.shape[2:])
        bt = layer * NB + bt
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
            if window:
                valid &= t[None, None, :] > posq[:, :, None] - window
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


#: most query rows (S * H) a row of the batch may bring: every row's
#: queries and outputs stand whole in VMEM beside the K/V buffers, and one
#: product scores them all against a compute block
_MAX_QUERY_ROWS = 512

#: the kernel's name in a device profile and in compiled text
DECODE_KERNEL_NAME = "paged_decode_attention"


def _decode_kernel(
    len_ref, start_ref, bt_ref, layer_ref,  # scalar prefetch: [B], [B], [B*MB], [1]
    q_ref,  # [B, S*H, hd] in VMEM: row r is query r // H, head r % H
    k_hbm, v_hbm,  # the pools whole, [L*NB, BS*KV, hd], left in HBM
    o_ref,  # [B, S*H, hd]
    kbuf, vbuf,  # [2, C*BS*KV, hd]: two compute blocks each
    sems,  # DMA semaphores [2 (k, v), 2 (slot)]
    rows_ref, cbs_ref,  # SMEM [B * NC]: the work list
    acc_ref, m_ref, l_ref,  # the running softmax of the row in hand
    *, scale: float, heads: int, n_kv: int, block_size: int, chunk: int,
    table: int, pool_blocks: int, max_s: int, window: int = 0,
):
    """Every scheduled row's attention over the blocks it holds, in ONE
    invocation. ``len_ref[b]`` is how many keys row b attends (0: not
    scheduled, skipped whole, output zeros) and ``start_ref[b]`` where its
    first query stands. The work is a list of (row, compute block) pairs
    built here from the lengths: a compute block is ``chunk`` table
    entries, fetched by the row's own block numbers from the pool (one DMA
    a block, the layer in the index) into one of two buffers while the
    pair before it is folded into a float32 online softmax. So the time
    follows the keys the rows hold, not the table's span, and nothing is
    written to a pool.

    A block arrives as the pool holds it, ``[BS*KV, hd]``: key t's kv head
    h is row ``t*KV + h`` (any other order would be a copy of the pool
    first). So one product scores every query head against every row of
    the block, and the mask keeps, beside ``t <= position``, only a query
    head's own kv head; the value product then sums over exactly those."""
    from jax.experimental.pallas import tpu as pltpu

    B, R, _ = q_ref.shape
    KV, C = n_kv, chunk
    rows_per_block = block_size * KV
    CK = C * block_size  # keys a compute block
    W = CK * KV  # its rows
    group = heads // KV
    base = layer_ref[0] * pool_blocks

    def list_row(b, n):
        def put(c, n):
            rows_ref[n] = b
            cbs_ref[n] = c
            return n + 1

        return lax.fori_loop(0, (len_ref[b] + CK - 1) // CK, put, n)

    n_items = lax.fori_loop(0, B, list_row, 0)
    o_ref[...] = jnp.zeros_like(o_ref)

    def fetch(i, slot, act):
        """``act`` (start, or wait for) the DMAs of pair ``i``'s blocks into
        buffer ``slot``: one for K and one for V a table entry."""
        entry = rows_ref[i] * table + cbs_ref[i] * C

        def one(j, carry):
            blk = base + bt_ref[entry + j]
            dst = pl.ds(pl.multiple_of(j * rows_per_block, rows_per_block),
                        rows_per_block)
            act(pltpu.make_async_copy(
                k_hbm.at[blk], kbuf.at[slot, dst], sems.at[0, slot]))
            act(pltpu.make_async_copy(
                v_hbm.at[blk], vbuf.at[slot, dst], sems.at[1, slot]))
            return carry

        lax.fori_loop(0, C, one, 0)

    start, wait = (lambda dma: dma.start()), (lambda dma: dma.wait())

    @pl.when(n_items > 0)
    def _first():
        fetch(0, 0, start)

    def fold(i, carry):
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < n_items)
        def _next():
            fetch(i + 1, 1 - slot, start)

        fetch(i, slot, wait)
        row, cb = rows_ref[i], cbs_ref[i]

        @pl.when(cb == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        r = lax.broadcasted_iota(jnp.int32, (R, W), 0)
        w = lax.broadcasted_iota(jnp.int32, (R, W), 1)
        qpos = jnp.minimum(start_ref[row] + r // heads, max_s - 1)
        visible = (cb * CK + w // KV <= qpos) & (
            w % KV == (r % heads) // group)
        if window:  # a window layer's query sees the last ``window`` keys
            visible &= cb * CK + w // KV > qpos - window
        s = lax.dot_general(
            q_ref[row], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * LOG2E)  # [R, W], base-2 domain
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(
            jnp.maximum(m_prev, s.max(axis=-1, keepdims=True)), -1e29
        )
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        v = vbuf[slot]
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        l_ref[:, :1] = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[:, :1] = m_new

        @pl.when(cb == (len_ref[row] + CK - 1) // CK - 1)
        def _finalize():
            l = jnp.maximum(l_ref[:, :1], 1e-30)
            o_ref[row] = (acc_ref[...] / l).astype(o_ref.dtype)

        return carry

    lax.fori_loop(0, n_items, fold, 0)


def decode_kernel_fits(queries: int, heads: int, n_kv: int, head_dim: int,
                       block_size: int, dtype) -> bool:
    """Whether the pallas kernel can take a pool of this geometry as it
    stands: a block is a ``[BS*KV, hd]`` matrix of whole tiles (``hd`` whole
    lanes of 128, the rows whole sublane tiles of the pool's type) and a
    row's ``queries * heads`` query rows are scored in one product."""
    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return (head_dim % 128 == 0 and (block_size * n_kv) % sublanes == 0
            and queries * heads <= _MAX_QUERY_ROWS)


def decode_keys_read(lengths, block_size: int, table: int,
                     tile: int = DEFAULT_TILE) -> int:
    """Keys the decode kernel fetches for rows of ``lengths`` keys (any
    shape): each rounded up to the compute block. Host arithmetic, for
    counters."""
    ck = blocks_per_chunk(table, block_size, tile) * block_size
    return int((-(-np.asarray(lengths, np.int64) // ck) * ck).sum())


def _pallas_paged_attention(
    q: jax.Array,  # [B, S, H, hd]
    k_pool: jax.Array,  # [NB, BS, KV, hd], or [L, NB, BS, KV, hd] with layer
    v_pool: jax.Array,
    bt: jax.Array,  # [B, MB] int32
    starts: jax.Array,  # [B] int32: the first query's position
    layer: Optional[jax.Array] = None,  # scalar: which layer of a whole pool
    live: Optional[jax.Array] = None,  # [B] bool: rows to attend (None: all)
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
    window: int = 0,
) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    TRACE_COUNT["pallas"] += 1
    B, S, H, hd = q.shape
    if k_pool.ndim == 4:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    L, NB, BS, KV, _ = k_pool.shape
    MB = bt.shape[1]
    R = S * H
    if not interpret and not decode_kernel_fits(S, H, KV, hd, BS, k_pool.dtype):
        raise ValueError(
            f"the pallas paged kernel takes blocks of whole tiles (head_dim "
            f"{hd} a multiple of 128, block_size x kv heads {BS} x {KV} whole "
            f"sublane tiles) and at most {_MAX_QUERY_ROWS} query rows a row "
            f"({S} x {H})"
        )
    C = blocks_per_chunk(MB, BS, tile)
    max_s = MB * BS
    # the pools as [L*NB, BS*KV, hd]: a block's keys and kv heads as the rows
    # of one matrix, the order the pool holds them in (a reshape that moves
    # no byte; [BS, KV*hd] would be a copy of the pool on a TPU, whose
    # tiles span the last two axes)
    pools = [p.reshape(L * NB, BS * KV, hd) for p in (k_pool, v_pool)]
    starts = starts.astype(jnp.int32)
    lengths = jnp.minimum(starts + S, max_s)
    if live is not None:
        lengths = jnp.where(live, lengths, 0)
    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(hd), heads=H, n_kv=KV,
        block_size=BS, chunk=C, table=MB, pool_blocks=NB, max_s=max_s,
        window=int(window),
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, C * BS * KV, hd), k_pool.dtype),
                pltpu.VMEM((2, C * BS * KV, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((B * (MB // C),), jnp.int32),
                pltpu.SMEM((B * (MB // C),), jnp.int32),
                pltpu.VMEM((R, hd), jnp.float32),
                pltpu.VMEM((R, 128), jnp.float32),
                pltpu.VMEM((R, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, R, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=DECODE_KERNEL_NAME,
    )(lengths, starts, bt.astype(jnp.int32).reshape(-1),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(B, R, hd), *pools)
    return out.reshape(B, S, H, hd)


def _fused_write_lax(k_pool, v_pool, bt, starts, new_k, new_v, layer=None,
                     live=None):
    """The decode step's scatter behind the fused-call interface: write
    row b's step K/V at ``(bt[b, starts//BS], starts % BS)`` (of ``layer``,
    for whole ``[L, NB, BS, KV, hd]`` pools: in place on a loop carry). A
    row that is not ``live`` writes to the trash block: nobody reads what
    it computes, and it may not hold the block its position names."""
    B = starts.shape[0]
    BS = k_pool.shape[-3]
    blk = bt[jnp.arange(B), starts // BS]
    if live is not None:
        blk = jnp.where(live, blk, 0)
    at = (blk, starts % BS) if layer is None else (layer, blk, starts % BS)
    return k_pool.at[at].set(new_k), v_pool.at[at].set(new_v)


def paged_attention(
    q: jax.Array,  # [B, S, H, hd]
    k_pool: jax.Array,  # [NB, BS, KV, hd]; with ``layer`` [L, NB, BS, KV, hd]
    v_pool: jax.Array,
    bt: jax.Array,  # [B, MB] block table
    starts: jax.Array,  # [B] first query's global position per row
    *,
    layer: Optional[jax.Array] = None,  # scalar: the layer of whole pools
    live: Optional[jax.Array] = None,  # [B] bool: the rows to attend
    self_k: Optional[jax.Array] = None,  # [B, S, KV, hd] (read-only mode)
    self_v: Optional[jax.Array] = None,
    self_mask: Optional[jax.Array] = None,  # [B, S, S] bool (tree verify)
    new_k: Optional[jax.Array] = None,  # [B, KV, hd] (fused decode write)
    new_v: Optional[jax.Array] = None,
    kernel: str = "auto",  # "auto" | "lax" | "pallas"
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
    window: int = 0,  # > 0: keys ``t > posq - window`` only (a window layer)
):
    """Blocked paged attention over the pool — returns [B, S, H, hd],
    or ``(out, k_pool, v_pool)`` when ``new_k``/``new_v`` carry the
    decode step's KV write (S must be 1; the write lands at ``starts``,
    a scatter in front of the attention on either kernel).

    Query s of row b sits at global position ``min(starts[b]+s, max_s-1)``
    and sees pool keys at ``t <= posq`` — identical math to the gather
    oracle's masked dense attention, without ever building the gathered
    view. With ``self_k``/``self_v``, pool keys are restricted to
    ``t < starts`` and the fresh suffix attends itself under the causal
    (default) or ``self_mask`` tree mask (the read-only verify modes;
    lax path only).

    ``layer`` makes the pools the whole ``[L, NB, BS, KV, hd]`` arrays a
    paged program carries through its layer scan, read (and written) at
    that layer with no slice taken out. ``live`` names the rows a decode
    dispatch scheduled: any other row is not attended (its output is
    zeros, its write goes to the trash block), and the pallas kernel
    fetches nothing for it.

    ``window`` > 0 narrows what a query sees to its last ``window`` keys
    (its own among them), on either kernel; the table may then be a RUN of
    a row's blocks that begins at any block, with ``starts`` counted from
    that block's first key: the masks compare differences of positions only
    (``models/sparse_window.py`` hands a window layer's decode step the run
    that ends at the row's position, so the kernel fetches that and no more).

    ``kernel="auto"`` is the compiled pallas kernel on a TPU for one query
    a row (the decode step) and the lax scan elsewhere; a kernel the TPU
    compiler refuses raises, nothing falls back. ``interpret=True``
    (tests) runs the pallas kernel through the interpreter on any backend.
    """
    if kernel == "auto":
        fits = q.shape[1] == 1 and self_k is None and decode_kernel_fits(
            1, q.shape[2], *k_pool.shape[-2:], k_pool.shape[-3], k_pool.dtype)
        kernel = "pallas" if fits and jax.default_backend() == "tpu" else "lax"
    if kernel not in ("lax", "pallas"):
        raise ValueError(f"unknown paged-attention kernel {kernel!r}")
    if self_mask is not None and self_k is None:
        raise ValueError("self_mask requires self_k/self_v")
    if window and self_k is not None:
        raise ValueError("window excludes self_k/self_v")
    if (layer is None) != (k_pool.ndim == 4):
        raise ValueError("layer goes with whole [L, NB, BS, KV, hd] pools")
    fused = new_k is not None
    if fused:
        if self_k is not None:
            raise ValueError("fused KV write excludes self_k/self_v")
        if q.shape[1] != 1:
            raise ValueError(
                f"fused KV write is decode-only (S=1), got S={q.shape[1]}"
            )
        TRACE_COUNT["fused"] += 1
        k_pool, v_pool = _fused_write_lax(
            k_pool, v_pool, bt, starts, new_k, new_v, layer, live
        )
    if kernel == "pallas" and self_k is None:
        out = _pallas_paged_attention(
            q, k_pool, v_pool, bt, starts, layer, live, tile,
            interpret=interpret, window=window,
        )
    else:
        out = _lax_paged_attention(
            q, k_pool, v_pool, bt, starts, self_k, self_v, tile,
            self_mask=self_mask, layer=layer, window=window,
        )
        if live is not None:
            out = jnp.where(live[:, None, None, None], out, 0)
    return (out, k_pool, v_pool) if fused else out


__all__ = [
    "paged_attention",
    "blocks_per_chunk",
    "decode_kernel_fits",
    "decode_keys_read",
    "DECODE_KERNEL_NAME",
    "DEFAULT_TILE",
    "TRACE_COUNT",
]
