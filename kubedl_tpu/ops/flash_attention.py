"""Flash attention: fused pallas TPU kernel with online softmax.

Single-chip counterpart of `kubedl_tpu.parallel.ring` (which runs the same
recurrence *across* chips): scores never materialize in HBM — each (q-block,
k-block) tile streams through VMEM, the MXU does the two matmuls, and a
running (max, sum, acc) triple in VMEM scratch folds blocks in
(the flash-attention recurrence). Memory is O(S·hd) instead of O(S²);
causal blocks above the diagonal are predicated off entirely (half the
FLOPs at long S).

Grid layout: (batch, q_heads, q_blocks, k_blocks), k innermost so the
scratch accumulator carries across k-steps of one q-tile — the canonical
pallas accumulation pattern (pallas_guide.md: grid iterates last dim
fastest; scratch persists). GQA is free: the K/V BlockSpec index map sends
q-head h to kv-head h//group, no repeated K/V in memory.

Backward is a custom VJP over ONE fused pallas kernel
(`_bwd_fused_kernel`): dq accumulates per-q-block in scratch while dk/dv
accumulate in a whole-sequence f32 VMEM scratch across the entire GQA
group (one QK^T recompute, one exp, one dO·V^T per tile — the canonical
flash-2 two-kernel split pays those twice and then needs a dk/dv
group-sum pass this kernel doesn't). The split kernels remain as the
fallback for sequences whose dk+dv scratch exceeds scoped VMEM
(Sk·hd·8 > 8MB). P is recomputed from the saved lse in both paths — same
O(S·hd) memory profile as the forward. 1024x1024 tiles are the measured
v5e sweet spot (k-tile auto-clamps to 512 at long S); in-model the fused
path cut attention custom-call time from 204 to 126 ms/step on the
bench model (2.6x+ faster than the stock jax pallas TPU flash kernel).

Tests run the kernels in pallas interpret mode by passing
``interpret=True``; numerics match the dense oracle
`kubedl_tpu.models.llama.attention`. Without it the kernel is compiled for
the TPU, and a shape it cannot tile or the compiler refuses raises.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30
#: softmax runs in the exp2 domain: the TPU VPU's transcendental unit is a
#: 2^x evaluator (e^x lowers to 2^(x·log2e)), so folding log2(e) into the
#: score scale turns every exp into a bare exp2 — one fewer VPU pass over
#: each [bq, bk] tile. lse is internal to _flash and stays in BASE-2
#: units end to end (fwd emits m2 + log2(l), bwd exponentiates with exp2).
LOG2E = math.log2(math.e)


def _tile_preds(causal: bool, qi, kj, block_q: int, block_k: int):
    """(run, on_diag) for the (q-block ``qi``, k-block ``kj``) tile of a
    causal grid. ``run``: the tile has any unmasked element (tiles
    strictly above the diagonal are skipped outright). ``on_diag``: the
    tile STRADDLES the diagonal and must pay the masking passes (iota +
    compare + select are three VPU sweeps over [bq, bk]); tiles fully
    below the diagonal — every full tile at long S — skip them. Returns
    (None, None) for non-causal grids, which run every tile unmasked."""
    if not causal:
        return None, None
    run = kj * block_k <= qi * block_q + block_q - 1
    on_diag = qi * block_q < kj * block_k + block_k - 1
    return run, on_diag


def _dispatch_tiles(causal: bool, run, on_diag, step) -> None:
    """Invoke ``step(apply_mask)`` under the shared causal predication
    (one definition for all four kernels — fwd, fused bwd, split dq,
    split dk/dv — so the boundary conditions cannot drift apart)."""
    if not causal:
        step(False)
        return

    @pl.when(jnp.logical_and(run, jnp.logical_not(on_diag)))
    def _full_tile():
        step(False)

    @pl.when(jnp.logical_and(run, on_diag))
    def _diag_tile():
        step(True)


def _rope_operands(bq: int, bk: int, hd: int, cos, sin, q_major: bool):
    """(extra in_specs, extra args) for one pallas_call's fused-rope
    cos/sin operands — [cos_q, sin_q, cos_k, sin_k], the q table sliced by
    the q-block index and the k table by the k-block index. One definition
    for all four call sites (same protection _tile_preds gives the causal
    predication). ``q_major``: True for (b,h,i,j) grids (fwd, fused bwd,
    split dq), False for the transposed (b,h,j,i) dk/dv grid."""
    h2 = hd // 2
    if q_major:
        cq = pl.BlockSpec((bq, h2), lambda b, h, i, j: (i, 0))
        ck = pl.BlockSpec((bk, h2), lambda b, h, i, j: (j, 0))
    else:
        cq = pl.BlockSpec((bq, h2), lambda b, h, j, i: (i, 0))
        ck = pl.BlockSpec((bk, h2), lambda b, h, j, i: (j, 0))
    return [cq, cq, ck, ck], [cos, sin, cos, sin]


def _rope_rotate(x, cos, sin, inverse: bool = False):
    """Rotate the split-halves pairs of ``x`` [rows, hd] by the per-row
    angles (``cos``/``sin`` [rows, hd/2]) — the models.llama.apply_rope
    convention, executed on a VMEM tile instead of a whole [B,S,H,hd]
    array in HBM. f32 math, result cast back to x.dtype. ``inverse``
    applies the transpose rotation (rotation matrices are orthogonal:
    R^-1 = R^T = rotation by -θ) — how the backward kernels emit
    gradients w.r.t. the PRE-rope q/k."""
    h2 = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[:, :h2], x32[:, h2:]
    if inverse:
        sin = -sin
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _fwd_kernel(
    q_ref, k_ref, v_ref, *rest,
    scale: float, causal: bool, block_q: int, block_k: int, n_k: int,
    aug_v: bool, rope: bool, group: int,
):
    if rope:
        (cos_q_ref, sin_q_ref, cos_k_ref, sin_k_ref,
         o_ref, lse_ref, acc_ref, m_ref, q_rot_ref, k_rot_ref,
         *l_scratch) = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, *l_scratch = rest
    h = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    hd = q_ref.shape[-1]
    l_ref = l_scratch[0] if l_scratch else None

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        if l_ref is not None:
            l_ref[:] = jnp.zeros_like(l_ref)
        if rope:
            # rotary fused into the kernel (the XLA-side rope — f32
            # rotate + concat + relayouts over whole [B,S,H,hd] arrays —
            # profiled at ~37ms/step on the bench model). Each rotation
            # happens ONCE per position: q per q-block here (j==0 runs
            # for every i); k into a whole-sequence scratch below (naive
            # per-tile rotation re-rotated K n_q times — measured +88ms
            # at S=8192 where n_q=8).
            q_rot_ref[:] = _rope_rotate(
                q_ref[0, 0], cos_q_ref[...], sin_q_ref[...]
            )

    if rope:
        # k-block j's first causal visit is at q-block (j*bk)//bq; the
        # scratch then serves every later i AND the rest of the GQA group
        # (the grid walks a kv-head's q-heads consecutively; sequential
        # grid semantics are pinned on this pallas_call)
        i_first = (j * block_k) // block_q if causal else 0

        @pl.when(jnp.logical_and(h % group == 0, i == i_first))
        def _load_k_rot():
            k_rot_ref[pl.ds(j * block_k, block_k), :] = _rope_rotate(
                k_ref[0, 0], cos_k_ref[...], sin_k_ref[...]
            )

    run, on_diag = _tile_preds(causal, i, j, block_q, block_k)

    def _step(apply_mask):
        if rope:
            q = q_rot_ref[:]
            k = k_rot_ref[pl.ds(j * block_k, block_k), :]
        else:
            q = q_ref[0, 0]  # [bq, hd]
            k = k_ref[0, 0]  # [bk, hd]
        v = v_ref[0, 0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)  # [bq, bk], base-2 domain
        if apply_mask:
            rows = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:, :1]  # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        if aug_v:
            # V carries a ones column: the softmax denominator comes out
            # of the SAME MXU matmul as P·V (the lane padding at
            # hd % 128 != 0 makes the extra column free) and the l-update
            # VPU reduce over [bq, bk] disappears — acc's last column IS l
            v_aug = jnp.concatenate(
                [v, jnp.ones((v.shape[0], 1), v.dtype)], axis=-1
            )
            pv = lax.dot_general(
                p.astype(v.dtype), v_aug, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[:] = acc_ref[:] * corr + pv
        else:
            l_new = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
            pv = lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[:] = acc_ref[:] * corr + pv
            l_ref[:, :1] = l_new
        m_ref[:, :1] = m_new

    _dispatch_tiles(causal, run, on_diag, _step)

    @pl.when(j == n_k - 1)
    def _finalize():
        if aug_v:
            l = jnp.maximum(acc_ref[:, hd:hd + 1], 1e-30)
        else:
            l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:, :hd] / l).astype(o_ref.dtype)
        # lse is [B, H, Sq, 1] (trailing singleton keeps the block shape
        # legal for mosaic's (8, 128) tiling rule) and stays in BASE-2
        # units (m is the base-2 running max): lse never leaves _flash,
        # and any XLA-side op on a [B,H,S,1] tensor is layout-pathological
        # (a single *LOG2E multiply profiled at 9.6ms/step) — so the
        # backward consumes these units directly.
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log2(l)


def _fwd(
    q: jax.Array,  # [B, H, Sq, hd]
    k: jax.Array,  # [B, KV, Sk, hd]
    v: jax.Array,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
    cos: Optional[jax.Array] = None,  # [Sq, hd/2] f32 — fused rope
    sin: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    group = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(f"seq lengths ({Sq},{Sk}) must divide blocks ({bq},{bk})")
    n_q, n_k = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    rope = cos is not None

    # ones-augmented V only pays when hd leaves lane-padding slack (the
    # [bq, hd+1] MXU output tile costs the same passes as [bq, hd] iff
    # hd % 128 != 0); at hd=128k it would DOUBLE the P·V matmul instead
    aug_v = (hd % 128) != 0
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, n_k=n_k, aug_v=aug_v, rope=rope,
        group=group,
    )
    scratch = [
        pltpu.VMEM((bq, hd + 1 if aug_v else hd), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
    ]
    if rope:
        # once-per-position rotation caches (see _fwd_kernel)
        scratch.append(pltpu.VMEM((bq, hd), q.dtype))
        scratch.append(pltpu.VMEM((Sk, hd), k.dtype))
    if not aug_v:
        scratch.append(pltpu.VMEM((bq, 128), jnp.float32))
    in_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
    ]
    args = [q, k, v]
    if rope:
        specs, extra = _rope_operands(bq, bk, hd, cos, sin, q_major=True)
        in_specs += specs
        args += extra
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        # sequential grid semantics (also the mosaic default): the rope
        # k-cache persists across the h and i grid dims, not just the
        # innermost j — pin the assumption explicitly. Same raised VMEM
        # ceiling as the backward (large-tile experiments at long S).
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args)
    # lse keeps its kernel-native [B, H, Sq, 1] shape all the way into the
    # backward: squeezing to [B, H, Sq] here made the residual-save /
    # re-expand round trip materialize a sublane-granularity relayout copy
    # (profiled at 13ms/step on the bench model)
    return out, lse


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, *rest,
    scale: float, causal: bool, block_q: int, block_k: int, n_k: int,
    rope: bool, group: int,
):
    """dQ kernel: grid (B, H, n_q, n_k), k innermost — the dq tile for one
    q-block accumulates across k-blocks in VMEM scratch (same pattern as
    the forward, with p recomputed from the saved lse; D = rowsum(dO·O)
    computed per q-block in VMEM)."""
    if rope:
        (cos_q_ref, sin_q_ref, cos_k_ref, sin_k_ref,
         dq_ref, acc_ref, d_acc, q_rot_ref, k_rot_ref) = rest
    else:
        dq_ref, acc_ref, d_acc = rest
    h = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        d_acc[:, :1] = (
            do_ref[0, 0].astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32)
        ).sum(axis=-1, keepdims=True)
        if rope:
            q_rot_ref[:] = _rope_rotate(
                q_ref[0, 0], cos_q_ref[...], sin_q_ref[...]
            )

    if rope:
        i_first = (j * block_k) // block_q if causal else 0

        @pl.when(jnp.logical_and(h % group == 0, i == i_first))
        def _load_k_rot():
            k_rot_ref[pl.ds(j * block_k, block_k), :] = _rope_rotate(
                k_ref[0, 0], cos_k_ref[...], sin_k_ref[...]
            )

    run, on_diag = _tile_preds(causal, i, j, block_q, block_k)

    def _step(apply_mask):
        if rope:
            q = q_rot_ref[:]
            k = k_rot_ref[pl.ds(j * block_k, block_k), :]
        else:
            q = q_ref[0, 0]
            k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [bq, 1], base-2 (pre-scaled by LOG2E)
        d = d_acc[:, :1]  # [bq, 1]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)
        if apply_mask:
            rows = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp2(s - lse)
        dp = lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d)
        acc_ref[:] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _dispatch_tiles(causal, run, on_diag, _step)

    @pl.when(j == n_k - 1)
    def _finalize():
        dq = acc_ref[:]
        if rope:
            dq = _rope_rotate(dq, cos_q_ref[...], sin_q_ref[...], inverse=True)
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, *rest,
    scale: float, causal: bool, block_q: int, block_k: int, n_q: int,
    rope: bool,
):
    """dK/dV kernel: grid (B, H, n_k, n_q), q innermost — each k-block's
    gradient accumulates across the q-blocks that attend to it. D is
    recomputed per tile here (q-blocks are the INNER axis, so there is no
    per-q-block init point to cache it at — the [bq, hd] mul+reduce is
    noise next to the [bq, bk] tile work)."""
    if rope:
        (cos_q_ref, sin_q_ref, cos_k_ref, sin_k_ref,
         dk_ref, dv_ref, dk_acc, dv_acc, q_rot_ref, k_rot_ref) = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    j = pl.program_id(2)
    i = pl.program_id(3)

    if rope:
        # q-block i's first visit (j outer here) is at j==0, which runs
        # for every i under causality — the whole-sequence q cache then
        # serves all later j; k is fixed per (h, j): rotate at its first
        # running i
        @pl.when(j == 0)
        def _load_q_rot():
            q_rot_ref[pl.ds(i * block_q, block_q), :] = _rope_rotate(
                q_ref[0, 0], cos_q_ref[...], sin_q_ref[...]
            )

        i_first = (j * block_k) // block_q if causal else 0

        @pl.when(i == i_first)
        def _load_k_rot():
            k_rot_ref[:] = _rope_rotate(
                k_ref[0, 0], cos_k_ref[...], sin_k_ref[...]
            )

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run, on_diag = _tile_preds(causal, i, j, block_q, block_k)

    def _step(apply_mask):
        if rope:
            q = q_rot_ref[pl.ds(i * block_q, block_q), :]
            k = k_rot_ref[:]
        else:
            q = q_ref[0, 0]
            k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # base-2 (pre-scaled by LOG2E)
        d = (do * o_ref[0, 0].astype(jnp.float32)).sum(axis=-1, keepdims=True)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)
        if apply_mask:
            rows = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp2(s - lse)  # [bq, bk]
        dv_acc[:] += lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0, 0],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - d)).astype(q.dtype)
        dk_acc[:] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale

    _dispatch_tiles(causal, run, on_diag, _step)

    @pl.when(i == n_q - 1)
    def _finalize():
        dk = dk_acc[:]
        if rope:
            dk = _rope_rotate(dk, cos_k_ref[...], sin_k_ref[...], inverse=True)
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, *rest,
    scale: float, causal: bool, block_q: int, block_k: int,
    n_q: int, n_k: int, group: int, rope: bool,
):
    """Single-pass flash backward: dq, dk AND dv from one traversal.

    The classic split (separate dQ and dK/dV kernels, flash-2 style) pays
    the expensive part — the QK^T recompute, the exp, and the dO·V^T
    product — TWICE. Here the grid is (B, H, n_q, n_k) with k innermost:
    dq accumulates per-q-block in scratch exactly like the split kernel,
    while dk/dv accumulate into a WHOLE-SEQUENCE f32 VMEM scratch
    ([Sk, hd] = 512KB at S=2048) and are written out during the final
    q-block pass (i == n_q-1 visits every j, causality never skips the
    last q row-block). One QK matmul, one exp, one dp per tile — the
    measured win on the bench model is ~19% of the whole train step.

    GQA folds into the same scratch: the grid walks the `group` q-heads
    of one kv-head consecutively, so dk/dv simply keep accumulating
    across them (init on the group's first head, write-out on its last)
    and the kernel emits [B, KV, Sk, hd] directly — no per-q-head dk/dv
    arrays in HBM and no group-sum pass afterwards.

    With ``rope`` the kernel takes PRE-rope q/k, rotates tiles in VMEM
    (identically to the forward), and inverse-rotates dq/dk at write-out
    so the emitted gradients are w.r.t. the pre-rope inputs — summing the
    GQA group's rotated dk first and inverse-rotating once is valid
    because the rotation is linear and per-position.
    """
    if rope:
        (cos_q_ref, sin_q_ref, cos_k_ref, sin_k_ref,
         dq_ref, dk_ref, dv_ref,
         dq_acc, dk_acc, dv_acc, d_acc, q_rot_ref, k_rot_ref) = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, d_acc = rest
    h = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    first_in_group = h % group == 0
    last_in_group = h % group == group - 1

    @pl.when(jnp.logical_and(first_in_group,
                             jnp.logical_and(i == 0, j == 0)))
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init_q():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        # D_i = rowsum(dO·O) for this q-block, once per (h, i) — in VMEM,
        # instead of an XLA pre-pass that materialized an f32 relayout of
        # the whole dO/O pair in HBM
        d_acc[:, :1] = (
            do_ref[0, 0].astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32)
        ).sum(axis=-1, keepdims=True)
        if rope:
            q_rot_ref[:] = _rope_rotate(
                q_ref[0, 0], cos_q_ref[...], sin_q_ref[...]
            )

    if rope:
        # once-per-position k rotation (see _fwd_kernel: per-tile
        # re-rotation cost n_q re-runs — measured +88ms at S=8192)
        i_first = (j * block_k) // block_q if causal else 0

        @pl.when(jnp.logical_and(first_in_group, i == i_first))
        def _load_k_rot():
            k_rot_ref[pl.ds(j * block_k, block_k), :] = _rope_rotate(
                k_ref[0, 0], cos_k_ref[...], sin_k_ref[...]
            )

    run, on_diag = _tile_preds(causal, i, j, block_q, block_k)

    def _step(apply_mask):
        if rope:
            q = q_rot_ref[:]
            k = k_rot_ref[pl.ds(j * block_k, block_k), :]
        else:
            q = q_ref[0, 0]
            k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        do32 = do.astype(jnp.float32)
        lse = lse_ref[0, 0]  # base-2 (pre-scaled by LOG2E)
        d = d_acc[:, :1]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)
        if apply_mask:
            rows = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0
            )
            cols = j * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp2(s - lse)  # [bq, bk]
        dv_acc[pl.ds(j * block_k, block_k), :] += lax.dot_general(
            p.astype(do.dtype), do,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            do32, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d)
        ds_c = ds.astype(q.dtype)
        dq_acc[:] += lax.dot_general(
            ds_c, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        dk_acc[pl.ds(j * block_k, block_k), :] += lax.dot_general(
            ds_c, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _dispatch_tiles(causal, run, on_diag, _step)

    @pl.when(j == n_k - 1)
    def _fin_q():
        dq = dq_acc[:]
        if rope:
            dq = _rope_rotate(dq, cos_q_ref[...], sin_q_ref[...], inverse=True)
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(last_in_group, i == n_q - 1))
    def _fin_kv():
        dk = dk_acc[pl.ds(j * block_k, block_k), :]
        if rope:
            dk = _rope_rotate(dk, cos_k_ref[...], sin_k_ref[...], inverse=True)
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[pl.ds(j * block_k, block_k), :].astype(
            dv_ref.dtype
        )


#: cap on the whole-sequence dk+dv f32 scratch of the fused backward;
#: beyond it (Sk * hd * 8 bytes) the split two-kernel path is used
_FUSED_BWD_SCRATCH_BYTES = 8 << 20
#: above this scratch size the fused kernel's k-tile is clamped to 512 so
#: scratch + score tiles stay inside scoped VMEM (measured on v5e at
#: S=8192: 1024x512 fused = 850ms/grad vs 950ms split, vs compile-OOM at
#: 1024x1024)
_FUSED_BWD_SMALL_TILE_BYTES = 2 << 20
#: per-kernel scoped-VMEM ceiling for ALL four kernels (fwd + the three
#: backward variants): the fused backward at S=8192 (whole-seq dk/dv f32
#: + rope caches + [bq,bk] f32 score intermediates) needs 16.2MB against
#: mosaic's default 16MB, and the forward shares the ceiling for
#: large-tile experiments at long S — v5e cores have far more physical
#: VMEM; raise the soft limit rather than shrinking the measured-optimal
#: tiles
_VMEM_LIMIT_BYTES = 24 << 20


def _compiler_params():
    """The pinned mosaic assumptions, in ONE place for all four
    pallas_call sites: fully-sequential grid semantics (scratch
    accumulators and the rope rotation caches persist across non-inner
    grid dims) + the raised VMEM ceiling."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 4,
        vmem_limit_bytes=_VMEM_LIMIT_BYTES,
    )


def _bwd_pallas(
    res, do: jax.Array, causal: bool, block_q: int, block_k: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash backward dispatcher. Primary path: the single-pass
    `_bwd_fused_kernel` (dq + group-folded dk/dv in one traversal), used
    while the whole-sequence dk+dv scratch (Sk*hd*8 bytes) fits scoped
    VMEM (<= 8MB; above 2MB the k-tile is re-fit to <= 512 so scratch +
    score tiles coexist). Fallback: the classic flash-2 split — a dQ
    kernel and a dK/dV kernel at q-head granularity whose dk/dv are then
    summed over the GQA group. Both recompute P from the saved lse and
    keep the forward's O(S·hd) memory profile."""
    from jax.experimental.pallas import tpu as pltpu

    if len(res) == 7:  # fused-rope variant: pre-rope q/k + the tables
        q, k, v, cos, sin, out, lse = res
        rope = True
    else:
        q, k, v, out, lse = res
        cos = sin = None
        rope = False
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    group = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    n_q, n_k = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)

    # lse arrives from the forward ALREADY in base-2 units ([B,H,Sq,1]):
    # p = 2^(s·scale·log2e − lse2) = e^(s·scale − lse). No XLA-side op
    # may touch it — anything on a [B,H,S,1] tensor is layout-pathological
    # (a single multiply profiled at 9.6ms/step on the bench model).
    # D_i = rowsum(dO·O) is computed INSIDE the kernels (per q-block, in
    # VMEM): as an XLA pre-pass it materialized an f32 relayout of the
    # whole dO (profiled at ~7ms/step).
    lse4 = lse  # [B, H, Sq, 1], base-2

    scratch_bytes = Sk * hd * 8
    fused_ok = scratch_bytes <= _FUSED_BWD_SCRATCH_BYTES
    fused_bk = bk
    if fused_ok and scratch_bytes > _FUSED_BWD_SMALL_TILE_BYTES:
        # re-FIT (not clamp) the k-tile: min(bk, 512) could stop dividing
        # Sk (e.g. S=5376 fits 896-tiles but not 512), which would
        # silently drop the tail k-blocks from dk/dv. fit_block returns 0
        # when no <=512 tiling exists — use the split path then (its
        # tiles keep the caller's bk).
        fused_bk = fit_block(Sk, 512)
        fused_ok = fused_bk > 0
    if fused_ok:
        bk = fused_bk
        n_q, n_k = Sq // bq, Sk // bk
        q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0))
        kv_spec = pl.BlockSpec(
            (1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)
        )
        row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
        # dk/dv come out at KV-HEAD granularity: the kernel accumulates
        # the whole GQA group in its scratch (grid walks a kv-head's
        # q-heads consecutively), so no group-sum pass and group-x fewer
        # HBM bytes written
        dkv_spec = pl.BlockSpec(
            (1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)
        )
        in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, q_spec]
        args = [q, k, v, do, lse4, out]
        scratch = [
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((Sk, hd), jnp.float32),
            pltpu.VMEM((Sk, hd), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ]
        if rope:
            specs, extra = _rope_operands(bq, bk, hd, cos, sin, q_major=True)
            in_specs += specs
            args += extra
            scratch += [
                pltpu.VMEM((bq, hd), q.dtype),
                pltpu.VMEM((Sk, hd), k.dtype),
            ]
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_fused_kernel, scale=scale, causal=causal,
                block_q=bq, block_k=bk, n_q=n_q, n_k=n_k, group=group,
                rope=rope,
            ),
            grid=(B, H, n_q, n_k),
            in_specs=in_specs,
            out_specs=[q_spec, dkv_spec, dkv_spec],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
                jax.ShapeDtypeStruct((B, KV, Sk, hd), k.dtype),
                jax.ShapeDtypeStruct((B, KV, Sk, hd), v.dtype),
            ],
            scratch_shapes=scratch,
            # PIN fully-sequential grid semantics: the dk/dv output blocks
            # (index map ignores j) are revisited non-consecutively across
            # (h, i) passes, and correctness relies on the final in-order
            # copy-out at (last q-head of the group, i=n_q-1) overwriting
            # every earlier flush. That only holds under 'arbitrary'
            # (sequential) dimension semantics — a parallel/Mosaic-
            # pipelined grid would silently corrupt gradients, so the
            # assumption is made explicit rather than inherited as a
            # default (ADVICE r4).
            compiler_params=_compiler_params(),
            interpret=interpret,
        )(*args)
        return dq, dk, dv

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))

    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, q_spec]
    args = [q, k, v, do, lse4, out]
    scratch = [
        pltpu.VMEM((bq, hd), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
    ]
    if rope:
        specs, extra = _rope_operands(bq, bk, hd, cos, sin, q_major=True)
        in_specs += specs
        args += extra
        scratch += [
            pltpu.VMEM((bq, hd), q.dtype),
            pltpu.VMEM((Sk, hd), k.dtype),
        ]
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, n_k=n_k, rope=rope, group=group,
        ),
        grid=(B, H, n_q, n_k),
        in_specs=in_specs,
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype)],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args)[0]

    # dk/dv at q-head granularity (grid swaps the two inner axes)
    q_spec2 = pl.BlockSpec((1, 1, bq, hd), lambda b, h, j, i: (b, h, i, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h // group, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0))
    dkv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0))

    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, q_spec2]
    args2 = [q, k, v, do, lse4, out]
    scratch2 = [
        pltpu.VMEM((bk, hd), jnp.float32),
        pltpu.VMEM((bk, hd), jnp.float32),
    ]
    if rope:
        specs, extra = _rope_operands(bq, bk, hd, cos, sin, q_major=False)
        in_specs2 += specs
        args2 += extra
        scratch2 += [
            pltpu.VMEM((Sq, hd), q.dtype),
            pltpu.VMEM((bk, hd), k.dtype),
        ]
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, n_q=n_q, rope=rope,
        ),
        grid=(B, H, n_k, n_q),
        in_specs=in_specs2,
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, hd), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, hd), v.dtype),
        ],
        scratch_shapes=scratch2,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args2)
    dk = dk_h.reshape(B, KV, group, Sk, hd).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, KV, group, Sk, hd).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, block_q, block_k, bwd_block_q, bwd_block_k, interpret):
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, bwd_block_q, bwd_block_k, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _fwd(q, k, v, causal, block_q, block_k, interpret)
    # named so a remat policy can SAVE the kernel's residuals: no policy
    # can name a custom-call output, so without these tags `lse` is never
    # saveable and jax.checkpoint must re-run the whole forward kernel in
    # the backward pass (profiled at ~43ms/step on the bench model)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, bwd_block_q, bwd_block_k, interpret, res, do):
    return _bwd_pallas(res, do, causal, bwd_block_q, bwd_block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_rope(
    q, k, v, cos, sin,
    causal, block_q, block_k, bwd_block_q, bwd_block_k, interpret,
):
    """Fused-rope variant: takes PRE-rope q/k plus the rope tables; the
    kernels rotate tiles in VMEM (fwd and bwd), and the backward emits
    gradients w.r.t. the pre-rope inputs via the inverse rotation. The
    XLA-side rope (rotate + concat + relayout over whole [B,S,H,hd]
    arrays, fwd and again in bwd) profiled at ~37ms/step on the bench
    model; in-kernel it is a [rows, hd] VPU epilogue."""
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret, cos=cos, sin=sin)
    return out


def _flash_rope_fwd(
    q, k, v, cos, sin,
    causal, block_q, block_k, bwd_block_q, bwd_block_k, interpret,
):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _fwd(q, k, v, causal, block_q, block_k, interpret, cos=cos, sin=sin)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, cos, sin, out, lse)


def _flash_rope_bwd(
    causal, block_q, block_k, bwd_block_q, bwd_block_k, interpret, res, do
):
    dq, dk, dv = _bwd_pallas(res, do, causal, bwd_block_q, bwd_block_k, interpret)
    # the rope tables are iota-derived constants, not trainable state:
    # symbolic zeros would be ideal but custom_vjp wants real arrays; XLA
    # DCEs these
    return dq, dk, dv, jnp.zeros_like(res[3]), jnp.zeros_like(res[4])


_flash_rope.defvjp(_flash_rope_fwd, _flash_rope_bwd)


# optimize_remat must stay OFF: its remat_opt machinery re-runs the
# forward kernel in the backward scan REGARDLESS of checkpoint policy
# (verified by counting _fwd_kernel custom-calls in the lowered HLO).
# Instead the residuals are tagged with checkpoint_name in _flash_fwd and
# the name-saving remat policies ("dots_flash" default, "flash_rope" the
# measured bench winner — models/llama.remat_policy_for) save them; with
# that pairing the lowered module contains exactly ONE _fwd_kernel, and
# tests/test_ops.py::TestRematKernelCounts guards the property. Under
# plain "dots" the backward re-runs it (~43ms/step profiled).
_flash.defvjp(_flash_fwd, _flash_bwd)


#: Times the pallas kernel was traced into a compiled graph. Incremented at
#: trace time (once per compile, not per step) — bench.py asserts this is
#: nonzero to prove the fused kernel is in the hot path, not the oracle.
TRACE_COUNT = 0


def flash_attention(
    q: jax.Array,  # [B, S, H, hd] — llama layout
    k: jax.Array,  # [B, S, KV, hd]
    v: jax.Array,
    causal: bool = True,
    mask: Optional[jax.Array] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    bwd_block_q: int = 1024,
    bwd_block_k: int = 1024,
    interpret: bool = False,
    rope_cos: Optional[jax.Array] = None,  # [S, hd/2]: fuse rotary into
    rope_sin: Optional[jax.Array] = None,  # the kernel (q/k arrive PRE-rope)
) -> jax.Array:
    """Drop-in for `kubedl_tpu.models.llama.attention` (same signature, so
    it slots into `llama_forward(..., attn_fn=flash_attention)`). Arbitrary
    masks go to the dense oracle — flash handles the causal/full cases
    that training uses; a sequence length no tiling fits raises. Forward
    and backward kernels tile independently. Default 1024x1024 tiles are the measured v5e sweet spot
    in-model (S=2048, hd=64: 649ms fwd+bwd for the 24-layer bench model vs
    974ms at 256-tiles, 1673ms for the stock jax pallas TPU kernel; 2048
    tiles exceed VMEM). Small sequences clamp blocks to S automatically."""
    if mask is not None:
        from kubedl_tpu.models.llama import apply_rope, attention

        if rope_cos is not None:  # the dense route still applies the rotary
            q = apply_rope(q, rope_cos, rope_sin)
            k = apply_rope(k, rope_cos, rope_sin)
        return attention(q, k, v, causal=causal, mask=mask)
    qt = q.transpose(0, 2, 1, 3)  # [B, H, S, hd]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    S = qt.shape[2]
    # fit every tiling to the actual sequence length (a seq divisible by
    # 128 but not by the preferred block shrinks the block, not the path)
    bq = fit_block(S, block_q)
    bk = fit_block(S, block_k)
    bwd_q = fit_block(S, bwd_block_q)
    bwd_k = fit_block(S, bwd_block_k)
    if not (bq and bk and bwd_q and bwd_k):
        raise ValueError(
            f"flash attention cannot tile seq_len={S}: not a multiple of "
            f"128 and longer than one block ({block_q}x{block_k})"
        )
    # counted only on the actual kernel path — the masked dense route must
    # not satisfy the bench's "pallas kernel really traced" gate
    global TRACE_COUNT
    TRACE_COUNT += 1
    if rope_cos is not None:
        cos32 = rope_cos.astype(jnp.float32)
        sin32 = rope_sin.astype(jnp.float32)
        out = _flash_rope(
            qt, kt, vt, cos32, sin32, causal, bq, bk, bwd_q, bwd_k, interpret
        )
    else:
        out = _flash(qt, kt, vt, causal, bq, bk, bwd_q, bwd_k, interpret)
    return out.transpose(0, 2, 1, 3)


def fit_block(seq_len: int, want: int) -> int:
    """Largest legal block <= ``want`` for this sequence length: the whole
    sequence if it fits in one block, else the largest multiple-of-128
    divisor (mosaic tiling wants 128-lane-aligned score tiles). 0 = no
    legal block — flash_attention raises."""
    if seq_len <= want:
        return seq_len
    for b in range(min(want, seq_len), 127, -128):
        if b % 128 == 0 and seq_len % b == 0:
            return b
    return 0


def supports(seq_len: int, block_q: int = 1024, block_k: int = 1024) -> bool:
    """Whether a legal tiling exists for this shape (a seq divisible by 128
    always tiles — the block shrinks below the preferred size if needed)."""
    return fit_block(seq_len, block_q) > 0 and fit_block(seq_len, block_k) > 0


def make_flash_attention(
    mesh,
    batch_axes: Tuple[str, ...] = ("replica", "data", "fsdp"),
    head_axis: str = "tensor",
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
):
    """Mesh-aware flash attention for the trainer hot path.

    pallas_call can't be auto-partitioned by XLA's SPMD partitioner, so on a
    multi-device mesh the kernel is wrapped in `shard_map` over the batch
    (data-like) and head (tensor) axes — attention is embarrassingly
    parallel over both, so the body needs no collectives. On a trivial mesh
    the kernel is called directly.
    """
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    bt = tuple(
        a for a in batch_axes if a in mesh.axis_names and mesh.shape[a] > 1
    )
    ht = (
        head_axis
        if head_axis in mesh.axis_names and mesh.shape[head_axis] > 1
        else None
    )

    if not bt and ht is None:

        def direct(q, k, v, causal=True, mask=None, rope_cos=None,
                   rope_sin=None):
            return flash_attention(
                q, k, v, causal=causal, mask=mask,
                block_q=block_q, block_k=block_k, interpret=interpret,
                rope_cos=rope_cos, rope_sin=rope_sin,
            )

        direct.fused_rope = True  # callers may pass q/k PRE-rope + tables
        return direct

    def build(head, rope):
        spec = P(bt if bt else None, None, head, None)  # [B, S, H, hd]
        rope_spec = P(None, None)  # [S, hd/2], replicated (S not sharded)
        fn = functools.partial(
            flash_attention, causal=True,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        if rope:
            body = lambda q, k, v, cos, sin: fn(q, k, v, rope_cos=cos,
                                                rope_sin=sin)
            in_specs = (spec, spec, spec, rope_spec, rope_spec)
        else:
            body = fn
            in_specs = (spec, spec, spec)
        inner = shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=spec,
            check_vma=False,
        )
        return NamedSharding(mesh, spec), inner

    variants = {
        (key, rope): build(key, rope)
        for key in ({None, ht} if ht is not None else {None})
        for rope in (False, True)
    }

    def attn_fn(q, k, v, causal=True, mask=None, rope_cos=None,
                rope_sin=None):
        if mask is not None or not causal:
            from kubedl_tpu.models.llama import apply_rope, attention

            if rope_cos is not None:
                q = apply_rope(q, rope_cos, rope_sin)
                k = apply_rope(k, rope_cos, rope_sin)
            return attention(q, k, v, causal=causal, mask=mask)
        # head sharding needs every head count divisible by the axis
        t = mesh.shape[ht] if ht is not None else 1
        key = ht if ht is not None and q.shape[2] % t == 0 and k.shape[2] % t == 0 else None
        sharding, inner = variants[(key, rope_cos is not None)]
        q = jax.lax.with_sharding_constraint(q, sharding)
        k = jax.lax.with_sharding_constraint(k, sharding)
        v = jax.lax.with_sharding_constraint(v, sharding)
        if rope_cos is not None:
            return inner(q, k, v, rope_cos.astype(jnp.float32),
                         rope_sin.astype(jnp.float32))
        return inner(q, k, v)

    attn_fn.fused_rope = True
    return attn_fn
