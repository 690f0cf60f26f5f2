"""The state-space recurrence of a Mamba-2 mixer, three ways and a kernel.

For one head with input ``x_t`` in R^P, decay ``a_t = dt_t * A`` (``A < 0``,
``dt_t >= 0``) and the group's ``B_t``, ``C_t`` in R^N::

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T        (S in R^{P x N})
    y_t = S_t C_t

- :func:`ssd_chunked` is what prefill runs: the block decomposition of the
  same sum ("state-space duality"). Inside a chunk of ``chunk`` tokens the
  output is a masked matrix product (``C B^T`` weighted by the decay between
  the two positions); between chunks the state is carried by the recurrence,
  one step a chunk. No token-by-token loop.
- :func:`ssd_step` is what decode runs: one step of the recurrence on the
  carried state, every row's.
- :func:`ssd_step_rows` is that step as a Pallas kernel over the WHOLE state
  of a served batch, ``[B, L, H, P, N]`` with the layer in the index, for
  the rows a decode dispatch scheduled and no other: what decode runs on a
  TPU where :func:`step_kernel_fits`.
- :func:`ssd_sequential` is the plain twin the tests hold both to: a
  ``lax.scan`` over tokens.

All three take ``dt`` already through its softplus and work in float32; the
``D x`` skip term, the gate and the norm belong to the mixer
(``models/hybrid_ssm.py``). A position with ``dt = 0`` leaves the state as it
was and adds nothing: that is how a padded tail is made to do no harm.
One group of ``B``/``C`` is shared by every head (``n_groups`` 1); the
functions take them as ``[..., N]`` without a group axis.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def ssd_sequential(
    x: jax.Array,  # [B, S, H, P]
    dt: jax.Array,  # [B, S, H], after softplus; 0 at padded positions
    A: jax.Array,  # [H], negative
    Bm: jax.Array,  # [B, S, N]
    Cm: jax.Array,  # [B, S, N]
    state: jax.Array,  # [B, H, P, N]
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence token by token. Returns ``(y [B, S, H, P], state)``."""
    f32 = jnp.float32
    x, dt, Bm, Cm = (t.astype(f32) for t in (x, dt, Bm, Cm))

    def step(S, inp):
        xt, dtt, bt, ct = inp  # [B,H,P] [B,H] [B,N] [B,N]
        S, y = ssd_step(xt, dtt, A, bt, ct, S)
        return S, y

    state, ys = lax.scan(
        step, state.astype(f32),
        (x.swapaxes(0, 1), dt.swapaxes(0, 1), Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)),
    )
    return ys.swapaxes(0, 1), state


def ssd_step(
    x: jax.Array,  # [B, H, P]
    dt: jax.Array,  # [B, H]
    A: jax.Array,  # [H]
    Bm: jax.Array,  # [B, N]
    Cm: jax.Array,  # [B, N]
    state: jax.Array,  # [B, H, P, N] float32
) -> Tuple[jax.Array, jax.Array]:
    """One step for every row. Returns ``(state, y [B, H, P])``, float32."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))  # [B, H]
    dx = dt[..., None] * x.astype(f32)  # [B, H, P]
    state = (decay[..., None, None] * state
             + dx[..., None] * Bm.astype(f32)[:, None, None, :])
    y = jnp.einsum("bhpn,bn->bhp", state, Cm.astype(f32))
    return state, y


#: the kernel's name in a device profile and in compiled text
STEP_KERNEL_NAME = "ssd_step_rows"
#: heads one DMA of the kernel moves: a slab arrives and leaves in pieces of
#: this many heads, so that the first is computed while the others still fly
#: (8 and 16 read alike at 3 rows and 8 a little faster at more, 32 and 64
#: slower at every count: PERF.md section 6, PR 40)
_HEADS_PER_DMA = 8
#: slabs the kernel holds at once: one arriving, one in hand, one leaving
_SLOTS = 3


def step_kernel_fits(state: jax.Array) -> bool:
    """Whether :func:`ssd_step_rows` can take ``state [B, L, H, P, N]`` as it
    stands: float32, and a head's ``[P, N]`` plane whole tiles of 8 sublanes
    by 128 lanes. Anything else (the tiny test preset's ``N`` = 16) takes
    :func:`ssd_step`."""
    return (state.ndim == 5 and state.dtype == jnp.float32
            and state.shape[-1] % 128 == 0 and state.shape[-2] % 8 == 0)


def scheduled_rows(live: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``live [B]`` bool as the kernel's work list: ``(rows [B] int32, the
    scheduled rows' indices first, ascending; count [] int32)``."""
    rows = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    return rows, jnp.sum(live, dtype=jnp.int32)


def _step_rows_kernel(
    rows_ref, at_ref,  # scalar prefetch: [B] the work list, [2] (its length, the layer)
    decay_ref,  # SMEM [B, H]: exp(dt A)
    dx_ref,  # VMEM [B, P, W]: dt x, head h's P values down column h; W whole lanes
    b_ref, c_ref,  # VMEM [B, 1, N]
    s_hbm,  # the state whole, [B * L, H, P, N], left in HBM
    y_ref,  # VMEM [B, P, W]
    s_out,  # the same array (aliased): written where it was read, nowhere else
    buf,  # VMEM [_SLOTS, H, P, N]
    sems,  # DMA semaphores [2 (in, out), _SLOTS, pieces]
    *, layers: int, piece: int,
):
    """One step of the recurrence for the ``at_ref[0]`` rows listed first in
    ``rows_ref``, on layer ``at_ref[1]``'s slabs, in ONE invocation. A slab
    ``[H, P, N]`` is brought in once, in pieces of ``piece`` heads, into one
    of three buffers; head by head ``S' = decay S + dx B^T`` is computed in
    the buffer and ``y = S' C`` summed from it; each piece is written back
    where it came from as soon as it is done, while the next row's slab
    arrives. A row that is not listed is never named in a DMA: its slab is
    neither read nor written, and its ``y`` is zeros.

    ``dx`` broadcasts along the lanes and ``y`` is summed along them, so both
    cross the kernel's boundary with ``P`` down the sublanes, a head a column
    (the transposes are XLA's, over ``[B, H, P]``). A piece's heads are
    computed by straight-line code and the pieces by a loop that rolls the
    row's ``dx`` a piece's columns on, so that the code names static columns
    and is a piece long, not a slab (at 32 rows 7.8 µs a slab where the
    DMAs alone take 6.8; a loop over single heads with a roll a head read
    12.8, all 64 heads unrolled 7.6 for eight times the code to trace:
    PERF.md section 6, PR 40)."""
    from jax.experimental.pallas import tpu as pltpu

    _, P, W = dx_ref.shape
    H = buf.shape[1]
    n_rows, layer = at_ref[0], at_ref[1]
    pieces = H // piece

    def copy(i, q, out):
        """The DMA of piece ``q`` of list entry ``i``'s slab: in, or out."""
        slot, slab = lax.rem(i, _SLOTS), rows_ref[i] * layers + layer
        heads = pl.ds(q * piece, piece)
        if out:
            return pltpu.make_async_copy(
                buf.at[slot, heads], s_out.at[slab, heads], sems.at[1, slot, q])
        return pltpu.make_async_copy(
            s_hbm.at[slab, heads], buf.at[slot, heads], sems.at[0, slot, q])

    def each_piece(i, out, act):
        lax.fori_loop(0, pieces, lambda q, _: act(copy(i, q, out)), None)

    start, wait = (lambda dma: dma.start()), (lambda dma: dma.wait())
    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n_rows > 0)
    def _first():
        each_piece(0, False, start)

    def one_row(i, carry):
        @pl.when(i >= _SLOTS - 1)
        def _freed():  # the buffer the next slab takes has left
            each_piece(i - (_SLOTS - 1), True, wait)

        @pl.when(i + 1 < n_rows)
        def _next():
            each_piece(i + 1, False, start)

        slot, row = lax.rem(i, _SLOTS), rows_ref[i]
        b, c = b_ref[row], c_ref[row]  # [1, N]
        column = lax.broadcasted_iota(jnp.int32, (P, W), 1)

        def one_piece(q, carry):
            dx, y = carry  # [P, W]: this piece's heads stand in the first columns
            copy(i, q, False).wait()
            for j in range(piece):
                h = q * piece + j
                s = decay_ref[row, h] * buf[slot, h] + dx[:, j:j + 1] * b  # [P, N]
                buf[slot, h] = s
                y = jnp.where(column == h, jnp.sum(s * c, axis=1, keepdims=True), y)
            copy(i, q, True).start()
            return pltpu.roll(dx, W - piece, axis=1), y

        _, y_ref[row] = lax.fori_loop(
            0, pieces, one_piece, (dx_ref[row], jnp.zeros((P, W), jnp.float32)))
        return carry

    lax.fori_loop(0, n_rows, one_row, 0)
    for back in range(_SLOTS - 1, 0, -1):  # what is still on its way out
        @pl.when(n_rows >= back)
        def _left(back=back):
            each_piece(n_rows - back, True, wait)


# jitted: a segment program reaches it from two sites, each inside nested
# scans that trace their bodies more than once, and traces the kernel once
@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step_rows(
    x: jax.Array,  # [B, H, P]
    dt: jax.Array,  # [B, H]
    A: jax.Array,  # [H]
    Bm: jax.Array,  # [B, N]
    Cm: jax.Array,  # [B, N]
    state: jax.Array,  # [B, L, H, P, N] float32: every row's, every layer's
    layer: jax.Array,  # scalar: which layer's slabs
    rows: jax.Array,  # [B] int32, count []: :func:`scheduled_rows`
    count: jax.Array,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`ssd_step` on layer ``layer``'s slabs of the first ``count`` rows
    of ``rows``, in place. Returns ``(state, y [B, H, P])``, float32: the
    arithmetic is :func:`ssd_step`'s (only the order of the sum over ``N`` in
    ``y`` is the kernel's own); a row that is not listed keeps its slab bit
    for bit, because nothing of it is fetched or written, and its ``y`` is
    zeros. On a TPU ``state`` has to pass :func:`step_kernel_fits`;
    ``interpret=True`` (tests) runs the kernel through the interpreter on
    any backend and at any shape."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, L, H, P, N = state.shape
    piece = _HEADS_PER_DMA if H % _HEADS_PER_DMA == 0 else H
    W = -(-H // 128) * 128  # a head a lane, whole lane tiles: what the roll takes
    dt = dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))  # [B, H]
    dx = (dt[..., None] * x.astype(f32)).swapaxes(1, 2)  # [B, P, H]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, state = pl.pallas_call(
        functools.partial(_step_rows_kernel, layers=L, piece=piece),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem, hbm],
            out_specs=[vmem, hbm],
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, H, P, N), f32),
                pltpu.SemaphoreType.DMA((2, _SLOTS, H // piece)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, P, W), f32),
                   jax.ShapeDtypeStruct((B * L, H, P, N), f32)],
        input_output_aliases={6: 1},  # the state, counted with the prefetched two
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(_SLOTS + 1) * H * P * N * 4 + (16 << 20)),
        interpret=interpret,
        name=STEP_KERNEL_NAME,
    )(rows, jnp.stack([count, jnp.asarray(layer, jnp.int32)]), decay,
      jnp.pad(dx, ((0, 0), (0, 0), (0, W - H))),
      Bm.astype(f32)[:, None, :], Cm.astype(f32)[:, None, :],
      state.reshape(B * L, H, P, N))
    return state.reshape(B, L, H, P, N), y[:, :, :H].swapaxes(1, 2)


def ssd_chunked(
    x: jax.Array,  # [B, S, H, P]
    dt: jax.Array,  # [B, S, H], after softplus; 0 at padded positions
    A: jax.Array,  # [H], negative
    Bm: jax.Array,  # [B, S, N]
    Cm: jax.Array,  # [B, S, N]
    state: jax.Array,  # [B, H, P, N] float32: the state before position 0
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """The same sum by chunks of ``chunk`` positions (one chunk of ``S`` where
    ``S`` is shorter). Returns ``(y [B, S, H, P],
    state after position S-1)``, float32.

    With ``La_i`` the running sum of ``a`` inside a chunk (inclusive), for a
    position ``i`` of chunk ``c`` and the state ``S_in`` entering that chunk::

        y_i = sum_{j <= i} (C_i . B_j) exp(La_i - La_j) dt_j x_j      (inside)
            + exp(La_i) S_in C_i                                       (carried)
        S_out = exp(La_last) S_in + sum_j exp(La_last - La_j) dt_j x_j B_j^T

    Every exponent is a sum of non-positive terms, so nothing overflows."""
    f32 = jnp.float32
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(int(chunk), S)
    if S % Q:  # a last chunk's tail: padded with dt = 0, which changes nothing
        pad = [(0, 0), (0, Q - S % Q)]
        y, state = ssd_chunked(
            jnp.pad(x, pad + [(0, 0), (0, 0)]), jnp.pad(dt, pad + [(0, 0)]), A,
            jnp.pad(Bm, pad + [(0, 0)]), jnp.pad(Cm, pad + [(0, 0)]), state, Q)
        return y[:, :S], state
    nc = S // Q
    xc = x.astype(f32).reshape(B, nc, Q, H, P)
    dtc = dt.astype(f32).reshape(B, nc, Q, H)
    Bc = Bm.astype(f32).reshape(B, nc, Q, N)
    Cc = Cm.astype(f32).reshape(B, nc, Q, N)
    La = jnp.cumsum(dtc * A.astype(f32), axis=2)  # [B, nc, Q, H], <= 0
    dx = dtc[..., None] * xc  # [B, nc, Q, H, P]

    # inside a chunk: a lower-triangular [Q, Q] product a head
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # C_i . B_j, every head's
    Lah = La.swapaxes(2, 3)  # [B, nc, H, Q]
    seg = Lah[..., :, None] - Lah[..., None, :]  # [B, nc, H, i, j]
    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    # masked BEFORE the exponential: above the diagonal La_i - La_j > 0
    M = G[:, :, None] * jnp.exp(jnp.where(tri, seg, -jnp.inf))
    y = jnp.einsum("bchij,bcjhp->bcihp", M, dx)

    # what each chunk adds to the state it hands on, and its whole decay
    to_end = jnp.exp(La[:, :, -1:, :] - La)  # [B, nc, Q, H]
    add = jnp.einsum("bcjhp,bcjn->bchpn", to_end[..., None] * dx, Bc)
    whole = jnp.exp(La[:, :, -1, :])  # [B, nc, H]

    def carry(S_in, inp):
        add_c, whole_c = inp
        return whole_c[..., None, None] * S_in + add_c, S_in

    state, entering = lax.scan(
        carry, state.astype(f32), (add.swapaxes(0, 1), whole.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)  # [B, nc, H, P, N]
    y = y + jnp.exp(La)[..., None] * jnp.einsum("bcin,bchpn->bcihp", Cc, entering)
    return y.reshape(B, S, H, P), state
