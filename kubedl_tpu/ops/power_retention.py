"""Power retention of degree 2: the state-free form, two recurrent forms and a
kernel.

For a query head ``h`` of key group ``g`` with a decay ``gamma_t`` in (0, 1] a
group, a token (Buckman, Gelada, Zhang et al., "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239)::

    w_ts = exp(sum_{r=s+1..t} log gamma_r) (q_t . k_s)^2          (s <= t)
    y_t  = sum_s w_ts v_s / (sum_s w_ts + eps)

``(a . b)^2 = phi(a) . phi(b)`` for the symmetric square ``phi`` of a vector, so
the same sum is a recurrence on a state of ``phi(k) v^T``::

    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T       z_t = gamma_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

**The feature map** (:func:`phi`). The ``hd (hd + 1) / 2`` distinct products
``x_i x_j`` are laid out by diagonals of the outer product taken round the
corner: ``phi(x)[d, i] = c_d x_i x_{(i - d) mod hd}`` for ``d = 0 .. hd / 2``,
``c_0 = 1`` (the squares), ``c_d = sqrt 2`` (each pair ``{i, i - d}`` stands
once), and ``c_{hd/2} = 1`` (each pair ``{i, i - hd / 2}`` stands twice). That
is ``hd / 2 + 1`` rows of ``hd``: 65 x 128 = 8,320 for a head of 128, 0.8% over
the packed 8,256, and every row is the vector times a rotation of itself, which
the kernel makes with one lane roll and the chunked form with one product by
a matrix of zeros and ones: nothing is gathered and ``phi`` is never stored a
token. The state lies as ``S [.., hd / 2 + 1, hd (v), hd (i)]`` (a tile a
diagonal, the value down the sublanes) and ``z [.., hd / 2 + 1, hd]``.

- :func:`retention_reference`: the state-free sum above, the oracle.
- :func:`retention_chunked`: what prefill runs. Inside a chunk the masked
  ``[chunk, chunk]`` weights; between chunks the state, one step a chunk. A
  first chunk that starts a sequence reads no state.
- :func:`retention_step`: one step of the recurrence for every row: what
  decode runs on a CPU.
- :func:`retention_step_rows`: that step as a Pallas kernel over the WHOLE
  state of a served batch, ``[B, L, KV, ..]`` with the layer in the index, for
  the rows a decode dispatch scheduled and no other: what decode runs on a TPU
  where :func:`step_kernel_fits`.

All work in float32 with every product at the highest precision. A position
with ``log gamma = 0`` and ``k = 0`` leaves the state as it was and adds
nothing: that is how a padded tail, and a row a step does not advance, are
made to do no harm.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

#: added to the normaliser
EPS = 1e-6
_HIGHEST = lax.Precision.HIGHEST


def diagonals(hd: int) -> int:
    """Rows of :func:`phi` for a head of ``hd``."""
    if hd % 2:
        raise ValueError(f"head size {hd} is odd: the diagonals pair up round the corner")
    return hd // 2 + 1


def diagonal_weights(hd: int) -> np.ndarray:
    """``c_d``: 1 for the squares and for the half-way diagonal, sqrt 2 between."""
    c = np.full((diagonals(hd),), math.sqrt(2.0), np.float32)
    c[0] = c[-1] = 1.0
    return c


def features(hd: int) -> int:
    """Entries of :func:`phi`: 8,320 for a head of 128 (8,256 distinct products)."""
    return diagonals(hd) * hd


@functools.lru_cache(maxsize=None)
def _rotations(hd: int) -> np.ndarray:
    """``[hd, (hd / 2 + 1) hd]`` of zeros and ones: ``x @ _rotations`` is the
    ``hd / 2 + 1`` rotations of ``x`` side by side, ``roll(x, d)`` the ``d``-th."""
    out = np.zeros((hd, diagonals(hd), hd), np.float32)
    i = np.arange(hd)
    for d in range(diagonals(hd)):
        out[(i - d) % hd, d, i] = 1.0
    return out.reshape(hd, -1)


def phi(x: jax.Array) -> jax.Array:
    """``x [..., hd] -> [..., hd / 2 + 1, hd]`` float32 with ``phi(a) . phi(b) =
    (a . b)^2``: row ``d`` is ``c_d x * roll(x, d)``. The rotations are one
    product with a matrix of zeros and ones, which copies and so is exact
    (in one pass for a bfloat16 ``x``): a stack of 65 rolls is 65 strided
    writes into the result, and cost a prefill program more than its
    matrices (PERF.md section 6, PR 42)."""
    hd = x.shape[-1]
    rolled = jnp.einsum(
        "...j,jf->...f", x, jnp.asarray(_rotations(hd), x.dtype),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    ).reshape(*x.shape[:-1], diagonals(hd), hd)
    return jnp.asarray(diagonal_weights(hd))[:, None] * x.astype(jnp.float32)[..., None, :] * rolled


def retention_reference(
    q: jax.Array,  # [S, H, hd]
    k: jax.Array,  # [S, KV, hd]
    v: jax.Array,  # [S, KV, hd]
    log_g: jax.Array,  # [S, KV], <= 0
    eps: float = EPS,
) -> jax.Array:
    """The state-free form for one sequence from an empty state: the dense
    masked ``[S, S]`` weights of every head. Returns ``y [S, H, hd]`` float32."""
    f32 = jnp.float32
    S, H, hd = q.shape
    KV = k.shape[1]
    q = q.astype(f32).reshape(S, KV, H // KV, hd)
    a = jnp.cumsum(log_g.astype(f32), axis=0)  # [S, KV]
    scores = jnp.einsum("tgrd,sgd->grts", q, k.astype(f32), precision=_HIGHEST)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    span = a.T[:, :, None] - a.T[:, None, :]  # [KV, t, s]
    # masked BEFORE the exponential: above the diagonal a_t - a_s > 0
    w = jnp.exp(jnp.where(causal, span, -jnp.inf))[:, None] * scores * scores
    num = jnp.einsum("grts,sgv->tgrv", w, v.astype(f32), precision=_HIGHEST)
    den = jnp.sum(w, axis=-1).transpose(2, 0, 1)  # [S, KV, G]
    return (num / (den + eps)[..., None]).reshape(S, H, hd)


def retention_step(
    q: jax.Array,  # [B, H, hd]
    k: jax.Array,  # [B, KV, hd]
    v: jax.Array,  # [B, KV, hd]
    log_g: jax.Array,  # [B, KV]
    S: jax.Array,  # [B, KV, ND, hd, hd] float32
    z: jax.Array,  # [B, KV, ND, hd] float32
    eps: float = EPS,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One step for every row. Returns ``(S, z, y [B, H, hd])``, float32."""
    f32 = jnp.float32
    B, H, hd = q.shape
    KV = k.shape[1]
    g = jnp.exp(log_g.astype(f32))
    fk = phi(k)  # [B, KV, ND, hd]
    S = g[..., None, None, None] * S + fk[:, :, :, None, :] * v.astype(f32)[:, :, None, :, None]
    z = g[..., None, None] * z + fk
    fq = phi(q.reshape(B, KV, H // KV, hd))  # [B, KV, G, ND, hd]
    num = jnp.einsum("bgrdi,bgdvi->bgrv", fq, S, precision=_HIGHEST)
    den = jnp.einsum("bgrdi,bgdi->bgr", fq, z, precision=_HIGHEST)
    return S, z, (num / (den + eps)[..., None]).reshape(B, H, hd)


def _chunk_head(q, k, v, lg, S, z, skip, eps):
    """One chunk of one key group of one sequence: ``q [C, G, hd]``, ``k``,
    ``v [C, hd]``, ``lg [C]``, the state entering, ``skip`` (a scalar bool:
    the state is known to be zeros and is not read)."""
    f32 = jnp.float32
    C, G, hd = q.shape
    a = jnp.cumsum(lg)  # [C], <= 0
    # exact products of the inputs' own type (one pass where that is bfloat16)
    scores = jnp.einsum("trd,sd->rts", q, k, precision=_HIGHEST,
                        preferred_element_type=f32)  # [G, C, C]
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    w = jnp.exp(jnp.where(causal, a[:, None] - a[None, :], -jnp.inf)) * scores * scores
    num = jnp.einsum("rts,sv->trv", w, v.astype(f32), precision=_HIGHEST)
    den = jnp.sum(w, axis=-1).T  # [C, G]

    def carried():
        fq = phi(q)  # [C, G, ND, hd]: a chunk of one group at a time, never all heads
        n = jnp.einsum("trdi,dvi->trv", fq, S, precision=_HIGHEST)
        d = jnp.einsum("trdi,di->tr", fq, z, precision=_HIGHEST)
        into = jnp.exp(a)
        return into[:, None, None] * n, into[:, None] * d

    n2, d2 = lax.cond(skip, lambda: (jnp.zeros((C, G, hd), f32), jnp.zeros((C, G), f32)),
                      carried)
    y = (num + n2) / (den + d2 + eps)[..., None]
    fk = jnp.exp(a[-1] - a)[:, None, None] * phi(k)  # [C, ND, hd]
    whole = jnp.exp(a[-1])
    S = whole * S + jnp.einsum("sdi,sv->dvi", fk, v.astype(f32), precision=_HIGHEST)
    z = whole * z + jnp.sum(fk, axis=0)
    return y, S, z


def retention_chunked(
    q: jax.Array,  # [B, T, H, hd]
    k: jax.Array,  # [B, T, KV, hd]; zeros at padded positions
    v: jax.Array,  # [B, T, KV, hd]
    log_g: jax.Array,  # [B, T, KV]; 0 at padded positions
    S: jax.Array,  # [B, KV, ND, hd, hd] float32: the state before position 0
    z: jax.Array,  # [B, KV, ND, hd] float32
    chunk: int,
    first_reads_no_state: Optional[jax.Array] = None,
    eps: float = EPS,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The same sum by chunks of ``chunk`` positions (one chunk of ``T`` where
    ``T`` is shorter). Returns ``(y [B, T, H, hd], S, z)`` after position
    ``T - 1``, float32.

    With ``a_t`` the running sum of ``log gamma`` inside a chunk (inclusive)
    and ``S_in``, ``z_in`` the state entering it::

        num_t = sum_{s <= t} exp(a_t - a_s) (q_t . k_s)^2 v_s + exp(a_t) phi(q_t)^T S_in
        den_t = sum_{s <= t} exp(a_t - a_s) (q_t . k_s)^2     + exp(a_t) phi(q_t)^T z_in
        S_out = exp(a_C) S_in + sum_s exp(a_C - a_s) phi(k_s) v_s^T

    Every exponent is a sum of non-positive terms, so nothing overflows.
    ``first_reads_no_state`` (a scalar bool, traced): the state handed in is
    zeros for every row, and the first chunk takes no product with it (its
    65 x 128 x 128 a group, a token: five times the chunk's own work at 1,024
    tokens). One key group is computed at a time, so ``phi`` of a chunk's
    queries stands for five heads, not forty."""
    f32 = jnp.float32
    B, T, H, hd = q.shape
    KV = k.shape[2]
    C = min(int(chunk), T)
    if T % C:  # a last chunk's tail: padded with log gamma = 0 and k = 0
        pad = [(0, 0), (0, C - T % C), (0, 0), (0, 0)]
        y, S, z = retention_chunked(
            jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), jnp.pad(log_g, pad[:3]),
            S, z, C, first_reads_no_state, eps)
        return y[:, :T], S, z
    nc = T // C
    skip_first = jnp.asarray(False if first_reads_no_state is None else first_reads_no_state)

    def chunks(t, *tail):  # [B, T, ...] -> [nc, B, C, ...], in its own type
        return t.reshape(B, nc, C, *tail).swapaxes(0, 1)

    def one_chunk(state, inp):
        S, z = state
        c, qc, kc, vc, lc = inp
        skip = skip_first & (c == 0)

        def one_row(qr, kr, vr, lr, Sr, zr):  # the groups in turn
            return lax.map(
                lambda t: _chunk_head(*t, skip, eps),
                (qr.swapaxes(0, 1), kr.swapaxes(0, 1), vr.swapaxes(0, 1), lr.T, Sr, zr))

        y, S, z = jax.vmap(one_row)(qc.reshape(B, C, KV, H // KV, hd), kc, vc, lc, S, z)
        return (S, z), y  # y [B, KV, C, G, hd]

    (S, z), y = lax.scan(
        one_chunk, (S.astype(f32), z.astype(f32)),
        (jnp.arange(nc), chunks(q, H, hd), chunks(k, KV, hd), chunks(v, KV, hd),
         chunks(log_g.astype(f32), KV)))
    # [nc, B, KV, C, G, hd] -> [B, T, H, hd]
    y = y.transpose(1, 0, 3, 2, 4, 5).reshape(B, T, H, hd)
    return y, S, z


# ---- the kernel --------------------------------------------------------------

#: the kernel's name in a device profile and in compiled text
STEP_KERNEL_NAME = "retention_step_rows"


def step_kernel_fits(S: jax.Array) -> bool:
    """Whether :func:`retention_step_rows` can take ``S [B, L, KV, ND, hd,
    hd]`` as it stands: float32, a diagonal's ``[hd, hd]`` plane whole tiles of
    8 sublanes by 128 lanes. Anything else (the tiny test preset's ``hd`` =
    16) takes :func:`retention_step`."""
    return (S.ndim == 6 and S.dtype == jnp.float32 and S.shape[-1] % 128 == 0
            and S.shape[-2] == S.shape[-1] and S.shape[-3] == diagonals(S.shape[-1]))


def _step_rows_kernel(
    rows_ref, at_ref,  # scalar prefetch: [B] the work list, [2] (its length, the layer)
    g_ref,  # SMEM [B, KV]: gamma
    q_ref,  # VMEM [1, 1, G, hd]: the group's query heads of the row
    k_ref, v_ref,  # VMEM [1, 1, 1, hd]
    s_ref,  # VMEM [1, 1, ND, hd, hd]: the row's state of this layer and group
    z_ref,  # VMEM [1, 1, ND, hd]
    y_ref,  # VMEM [1, 1, hd, W]: head r's output down column r
    s_out, z_out,  # the same arrays (aliased)
    fk, fq,  # VMEM [NDp, hd], [G, NDp, hd]: phi(k), phi(q), a diagonal a row
    vcol,  # VMEM [hd, hd]: v down the sublanes, the same in every lane
    *, eps: float,
):
    """One step of the recurrence for list entry ``program_id(0)`` and key
    group ``program_id(1)``: the state's tiles come in once (the pipeline's
    DMA), ``phi(k)`` and the group's ``phi(q)`` are built here from lane
    rolls, and for each block of 8 values ``S' = gamma S + v phi(k)`` is
    computed a diagonal at a time, stored, and multiplied into the five
    read-outs while it is in registers; the lanes are summed once a block.
    Entries past the list's length compute nothing and name the block of the
    last entry, which the pipeline therefore neither fetches nor writes
    again."""
    from jax.experimental.pallas import tpu as pltpu

    i, g = pl.program_id(0), pl.program_id(1)
    n_rows = at_ref[0]
    _, _, G, hd = q_ref.shape
    ND = s_ref.shape[2]
    W = y_ref.shape[-1]
    c = diagonal_weights(hd)
    f32 = jnp.float32

    @pl.when(i < n_rows)
    def _step():
        gamma = g_ref[rows_ref[i], g]

        def rows_of_phi(x):  # x [1, hd] -> ND rows [1, hd]
            xb = jnp.broadcast_to(x.astype(f32), (8, hd))
            for d in range(ND):
                yield d, (float(c[d]) * xb * (pltpu.roll(xb, d, 1) if d else xb))[0:1]

        for d, row in rows_of_phi(k_ref[0, 0]):  # rows past ND are never read
            fk[d:d + 1, :] = row
        for r in range(G):
            for d, row in rows_of_phi(q_ref[0, 0, r:r + 1]):
                fq[r, d:d + 1, :] = row
        vcol[...] = jnp.broadcast_to(v_ref[0, 0].astype(f32), (hd, hd)).T

        # the normaliser: [ND, hd], a diagonal a row
        zn = gamma * z_ref[0, 0] + fk[0:ND, :]
        z_out[0, 0] = zn
        lane = lax.broadcasted_iota(jnp.int32, (8, W), 1)
        den = jnp.zeros((8, W), f32)
        for r in range(G):
            total = jnp.sum(jnp.sum(zn * fq[r, 0:ND, :], axis=1, keepdims=True),
                            axis=0, keepdims=True)  # [1, 1]
            den = jnp.where(lane == r, total, den)
        scale = 1.0 / (den + eps)  # [8, W]: 1 / eps in the lanes of no head

        def values(b, carry):  # 8 of the value's hd entries
            at = pl.ds(pl.multiple_of(b * 8, 8), 8)
            vb = vcol[at, :]  # [8, hd]
            acc = [jnp.zeros((8, hd), f32) for _ in range(G)]
            for d in range(ND):
                s = gamma * s_ref[0, 0, d, at, :] + vb * fk[d:d + 1, :]
                s_out[0, 0, d, at, :] = s
                for r in range(G):
                    acc[r] = acc[r] + s * fq[r, d:d + 1, :]
            out = jnp.zeros((8, W), f32)
            for r in range(G):
                out = jnp.where(lane == r, jnp.sum(acc[r], axis=1, keepdims=True), out)
            y_ref[0, 0, at, :] = out * scale
            return carry

        lax.fori_loop(0, hd // 8, values, 0)

    @pl.when((n_rows == 0) & (i == 0) & (g == 0))
    def _nothing_listed():  # the one block the pipeline moves goes back as it came
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


# jitted: a segment program reaches it inside nested scans that trace their
# bodies more than once, and traces the kernel once
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def retention_step_rows(
    q: jax.Array,  # [B, H, hd]
    k: jax.Array,  # [B, KV, hd]
    v: jax.Array,  # [B, KV, hd]
    log_g: jax.Array,  # [B, KV]
    S: jax.Array,  # [B, L, KV, ND, hd, hd] float32: every row's, every layer's
    z: jax.Array,  # [B, L, KV, ND, hd] float32
    layer: jax.Array,  # scalar: which layer's slabs
    rows: jax.Array,  # [B] int32, count []: ``ssd_scan.scheduled_rows``
    count: jax.Array,
    eps: float = EPS,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`retention_step` on layer ``layer``'s slabs of the first ``count``
    rows of ``rows``, in place. Returns ``(S, z, y [B, H, hd])``, float32: the
    arithmetic is :func:`retention_step`'s (only the order of the read-out's
    sum is the kernel's own); a row that is not listed keeps its slab bit for
    bit, because nothing of it is fetched or written (with an empty list the
    pipeline moves one block, which goes back as it came), and its ``y`` is
    zeros. On a TPU ``S`` has to pass :func:`step_kernel_fits`;
    ``interpret=True`` (tests) runs the kernel through the interpreter on any
    backend and at any shape."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, L, KV, ND, hd, _ = S.shape
    H = q.shape[1]
    G = H // KV
    W = -(-G // 128) * 128  # a head a lane, whole lane tiles
    NDp = -(-ND // 8) * 8

    def of_row(i, g, rows_ref, at_ref):
        # past the list's end: the last entry's last block, which stays put
        e = jnp.minimum(i, jnp.maximum(at_ref[0] - 1, 0))
        return rows_ref[e], jnp.where(i < at_ref[0], g, KV - 1), 0, 0

    def of_slab(i, g, rows_ref, at_ref):
        row, group, *_ = of_row(i, g, rows_ref, at_ref)
        return row * L + at_ref[1], group, 0, 0, 0

    def of_norm(i, g, rows_ref, at_ref):
        return of_slab(i, g, rows_ref, at_ref)[:4]

    slab = pl.BlockSpec((1, 1, ND, hd, hd), of_slab)
    norm = pl.BlockSpec((1, 1, ND, hd), of_norm)
    block = 4 * ND * hd * hd
    y, S, z = pl.pallas_call(
        functools.partial(_step_rows_kernel, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, G, hd), of_row),
                pl.BlockSpec((1, 1, 1, hd), of_row),
                pl.BlockSpec((1, 1, 1, hd), of_row),
                slab, norm,
            ],
            out_specs=[pl.BlockSpec((1, 1, hd, W), of_row), slab, norm],
            scratch_shapes=[
                pltpu.VMEM((NDp, hd), f32),
                pltpu.VMEM((G, NDp, hd), f32),
                pltpu.VMEM((hd, hd), f32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, KV, hd, W), f32),
                   jax.ShapeDtypeStruct((B * L, KV, ND, hd, hd), f32),
                   jax.ShapeDtypeStruct((B * L, KV, ND, hd), f32)],
        # the state and the normaliser, counted with the prefetched two
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * block + (24 << 20)),
        interpret=interpret,
        name=STEP_KERNEL_NAME,
    )(rows, jnp.stack([count, jnp.asarray(layer, jnp.int32)]),
      jnp.exp(log_g.astype(f32)),
      q.astype(f32).reshape(B, KV, G, hd), k.astype(f32)[:, :, None, :],
      v.astype(f32)[:, :, None, :],
      S.reshape(B * L, KV, ND, hd, hd), z.reshape(B * L, KV, ND, hd))
    # [B, KV, hd (v), W (head)] -> [B, H, hd]; rows not listed read as zeros
    y = y[..., :G].swapaxes(2, 3).reshape(B, H, hd)
    mine = jnp.zeros((B,), bool).at[rows].set(jnp.arange(B) < count)
    return (S.reshape(B, L, KV, ND, hd, hd), z.reshape(B, L, KV, ND, hd),
            jnp.where(mine[:, None, None], y, 0.0))
