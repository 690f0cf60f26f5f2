"""The state-space recurrence of a Mamba-2 mixer, three ways.

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
  carried state.
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

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


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
