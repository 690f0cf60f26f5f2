"""The two products of a routed expert layer as one grouped-matmul kernel.

The layer's kept assignments stand in their order by expert: ``xs [n, D]``,
expert ``g``'s ``load[g]`` rows after expert ``g - 1``'s, the assignments that
count for no expert here last. For the rows of group ``g``::

    h   = xs @ w_in[first + g]            ([D, 2F]: gate beside up)
    act = silu(h[:, :F]) * h[:, F:]
    out = act @ w_out[first + g]          (float32)

- :func:`expert_gmm` is that as a Pallas kernel over the WHOLE stacks
  ``w_in [G, D, 2F]`` and ``w_out [G, F, D]`` (every layer's experts; ``first``
  names the layer's first, so nothing is sliced out). Its work list is one
  entry for each (group, row tile) pair in which the group has a row: an
  expert nobody was routed to is never fetched, a row tile past ``sum(load)``
  is never run, and a tile that two groups share is visited once by each with
  the other's rows masked (the metadata is that of
  ``jax.experimental.pallas.ops.tpu.megablox``, which does not fuse the two
  products). An expert wider than the kernel's room is walked in tiles of
  ``F``: a tile of ``h``, its activation, ``acc += act @ w_out[tile]``.
- :func:`tile_sizes` chooses the row tile and the width tile from the shapes,
  :func:`expert_gmm_fits` says whether a TPU can take them (:func:`rows_for`:
  the row tile if it can, else 0), :func:`work_list` is the kernel's list of
  entries and :func:`row_tiles` their count, from ``load`` alone.

The plain twin is ``models/sparse_window._grouped`` (two ``lax.ragged_dot``),
which every other backend runs and the tests hold the kernel to.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

#: the kernel's name in a device profile and in compiled text
KERNEL_NAME = "expert_gmm"
#: what the three weight tiles of a step (gate, up, out), each held twice so
#: that the next arrives while this one is multiplied, may take of the chip's
#: 128 MiB of VMEM: Mellum2's expert whole (12.4 MB twice), a quarter of
#: command-a's (an eighth of its width at a time)
_WEIGHT_BYTES = 32 << 20
#: rows a tile takes where every entry of the work list fetches its weights
#: anew (an expert walked in tiles of ``F``): a row does 1 FLOP a byte of
#: them, so up to the chip's ridge (240) the entry waits for the fetch either
#: way, and a larger tile splits fewer groups in two
_RIDGE_ROWS = 256


def _min_rows(dtype) -> int:
    """The sublanes of one tile of ``dtype``: 8 of four bytes, 16 of two."""
    return 32 // jnp.dtype(dtype).itemsize


def tile_sizes(n: int, D: int, F: int, experts: int, dtype) -> Tuple[int, int]:
    """``(rows, width)`` of the kernel's tiles for ``n`` assignments that a
    router spreads over ``experts`` (all it scores, held here or not).

    ``width``: all of ``F`` where the expert's three weight tiles fit
    :data:`_WEIGHT_BYTES` twice, else the largest multiple of 128 lanes that
    divides ``F`` and does. ``rows``: the mean group's size as a power of two
    where the expert is fetched whole (a group's later tiles then reuse it,
    and a smaller tile multiplies fewer rows that are not the group's), else
    :data:`_RIDGE_ROWS`; no less than one tile of ``dtype`` and no more than
    ``n`` rounded up to such tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    width = F
    if 6 * D * F * itemsize > _WEIGHT_BYTES:
        fitting = [w for w in range(128, F, 128)
                   if F % w == 0 and 6 * D * w * itemsize <= _WEIGHT_BYTES]
        width = max(fitting, default=F)
    low = _min_rows(dtype)
    rows = _RIDGE_ROWS if width < F else 1 << max(n // experts - 1, 0).bit_length()
    return max(low, min(rows, -(-n // low) * low)), width


def vmem_bytes(rows: int, width: int, D: int, dtype) -> int:
    """What a call with those tiles asks of VMEM: the weight tiles, the row
    tile and the output tile twice each, the accumulator, the tile of ``h``
    and its activation, and room for what the compiler keeps."""
    itemsize = jnp.dtype(dtype).itemsize
    return (6 * D * width * itemsize + 2 * rows * D * itemsize + 3 * rows * D * 4
            + 4 * rows * width * 4 + (8 << 20))


def expert_gmm_fits(n: int, w_in: jax.Array, w_out: jax.Array, experts: int) -> bool:
    """Whether a TPU can take :func:`expert_gmm` at these shapes as they
    stand: weights of two bytes, ``D`` and ``F`` whole lanes, and the tiles
    inside the VMEM of a chip (96 MiB of its 128). Anything else (the tiny
    test presets' ``D`` = 64) takes the plain twin."""
    D, F = w_out.shape[-1], w_out.shape[-2]  # [..., F, D]: the stacks flat or by layer
    if w_in.dtype != jnp.bfloat16 or w_out.dtype != w_in.dtype or D % 128 or F % 128:
        return False
    rows, width = tile_sizes(n, D, F, experts, w_in.dtype)
    return (F % width == 0 and width % 128 == 0
            and vmem_bytes(rows, width, D, w_in.dtype) <= 96 << 20)


def rows_for(n: int, w_in: jax.Array, w_out: jax.Array, experts: int) -> int:
    """The row tile :func:`expert_gmm` gives ``n`` assignments at these
    stacks, or 0 where a TPU cannot take them (:func:`expert_gmm_fits`): what
    a caller needs to count the kernel's entries with :func:`row_tiles`."""
    if not expert_gmm_fits(n, w_in, w_out, experts):
        return 0
    return tile_sizes(n, w_out.shape[-1], w_out.shape[-2], experts, w_in.dtype)[0]


def _group_tiles(load: jax.Array, rows: int):
    """``(first tile, tiles)`` of each group, ``[..., count]``: the row tiles
    that hold at least one of its rows, none for an empty group. The groups
    stand one after another along the last axis."""
    ends = jnp.cumsum(load, axis=-1)
    first = (ends - load) // rows
    return first, jnp.where(load > 0, (ends - 1) // rows - first + 1, 0)


def row_tiles(load: jax.Array, rows: int) -> jax.Array:
    """Entries of the kernel's work list for group sizes ``load [..., count]``
    (any leading axes: a layer each) and a row tile of ``rows``: the sum over
    groups of the tiles each has a row in, int32."""
    return jnp.sum(_group_tiles(load, rows)[1], dtype=jnp.int32)


def work_list(load: jax.Array, rows: int, n_tiles: int):
    """``(group [most], tile [most])``, int32: the kernel's entries in order,
    each a group ``load [count]`` gives a row and a row tile (of ``n_tiles``)
    that holds one; the first :func:`row_tiles` of them count and the grid
    ends there. ``most`` is ``n_tiles + count - 1``: every group but one may
    begin inside a tile."""
    count = load.shape[0]
    first_tile, tiles_of = _group_tiles(load, rows)
    ends = jnp.cumsum(tiles_of)  # of each group's entries in the list
    at = jnp.arange(n_tiles + count - 1, dtype=jnp.int32)
    # an entry's group: how many groups' entries end at or before it
    group = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), count - 1)
    tile = jnp.minimum(first_tile[group] + at - (ends - tiles_of)[group], n_tiles - 1)
    return group, tile


def _kernel(
    group_ref, tile_ref,  # scalar prefetch: [entries] each entry's group and row tile
    edge_ref,  # [count + 1]: the row each group begins at, and the last one's end
    first_ref,  # [1]: the first group's place in the stacks
    x_ref,  # VMEM [rows, D]
    gate_ref, up_ref,  # VMEM [1, D, width]: the expert's tile of w_in, twice
    down_ref,  # VMEM [1, width, D]
    o_ref,  # VMEM [rows, D] float32: kept while the row tile stays
    acc_ref,  # VMEM [rows, D] float32
):
    """One entry of the work list, one tile of the expert's width: ``acc +=
    act(x @ w_in[tile]) @ w_out[tile]``; at the last tile the group's rows of
    ``acc`` go into the output tile, whose other rows stay as they are (an
    earlier group's, or nothing anybody reads). The roundings are the plain
    twin's: ``h`` in the weights' type, the activation in float32, the second
    product accumulated in float32."""
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    gate = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32).astype(x.dtype)
    up = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32).astype(x.dtype)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    acc_ref[...] += jnp.dot(act, down_ref[0], preferred_element_type=jnp.float32)

    @pl.when(f == pl.num_programs(1) - 1)
    def _store():
        g = group_ref[i]
        row = tile_ref[i] * o_ref.shape[0] + lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mine = (row >= edge_ref[g]) & (row < edge_ref[g + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...], o_ref[...])


# jitted: a program reaches it from every layer of a period, inside a scan
# that traces its body more than once, and traces the kernel once
@functools.partial(jax.jit, static_argnames=("experts", "tiles", "interpret"))
def expert_gmm(
    xs: jax.Array,  # [n, D]: the assignments' tokens in their order by expert
    w_in: jax.Array,  # [G, D, 2F]: every layer's experts, gate beside up
    w_out: jax.Array,  # [G, F, D]
    load: jax.Array,  # [count] int32: rows of each group, in order
    first: jax.Array,  # scalar: the first group's expert among the G
    experts: int,  # experts the router spread the n assignments over
    tiles: Optional[Tuple[int, int]] = None,
    interpret: bool = False,
) -> jax.Array:
    """``out [n, D]`` float32: rows ``[0, sum(load))`` are the layer's two
    products for the assignment that stands there (before its gate); a row
    past them is NOT written and holds whatever the buffer held, so the caller
    reads it under a mask. The kernel runs :func:`row_tiles` of ``load``
    entries, those :func:`work_list` names.

    ``tiles`` ``(rows, width)`` overrides :func:`tile_sizes` (tests). On a TPU
    the shapes have to pass :func:`expert_gmm_fits`; ``interpret=True``
    (tests) runs the kernel through the interpreter on any backend, at any
    shape whose ``F`` the width divides."""
    from jax.experimental.pallas import tpu as pltpu

    n, D = xs.shape
    F = w_out.shape[1]
    rows, width = tiles or tile_sizes(n, D, F, experts, w_in.dtype)
    if F % width:
        raise ValueError(f"a width tile of {width} does not divide F = {F}")
    if n % rows:  # a last tile's tail: rows of no group
        xs = jnp.pad(xs, ((0, rows - n % rows), (0, 0)))
    load = load.astype(jnp.int32)
    group, tile = work_list(load, rows, xs.shape[0] // rows)
    edges = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(load)])
    steps = F // width

    def rows_at(i, f, group, tile, edges, first):
        return tile[i], 0

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # with no entry one still runs, of an empty group: it stores nothing
            grid=(jnp.maximum(row_tiles(load, rows), 1), steps),
            in_specs=[
                pl.BlockSpec((rows, D), rows_at),
                pl.BlockSpec((1, D, width), lambda i, f, group, tile, edges, first: (
                    first[0] + group[i], 0, f)),
                pl.BlockSpec((1, D, width), lambda i, f, group, tile, edges, first: (
                    first[0] + group[i], 0, steps + f)),
                pl.BlockSpec((1, width, D), lambda i, f, group, tile, edges, first: (
                    first[0] + group[i], f, 0)),
            ],
            out_specs=pl.BlockSpec((rows, D), rows_at),
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(rows, width, D, xs.dtype)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(group, tile, edges, jnp.asarray(first, jnp.int32).reshape(1),
      xs, w_in, w_in, w_out)
    return out[:n]
