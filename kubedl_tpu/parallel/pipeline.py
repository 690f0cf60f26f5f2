"""Pipeline parallelism: GPipe-style microbatched stage execution.

The layer stack is split into ``n`` stages along a ``pipe`` mesh axis; each
device owns one stage's weights (sharded on the stacked leading axis) and
activations flow stage-to-stage with `lax.ppermute` — a neighbor transfer
that rides ICI, never DCN. Scheduling is the classic GPipe fill/drain: with
M microbatches the loop runs M + n - 1 ticks, every device executing the
same compiled tick body (SPMD — no per-stage programs to compile).

Differentiable end-to-end: the tick loop is a `lax.scan`, so reverse-mode
AD through the whole pipeline works and the backward pass is itself a
pipeline (reversed ring) — no hand-written backward schedule needed.

Bubble fraction is (n-1)/(M+n-1); callers pick M >= 4n to keep it small.
The reference has no in-process parallelism at all (SURVEY.md §2.5: TP/PP
absent) — this is net-new TPU capability.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class PipelineHooks:
    """What a model family provides to run through the GPipe pipeline
    (llama.pipeline_hooks / moe.pipeline_hooks): pure functions so the
    trainer stays family-agnostic (VERDICT r2 #5: the round-2 pipeline
    loss hardcoded Llama)."""

    #: embed(params, tokens [B,S]) -> activations [B, S, D]
    embed: Callable
    #: rope(S) -> (cos, sin) position tables
    rope: Callable
    #: make_stage(attn_fn, cos, sin, tp_axis=, ep_axis=) ->
    #:   stage_fn(layer_params_slice, x) -> (y, aux_scalar)
    make_stage: Callable
    #: head_loss(params, h [B,S,D], tokens, aux_mean) -> scalar loss
    head_loss: Callable
    n_layers: int


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x_mb: jax.Array,  # [M, mb, ...] microbatched input (replicated)
    axis_name: str,
):
    """Run microbatches through the stage ring (call under shard_map).

    ``stage_fn(stage_params, x) -> (y, aux)`` applies THIS device's stage
    (its slice of the layer stack); ``aux`` is a scalar auxiliary-loss
    contribution (e.g. MoE load balancing), summed over VALID ticks only
    (fill/drain ticks process clamped garbage microbatches and must not
    pollute it). Returns ``(out, aux_sum)``: the last stage's outputs
    replicated across the pipe axis [M, mb, ...], and the aux sum over
    every (layer, microbatch) this pipeline processed (psum over pipe).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    M = x_mb.shape[0]

    state0 = jnp.zeros_like(x_mb[0])
    out0 = jnp.zeros_like(x_mb)

    def tick(carry, t):
        state, out, aux_sum = carry
        # stage 0 ingests microbatch t (clamped during drain); others take
        # the activation handed over from the previous stage last tick
        feed = lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
        )
        x = jnp.where(idx == 0, feed, state)
        y, aux = stage_fn(stage_params, x)
        # stage idx processes microbatch t - idx at tick t; only ticks
        # carrying a real microbatch contribute aux
        valid = (t >= idx) & (t - idx < M)
        aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
        # the last stage completes microbatch t-(n-1) at tick t
        mb_done = t - (n - 1)
        write = (idx == n - 1) & (mb_done >= 0)
        slot = jnp.clip(mb_done, 0, M - 1)
        cur = lax.dynamic_index_in_dim(out, slot, axis=0, keepdims=False)
        upd = jnp.where(write, y, cur)
        out = lax.dynamic_update_index_in_dim(out, upd, slot, axis=0)
        state = lax.ppermute(y, axis_name, [(i, (i + 1) % n) for i in range(n)])
        return (state, out, aux_sum), None

    (_, out, aux_sum), _ = lax.scan(
        tick, (state0, out0, jnp.zeros((), jnp.float32)), jnp.arange(M + n - 1)
    )
    # replicate the last stage's outputs to every stage (cheap at our M*mb;
    # keeps out_specs simple and check_rep happy being explicit)
    out = lax.psum(jnp.where(idx == n - 1, out, jnp.zeros_like(out)), axis_name)
    return out, lax.psum(aux_sum, axis_name)


def make_pipeline(
    mesh: Mesh,
    stage_fn: Callable,
    pipe_axis: str = "pipe",
    param_specs=None,
    data_axes: tuple = (),
):
    """Wrap pipeline_apply in shard_map over ``pipe_axis`` (and, for the
    activations' microbatch dim, over ``data_axes`` — GPipe composes with
    data parallelism for free: each dp shard runs its own pipeline over the
    same stage weights).

    ``param_specs`` is the PartitionSpec tree for the stacked stage params
    (leading axis on ``pipe_axis``; inner dims may additionally name
    "tensor"/"expert" axes, whose collectives the stage body issues
    itself). Defaults to P(pipe_axis) broadcast over every leaf.

    Returns ``run(stacked_params, x_mb) -> (out, aux_sum)`` where
    ``stacked_params`` leaves have a leading [n_stages, ...] axis and
    ``x_mb`` is [M, mb, ...] with mb sharded over ``data_axes``. The aux
    sum is additionally psum'd over the data axes, so it is a replicated
    scalar: the caller divides by (n_layers * M * dp) for a mean.
    """
    from jax import shard_map

    pspec = param_specs if param_specs is not None else P(pipe_axis)
    dt = tuple(a for a in data_axes if a in mesh.axis_names and mesh.shape[a] > 1)
    xspec = P(None, dt if dt else None)  # [M, mb, ...rest replicated]

    def local(stage_params, x_mb):
        out, aux = pipeline_apply(stage_fn, stage_params, x_mb, pipe_axis)
        for a in dt:  # replicate the aux scalar across data shards too
            aux = lax.psum(aux, a)
        return out, aux

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )
