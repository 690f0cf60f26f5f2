"""The K/V pool stays in place through a paged program's layer scan.

Holds the property, not a speed: a paged program compiled with its cache
donated needs no temporary the size of a pool. With the pools scanned in as
``xs`` and stacked out as ``ys`` (the form ``models/llama.py`` had) every
layer sliced its pool out and wrote the whole layer back, and the compiled
program kept about three pools of temporaries; carried through the scan
and written at ``[layer, blk, off]`` it keeps a twentieth of one.

The model is ``tiny`` and the pool is made large beside everything else
(2,049 blocks of 16 tokens, 8.4 MB each for K and V), so a pool-sized
temporary cannot hide among the activations."""

import jax
import jax.numpy as jnp
import pytest

from kubedl_tpu.models import llama

B, MAX_SEQ, BS, NUM_BLOCKS = 4, 64, 16, 2049
N_STEPS, SUFFIX = 4, 16


def _decode_segment(cfg):
    def fn(params, cache, tokens, temps, key):
        return llama.paged_decode_segment(
            params, cache, tokens, temps, key, cfg=cfg, n_steps=N_STEPS,
            greedy=True)
    i32, f32 = jnp.int32, jnp.float32
    return fn, (
        jax.ShapeDtypeStruct((B, 1), i32), jax.ShapeDtypeStruct((B,), f32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)),
    )


def _prefill_from(cfg):
    def fn(params, cache, tokens, lengths, starts, rows):
        return llama.paged_prefill_from(
            params, cache, tokens, lengths, starts, cfg, rows=rows)
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    return fn, (jax.ShapeDtypeStruct((1, SUFFIX), jnp.int32), one, one, one)


def _prefill_batched(cfg):
    def fn(params, cache, tokens, lengths, rows):
        return llama.paged_prefill_batched(
            params, cache, tokens, lengths, cfg, rows=rows)
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    return fn, (jax.ShapeDtypeStruct((1, SUFFIX), jnp.int32), one, one)


PROGRAMS = {
    "paged_decode_segment": _decode_segment,
    "paged_prefill_from": _prefill_from,
    "paged_prefill_batched": _prefill_batched,
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_donated_pool_needs_no_pool_sized_temporary(program):
    cfg = llama.preset("tiny")
    params = jax.eval_shape(
        lambda k: llama.llama_init(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: llama.init_paged_cache(cfg, B, MAX_SEQ, NUM_BLOCKS, BS))
    pool_bytes = cache["k"].size * cache["k"].dtype.itemsize
    fn, rest = PROGRAMS[program](cfg)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *rest).compile()
    mem = compiled.memory_analysis()
    # the returned pools ARE the donated ones: both alias their arguments
    assert mem.alias_size_in_bytes >= 2 * pool_bytes, (
        program, mem.alias_size_in_bytes, pool_bytes)
    assert mem.temp_size_in_bytes < pool_bytes / 2, (
        f"{program}: {mem.temp_size_in_bytes} bytes of temporaries beside "
        f"a pool of {pool_bytes}: a copy of the pool travels through the "
        f"layer scan")
