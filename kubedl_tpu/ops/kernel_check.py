"""Numerics checks of the Pallas kernels against plain references.

The only place Mosaic's tiling, pipelining and sequential-grid assumptions
are provable is the chip (interpret mode exercises none of them), so the
comparisons live here where `chip_smoke.py`, `bench.py` and the tests can
all call them: the flash forward and backward against float32 dense
attention, the fused single-pass backward against the split two-kernel
one, the in-kernel rotary against an explicit one, and the paged decode
kernels against the lax chunked scan. Every check returns a dict of
max-abs differences with ``ok`` and ``compiled`` — whether the lowered
program holds a ``tpu_custom_call``, i.e. the kernel itself and not the
interpreter or a reference path.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from kubedl_tpu.models import llama
from kubedl_tpu.models import paged_attention as pa
from kubedl_tpu.ops import flash_attention_module as fa

#: allowed max-abs difference as a share of the reference's largest
#: magnitude: about eight bf16 ulps (2^-8 each). Both sides accumulate in
#: f32; they differ in where q/k/p round to bf16.
BF16_TOL = 0.03


def _compare(out: Dict, name: str, got, want, tol: float) -> None:
    got = np.asarray(jax.device_get(got), np.float32)
    want = np.asarray(jax.device_get(want), np.float32)
    diff = float(np.abs(got - want).max())
    out[f"{name}_max_abs_diff"] = round(diff, 6)
    out["finite"] = out.get("finite", True) and bool(np.isfinite(got).all())
    out["ok"] = (
        out.get("ok", True) and out["finite"]
        and diff <= tol * max(float(np.abs(want).max()), 1.0)
    )


def _run(fn, *args):
    """Compile once; the result, and whether the program holds a kernel."""
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled(*args), "tpu_custom_call" in compiled.as_text()


def flash_check(
    B: int, S: int, H: int, KV: int, hd: int, *, block: int = 1024,
    dtype=jnp.bfloat16, interpret: bool = False, seed: int = 7,
    tol: float = BF16_TOL,
) -> Dict:
    """Flash forward + backward at one shape, three ways: against dense
    float32 attention; the fused single-pass backward against the split
    two-kernel one (the fused kernel's dk/dv rest on fully-sequential grid
    semantics); fused in-kernel rotary (with the inverse rotation in the
    backward) against `apply_rope` outside the kernel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    cos, sin = llama.rope_table(hd, 10000.0, S)

    def flash(q, k, v, **kw):
        return fa.flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            bwd_block_q=block, bwd_block_k=block, interpret=interpret, **kw
        )

    def sq(o):
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: sq(fn(q, k, v)), argnums=(0, 1, 2)
        ))(q, k, v)[1]

    def dense(q, k, v):
        with jax.default_matmul_precision("highest"):
            return llama.attention(*(t.astype(jnp.float32) for t in (q, k, v)))

    out: Dict = {"shape": f"B{B} S{S} H{H} KV{KV} hd{hd}"}
    got, out["compiled"] = _run(flash, q, k, v)
    _compare(out, "out", got, jax.jit(dense)(q, k, v), tol)
    fused = grads(flash)
    for name, a, b in zip(("dq", "dk", "dv"), fused, grads(dense)):
        _compare(out, name, a, b, tol)
    old = fa._FUSED_BWD_SCRATCH_BYTES
    try:
        fa._FUSED_BWD_SCRATCH_BYTES = 0  # force the split two-kernel path
        split = grads(flash)  # fresh jit: traces the split path
    finally:
        fa._FUSED_BWD_SCRATCH_BYTES = old
    for name, a, b in zip(("dq", "dk", "dv"), fused, split):
        _compare(out, f"split_{name}", a, b, tol)
    # the two rope paths round q/k to bf16 at different points (pre- vs
    # post-rotation), so agreement is to bf16 ulps, not bitwise
    g_rope = grads(lambda q, k, v: flash(q, k, v, rope_cos=cos, rope_sin=sin))
    g_explicit = grads(lambda q, k, v: flash(
        llama.apply_rope(q, cos, sin), llama.apply_rope(k, cos, sin), v
    ))
    for name, a, b in zip(("dq", "dk", "dv"), g_rope, g_explicit):
        _compare(out, f"rope_{name}", a, b, tol)
    return out


def paged_check(
    B: int, KV: int, group: int, hd: int, *, block_size: int = 16,
    max_tokens: int = 2048, S: int = 1, fused: bool = False,
    dtype=jnp.bfloat16, interpret: bool = False, seed: int = 0,
    tol: float = BF16_TOL,
) -> Dict:
    """The pallas paged kernel against `_lax_paged_attention` over a
    shuffled block table with ragged row lengths (a full row, an empty
    one, a block boundary); ``fused`` adds the decode step's KV write and
    compares the written pools too."""
    rng = np.random.default_rng(seed)
    MB = max_tokens // block_size
    NB = 1 + B * MB
    H = KV * group
    kp = jnp.asarray(rng.standard_normal((NB, block_size, KV, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((NB, block_size, KV, hd)), dtype)
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    bt = jnp.asarray(
        1 + rng.permutation(B * MB).reshape(B, MB).astype(np.int32)
    )
    last = max_tokens - S
    starts = rng.integers(0, last + 1, B).astype(np.int32)
    starts[:3] = [last, 0, min(block_size, last)][: min(B, 3)]
    starts = jnp.asarray(starts)
    new = [
        jnp.asarray(rng.standard_normal((B, KV, hd)), dtype) for _ in range(2)
    ] if fused else []

    def pallas(q, kp, vp, bt, starts, *new):
        kw = {"new_k": new[0], "new_v": new[1]} if new else {}
        return pa.paged_attention(
            q, kp, vp, bt, starts, kernel="pallas", interpret=interpret, **kw
        )

    def lax_ref(q, kp, vp, bt, starts, *new):
        if new:
            kp, vp = pa._fused_write_lax(kp, vp, bt, starts, *new)
        o = pa._lax_paged_attention(
            q, kp, vp, bt, starts, None, None, pa.DEFAULT_TILE
        )
        return (o, kp, vp) if new else o

    args = (q, kp, vp, bt, starts, *new)
    got, compiled = _run(pallas, *args)
    want = jax.jit(lax_ref)(*args)
    out: Dict = {
        "shape": f"B{B} S{S} KV{KV} group{group} hd{hd} BS{block_size} "
                 f"T{max_tokens}" + (" fused" if fused else ""),
        "compiled": compiled,
    }
    if not fused:
        got, want = (got,), (want,)
    for name, a, b in zip(("out", "k_pool", "v_pool"), got, want):
        _compare(out, name, a, b, tol)
    return out
