"""Pallas kernel tests: interpret mode, asked for by argument (the same
kernels run compiled on a TPU — tests/test_chip_compile.py compiles them
for one). The dense oracle llama.attention is the numerics reference.
Multi-tile cases use S=256 with 128-blocks: `fit_block` admits no smaller
tile of a longer sequence, and an untileable shape raises."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.models import llama
from kubedl_tpu.ops import flash_attention_module as fa

flash_attention = functools.partial(fa.flash_attention, interpret=True)


def _qkv(key, B=2, S=256, H=4, KV=2, hd=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (B, S, H, hd), dtype),
        jax.random.normal(kk, (B, S, KV, hd), dtype),
        jax.random.normal(kv, (B, S, KV, hd), dtype),
    )


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        want = llama.attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_gqa_grouping(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), H=8, KV=2)
        want = llama.attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_single_block(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), S=64)
        want = llama.attention(q, k, v, causal=True)
        got = flash_attention(q, k, v)  # blocks larger than S -> one block
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize(
        "H,KV", [(4, 2), (8, 2), (8, 1)],  # group 2, 4, and MQA (group=H)
        ids=["group2", "group4", "mqa"],
    )
    def test_gradients_match_dense(self, causal, H, KV):
        """The fused backward accumulates dk/dv across the whole GQA group
        in kernel scratch (init on the group's first head, write-out on
        its last) — exercised at group sizes beyond the bench model's 2."""
        q, k, v = _qkv(jax.random.PRNGKey(3), B=1, H=H, KV=KV, hd=16)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, block_q=128,
                                block_k=128, bwd_block_q=128, bwd_block_k=128)
            return (o * o).sum()

        def loss_dense(q, k, v):
            o = llama.attention(q, k, v, causal=causal)
            return (o * o).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_llama_forward_with_flash(self):
        cfg = llama.TINY
        params = llama.llama_init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                    cfg.vocab_size)
        want = llama.llama_forward(params, tokens, cfg)
        got = llama.llama_forward(params, tokens, cfg, attn_fn=flash_attention)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)

    def test_mask_falls_back_to_dense(self):
        q, k, v = _qkv(jax.random.PRNGKey(4), S=32)
        mask = jnp.ones((1, 1, 1, 32, 32), bool)
        got = flash_attention(q, k, v, causal=False, mask=mask)
        want = llama.attention(q, k, v, causal=False, mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("H,KV", [(4, 2), (8, 1)], ids=["gqa", "mqa"])
    def test_fused_rope_matches_explicit_rope(self, H, KV):
        """rope_cos/rope_sin fuse the rotary into the kernel: q/k go in
        PRE-rope and the output must match apply_rope + kernel (and the
        dense oracle), forward and gradients — including the inverse
        rotation that makes the backward emit pre-rope gradients."""
        S, hd = 256, 32
        q, k, v = _qkv(jax.random.PRNGKey(6), B=1, S=S, H=H, KV=KV, hd=hd)
        cos, sin = llama.rope_table(hd, 10000.0, S)
        tiles = dict(block_q=128, block_k=128, bwd_block_q=128,
                     bwd_block_k=128)

        def loss_fused(q, k, v):
            o = flash_attention(q, k, v, rope_cos=cos, rope_sin=sin, **tiles)
            return (o * o).sum()

        def loss_explicit(q, k, v):
            o = flash_attention(
                llama.apply_rope(q, cos, sin), llama.apply_rope(k, cos, sin),
                v, **tiles,
            )
            return (o * o).sum()

        np.testing.assert_allclose(
            np.asarray(loss_fused(q, k, v)), np.asarray(loss_explicit(q, k, v)),
            rtol=1e-5,
        )
        g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_explicit, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_fused_rope_split_backward_path(self, monkeypatch):
        """The split two-kernel backward must apply the same in-kernel
        rotation + inverse-rotation as the fused path."""
        S, hd = 256, 16
        q, k, v = _qkv(jax.random.PRNGKey(7), B=1, S=S, H=4, KV=2, hd=hd)
        cos, sin = llama.rope_table(hd, 10000.0, S)

        def loss(q, k, v):
            o = flash_attention(q, k, v, block_q=128, block_k=128,
                                bwd_block_q=128, bwd_block_k=128,
                                rope_cos=cos, rope_sin=sin)
            return (o * o).sum()

        g_fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.setattr(fa, "_FUSED_BWD_SCRATCH_BYTES", 0)
        g_split = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fused, g_split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_untileable_shape_raises(self):
        # S=48 with 32-blocks has no legal tiling: an error naming the
        # sequence length, never the dense oracle in silence
        q, k, v = _qkv(jax.random.PRNGKey(5), S=48)
        with pytest.raises(ValueError, match="seq_len=48"):
            flash_attention(q, k, v, block_q=32, block_k=32)


class TestBlockFitting:
    def test_fused_bwd_odd_long_seq_gradients(self):
        """Regression: the fused backward's long-S k-tile shrink must
        RE-FIT, not clamp — at S=5376/hd=16 the scratch threshold is
        crossed and fit_block picks 896; a min(bk,512) clamp stopped
        dividing S and silently dropped the tail k-blocks (NaN dk/dv,
        dq off by 1e-2)."""
        from kubedl_tpu.ops import flash_attention_module as fam

        S, hd = 5376, 16
        # shrink the thresholds so the tiny test shape crosses them the
        # way S=5376/hd=64 does in production (Sk*hd*8 = 672KB here)
        old_small, old_cap = (
            fam._FUSED_BWD_SMALL_TILE_BYTES, fam._FUSED_BWD_SCRATCH_BYTES,
        )
        fam._FUSED_BWD_SMALL_TILE_BYTES = 256 << 10
        fam._FUSED_BWD_SCRATCH_BYTES = 1 << 20
        try:
            q, k, v = _qkv(jax.random.PRNGKey(5), B=1, S=S, H=2, KV=1, hd=hd)

            def loss_flash(q, k, v):
                o = flash_attention(q, k, v, causal=True)
                return (o * o).sum()

            def loss_dense(q, k, v):
                o = llama.attention(q, k, v, causal=True)
                return (o * o).sum()

            g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(g1, g2):
                assert np.isfinite(np.asarray(a)).all()
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=2e-3, rtol=2e-3)
        finally:
            fam._FUSED_BWD_SMALL_TILE_BYTES = old_small
            fam._FUSED_BWD_SCRATCH_BYTES = old_cap

    def test_fit_block(self):
        from kubedl_tpu.ops.flash_attention import fit_block, supports

        assert fit_block(2048, 1024) == 1024
        assert fit_block(1536, 1024) == 768   # largest 128-multiple divisor
        assert fit_block(1280, 1024) == 640
        assert fit_block(64, 1024) == 64      # whole seq in one block
        assert fit_block(100, 1024) == 100    # whole seq fits one block
        assert fit_block(100, 64) == 0        # >64, no 128-multiple divisor
        assert supports(1536) and supports(2048) and supports(32)
        assert not supports(1000000007)       # prime > block

    def test_odd_seq_len_uses_flash_not_dense(self):
        """seq 1536 (divisible by 512, not 1024) must still run the fused
        kernel (regression: r2 review — default-block bump silently
        narrowed support)."""
        from kubedl_tpu.models.llama import attention

        B, S, H, KV, hd = 1, 256, 2, 1, 16  # 256 % 128 == 0, < 1024
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))
        got = flash_attention(q, k, v, block_q=1024, block_k=1024)
        want = attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestRematKernelCounts:
    """Regression guard for the round-4 remat fix: with "flash_rope" the
    backward scan must NOT re-run the forward attention kernel (nor its
    input chain). The jaxpr-level signature: exactly TWO pallas_calls in
    the grad (fwd kernel + fused bwd kernel). Under "dots" the residuals
    aren't saveable so remat re-runs the forward — a third pallas_call.
    If defvjp(optimize_remat=True) ever returns, or the checkpoint_name
    tags drift, the flash_rope count jumps to 3 and this fails."""

    def _grad_pallas_count(self, policy: str) -> int:
        import dataclasses

        from kubedl_tpu.models import llama
        from kubedl_tpu.ops import flash_attention_module as fa

        cfg = dataclasses.replace(
            llama.TINY, remat=True, remat_policy=policy, dtype=jnp.float32
        )
        params = jax.eval_shape(
            lambda: llama.llama_init(jax.random.PRNGKey(0), cfg)
        )
        toks = jax.ShapeDtypeStruct((2, 64), jnp.int32)

        def attn(q, k, v, causal=True, mask=None):
            return fa.flash_attention(
                q, k, v, causal=causal, mask=mask, interpret=True
            )

        loss = lambda p, b: llama.llama_loss(p, b, cfg, attn)
        jaxpr = str(jax.make_jaxpr(jax.grad(loss))(params, toks))
        return jaxpr.count("pallas_call")

    def test_flash_rope_never_reruns_forward_kernel(self):
        assert self._grad_pallas_count("flash_rope") == 2

    def test_dots_documents_the_rerun(self):
        # not a bug — "dots" cannot name custom-call outputs; this pins
        # the contrast so the flash_rope assertion above stays meaningful
        assert self._grad_pallas_count("dots") == 3


class TestKernelCheck:
    """The comparisons chip_smoke.py and bench.py run on the chip
    (kubedl_tpu/ops/kernel_check.py), driven here through the interpreter
    at small shapes: they must pass on a correct kernel, say that no
    compiled kernel was in the program, and fail on a wrong answer."""

    def test_flash_check_passes_in_interpret_mode(self):
        from kubedl_tpu.ops import kernel_check

        r = kernel_check.flash_check(
            1, 256, 4, 2, 16, block=128, dtype=jnp.float32, interpret=True
        )
        assert r["ok"] and r["finite"] and not r["compiled"], r
        for leg in ("", "split_", "rope_"):
            assert r[f"{leg}dk_max_abs_diff"] < 1e-4, r

    @pytest.mark.parametrize(
        "KV,group,hd,S,fused",
        [(2, 2, 16, 1, False), (2, 2, 16, 1, True), (1, 4, 32, 8, False)],
        ids=["decode", "decode-fused-write", "mqa-suffix"],
    )
    def test_paged_check_passes_in_interpret_mode(self, KV, group, hd, S, fused):
        from kubedl_tpu.ops import kernel_check

        r = kernel_check.paged_check(
            3, KV, group, hd, max_tokens=64, S=S, fused=fused,
            dtype=jnp.float32, interpret=True,
        )
        assert r["ok"] and not r["compiled"], r
        assert ("k_pool_max_abs_diff" in r) == fused

    def test_a_wrong_kernel_fails_the_check(self, monkeypatch):
        from kubedl_tpu.models import paged_attention as pa
        from kubedl_tpu.ops import kernel_check

        real = pa._pallas_paged_attention
        monkeypatch.setattr(
            pa, "_pallas_paged_attention",
            lambda *a, **kw: real(*a, **kw) * 1.5,
        )
        r = kernel_check.paged_check(
            3, 2, 2, 16, max_tokens=64, dtype=jnp.float32, interpret=True
        )
        assert not r["ok"], r
