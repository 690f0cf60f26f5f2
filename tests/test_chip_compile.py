"""The Pallas kernels of the main path, compiled for a described v5e chip.

Interpret-mode tests cannot see what Mosaic refuses (a block not aligned to
the tiling, too much VMEM, a kernel that cannot be partitioned); the TPU
compiler is installed here and compiles for a chip that is described, not
attached. These are the only tests that load it, and this is the only file
that may: one process at a time can hold the TPU library, so the topology
is described inside a fixture (never at import or collection time), in the
test's own process, and every test that needs it lives here. Nothing runs —
a compile that passes is not a chip run (`chip_smoke.py` is).

Shapes: Llama-3.2-1B's attention (32 Q / 8 KV heads, hd 64) at S=2048 for
the flash kernels, and the decode shapes of Llama-3.2-1B and Gemma-2B
(B=8, 16-token blocks, a block table for 2048 tokens) for the paged ones.
"""

import os

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def on_chip(topo):
    """Shapes placed on one described chip: ``on_chip(shape, dtype)``."""
    import jax
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


def _compiled_text(fn, *args) -> str:
    import jax

    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("which", ["forward", "backward-fused", "backward-split"])
def test_flash_kernels_compile_at_llama_1b_heads(on_chip, which, monkeypatch):
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.ops import flash_attention_module as fa

    S, H, KV, hd = 2048, 32, 8, 64
    q = on_chip((1, S, H, hd), jnp.bfloat16)
    kv = on_chip((1, S, KV, hd), jnp.bfloat16)
    rope = on_chip((S, hd // 2), jnp.float32)

    def attn(q, k, v, cos, sin):  # the trainer's path: rotary fused in
        return fa.flash_attention(q, k, v, rope_cos=cos, rope_sin=sin)

    def loss(q, k, v, cos, sin):
        return jnp.sum(attn(q, k, v, cos, sin).astype(jnp.float32) ** 2)

    if which == "backward-split":
        monkeypatch.setattr(fa, "_FUSED_BWD_SCRATCH_BYTES", 0)
    fn = attn if which == "forward" else jax.grad(loss, argnums=(0, 1, 2))
    text = _compiled_text(fn, q, kv, kv, rope, rope)
    assert "tpu_custom_call" in text
    kernels = {"forward": 1, "backward-fused": 2, "backward-split": 3}
    assert text.count("custom_call_target=\"tpu_custom_call\"") >= kernels[which]


@pytest.mark.parametrize("fused", [False, True], ids=["read", "fused-write"])
@pytest.mark.parametrize(
    "KV,group,hd", [(8, 4, 128), (1, 8, 256)], ids=["mistral-7b", "gemma-2b"],
)
def test_paged_kernels_compile_at_decode_shapes(on_chip, KV, group, hd, fused):
    """The decode kernel (``paged_decode_attention``) over one layer's pool
    as it stands: the block a ``[BS*KV, hd]`` matrix, no copy of the pool in
    front of it. (Llama-3.2-1B's heads of 64 are half a lane tile: the
    kernel refuses them by name, ``tests/test_paged_attention.py``.)"""
    import jax.numpy as jnp

    from kubedl_tpu.models import paged_attention as pa

    B, BS, MB = 8, 16, 2048 // 16
    pool = on_chip((1 + B * MB, BS, KV, hd), jnp.bfloat16)
    args = [
        on_chip((B, 1, KV * group, hd), jnp.bfloat16), pool, pool,
        on_chip((B, MB), jnp.int32), on_chip((B,), jnp.int32),
    ]
    if fused:
        args += [on_chip((B, KV, hd), jnp.bfloat16)] * 2

    def call(q, kp, vp, bt, starts, *new):
        kw = {"new_k": new[0], "new_v": new[1]} if new else {}
        return pa.paged_attention(q, kp, vp, bt, starts, kernel="pallas", **kw)

    text = _compiled_text(call, *args)
    assert "tpu_custom_call" in text and pa.DECODE_KERNEL_NAME in text
    pool_text = f"bf16[{1 + B * MB},{BS},{KV},{hd}]"
    copies = [ln for ln in text.splitlines() if " copy(" in ln and pool_text in ln]
    assert not copies, copies[:2]


def _mistral_2_layers(on_chip, B, NB=1087, BS=16):
    """Mistral-7B's attention widths at 2 layers and a 4096-key table, as
    shapes on the described chip: ``(cfg, params, cache, i32)``."""
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq=4096, rope_theta=1e6, dtype=jnp.bfloat16,
    )
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: on_chip(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: llama.llama_init(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(
        lambda: llama.init_paged_cache(cfg, B, 4096, NB, BS)))
    return cfg, params, cache, lambda *s: on_chip(s, jnp.int32)


def test_decode_segment_with_the_kernel_compiles_in_place(on_chip, monkeypatch):
    """The 4-step decode segment as a TPU's ``ModelRunner`` runs it (the
    blocked arm: scatter in place, then the decode kernel over the whole
    pools with the layer in the index), at Mistral-7B's widths, 2 layers, 16
    rows, a 4096-key table: the custom call stands inside the layer loop, no
    conditional is left (the kernel's work follows the rows' lengths, not a
    span), no operation copies a pool-shaped array and the pools are donated
    and written in place (PR 29's property), and the program's temporaries
    are no larger than the gathered program's with its span branches."""
    import functools
    import re

    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import llama
    from kubedl_tpu.models import paged_attention as pa

    B, BS, NB, spans = 16, 16, 4159, (1024, 2048, 4096)
    cfg, params, cache, i32 = _mistral_2_layers(on_chip, B, NB, BS)
    common = (params, cache, i32(B, 1), on_chip((B,), jnp.float32),
              on_chip((2,), jnp.uint32))
    # this process's backend is the CPU, where "auto" is the lax arm: name
    # the kernel a TPU's "auto" takes
    monkeypatch.setattr(pa, "paged_attention", functools.partial(
        pa.paged_attention, kernel="pallas"))

    def with_kernel(p, c, tokens, temps, key, live):
        return llama.paged_decode_segment(
            p, c, tokens, temps, key, cfg, n_steps=4, greedy=True,
            kv_attention="blocked", live=live)

    def gathered(p, c, tokens, temps, key, live_to):
        return llama.paged_decode_segment(
            p, c, tokens, temps, key, cfg, n_steps=4, greedy=True,
            spans=spans, live_to=live_to)

    kernel = jax.jit(with_kernel, donate_argnums=(1,)).lower(
        *common, on_chip((B,), jnp.bool_)).compile()
    text = kernel.as_text()
    assert " conditional(" not in text
    # one custom call, in the layer scan's body inside the step loop's
    (called,) = [ln for ln in text.splitlines() if pa.DECODE_KERNEL_NAME in ln
                 and "custom_call_target=\"tpu_custom_call\"" in ln]
    assert re.search(r"/while/body/.*/while/body/.*pallas_call", called), called
    pool = f"bf16[{cfg.n_layers},{NB},{BS},{cfg.n_kv_heads},{cfg.head_dim}]"
    copies = [ln for ln in text.splitlines() if " copy(" in ln and pool in ln]
    assert not copies, copies[:2]
    assert text.count("may-alias") + text.count("must-alias") >= 2  # k and v
    view = jax.jit(gathered, donate_argnums=(1,)).lower(*common, i32()).compile()
    got, had = kernel.memory_analysis(), view.memory_analysis()
    assert got.temp_size_in_bytes <= had.temp_size_in_bytes, (
        got.temp_size_in_bytes, had.temp_size_in_bytes)


@pytest.mark.parametrize("program", ["decode_segment", "prefill_from"])
def test_view_span_branches_compile_in_place(on_chip, program):
    """The paged gather programs with the view's spans as branches (PR 31),
    at Mistral-7B's attention widths, 2 layers, a 4096-key table: the TPU
    compiler keeps one conditional with a branch a span inside the layer
    loop, and the K/V pools still travel through it in place: no operation
    copies a pool-shaped array (PR 29's property, which a conditional that
    took the pools by value would lose)."""
    import re

    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import llama

    B, BS, NB, spans = 4, 16, 1087, (1024, 2048, 4096)
    cfg, params, cache, i32 = _mistral_2_layers(on_chip, B, NB, BS)
    if program == "decode_segment":
        def fn(p, c, tokens, temps, key, live_to):
            return llama.paged_decode_segment(
                p, c, tokens, temps, key, cfg, n_steps=4, greedy=True,
                spans=spans, live_to=live_to)

        args = (params, cache, i32(B, 1), on_chip((B,), jnp.float32),
                on_chip((2,), jnp.uint32), i32())
    else:
        def fn(p, c, toks, lens, starts, rows, live_to):
            return llama.paged_prefill_from(
                p, c, toks, lens, starts, cfg, rows=rows, spans=spans,
                live_to=live_to)

        args = (params, cache, i32(1, 256), i32(1), i32(1), i32(1), i32())
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    (cond,) = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}", text)
    assert len(cond.split(",")) == len(spans)
    pool = f"bf16[{cfg.n_layers},{NB},{BS},{cfg.n_kv_heads},{cfg.head_dim}]"
    copies = [ln for ln in text.splitlines() if " copy(" in ln and pool in ln]
    assert not copies, copies[:2]


@pytest.mark.parametrize("program", ["decode_segment", "prefill_from"])
def test_hybrid_programs_fit_the_chip_whole(on_chip, program, monkeypatch):
    """The hybrid runner's own programs at granite-4.0-h-micro's size, all 40
    layers, 32 rows of 8192 keys: what the compiler needs for arguments and
    temporaries stays under the 15 GiB a 16 GB chip leaves a program. Two
    layouts hold that: the K/V pool keeps a token's 8 heads of 64 side by
    side (a last axis of 64 is padded to 128 lanes: the pool twice over, and
    the first compile asked for 16.6 GB), and ``in_proj`` is three leaves (a
    ``[2048, 8512]`` leaf is stored transposed and every program copied its
    1.17 GB). So no operation copies a pool or a stack of projections.

    The decode segment is compiled as a TPU process traces it: the state goes
    whole to the kernel ``ssd_step_rows`` (PR 40), one custom call inside the
    layer loops inside the step loop, aliased in and out, so nothing copies
    the 2.42 GB of state and no fusion makes an array of its shape (the
    einsum form's update in place was one, every row's slab of the layer
    read and written)."""
    import re

    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import hybrid_ssm
    from kubedl_tpu.ops import ssd_scan
    from kubedl_tpu.serving.model_runner import HybridRunner

    # this process's backend is the CPU: what `hybrid_ssm.steps_listed_rows`
    # observes on a TPU (jax's own code asks `xla_bridge`, not this name)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, max_seq, BS = 32, 8192, 16
    runner = HybridRunner("granite-4.0-h-micro", max_batch=B, max_seq=max_seq,
                          kv_block_size=BS)
    cfg = runner.cfg
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: on_chip(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: hybrid_ssm.hybrid_init(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(
        lambda: hybrid_ssm.init_cache(cfg, B, max_seq, 1 + B * max_seq // BS, BS)))
    i32 = lambda *s: on_chip(s, jnp.int32)  # noqa: E731
    if program == "decode_segment":
        lowered = runner._segment_fn(4, True).lower(
            params, cache, i32(B, 1), on_chip((B,), jnp.float32),
            on_chip((2,), jnp.uint32), on_chip((B,), jnp.bool_), i32())
    else:
        lowered = runner._prefill_from.lower(
            params, cache, i32(1, 1024), i32(1), i32(1), i32(1),
            on_chip((B, cfg.vocab_size), jnp.float32), i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2**30
    text = compiled.as_text().splitlines()
    big = [ln for ln in text if " copy(" in ln and (
        f"bf16[{cfg.periods},{1 + B * max_seq // BS}," in ln
        or (f"bf16[{cfg.n_mamba},{cfg.dim}," in ln and ",64]" not in ln))]
    assert not big, big[:2]
    if program == "decode_segment":
        assert ssd_scan.step_kernel_fits(cache["ssm"])
        state = "f32[%d,%d,%d,%d,%d]" % cache["ssm"].shape
        made = [ln for ln in text if re.match(
            rf"\s*(ROOT )?%\S+ = {re.escape(state)}\S* (copy|fusion)\(", ln)]
        assert not made, made[:2]
        calls = [ln for ln in text if ssd_scan.STEP_KERNEL_NAME in ln
                 and "custom_call_target=\"tpu_custom_call\"" in ln]
        # before and after the attention layer of a period: two call sites
        assert len(calls) == 2, calls
        for ln in calls:
            assert re.search(r"/while/body/.*/while/body/.*/while/body/.*pallas_call", ln), ln
            assert "output_to_operand_aliasing" in ln, ln


def _as_a_tpu_routes_its_experts(monkeypatch):
    """This process's backend is the CPU, where the expert layer takes its
    plain twin: let the shapes alone decide, as they do on a TPU."""
    from kubedl_tpu.models import sparse_window
    from kubedl_tpu.ops import expert_gmm

    monkeypatch.setattr(
        sparse_window, "grouped_by_kernel", lambda n, moe, cfg: expert_gmm.rows_for(
            n, moe["w_in"], moe["w_out"], cfg.n_experts))


def _the_expert_kernel_is_the_layers_products(text: str, cfg, in_steps: bool):
    """One call of ``expert_gmm`` a layer of the period, inside the layer loop
    (a decode segment's inside its step loop too), and nothing beside it
    that multiplies by experts: no ``ragged-dot``, no operand or result with
    an axis of every held expert before an axis of their width."""
    import re

    from kubedl_tpu.ops import expert_gmm

    calls = [ln for ln in text.splitlines() if expert_gmm.KERNEL_NAME in ln
             and "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == len(cfg.period), calls
    loops = r"/while/body/.*/while/body/.*pallas_call" if in_steps else r"/while/body/.*pallas_call"
    for ln in calls:
        assert re.search(loops, ln), ln
    assert "ragged-dot" not in text
    every = re.compile(rf"\[\d+,{cfg.held},({2 * cfg.expert_ffn}|{cfg.expert_ffn})\]")
    made = [ln for ln in text.splitlines() if every.search(ln.split(" = ")[-1].split("(")[0])]
    assert not made, made[:2]


@pytest.mark.parametrize("program", ["decode_segment", "prefill_from"])
def test_sparse_window_programs_fit_the_chip(on_chip, program, monkeypatch):
    """The sparse-window runner's own programs at Mellum2-12B-A2.5B's widths,
    12 of its 28 layers (three whole periods), 16 rows of 8192 keys and both
    pools: arguments and temporaries stay under the 15 GiB a 16 GB chip
    leaves a program (12.35 GB of weights and pools by the configuration's
    table). No operation copies a pool, and none copies the stack of expert
    weights: the expert layer's two products are the kernel ``expert_gmm``,
    compiled as a TPU process traces it, which is given the whole stack with
    the layer in the index of its fetches, so no layer's 0.79 GB of experts
    is sliced out. One custom call a layer in both programs (a decode step's
    16 rows take the path a chunk's 1024 take), inside the layer loop; no
    ``ragged-dot`` and no product over every expert is left in either."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import sparse_window
    from kubedl_tpu.serving.model_runner import SparseWindowRunner

    _as_a_tpu_routes_its_experts(monkeypatch)
    cfg = dataclasses.replace(sparse_window.MELLUM2_12B, periods=3)
    monkeypatch.setitem(sparse_window.PRESETS, "mellum2-l12", cfg)
    B, max_seq, BS = 16, 8192, 16
    runner = SparseWindowRunner("mellum2-l12", max_batch=B, max_seq=max_seq,
                                kv_block_size=BS)
    window_blocks = runner.size_window_pool(1024)
    assert window_blocks == 1 + B * 129
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: on_chip(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: sparse_window.sparse_init(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: sparse_window.init_cache(
        cfg, B, max_seq, 1 + B * max_seq // BS, window_blocks, BS)))
    i32 = lambda *s: on_chip(s, jnp.int32)  # noqa: E731
    if program == "decode_segment":
        lowered = runner._segment_fn(4, True).lower(
            params, cache, i32(B, 1), on_chip((B,), jnp.float32),
            on_chip((2,), jnp.uint32), i32(B), i32())
    else:
        lowered = runner._prefill_from.lower(
            params, cache, i32(1, 1024), i32(1), i32(1), i32(1),
            on_chip((B, cfg.vocab_size), jnp.float32), i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2**30
    text = compiled.as_text()
    big = [ln for ln in text.splitlines() if " copy(" in ln and (
        f"bf16[{cfg.n_full},{1 + B * max_seq // BS}," in ln
        or f"bf16[{cfg.n_window},{window_blocks}," in ln
        or f"bf16[{cfg.n_layers},{cfg.n_experts}," in ln
        or f"bf16[{cfg.n_layers * cfg.n_experts}," in ln)]
    assert not big, big[:2]
    _the_expert_kernel_is_the_layers_products(text, cfg, program == "decode_segment")


@pytest.mark.parametrize("program", ["decode_segment", "prefill_from"])
def test_parallel_block_programs_fit_the_chip_on_the_blocked_arm(on_chip, program, monkeypatch):
    """The sparse-window runner's programs under the parallel block's settings
    at command-a-plus-05-2026's widths, one chip's share (4 layers, 16 of 128
    experts, an eighth of the vocabulary), 16 rows of 32768 keys, on the
    blocked arm: arguments and temporaries stay under the 15 GiB a 16 GB chip
    leaves a program (12.6 GB of weights and pools by the configuration's
    table), where the gathered view's float32 scores of 128 heads alone would
    be 16 GiB at this span. No operation copies a pool or a stack of expert
    weights. The decode segment calls the decode kernel once a layer, in a
    window layer over the run of blocks that ends at the row (the kernel
    compiled with a window), over pools it reads as they stand; the prefill
    program folds its keys in a loop and holds no attention kernel. Both hold
    the expert kernel once a layer (``expert_gmm``, the held share's 16
    experts walked in tiles of their width), no ``ragged-dot``, no product
    over every held expert, and no conditional: a share's products are
    compiled once."""
    import functools

    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import paged_attention as pa
    from kubedl_tpu.models import sparse_window
    from kubedl_tpu.serving.model_runner import SparseWindowRunner

    _as_a_tpu_routes_its_experts(monkeypatch)
    cfg = sparse_window.preset("command-a-plus-05-2026-l4")
    B, max_seq, BS = 16, 32768, 16
    runner = SparseWindowRunner("command-a-plus-05-2026-l4", max_batch=B, max_seq=max_seq,
                                kv_block_size=BS, kv_attention="blocked")
    window_blocks = runner.size_window_pool(1024)
    assert window_blocks == 1 + B * 321 and runner.spans == (max_seq,)
    # this process's backend is the CPU, where "auto" is the lax arm: name
    # the kernel a TPU's "auto" takes for one query a row
    real = pa.paged_attention
    monkeypatch.setattr(pa, "paged_attention", lambda q, *a, **kw: real(
        q, *a, **{**kw, "kernel": "pallas" if q.shape[1] == 1 else "lax"}))
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: on_chip(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: sparse_window.sparse_init(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: sparse_window.init_cache(
        cfg, B, max_seq, 1 + B * max_seq // BS, window_blocks, BS, blocked=True)))
    i32 = lambda *s: on_chip(s, jnp.int32)  # noqa: E731
    if program == "decode_segment":
        lowered = runner._segment_fn(4, True).lower(
            params, cache, i32(B, 1), on_chip((B,), jnp.float32),
            on_chip((2,), jnp.uint32), i32(B))
    else:
        lowered = runner._prefill_from.lower(
            params, cache, i32(1, 1024), i32(1), i32(1), i32(1),
            on_chip((B, cfg.vocab_size), jnp.float32))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2**30
    text = compiled.as_text()
    big = [ln for ln in text.splitlines() if " copy(" in ln and (
        f"bf16[{cfg.n_full},{1 + B * max_seq // BS}," in ln
        or f"bf16[{cfg.n_window},{window_blocks}," in ln
        or f"bf16[{cfg.n_layers},{cfg.held}," in ln
        or f"bf16[{cfg.n_layers * cfg.held}," in ln)]
    assert not big, big[:2]
    calls = [ln for ln in text.splitlines() if pa.DECODE_KERNEL_NAME in ln
             and "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == (len(cfg.period) if program == "decode_segment" else 0), calls
    # no span to branch on, and a share's products stand once
    assert text.count(" conditional(") == 0
    _the_expert_kernel_is_the_layers_products(text, cfg, program == "decode_segment")


@pytest.mark.parametrize("program", ["decode_segment", "prefill_from"])
def test_retention_programs_fit_the_chip(on_chip, program, monkeypatch):
    """The retention runner's own programs at the cell's size
    (``brumby-14b-base-l8``: 8 of Brumby-14B-Base's 40 layers at published
    widths, 16 rows): 8.40 GB of weights and 4.40 GB of state, all a row owns.
    What the compiler needs for arguments and temporaries stays under the
    15 GiB a 16 GB chip leaves a program, and no program copies the slab
    array or makes another of its shape.

    The decode segment is compiled as a TPU process traces it: the state goes
    whole to the kernel ``retention_step_rows``, one custom call inside the
    layer loop inside the step loop, aliased in and out. The suffix prefill
    holds both of its first chunk's arms (a fresh row reads no state), and
    ``phi`` of a chunk's queries stands for one key group at a time."""
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import retention
    from kubedl_tpu.ops import power_retention
    from kubedl_tpu.serving.model_runner import RetentionRunner

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(retention.BRUMBY_14B_BASE, n_layers=8)
    monkeypatch.setitem(retention.PRESETS, "brumby-14b-base-l8", cfg)
    B = 16
    runner = RetentionRunner("brumby-14b-base-l8", max_batch=B, max_seq=8192)
    assert runner.block_bytes == 0 and runner.state_bytes_per_row == 8 * 8 * 8320 * 129 * 4
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: on_chip(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: retention.retention_init(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: retention.init_cache(cfg, B)))
    assert set(cache) == {"pos", "S", "z"}  # no pool, no block table
    i32 = lambda *s: on_chip(s, jnp.int32)  # noqa: E731
    if program == "decode_segment":
        lowered = runner._segment_fn(4, True).lower(
            params, cache, i32(B, 1), on_chip((B,), jnp.float32),
            on_chip((2,), jnp.uint32), on_chip((B,), jnp.bool_))
    else:
        lowered = runner._prefill_from.lower(
            params, cache, i32(1, 1024), i32(1), i32(1), i32(1),
            on_chip((B, cfg.vocab_size), jnp.float32))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2**30
    text = compiled.as_text().splitlines()
    state = "f32[%d,%d,%d,%d,%d,%d]" % cache["S"].shape
    # a prefill program writes its row's slab of a layer back in place: one
    # fusion of the array's shape, aliased to its operand, and no copy
    kinds = "copy|fusion" if program == "decode_segment" else "copy"
    made = [ln for ln in text if re.match(
        rf"\s*(ROOT )?%\S+ = {re.escape(state)}\S* ({kinds})\(", ln)]
    assert not made, made[:2]
    if program == "decode_segment":
        assert power_retention.step_kernel_fits(cache["S"])
        calls = [ln for ln in text if power_retention.STEP_KERNEL_NAME in ln
                 and "custom_call_target=\"tpu_custom_call\"" in ln]
        assert len(calls) == 1, calls
        assert re.search(r"/while/body/.*/while/body/.*pallas_call", calls[0]), calls[0]
        assert "output_to_operand_aliasing" in calls[0], calls[0]
