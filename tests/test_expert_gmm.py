"""The expert layer's grouped-matmul kernel (ops/expert_gmm.py), through the
Pallas interpreter on the CPU.

Held to the reference loop of ``tests/test_sparse_window.py`` (every expert
computing every row under its gate, plain float32) at the tiny presets'
shapes, for the whole stack and for a held share, and to a loop over the
groups for the kernel alone. The kernel and the loop compute the same float32
sums in another order, so they agree to a few 1e-6 of a value of deviation 1;
``TOL`` (1e-4) is what that file allows, and a dropped group, a row of the
wrong expert or a tile of ``F`` left out miss it by orders of magnitude.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.models import sparse_window as sw
from kubedl_tpu.ops import expert_gmm

TOL = 1e-4


def forced_kernel(mp, tiles=None, seen=None):
    """What a TPU process observes, steered for a CPU: the expert layer told
    that the kernel takes its shapes (the tiny presets' ``D`` is 64), and the
    interpreter in the compiled kernel's place. ``tiles``: the kernel's
    ``(rows, width)`` instead of what the shapes give; ``seen``: a list that
    takes each call's ``(load, entries, tile)``, the entries the kernel runs
    and the row tile each names, as traced or concrete values. Holds for as
    long as ``mp`` does: programs trace on first use."""
    real = expert_gmm.expert_gmm

    def rows_of(n, D, F, experts, dtype):
        return (tiles or expert_gmm.tile_sizes(n, D, F, experts, dtype))[0]

    def kernel(xs, w_in, w_out, load, first, experts):
        if seen is not None:
            rows = rows_of(*xs.shape, w_out.shape[1], experts, w_in.dtype)
            seen.append((load, expert_gmm.row_tiles(load, rows),
                         expert_gmm.work_list(load, rows, -(-xs.shape[0] // rows))[1]))
        return real(xs, w_in, w_out, load, first, experts=experts, tiles=tiles, interpret=True)

    mp.setattr(sw, "grouped_by_kernel", lambda n, moe, cfg: rows_of(
        n, cfg.dim, cfg.expert_ffn, cfg.n_experts, moe["w_in"].dtype))
    mp.setattr(expert_gmm, "expert_gmm", kernel)


def _stacks(G=12, D=64, F=256, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    w_in = (jax.random.normal(k[0], (G, D, 2 * F), jnp.float32) / 8).astype(dtype)
    w_out = (jax.random.normal(k[1], (G, F, D), jnp.float32) / 16).astype(dtype)
    return w_in, w_out


def _loop(xs, w_in, w_out, load, first):
    """Group by group, in float32: ``(out [n, D], rows that belong to a group)``."""
    n, D = xs.shape
    F = w_out.shape[1]
    out, r = np.zeros((n, D), np.float32), 0
    for g, c in enumerate(np.asarray(load)):
        h = np.asarray(xs[r:r + c], np.float32) @ np.asarray(w_in[first + g], np.float32)
        act = h[:, :F] / (1.0 + np.exp(-h[:, :F])) * h[:, F:]
        out[r:r + c] = act @ np.asarray(w_out[first + g], np.float32)
        r += c
    return out, r


CASES = {
    # load, first, (rows, width): what each meets
    "empty groups between": ([5, 0, 1, 20, 0, 3], 6, (8, 256)),
    "a group of one row": ([1, 0, 0, 0, 0, 0], 0, (8, 256)),
    "a tile three groups share": ([3, 2, 2, 0, 9, 0], 2, (8, 256)),
    "a group over five tiles": ([2, 37, 0, 0, 0, 1], 1, (8, 256)),
    "the width in two tiles": ([10, 10, 10, 10, 10, 10], 3, (16, 128)),
    "the width in four tiles": ([0, 31, 0, 30, 0, 5], 6, (32, 64)),
    "every row one group's": ([96, 0, 0, 0, 0, 0], 3, (32, 128)),
    "no row at all": ([0, 0, 0, 0, 0, 0], 0, (8, 256)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_loop_over_the_groups(case):
    """The kernel's rows ``[0, sum(load))`` are the loop's, whichever way the
    groups fall across the row tiles and the width across its tiles; its
    work list is one entry for each (group, tile) pair that holds a row."""
    load, first, tiles = CASES[case]
    xs = jax.random.normal(jax.random.PRNGKey(5), (96, 64), jnp.float32)
    w_in, w_out = _stacks()
    out = expert_gmm.expert_gmm(
        xs, w_in, w_out, jnp.asarray(load, jnp.int32), jnp.int32(first), experts=6,
        tiles=tiles, interpret=True)
    want, r = _loop(xs, w_in, w_out, load, first)
    assert out.shape == (96, 64) and out.dtype == jnp.float32
    assert np.abs(np.asarray(out)[:r] - want[:r]).max(initial=0.0) <= TOL
    edges = np.concatenate([[0], np.cumsum(load)])
    pairs = [(g, t) for g, (a, b) in enumerate(zip(edges[:-1], edges[1:])) if b > a
             for t in range(a // tiles[0], (b - 1) // tiles[0] + 1)]
    assert int(expert_gmm.row_tiles(jnp.asarray(load), tiles[0])) == len(pairs)
    group, tile = expert_gmm.work_list(jnp.asarray(load, jnp.int32), tiles[0], 96 // tiles[0])
    assert list(zip(np.asarray(group).tolist(), np.asarray(tile).tolist()))[:len(pairs)] == pairs
    if case == "a tile three groups share":
        assert len(pairs) == 5  # tile 0: groups 0, 1, 2 and the start of 4; tile 1: its rest
    if case == "no row at all":
        assert pairs == []


def test_a_row_tile_past_the_groups_is_not_run():
    """Rows past ``sum(load)`` (tokens not kept, assignments to experts held
    elsewhere) stand last: the work list names no tile that holds only such
    rows, so a poisoned tail changes nothing and is not even multiplied (its
    NaNs would show in no row that is read, and the tiles it fills are not
    written: they keep what the output buffer held)."""
    load = jnp.asarray([7, 0, 6, 0, 0, 0], jnp.int32)  # 13 rows of 96: two tiles of 8
    xs = jax.random.normal(jax.random.PRNGKey(6), (96, 64), jnp.float32)
    w_in, w_out = _stacks()
    run = functools.partial(expert_gmm.expert_gmm, w_in=w_in, w_out=w_out, load=load,
                            first=jnp.int32(0), experts=6, tiles=(8, 256), interpret=True)
    out, poisoned = run(xs), run(xs.at[16:].set(jnp.nan))
    assert int(expert_gmm.row_tiles(load, 8)) == 3  # group 0 in tile 0; group 2 in tiles 0 and 1
    assert np.asarray(expert_gmm.work_list(load, 8, 12)[1])[:3].tolist() == [0, 0, 1]
    assert np.array_equal(np.asarray(out)[:13], np.asarray(poisoned)[:13])
    assert np.isfinite(np.asarray(poisoned)[:13]).all()


def test_the_tiles_come_from_the_shapes():
    """The two configurations the kernel serves, and the tiny presets it does
    not: Mellum2's expert is fetched whole and its row tile is the mean group
    (16 assignments of a decode step's 128 over 64 experts: the smallest
    bfloat16 tile; 128 of a chunk's 8,192); command-a's is walked in eight
    tiles of 512 of its width with a row tile at the ridge, or all of a decode
    step's 128 assignments."""
    bf = jnp.bfloat16
    assert expert_gmm.tile_sizes(128, 2304, 896, 64, bf) == (16, 896)
    assert expert_gmm.tile_sizes(8192, 2304, 896, 64, bf) == (128, 896)
    assert expert_gmm.tile_sizes(128, 4096, 4096, 128, bf) == (128, 512)
    assert expert_gmm.tile_sizes(8192, 4096, 4096, 128, bf) == (256, 512)
    assert expert_gmm.tile_sizes(24, 64, 32, 8, jnp.float32) == (8, 32)
    S = jax.ShapeDtypeStruct
    for n, D, F, G, experts in ((128, 2304, 896, 28 * 64, 64), (8192, 2304, 896, 28 * 64, 64),
                                (128, 4096, 4096, 64, 128), (8192, 4096, 4096, 64, 128)):
        assert expert_gmm.expert_gmm_fits(n, S((G, D, 2 * F), bf), S((G, F, D), bf), experts)
        rows, width = expert_gmm.tile_sizes(n, D, F, experts, bf)
        assert expert_gmm.vmem_bytes(rows, width, D, bf) < 64 << 20
    tiny = sw.TINY_SPARSE
    assert not expert_gmm.expert_gmm_fits(
        24, S((48, 64, 64), jnp.float32), S((48, 32, 64), jnp.float32), tiny.n_experts)
    mellum = S((64, 2304, 1792), bf), S((64, 896, 2304), bf)
    assert expert_gmm.rows_for(128, *mellum, 64) == 16 and expert_gmm.rows_for(8192, *mellum, 64) == 128
    assert expert_gmm.rows_for(100, *mellum, 64) == 16  # a last tile's tail is padded
    assert expert_gmm.rows_for(128, *(S(w.shape, jnp.float32) for w in mellum), 64) == 0
    assert not sw.grouped_by_kernel(24, {"w_in": S((6, 8, 64, 64), bf), "w_out": S(
        (6, 8, 32, 64), bf)}, tiny)  # this process's backend is the CPU


def test_bfloat16_rounds_where_the_plain_twin_rounds():
    """At the weights' own type the kernel rounds ``h`` to bfloat16, takes the
    activation in float32 and rounds it once, and accumulates the second
    product in float32, as ``_grouped`` does: the two agree to float32
    rounding of sums in another order, not to a bfloat16 ulp."""
    load = jnp.asarray([20, 0, 33, 11, 0, 0], jnp.int32)
    xs = jax.random.normal(jax.random.PRNGKey(7), (64, 128), jnp.float32).astype(jnp.bfloat16)
    w_in, w_out = _stacks(G=8, D=128, F=256, dtype=jnp.bfloat16)
    out = expert_gmm.expert_gmm(xs, w_in, w_out, load, jnp.int32(2), experts=6,
                                tiles=(16, 128), interpret=True)
    twin = sw._grouped(xs, w_in, w_out, load, jnp.int32(2))
    gap = np.abs(np.asarray(out)[:64] - np.asarray(twin)[:64]).max()
    assert gap <= 2e-2 * np.abs(np.asarray(twin)[:64]).max()
    assert gap <= 0.05


# ---- under the expert layer -----------------------------------------------------


def _reference_moe(params, cfg, layer, h, first, count):
    """``test_sparse_window._reference_moe`` for either tiny preset: the
    reference's routing, then a loop over experts ``[first, first + count)``,
    each computing every row under its gate; the stacks hold them from
    ``cfg.expert_first`` on."""
    from benchmark.reference import parallel_sparse_ref, sparse_window_ref

    ref = parallel_sparse_ref if cfg.router_score == "sigmoid" else sparse_window_ref
    m = params["moe"]
    top_e, gates = ref.routing(h, m["router"][layer], cfg.top_k, "float32")
    y, F = jnp.zeros_like(h), cfg.expert_ffn
    for e in range(first, first + count):
        g = jnp.sum(jnp.where(top_e == e, gates, 0.0), axis=-1)
        gu = h @ m["w_in"][layer][e - cfg.expert_first]
        y = y + g[:, None] * ((jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ m["w_out"][layer][
            e - cfg.expert_first])
    return np.asarray(y), np.asarray(top_e)


STACKS = {"the whole stack": (sw.TINY_SPARSE, None, None),
          "a held share": (sw.TINY_PARALLEL, None, None),
          "a range of the whole": (sw.TINY_SPARSE, 4, 3)}


@pytest.mark.parametrize("tiles", [(8, 32), (16, 16)], ids=["one tile of F", "F in two tiles"])
@pytest.mark.parametrize("kept", ["every token", "tokens not kept", "one token"])
@pytest.mark.parametrize("stack", sorted(STACKS))
def test_the_expert_layer_through_the_kernel_is_the_reference_loop(stack, kept, tiles, monkeypatch):
    """``expert_layer`` with the kernel in ``_grouped``'s place, under a jit
    with the layer TRACED (as the layer scan hands it over): the reference's
    loop over the experts in hand, for all eight experts, for three of them
    and for the two a share holds of eight; ``load`` counts the kept tokens,
    and the kernel ran no tile past them."""
    cfg, first, count = STACKS[stack]
    seen = []
    forced_kernel(monkeypatch, tiles, seen)
    params = sw.sparse_init(jax.random.PRNGKey(3), cfg)
    T, layer = 37, 4
    h = jax.random.normal(jax.random.PRNGKey(1), (T, cfg.dim), jnp.float32)
    mask = {"every token": jnp.ones((T,), bool), "tokens not kept": jnp.arange(T) % 3 != 1,
            "one token": jnp.arange(T) == 20}[kept]
    got, load = jax.jit(lambda h, mask, layer: sw.expert_layer(
        h, params["moe"], layer, mask, cfg, first, count))(h, mask, jnp.int32(layer))
    lo = cfg.expert_first if first is None else first
    n = cfg.held if count is None else count
    want, top_e = _reference_moe(params, cfg, layer, h, lo, n)
    want = np.where(np.asarray(mask)[:, None], want, 0.0)
    counts = np.bincount(top_e[np.asarray(mask)].reshape(-1), minlength=cfg.n_experts)[lo:lo + n]
    assert np.array_equal(np.asarray(load), counts)
    assert np.abs(np.asarray(got) - want).max() <= TOL * max(1.0, np.abs(want).max())
    assert len(seen) == 1  # one call of the kernel, and nothing else multiplied


def test_a_decode_segment_counts_the_tiles_its_kernel_ran(monkeypatch):
    """``expert_tiles`` beside ``experts_touched``: three rows of which two
    keep tokens, four steps, six layers. A kept token's two assignments fall
    on two experts, so a layer's step touches 2 to 4; all 6 assignments of a
    step stand in one tile of 8 rows, so each touched expert is one entry of
    the work list and the two counters are equal. Without the kernel no tile
    is run."""
    cfg = sw.TINY_SPARSE
    params = sw.sparse_init(jax.random.PRNGKey(3), cfg)
    cache = sw.init_cache(cfg, 3, 256, 49, 49, 16)
    args = (params, cache, jnp.asarray([[5], [9], [2]], jnp.int32), jnp.zeros((3,), jnp.float32),
            jax.random.PRNGKey(0), jnp.asarray([4, 0, 3], jnp.int32))

    def segment(*args):  # traced anew at each jit: under the patch that holds then
        return jax.jit(functools.partial(sw.decode_segment, cfg=cfg, n_steps=4, greedy=True))(*args)

    plain_toks, _, _, _, plain = segment(*args)
    assert int(plain["expert_tiles"]) == 0 and int(plain["experts_touched"]) > 0
    with monkeypatch.context() as mp:
        forced_kernel(mp)
        toks, _, _, _, counted = segment(*args)
    assert np.array_equal(np.asarray(toks), np.asarray(plain_toks))
    assert int(counted["experts_touched"]) == int(plain["experts_touched"])
    assert int(counted["expert_tiles"]) == int(counted["experts_touched"])
    assert np.array_equal(np.asarray(counted["expert_tokens"]), np.asarray(plain["expert_tokens"]))
