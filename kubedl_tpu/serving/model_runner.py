"""The model's side of the serving engine: how the weights are made, the
K/V arrays and every device program, behind one class.

``LlamaEngine`` (serving/server.py) owns the schedule; what it schedules
ONTO is a ``ModelRunner``. The scheduler never names the model; the runner
never takes the engine's lock. Three facts live here and nowhere else:
which family of device functions runs (paged or contiguous, gather or
blocked attention: chosen once, in the constructor); the K/V row's format
(the ``[L, NB, BS, KV, hd]`` pools, ``block_bytes``, block export/import);
and which programs donate the cache (it lives here, every program that
consumes it reassigns it here, and the engine's host mirrors reach it only
through :meth:`upload_mirrors`). A new model kind brings a runner.

A fourth since the view got a span: how many keys of a row the paged
gather programs attend over. The engine says how far a dispatch reaches
(``live_to``); the runner owns the ladder of spans (:attr:`ModelRunner.spans`)
its programs hold, and each program takes the branch of the smallest span
that holds ``live_to``.

A fifth since the decoder's decode step got a kernel: what its attention
reads. On a TPU, over a paged pool the kernel can take as it stands,
:class:`ModelRunner`'s decode segments attend through
``paged_attention``'s Pallas kernel, which fetches each scheduled row's own
blocks (:attr:`ModelRunner.decode_tile`); anywhere else, and in every other
program, the gathered view with its spans. No option chooses: ``kv_attention``
still picks the suffix programs' arm, and the CPU's decode step.

Four runners stand here. :class:`ModelRunner` is the decoder's
(``models/llama.py``). :class:`HybridRunner` is the hybrid state-space
model's (``models/hybrid_ssm.py``): the same paged pools for its few
attention layers, and beside them a fixed slab of recurrent state a row,
which its programs reset, carry and advance themselves.
:class:`SparseWindowRunner` is the sparse-expert decoder's
(``models/sparse_window.py``): a pool for the layers that keep the whole
context and a second, with a block table of its own, for the layers that
read only a window of it. :class:`RetentionRunner` is the attention-free
decoder's (``models/retention.py``): no pool at all, a row is its slab of
recurrent state and ``block_bytes`` is 0, from which the engine knows to
build no block pool. All answer the methods the engine calls, under
the same program names; :func:`make_runner` picks one by the type of the
preset's config, and what they share is :class:`_Runner`.
"""

from __future__ import annotations

import functools
import logging
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubedl_tpu import chaos
from kubedl_tpu.models import (
    hybrid_ssm, llama, paged_attention, retention, sparse_window)

log = logging.getLogger("kubedl_tpu.serving.model_runner")


def _named(name: str, fn):
    """``fn`` under ``name``. JAX calls a jitted function's program
    ``jit_<__name__>``, and that is the name a device profile's
    ``XLA Modules`` line gives each execution — the only handle a trace
    has for telling a prefill from a decode (docs/observability.md
    "Device profiles"). A lambda or a ``functools.partial`` would read
    ``jit__lambda`` / ``jit__unknown`` there."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class _Runner:
    """What every runner has: the mirror upload, the ladder of view spans,
    and the two programs that know no model (the first-token sampler and
    the chain merge)."""

    #: the shortest span of the gathered view, in keys. The ladder is the
    #: powers of two from here up to ``max_seq``, ``max_seq`` itself the
    #: last: an engine whose ``max_seq`` is no longer than this has one
    #: span and compiles what it always compiled. A class constant and no
    #: option, like ``LlamaEngine.SEGMENT_BUCKETS``; a test may shrink it.
    SPAN_FLOOR = 1024
    #: bytes of recurrent state a row owns, whatever its context (beside
    #: its blocks, or instead of them where ``block_bytes`` is 0); 0: a row
    #: is its blocks and nothing else. Non-zero, the engine builds no prefix
    #: cache and refuses speculation and hand-off (a prefix is then more
    #: than a list of blocks).
    state_bytes_per_row = 0
    #: keys the layers of a second, windowed pool read back from a row's
    #: position; 0: one kind of block. Non-zero, the cache holds that pool
    #: (``wk``/``wv``) with its own table ``wbt``, the engine keeps a
    #: ``kv_blocks.WindowTable`` for it, and as with state a prefix is more
    #: than a list of blocks.
    window = 0
    #: what the last decode segment counted beside its tokens (device
    #: arrays by name, harvested with the tokens); None: nothing
    segment_counters = None
    #: keys a compute block of the decode kernel holds, where a runner's
    #: decode steps attend through it; 0: they do not
    decode_tile = 0

    def _build_samplers(self) -> None:
        # first-token sampler, ON DEVICE: fetching the prefill logits to
        # sample on the host moved the full [B, V] array to the host —
        # 8MB for Gemma-2B at B=8. Only the sampled ids ([B] int32)
        # cross now.
        def _pick(logits, temps, key):
            g = jax.random.gumbel(key, logits.shape, dtype=logits.dtype)
            z = jnp.where(
                temps[:, None] > 0.0,
                logits / jnp.maximum(temps[:, None], 1e-4) + g,
                logits,
            )
            return jnp.argmax(z, axis=-1).astype(jnp.int32)

        self.sample_first = jax.jit(_named("engine_sample_first", _pick))
        #: grafts prefill-sampled first tokens into the device token chain
        #: (llama.merge_chain_tokens) so interleaved admissions never force
        #: the chain back through the host
        self.merge_chain = jax.jit(
            _named("engine_merge_chain", lambda last, ids, mask: (
                llama.merge_chain_tokens(last, ids, mask)
            ))
        )

    def _restored(self, params, ckpt_dir: str, require_ckpt: bool):
        """``params`` as the newest checkpoint under ``ckpt_dir`` holds them
        (as they are where there is none, unless ``require_ckpt``)."""
        from kubedl_tpu.training import checkpoint

        step = checkpoint.latest_step(ckpt_dir) if ckpt_dir else None
        if require_ckpt and step is None:
            raise ValueError(f"no checkpoint found under {ckpt_dir!r}")
        if ckpt_dir and step is not None:
            state = checkpoint.restore_checkpoint(ckpt_dir, {"params": params})
            if state is not None:
                params = state["params"]
                log.info("restored checkpoint from %s", ckpt_dir)
            elif require_ckpt:
                raise ValueError(
                    f"no complete checkpoint step under {ckpt_dir!r} "
                    "(every step torn/incomplete)"
                )
        return params

    def _upload_mirror(self, arr):
        """Upload a host mirror as an XLA-OWNED device buffer.

        ``jnp.asarray`` zero-copy BORROWS an aligned numpy buffer, and the
        cache is donated into every jitted dispatch — donating a
        borrowed buffer lets XLA alias segment outputs onto it, which
        either scribbles sampled tokens into the live mirror or hands the
        harvest a stale view of the block table (both observed on the CPU
        backend; whether a given numpy allocation is 64-byte aligned is
        luck, hence flaky). The no-op add forces materialization into a
        fresh buffer XLA owns outright. The add is dispatched
        asynchronously, though, and the scheduler goes on editing the
        mirror in place: it reads a private snapshot, or the device sees
        whatever the mirror holds by the time the add runs (greedy
        streams then differ from run to run on the CPU backend)."""
        return jnp.asarray(arr.copy()) + 0

    def upload_mirrors(self, bt, pos=None, wbt=None) -> None:
        """Make the engine's authoritative HOST mirrors the paged cache's
        block table and, when given, positions and the windowed pool's
        table."""
        if pos is not None:
            self.cache["pos"] = self._upload_mirror(pos)
        self.cache["bt"] = self._upload_mirror(bt)
        if wbt is not None:
            self.cache["wbt"] = self._upload_mirror(wbt)

    @property
    def pool_shape(self) -> tuple:
        return tuple(self.cache["k"].shape)

    # -- the span of the gathered view ---------------------------------------

    @classmethod
    def span_ladder(cls, max_seq: int, block: int = 1) -> tuple:
        """The view's spans for a row of ``max_seq`` keys: powers of two
        times :attr:`SPAN_FLOOR` below ``max_seq`` (whole blocks only),
        then ``max_seq``."""
        spans, s = [], max(1, int(cls.SPAN_FLOOR))
        while s < max_seq:
            if s % block == 0:
                spans.append(s)
            s *= 2
        return tuple(spans) + (max_seq,)

    def span_for(self, live_to: Optional[int]) -> int:
        """The smallest span that holds positions ``[0, live_to)``, which
        is the branch a program given ``live_to`` takes; ``max_seq`` for
        None or anything longer."""
        if live_to is not None:
            for span in self.spans:
                if live_to <= span:
                    return span
        return self.spans[-1]

    def _live_to(self, live_to: Optional[int]) -> tuple:
        """The argument that picks a program's span: none at all for a
        runner with one span (its programs take none)."""
        if len(self.spans) == 1:
            return ()
        return (np.int32(self.max_seq if live_to is None else live_to),)

    def _live_mask(self, rows) -> np.ndarray:
        """``[max_batch]`` bool: the rows a decode dispatch scheduled
        (``rows``; None: every row)."""
        live = np.ones((self.max_batch,), bool)
        if rows is not None:
            live[:] = False
            live[list(rows)] = True
        return live

    def keys_read(self, positions, n_steps: int) -> Optional[int]:
        """Keys the attention of an ``n_steps`` decode segment fetches for
        scheduled rows standing at ``positions``, through the decode kernel
        (one layer's call; a layer that reads a window of them fetches
        fewer); None where a step attends over the gathered view, whose cost
        is its span and not the rows."""
        if not self.decode_tile:
            return None
        # step j of the segment attends a row's pos + j + 1 keys
        lengths = np.minimum(
            np.asarray(positions, np.int64)[:, None] + np.arange(1, n_steps + 1),
            self.max_seq)
        return paged_attention.decode_keys_read(
            lengths, self.kv_block_size, self.max_seq // self.kv_block_size,
            self.decode_tile)

    def _build_row_prefills(self, model_prefill) -> None:
        """``_view``, ``_prefill`` and ``_prefill_from`` of a paged runner whose
        model has ONE prefill function, ``model_prefill(params, cache, tokens,
        lengths, cfg, rows, starts=, spans=, live_to=)``: whole prompts, and
        suffixes from ``starts`` over the span that holds ``live_to``. The
        rows' last-token logits land at ``rows`` of ``acc``."""
        cfg, spans = self.cfg, self.spans

        def view(live_to):
            return {"spans": spans, "live_to": live_to[0]} if live_to else {}

        def prefill(p, c, t, l, rows, acc):
            lg, c = model_prefill(p, c, t, l, cfg, rows)
            return acc.at[rows].set(lg), c

        def prefill_from(p, c, t, l, st, rows, acc, *live_to):
            lg, c = model_prefill(p, c, t, l, cfg, rows, starts=st,
                                  **view(live_to))
            return acc.at[rows].set(lg), c

        self._view = view
        self._prefill = jax.jit(
            _named("engine_prefill", prefill), donate_argnums=(1,))
        self._prefill_from = jax.jit(
            _named("engine_prefill_from", prefill_from), donate_argnums=(1,))

    def _jit_segment(self, n_steps: int, greedy: bool, body):
        """``body`` jitted as this runner's ``n_steps`` decode segment, the
        cache (its second argument) donated, kept for the next dispatch. The
        step count is in the name: a module event of a device profile
        carries a duration and nothing else."""
        name = f"engine_decode_seg{n_steps}" + ("" if greedy else "_sampled")
        fn = self._segments[(n_steps, greedy)] = jax.jit(
            _named(name, body), donate_argnums=(1,))
        return fn

    def _prefill_rows(self, params, toks, lens, starts, rows, acc, live_to):
        """One prefill program of a paged runner whose ``_prefill`` and
        ``_prefill_from`` take the compact batch's ``rows`` and the logits so
        far (``acc``; None: none yet): whole prompts without ``starts``,
        suffixes over the span that holds ``live_to`` with them."""
        acc = self._no_logits if acc is None else acc
        if starts is None:
            logits, self.cache = self._prefill(
                params, self.cache, toks, lens, rows, acc)
        else:
            logits, self.cache = self._prefill_from(
                params, self.cache, toks, lens, starts, rows, acc,
                *self._live_to(live_to))
        return logits


class ModelRunner(_Runner):
    """One model's config, K/V arrays and jitted programs. Programs take
    ``params`` explicitly, so a second weight tree (hot swap) rides the
    same compiles. ``llama.preset`` and ``llama.llama_init`` are looked up
    on the module at call time: benchmark/program.py swaps both while it
    builds an engine."""

    def __init__(self, preset: str, *, max_batch: int, max_seq: int = 0,
                 paged: bool = True, kv_block_size: int = 16,
                 kv_attention: str = "gather", quantize: str = "",
                 mesh_axes: Optional[Dict] = None, spec_k: int = 0,
                 spec_candidates: int = 1, spec_tree: bool = False) -> None:
        self.cfg = cfg = llama.preset(preset)
        self.max_batch = max_batch
        self.max_seq = max_seq or min(cfg.max_seq, 512)
        self.paged = paged
        if paged:
            # the gathered view is [B, MB * BS]: max_seq rounds UP to a
            # whole number of blocks so view position t == logical t
            bs = max(1, int(kv_block_size))
            self.kv_block_size = bs
            self.max_seq = ((self.max_seq + bs - 1) // bs) * bs
            #: bytes one block holds across both pools and all layers —
            #: the unit prefix-cache budget accounting is charged in
            self.block_bytes = int(
                2 * cfg.n_layers * bs * cfg.n_kv_heads
                * cfg.head_dim * np.dtype(cfg.dtype).itemsize
            )
        if quantize and quantize != "int8":
            raise ValueError(f"unknown quantize mode {quantize!r}")
        self.quantize = quantize
        self.mesh = None
        if mesh_axes:
            # multi-chip serving (BASELINE target 5: Gemma-2B on v5e-4):
            # megatron-shard the weights over the mesh; XLA inserts the
            # collectives in the jitted decode/prefill
            from kubedl_tpu.api.topology import MeshSpec
            from kubedl_tpu.parallel.mesh import build_mesh

            spec = MeshSpec({k: int(v) for k, v in mesh_axes.items()})
            self.mesh = build_mesh(spec, jax.devices()[: spec.size()])
            log.info("serving over mesh %s", dict(mesh_axes))
        self.cache = None  # the K/V arrays, ``pos`` and ``bt``: new_cache()
        #: the spans of the gathered view, ascending, ``max_seq`` the last.
        #: Only the paged gather programs have a view to cut; with one span
        #: they take no ``live_to`` and are the programs they always were.
        self.spans = (self.max_seq,)
        if paged and kv_attention == "gather":
            self.spans = self.span_ladder(self.max_seq, self.kv_block_size)

        #: keys a compute block of the decode kernel holds; 0: the decode
        #: steps attend over the gathered view (or the option's lax arm)
        self.decode_tile = 0

        # ---- the one place that picks the device-function family ----
        if paged:
            att = {"kv_attention": kv_attention}
            spans = self.spans
            # what can be observed: a TPU, and a pool whose blocks the
            # kernel can take as they stand. Then a decode step reads each
            # scheduled row's own blocks, whatever ``kv_attention`` says
            if jax.default_backend() == "tpu" and (
                    paged_attention.decode_kernel_fits(
                        1, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, bs,
                        cfg.dtype)):
                self.decode_tile = paged_attention.DEFAULT_TILE
            decode_att = {"kv_attention": "blocked"} if self.decode_tile else att

            def view(live_to):
                """What a program handed ``live_to`` (a 1-tuple, or none
                with one span) passes on to its ``llama`` function."""
                return {"spans": spans, "live_to": live_to[0]} if live_to else {}

            def decode_step(p, c, t):
                return llama.paged_decode_step_batched(p, c, t, cfg, **decode_att)

            # a paged prefill program computes a COMPACT batch: the cache
            # rows ``rows`` that hold prompt tokens this dispatch, and no
            # others (`LlamaEngine._dispatch_prefill`). Their last-token
            # logits land at ``rows`` of ``acc``, the [max_batch, V] array
            # the first-token sampler takes, so the sampler, its noise and
            # the token chain stay indexed by cache row.
            def prefill(p, c, t, l, rows, acc):
                # whole-prompt prefill is LOCAL causal attention (no pool
                # read), so there is nothing for the blocked kernel to do
                lg, c = llama.paged_prefill_batched(p, c, t, l, cfg, rows=rows)
                return acc.at[rows].set(lg), c

            def prefill_from(p, c, t, l, st, rows, acc, *live_to):
                lg, c = llama.paged_prefill_from(
                    p, c, t, l, st, cfg, rows=rows, **att, **view(live_to))
                return acc.at[rows].set(lg), c

            #: ``acc`` of a tick's first prefill program: rows no program
            #: computes sample from zeros, and nobody reads their token
            self._no_logits = jnp.zeros(
                (max_batch, cfg.vocab_size), jnp.float32
            )
            # paged prefix-cache ops: entries normally share the row's
            # blocks by reference (no device copy at all); graft only
            # fires for array-payload entries (direct inserts in tests)
            graft, segment = llama.paged_graft_prefix, llama.paged_decode_segment
        else:
            att = decode_att = {}

            def decode_step(p, c, t):
                return llama.decode_step_batched(p, c, t, cfg)

            def prefill(p, c, t, l):
                return llama.prefill_batched(p, c, t, l, cfg)

            # suffix-only prefill (per-row start offsets): newly admitted
            # rows with a grafted prefix consume only their uncached tail.
            # Same power-of-2 bucketing as prefill, so compile count
            # stays bounded (<= one per bucket per path).
            def prefill_from(p, c, t, l, st):
                return llama.prefill_batched_from(p, c, t, l, st, cfg)

            # graft writes a cached entry's K/V into a row (donated:
            # in-place in HBM). One compile per entry bucket length.
            graft, segment = llama.copy_prefix_into_row, llama.decode_segment

            def view(live_to):
                return {}
        self._segment, self._view = functools.partial(segment, **decode_att), view

        # the cache is DONATED: decode/prefill update it in place in HBM
        # instead of allocating a fresh copy every step
        self._decode = jax.jit(
            _named("engine_decode_step", decode_step), donate_argnums=(1,)
        )
        self._prefill = jax.jit(
            _named("engine_prefill", prefill), donate_argnums=(1,)
        )
        self._prefill_from = jax.jit(
            _named("engine_prefill_from", prefill_from), donate_argnums=(1,)
        )
        self._graft = jax.jit(
            _named("engine_graft", lambda c, k, v, row, n: (
                graft(c, k, v, row, n)
            )),
            donate_argnums=(0,),
        )
        #: the copy-on-write primitive for the partial tail block of a
        #: paged graft or insert. One compile.
        self._copy_block = jax.jit(
            _named("engine_copy_block", lambda c, src, dst: (
                llama.copy_kv_block(c, src, dst)
            )),
            donate_argnums=(0,),
        ) if paged else None
        #: copies a contiguous row's prefix span out as a new entry (NOT
        #: donated — the live cache survives); paged inserts never
        #: materialize arrays
        self._extract = None if paged else jax.jit(
            _named("engine_extract", lambda c, row, p_len: (
                llama.extract_prefix_from_row(c, row, p_len)
            )),
            static_argnums=(2,),
        )

        self._build_samplers()
        #: jitted multi-step decode segments keyed by (n_steps, greedy),
        #: built on first use — llama.decode_segment
        self._segments: Dict[tuple, object] = {}

        # speculation's device half (paged only; the engine's `_spec_tick`
        # holds the policy)
        self._verify = self._verify_multi = self._verify_tree = None
        if spec_k:
            self._verify = jax.jit(
                _named("engine_verify", lambda p, c, t, l, st: (
                    llama.paged_verify(p, c, t, l, st, cfg, **att)
                )),
                donate_argnums=(1,),
            )
            #: multi-candidate scorer: READ-ONLY (cache NOT donated
            #: and not returned, so XLA drops every cache write) —
            #: the winner goes back through the standard _verify
            self._verify_multi = jax.jit(
                _named("engine_verify_multi", lambda p, c, t, l, st: (
                    llama.paged_verify_multi(p, c, t, l, st, cfg, **att)
                )),
            ) if spec_candidates > 1 else None
            #: tree scorer: like _verify_multi, READ-ONLY over the
            #: trie layout; the walked winner goes back through the
            #: standard write-path _verify. Fixed node budget
            #: 1 + N*k -> one compile.
            self._verify_tree = jax.jit(
                _named("engine_verify_tree",
                       lambda p, c, t, pos, m, l, st: (
                           llama.paged_verify_tree(
                               p, c, t, pos, m, l, st, cfg, **att)
                       )),
            ) if spec_tree else None

    # -- weights ------------------------------------------------------------

    def build_params(self, ckpt_dir: str, require_ckpt: bool = False):
        """Build one servable parameter tree end to end: init → checkpoint
        restore → optional int8 quantization → mesh sharding. The whole
        pipeline runs OFF the dispatch path (init time or a hot-swap
        load), and nothing is committed anywhere until it returns — a
        failure at any stage leaves every already-serving version
        untouched, never a torn tree. The ``serving.weight_swap`` chaos
        site fires at the top so injected corrupt-artifact / mid-swap
        crashes exercise exactly that contract.

        ``require_ckpt`` (hot-swap loads): a version whose artifact is
        missing or torn beyond recovery must FAIL the load — serving
        freshly initialized random weights under a version id would be a
        silent model swap. Init keeps the permissive behaviour (tests and
        cold starts serve the preset without a checkpoint)."""
        chaos.check("serving.weight_swap")
        params = self._restored(
            llama.llama_init(jax.random.PRNGKey(0), self.cfg), ckpt_dir,
            require_ckpt)
        if self.quantize == "int8":
            # weight-only int8: decode is HBM-bound and weights dominate
            # the bytes — halves the per-token floor (docs/serving.md)
            params = llama.quantize_params(params, self.cfg)
            log.info("serving with int8 weight-only quantization")
        if self.mesh is not None:
            params = llama.shard_serving_params(params, self.cfg, self.mesh)
        return params

    # -- the donated device state -------------------------------------------

    def new_cache(self, kv_blocks: int = 0) -> None:
        """(Re)build the K/V arrays, zeroed; a paged pool of ``kv_blocks``
        blocks. Called by the engine's constructor and by its error
        recovery: a program that raised after donation leaves ``cache``
        pointing at deleted buffers."""
        if self.paged:
            self.cache = llama.init_paged_cache(
                self.cfg, self.max_batch, self.max_seq, kv_blocks,
                self.kv_block_size,
            )
        else:
            self.cache = llama.init_batched_cache(
                self.cfg, self.max_batch, self.max_seq
            )

    def reset_row(self, row: int) -> None:
        """Contiguous admission: position 0; stale KV is masked by pos."""
        self.cache["pos"] = self.cache["pos"].at[row].set(0)

    # -- the K/V row's format -----------------------------------------------

    def fits_pool(self, kv_shape) -> bool:
        """Whether exported blocks of shape ``[L, n, BS, KV, hd]`` can be
        imported into this pool (any ``n``)."""
        L, _nb, bs, kv, hd = self.pool_shape
        return tuple(kv_shape[0:1]) + tuple(kv_shape[2:]) == (L, bs, kv, hd)

    def export_blocks(self, blocks):
        """``blocks``' K/V payloads as host arrays ``[L, n, BS, KV, hd]``
        (a disaggregated handoff's body)."""
        k, v = llama.export_kv_blocks(self.cache, blocks)
        return np.array(jax.device_get(k)), np.array(jax.device_get(v))

    def import_blocks(self, k, v, blocks) -> None:
        """Scatter exported payloads into ``blocks`` of this pool."""
        self.cache = llama.import_kv_blocks(self.cache, k, v, blocks)

    def copy_block(self, src: int, dst: int) -> None:
        self.cache = self._copy_block(self.cache, src, dst)

    def graft(self, k, v, row: int, length: int) -> None:
        """Write an array-payload prefix entry's K/V into ``row``."""
        self.cache = self._graft(self.cache, k, v, row, length)

    def extract(self, row: int, p_len: int):
        """A contiguous row's first ``p_len`` positions as ``(k, v)``."""
        return self._extract(self.cache, row, p_len)

    # -- the programs, run on the runner's cache ----------------------------

    def warmup(self, params) -> None:
        """One decode step that schedules no row: proof the model runs, by
        a program the ticks use too (``engine_decode_step`` was one more
        executable to trace, lower and load in every start, for no tick)."""
        self.decode_segment(
            1, True, params, jnp.zeros((self.max_batch, 1), jnp.int32),
            jnp.zeros((self.max_batch,), jnp.float32), jax.random.PRNGKey(0),
            live_to=1, rows=())
        jax.block_until_ready(self.cache["pos"])

    def prefill(self, params, toks, lens, starts=None, rows=None, acc=None,
                live_to: Optional[int] = None):
        """One prefill program: whole prompts from position 0, or, given
        ``starts``, suffixes that attend through the cache. Paged: ``rows``
        names the compact batch's cache rows and ``acc`` holds the logits
        of the tick's earlier programs (None: none yet). ``live_to``: one
        past the highest position a suffix program reads or writes, which
        picks its view's span (None: the whole table). Returns the
        ``[max_batch, V]`` logits."""
        args = [toks, lens] if starts is None else [toks, lens, starts]
        if self.paged:
            args += [rows, self._no_logits if acc is None else acc]
        if starts is None:
            fn = self._prefill
        else:
            fn = self._prefill_from
            args += self._live_to(live_to)
        logits, self.cache = fn(params, self.cache, *args)
        return logits

    def _segment_fn(self, n_steps: int, greedy: bool):
        """Jitted n-step decode with on-device sampling (cache donated);
        one compile per (segment size, greedy) combination. Over the
        gathered view with several spans it takes ``live_to`` last, and
        holds a branch a span; through the decode kernel it takes the
        ``[max_batch]`` mask of scheduled rows instead, and has no span."""
        fn = self._segments.get((n_steps, greedy))
        if fn is None:
            seg, cfg, view = self._segment, self.cfg, self._view
            if self.decode_tile:
                def body(p, c, tokens, temps, key, live):
                    return seg(p, c, tokens, temps, key, cfg=cfg,
                               n_steps=n_steps, greedy=greedy, live=live)
            else:
                def body(p, c, tokens, temps, key, *live_to):
                    return seg(p, c, tokens, temps, key, cfg=cfg,
                               n_steps=n_steps, greedy=greedy, **view(live_to))
            fn = self._jit_segment(n_steps, greedy, body)
        return fn

    def decode_segment(self, n_steps: int, greedy: bool, params, tokens,
                       temps, key, live_to: Optional[int] = None, rows=None,
                       takes=None):
        """``n_steps`` decode steps, sampled on the device, over the view
        span that holds ``live_to``: one past the highest position a row
        whose tokens are read will stand at (None: the whole table).
        ``rows`` names the rows the dispatch scheduled (None: every row):
        the decode kernel attends those and fetches nothing for the others;
        the gathered view computes every row at the span. ``takes`` is how
        many of the segment's tokens each keeps; a decoder's row is its
        blocks and ``pos``, which the mirrors put right before the next
        dispatch, so nothing here needs it.
        Returns ``(toks [B, n_steps], last [B, 1], key)``."""
        if self.decode_tile:
            how = (jnp.asarray(self._live_mask(rows)),)
        else:
            how = self._live_to(live_to)
        toks, last, key, self.cache = self._segment_fn(n_steps, greedy)(
            params, self.cache, tokens, temps, key, *how,
        )
        return toks, last, key

    def verify(self, params, toks, lens, starts):
        """The write-path verify: consume ``toks`` from ``starts``, return
        the target's argmax after each input ``[B, S]``."""
        ids, self.cache = self._verify(params, self.cache, toks, lens, starts)
        return ids

    def verify_multi(self, params, cand_toks, lens, starts):
        return self._verify_multi(params, self.cache, cand_toks, lens, starts)

    def verify_tree(self, params, toks, pos, mask, lens, starts):
        return self._verify_tree(
            params, self.cache, toks, pos, mask, lens, starts
        )


class HybridRunner(_Runner):
    """The hybrid state-space model's config, cache and jitted programs
    (``models/hybrid_ssm.py``), behind the methods and program names of
    :class:`ModelRunner`. A row owns its blocks in the pools of the
    attention layers (``block_bytes`` counts those layers alone) and a
    fixed slab of recurrent state (``state_bytes_per_row``). Nothing of the
    slab is the engine's to manage: a prefill program zeroes the slab of a
    row it starts at position 0 (an admission, or a re-admission after
    preemption) and carries it for a row it starts later (the next chunk of
    a prompt); a decode segment advances the slabs of the rows the dispatch
    scheduled (``rows``) and of no other. ``hybrid_ssm.preset`` and
    ``hybrid_ssm.hybrid_init`` are looked up on the module at call time, as
    the decoder's are.

    Paged, gather attention only. What rests on "a prefix is a list of
    blocks" has no meaning for a slab yet (prefix reuse, speculation's
    rollback, block hand-off): the engine refuses those at construction."""

    def __init__(self, preset: str, *, max_batch: int, max_seq: int = 0,
                 kv_block_size: int = 16) -> None:
        self.cfg = cfg = hybrid_ssm.preset(preset)
        self.max_batch = max_batch
        bs = self.kv_block_size = max(1, int(kv_block_size))
        self.max_seq = -(-(max_seq or min(cfg.max_seq, 512)) // bs) * bs
        self.block_bytes = int(
            2 * cfg.periods * bs * cfg.n_kv_heads * cfg.head_dim
            * np.dtype(cfg.dtype).itemsize
        )
        self.state_bytes_per_row = hybrid_ssm.state_bytes_per_row(cfg)
        self.cache = None
        self.spans = self.span_ladder(self.max_seq, bs)
        self._no_logits = jnp.zeros((max_batch, cfg.vocab_size), jnp.float32)
        self._build_row_prefills(hybrid_ssm.prefill)
        self._build_samplers()
        self._segments: Dict[tuple, object] = {}

    def build_params(self, ckpt_dir: str, require_ckpt: bool = False):
        """Init, then the newest checkpoint where there is one; committed
        nowhere until it returns (:meth:`ModelRunner.build_params`)."""
        chaos.check("serving.weight_swap")
        return self._restored(
            hybrid_ssm.hybrid_init(jax.random.PRNGKey(0), self.cfg), ckpt_dir,
            require_ckpt)

    def new_cache(self, kv_blocks: int = 0) -> None:
        """The pools, ``pos``, ``bt`` and every row's slab, zeroed."""
        self.cache = hybrid_ssm.init_cache(
            self.cfg, self.max_batch, self.max_seq, kv_blocks,
            self.kv_block_size)

    def warmup(self, params) -> None:
        """One decode step that advances no row: proof the model runs, by a
        program the ticks use too."""
        self.decode_segment(
            1, True, params, jnp.zeros((self.max_batch, 1), jnp.int32),
            jnp.zeros((self.max_batch,), jnp.float32), jax.random.PRNGKey(0),
            live_to=1, rows=())
        jax.block_until_ready(self.cache["pos"])
        self.segment_counters = None

    def prefill(self, params, toks, lens, starts=None, rows=None, acc=None,
                live_to: Optional[int] = None):
        """As :meth:`ModelRunner.prefill`. Without ``starts`` every row of
        the program begins from a zero slab; with them, the rows whose
        start is 0 do and the others carry theirs on."""
        return self._prefill_rows(params, toks, lens, starts, rows, acc, live_to)

    def _segment_fn(self, n_steps: int, greedy: bool):
        fn = self._segments.get((n_steps, greedy))
        if fn is None:
            cfg, view = self.cfg, self._view
            fn = self._jit_segment(
                n_steps, greedy, lambda p, c, tokens, temps, key, live, *live_to: (
                    hybrid_ssm.decode_segment(
                        p, c, tokens, temps, key, live, cfg, n_steps=n_steps,
                        greedy=greedy, **view(live_to))
                ))
        return fn

    def decode_segment(self, n_steps: int, greedy: bool, params, tokens,
                       temps, key, live_to: Optional[int] = None, rows=None,
                       takes=None):
        """As :meth:`ModelRunner.decode_segment`; the slabs of ``rows`` (the
        rows the dispatch scheduled; None: every row) advance, every other
        row's stays as it is. Leaves in ``segment_counters`` how many slabs
        the segment's steps fetched (``slabs_stepped``) of how many the rows
        hold (``slabs_held``): on a TPU the scheduled rows' alone."""
        toks, last, key, self.cache, self.segment_counters = self._segment_fn(
            n_steps, greedy)(
            params, self.cache, tokens, temps, key,
            jnp.asarray(self._live_mask(rows)), *self._live_to(live_to),
        )
        return toks, last, key


class SparseWindowRunner(_Runner):
    """The sparse-expert decoder's config, cache and jitted programs
    (``models/sparse_window.py``), behind the methods and program names of
    :class:`ModelRunner`. A row owns blocks of two kinds: in the pool of the
    full-attention layers, for its whole context (``block_bytes`` counts
    those layers), and in the pool of the window layers, for the last
    ``window`` keys and what a dispatch adds (``window_block_bytes``). The
    engine keeps both tables and uploads both; the programs here gather a
    full layer's view over the span ladder and a window layer's from the
    blocks that end at the row's position. A decode segment is told how many
    tokens each row keeps (``takes``): a step past that routes to no expert,
    and the segment's expert counters (``segment_counters``) count kept
    tokens alone. ``sparse_window.preset`` and ``sparse_window.sparse_init``
    are looked up on the module at call time, as the decoder's are.

    ``kv_attention="blocked"`` is the model's blocked arm: pools whose blocks
    ``paged_attention`` reads as they stand, no gathered view and so one
    span; a prefill program folds a row's keys in tiles, a decode step
    attends through ``paged_attention`` in both kinds of layer, which on a
    TPU (a pool the kernel can take) fetches each scheduled row's own blocks
    (:attr:`decode_tile`, as :class:`ModelRunner`'s).

    Paged only. What rests on "a prefix is a list of blocks" does not hold
    once a row's window blocks are released (prefix reuse, speculation's
    rollback, block hand-off): the engine refuses those at construction."""

    def __init__(self, preset: str, *, max_batch: int, max_seq: int = 0,
                 kv_block_size: int = 16, kv_attention: str = "gather") -> None:
        if kv_attention not in ("gather", "blocked"):
            raise ValueError(f"unknown kv_attention {kv_attention!r}")
        self.cfg = cfg = sparse_window.preset(preset)
        self.max_batch = max_batch
        bs = self.kv_block_size = max(1, int(kv_block_size))
        self.window = int(cfg.window)
        if self.window % bs:
            raise ValueError(
                f"preset {preset!r} reads a window of {self.window} keys, which "
                f"is not whole blocks of kv_block_size={bs}")
        self.max_seq = -(-(max_seq or min(cfg.max_seq, 512)) // bs) * bs
        token = 2 * bs * cfg.n_kv_heads * cfg.head_dim * np.dtype(cfg.dtype).itemsize
        self.block_bytes = int(cfg.n_full * token)
        self.window_block_bytes = int(cfg.n_window * token)
        self.cache = None
        self.window_blocks = 0  # size_window_pool()
        self.blocked = kv_attention == "blocked"
        #: keys a compute block of the decode kernel holds; 0: a decode step
        #: attends over gathered views, or through the blocked arm's lax scan
        self.decode_tile = paged_attention.DEFAULT_TILE if (
            self.blocked and jax.default_backend() == "tpu"
            and paged_attention.decode_kernel_fits(
                1, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, bs, cfg.dtype)) else 0
        self.spans = (self.max_seq,) if self.blocked else self.span_ladder(self.max_seq, bs)
        self._no_logits = jnp.zeros((max_batch, cfg.vocab_size), jnp.float32)
        self._build_row_prefills(sparse_window.prefill)
        self._build_samplers()
        self._segments: Dict[tuple, object] = {}

    def build_params(self, ckpt_dir: str, require_ckpt: bool = False):
        """Init, then the newest checkpoint where there is one; committed
        nowhere until it returns (:meth:`ModelRunner.build_params`)."""
        chaos.check("serving.weight_swap")
        return self._restored(
            sparse_window.sparse_init(jax.random.PRNGKey(0), self.cfg), ckpt_dir,
            require_ckpt)

    def size_window_pool(self, reach: int) -> int:
        """Size the window pool so that every row can hold its most at once:
        the window, one dispatch's ``reach`` of new positions (a prefill
        chunk, or the longest decode segment) and a block for the window's
        edge. Returns the pool's blocks, the trash block among them; the
        engine's ``WindowTable`` hands out the others. Once, before
        :meth:`new_cache`."""
        from kubedl_tpu.serving.kv_blocks import WindowTable

        self.window_blocks = 1 + self.max_batch * WindowTable.blocks_per_row(
            self.window, reach, self.kv_block_size,
            self.max_seq // self.kv_block_size)
        return self.window_blocks

    def new_cache(self, kv_blocks: int = 0) -> None:
        """Both pools, ``pos`` and both tables, zeroed."""
        self.cache = sparse_window.init_cache(
            self.cfg, self.max_batch, self.max_seq, kv_blocks,
            self.window_blocks, self.kv_block_size, blocked=self.blocked)

    def warmup(self, params) -> None:
        """One decode step that keeps no token: proof the model runs, by a
        program the ticks use too."""
        self.decode_segment(
            1, True, params, jnp.zeros((self.max_batch, 1), jnp.int32),
            jnp.zeros((self.max_batch,), jnp.float32), jax.random.PRNGKey(0),
            live_to=1, rows=(), takes=())
        jax.block_until_ready(self.cache["pos"])
        self.segment_counters = None

    def span_for(self, live_to: Optional[int]) -> int:
        """The blocked arm has no view: what a prefill program's full layers
        fold, ``live_to`` in whole tiles."""
        if not self.blocked:
            return super().span_for(live_to)
        tile = sparse_window.PREFILL_TILE
        return min(-(-(live_to or self.max_seq) // tile) * tile, self.max_seq)

    def prefill(self, params, toks, lens, starts=None, rows=None, acc=None,
                live_to: Optional[int] = None):
        """As :meth:`ModelRunner.prefill`."""
        return self._prefill_rows(params, toks, lens, starts, rows, acc, live_to)

    def _segment_fn(self, n_steps: int, greedy: bool):
        fn = self._segments.get((n_steps, greedy))
        if fn is None:
            cfg, view = self.cfg, self._view
            fn = self._jit_segment(
                n_steps, greedy, lambda p, c, tokens, temps, key, take, *live_to: (
                    sparse_window.decode_segment(
                        p, c, tokens, temps, key, take, cfg, n_steps=n_steps,
                        greedy=greedy, **view(live_to))
                ))
        return fn

    def decode_segment(self, n_steps: int, greedy: bool, params, tokens,
                       temps, key, live_to: Optional[int] = None, rows=None,
                       takes=None):
        """As :meth:`ModelRunner.decode_segment`; row ``rows[j]`` keeps the
        first ``takes[j]`` of the segment's tokens (None: every row keeps
        them all) and no other row keeps any. Leaves what the segment
        counted in ``segment_counters``."""
        take = np.full((self.max_batch,), n_steps, np.int32)
        if rows is not None:
            take[:] = 0
            take[list(rows)] = n_steps if takes is None else list(takes)
        toks, last, key, self.cache, self.segment_counters = self._segment_fn(
            n_steps, greedy)(
            params, self.cache, tokens, temps, key, jnp.asarray(take),
            *self._live_to(live_to),
        )
        return toks, last, key


class RetentionRunner(HybridRunner):
    """The attention-free decoder's config, cache and jitted programs
    (``models/retention.py``), behind the methods and program names of
    :class:`ModelRunner`. A row owns a fixed slab of recurrent state
    (``state_bytes_per_row``) and NO K/V block: ``block_bytes`` is 0, the
    cache is ``pos`` and the slabs, and the engine, told by that, builds no
    pool, admits by free rows and never preempts for want of blocks. The
    slab is the programs' to manage, by :class:`HybridRunner`'s rules: a
    prefill program zeroes the slab of a row it starts at position 0 and
    carries it for a row it starts later; a decode segment advances the
    slabs of the rows the dispatch scheduled (``rows``) and of no other.
    There is no view, so no span: ``max_seq`` bounds positions and nothing
    else. ``retention.preset`` and ``retention.retention_init`` are looked
    up on the module at call time, as the decoder's are.

    What rests on "a prefix is a list of blocks" has no meaning for a slab
    yet (prefix reuse, speculation's rollback, block hand-off): the engine
    refuses those at construction. The warm-up, the prefill and the decode
    segment's call are :class:`HybridRunner`'s own (with one span they hand
    the programs no ``live_to``); the programs are this model's."""

    block_bytes = 0

    def __init__(self, preset: str, *, max_batch: int, max_seq: int = 0,
                 kv_block_size: int = 16) -> None:
        self.cfg = cfg = retention.preset(preset)
        self.max_batch = max_batch
        # a prompt's chunks are cut in units of this, and nothing else is
        bs = self.kv_block_size = max(1, int(kv_block_size))
        self.max_seq = -(-(max_seq or min(cfg.max_seq, 512)) // bs) * bs
        self.state_bytes_per_row = retention.state_bytes_per_row(cfg)
        self.cache = None
        self.spans = (self.max_seq,)  # one: the programs take no ``live_to``
        self._no_logits = jnp.zeros((max_batch, cfg.vocab_size), jnp.float32)
        self._build_row_prefills(retention.prefill)
        self._build_samplers()
        self._segments: Dict[tuple, object] = {}

    def build_params(self, ckpt_dir: str, require_ckpt: bool = False):
        """Init, then the newest checkpoint where there is one; committed
        nowhere until it returns (:meth:`ModelRunner.build_params`)."""
        chaos.check("serving.weight_swap")
        return self._restored(
            retention.retention_init(jax.random.PRNGKey(0), self.cfg), ckpt_dir,
            require_ckpt)

    def new_cache(self, kv_blocks: int = 0) -> None:
        """``pos`` and every row's slab, zeroed; there is no pool to size."""
        self.cache = retention.init_cache(self.cfg, self.max_batch)

    def upload_mirrors(self, bt, pos=None, wbt=None) -> None:
        """The positions alone: the cache holds no block table."""
        if pos is not None:
            self.cache["pos"] = self._upload_mirror(pos)

    def span_for(self, live_to: Optional[int]) -> int:
        """0: no program gathers a view."""
        return 0

    def keys_read(self, positions, n_steps: int) -> Optional[int]:
        """0: a step reads the rows' state, and no key."""
        return 0

    def _segment_fn(self, n_steps: int, greedy: bool):
        fn = self._segments.get((n_steps, greedy))
        if fn is None:
            cfg = self.cfg
            fn = self._jit_segment(
                n_steps, greedy, lambda p, c, tokens, temps, key, live: (
                    retention.decode_segment(
                        p, c, tokens, temps, key, live, cfg, n_steps=n_steps,
                        greedy=greedy)
                ))
        return fn


def make_runner(preset: str, *, max_batch: int, max_seq: int = 0,
                paged: bool = True, kv_block_size: int = 16,
                kv_attention: str = "gather", quantize: str = "",
                mesh_axes: Optional[Dict] = None, spec_k: int = 0,
                spec_candidates: int = 1, spec_tree: bool = False):
    """The runner for ``preset``, by the type of its config: the one place
    that chooses. A preset of ``hybrid_ssm`` gets a :class:`HybridRunner`,
    one of ``sparse_window`` a :class:`SparseWindowRunner` and one of
    ``retention`` a :class:`RetentionRunner`, which refuse what they cannot
    do (``ValueError``, naming the reason: a row that holds recurrent state,
    beside its K/V blocks or in their place, or two kinds of block; only the
    sparse-window runner takes ``kv_attention="blocked"``); any other name is
    ``llama.preset``'s."""
    cfg = None
    for family in (hybrid_ssm, sparse_window, retention):
        try:
            cfg = family.preset(preset)
            break
        except KeyError:
            continue
    if cfg is None:
        return ModelRunner(
            preset, max_batch=max_batch, max_seq=max_seq, paged=paged,
            kv_block_size=kv_block_size, kv_attention=kv_attention,
            quantize=quantize, mesh_axes=mesh_axes, spec_k=spec_k,
            spec_candidates=spec_candidates, spec_tree=spec_tree)
    more = {}
    if isinstance(cfg, sparse_window.SparseWindowConfig):
        runner = SparseWindowRunner
        holds = "keeps two kinds of K/V block, one a window of the context,"
        draft = "would need window blocks the row has released"
        # this runner has the blocked arm: its model folds both pools in tiles
        more, kv_attention = {"kv_attention": kv_attention}, "gather"
    else:
        if isinstance(cfg, hybrid_ssm.HybridConfig):
            runner, holds = HybridRunner, "holds recurrent state beside its K/V blocks"
        else:
            runner, holds = RetentionRunner, "holds recurrent state and no K/V block"
        draft = ("would have to roll the recurrent state back, and only K/V "
                 "blocks can be freed in place")
    refused = {
        "kv_layout='contiguous' (and mesh_axes, which forces it)": not paged,
        "kv_attention='blocked'": kv_attention != "gather",
        "quantize": bool(quantize),
        f"spec_k > 0 (a rejected draft {draft})": spec_k > 0,
    }
    for what, asked in refused.items():
        if asked:
            raise ValueError(
                f"preset {preset!r} {holds} and cannot be served with {what}")
    return runner(preset, max_batch=max_batch, max_seq=max_seq,
                  kv_block_size=kv_block_size, **more)
