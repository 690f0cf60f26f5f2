"""Cross-replica sharded weight update + comm/compute overlap
(docs/performance.md "Sharded weight update & overlap").

The contract under test: shard_update/overlap_comm change ONLY where the
update runs (reduce-scatter -> 1/dp optimizer apply -> all-gather instead
of all-reduce -> replicated apply), never the math — loss trajectories are
pinned against the replicated seed path, checkpoints round-trip ACROSS
update layouts (an old replicated checkpoint restores into a sharded
trainer and vice versa), the async checkpointer handles the scattered
optimizer state, and the elastic 4 -> 2 -> 4 reshard-resume stays
loss-invariant with the sharded update on. The log_every cadence's
no-blocking-transfer discipline and the host-side gradient-bucket plan are
pinned here too.
"""

import dataclasses

import jax
import numpy as np
import pytest

from kubedl_tpu.api.topology import MeshSpec
from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import build_mesh
from kubedl_tpu.training.buckets import (
    MIN_SCATTER_BYTES,
    plan_grad_buckets,
)
from kubedl_tpu.training.data import SyntheticTokens
from kubedl_tpu.training.trainer import (
    TrainConfig,
    Trainer,
    state_bytes_per_device,
)


@pytest.fixture(autouse=True, scope="module")
def _fresh_compiles_only():
    """Keep JAX's persistent compilation cache out of this file. A program
    of the ``data=2 x fsdp=4`` mesh that XLA:CPU *loads* from the cache
    (``cpu_aot_loader``) runs its independent collectives side by side,
    each holding a thread of the eight-thread pool in its rendezvous, and
    deadlocks (``rendezvous.cc`` "Termination timeout", then abort); the
    same program compiled in this process runs them in order. The cache is
    on in a tier-1 worker once any earlier test has run a training pod in
    process (``training/entry.py`` turns it on for the process). Same
    idiom as ``test_chip_compile.py``."""
    from jax._src import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


#: trajectory tolerance vs the replicated arm: the sharded update is the
#: SAME math in a different placement, so only reduction-order float32
#: noise separates the arms (measured 0.0 on pure-data meshes)
TRAJ_TOL = dict(rtol=1e-5, atol=1e-5)


def make_cfg(**kw):
    kw.setdefault("model", llama.TINY)
    kw.setdefault("global_batch", 8)
    kw.setdefault("seq_len", 16)
    kw.setdefault("steps", 6)
    return TrainConfig(**kw)


def data_at(step=0, seed=5, gb=8, sl=16):
    it = iter(SyntheticTokens(gb, sl, llama.TINY.vocab_size, seed=seed))
    for _ in range(step):
        next(it)
    return it


def run_losses(trainer, steps, state=None, **fit_kw):
    losses = []
    state, summary = trainer.fit(
        data_at(int(jax.device_get(state["step"])) if state else 0),
        state=state, steps=steps,
        on_step=lambda i, m: losses.append(m["loss"]),
        **fit_kw,
    )
    return state, summary, [float(jax.device_get(l)) for l in losses]


def mesh_of(axes, ndev=None):
    devs = jax.devices()[:ndev] if ndev else None
    return build_mesh(MeshSpec(axes), devs)


class TestUpdateLayout:
    def test_opt_state_scattered_to_1_over_dp(self):
        mesh = mesh_of({"data": 4}, 4)
        sh = Trainer(make_cfg(shard_update=True), mesh)
        rep = Trainer(make_cfg(shard_update=False), mesh)
        assert sh.update_shardings is not None
        assert rep.update_shardings is None
        b_sh = state_bytes_per_device(sh.init_state())
        b_rep = state_bytes_per_device(rep.init_state())
        assert b_sh < b_rep
        # matmul leaves (>= MIN_SCATTER_BYTES) scatter 4-way; only the
        # few-KB norm vectors stay replicated, so the reduction is
        # within 25% of the ideal 1/4
        assert b_sh < b_rep / 4 * 1.25

    def test_small_leaves_keep_param_sharding(self):
        mesh = mesh_of({"data": 4}, 4)
        tr = Trainer(make_cfg(shard_update=True), mesh)
        ups = jax.tree_util.tree_leaves(tr.update_shardings)
        pss = jax.tree_util.tree_leaves(tr.param_shardings)
        mask = list(tr.grad_bucket_plan.scatter)
        assert len(ups) == len(pss) == len(mask)
        assert any(mask) and not all(mask)  # TINY has both kinds
        for u, p, scattered in zip(ups, pss, mask):
            if scattered:
                assert u.spec != p.spec
            else:
                assert u.spec == p.spec

    def test_no_data_axis_falls_back_to_replicated(self):
        tr = Trainer(make_cfg(shard_update=True), mesh_of({"data": 1}, 1))
        assert tr.update_shardings is None

    def test_pipeline_mesh_keeps_replicated_update(self):
        tr = Trainer(
            make_cfg(shard_update=True), mesh_of({"data": 2, "pipe": 2}, 4)
        )
        assert tr.update_shardings is None

    def test_summary_reports_update_layout(self):
        tr = Trainer(
            make_cfg(shard_update=True, overlap_comm=True, steps=2),
            mesh_of({"data": 4}, 4),
        )
        _, summary, _ = run_losses(tr, 2)
        assert summary["shard_update"] is True
        assert summary["overlap_comm"] is True
        assert summary["grad_buckets"] >= 1
        assert summary["opt_state_bytes_per_device"] > 0


class TestLossTrajectoryEquivalence:
    def _run(self, cfg, mesh):
        _, _, losses = run_losses(Trainer(cfg, mesh), cfg.steps)
        return losses

    def test_sharded_and_overlap_match_replicated_data_mesh(self):
        mesh = mesh_of({"data": 4}, 4)
        base = make_cfg(grad_accum=2, shard_update=False,
                        overlap_comm=False)
        ref = self._run(base, mesh)
        assert len(ref) == base.steps
        sharded = self._run(
            dataclasses.replace(base, shard_update=True), mesh
        )
        overlap = self._run(
            dataclasses.replace(base, shard_update=True,
                                overlap_comm=True), mesh
        )
        np.testing.assert_allclose(sharded, ref, **TRAJ_TOL)
        np.testing.assert_allclose(overlap, ref, **TRAJ_TOL)

    def test_sharded_matches_replicated_on_fsdp_mesh(self):
        # data composes with fsdp: the scatter lands on the stacked-layer
        # dim (the only dim safe to carry "data" on a model-sharded mesh)
        mesh = mesh_of({"data": 2, "fsdp": 4}, 8)
        base = make_cfg(shard_update=False, overlap_comm=False)
        ref = self._run(base, mesh)
        tr = Trainer(dataclasses.replace(base, shard_update=True), mesh)
        assert tr.update_shardings is not None
        _, _, sharded = run_losses(tr, base.steps)
        np.testing.assert_allclose(sharded, ref, **TRAJ_TOL)

    def test_indivisible_scatter_falls_back_not_wrong(self):
        # data=4 x fsdp=2: TINY's stacked dim (n_layers=2) does not divide
        # the data axis and every free dim is either model-sharded or
        # excluded — the trainer must fall back to the replicated update,
        # not scatter something unsafe
        mesh = mesh_of({"data": 4, "fsdp": 2}, 8)
        tr = Trainer(make_cfg(shard_update=True), mesh)
        assert tr.update_shardings is None
        base = make_cfg(shard_update=False)
        ref = self._run(base, mesh)
        _, _, got = run_losses(tr, base.steps)
        np.testing.assert_allclose(got, ref, **TRAJ_TOL)


class TestCheckpointAcrossLayouts:
    """checkpoint.py's format is layout-independent (per-shard global
    offsets, region-lazy assembly): a checkpoint written under ONE update
    layout must restore bit-exactly under the OTHER."""

    def _train_and_save(self, cfg, mesh, ckpt):
        tr = Trainer(cfg, mesh)
        state, _, losses = run_losses(tr, 3, ckpt_dir=ckpt, ckpt_every=3)
        return state, losses

    @pytest.mark.parametrize("src_sharded,dst_sharded",
                             [(False, True), (True, False)])
    def test_restore_across_update_layouts(self, tmp_path, src_sharded,
                                           dst_sharded):
        from kubedl_tpu.training.checkpoint import restore_checkpoint

        mesh = mesh_of({"data": 4}, 4)
        ckpt = str(tmp_path / "ck")
        cfg = make_cfg(shard_update=src_sharded, ckpt_async=False)
        src_state, src_losses = self._train_and_save(cfg, mesh, ckpt)

        dst = Trainer(
            make_cfg(shard_update=dst_sharded, ckpt_async=False), mesh
        )
        restored = restore_checkpoint(ckpt, dst.init_state())
        assert restored is not None
        assert int(jax.device_get(restored["step"])) == 3
        # bit-exact params through the cross-layout assembler
        for a, b in zip(jax.tree_util.tree_leaves(src_state["params"]),
                        jax.tree_util.tree_leaves(restored["params"])):
            np.testing.assert_array_equal(jax.device_get(a),
                                          jax.device_get(b))
        # ...and the restored run continues the source trajectory
        _, _, more = run_losses(dst, 6, state=restored)
        full = Trainer(
            make_cfg(shard_update=src_sharded, ckpt_async=False), mesh
        )
        _, _, ref = run_losses(full, 6)
        np.testing.assert_allclose(src_losses + more, ref, **TRAJ_TOL)

    def test_async_checkpointer_round_trips_scattered_state(self, tmp_path):
        from kubedl_tpu.training.checkpoint import restore_checkpoint

        mesh = mesh_of({"data": 4}, 4)
        ckpt = str(tmp_path / "ck")
        cfg = make_cfg(shard_update=True, ckpt_async=True)
        tr = Trainer(cfg, mesh)
        state, _, _ = run_losses(tr, 4, ckpt_dir=ckpt, ckpt_every=2)
        # fit joined the writer before returning: latest save is step 4
        restored = restore_checkpoint(ckpt, Trainer(cfg, mesh).init_state())
        assert restored is not None
        assert int(jax.device_get(restored["step"])) == 4
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(jax.device_get(a),
                                          jax.device_get(b))


class TestElasticReshardShardedUpdate:
    def test_4_2_4_reshard_resume_loss_invariant(self, tmp_path):
        """The sharded update re-scatters to the NEW data axis at every
        shape (4-way -> 2-way -> 4-way) while the elastic grad-accum
        rescale keeps the effective global batch constant — the
        trajectory must match the fixed-size sharded run."""
        from kubedl_tpu.elastic.resize import grad_accum_for_world
        from kubedl_tpu.training.checkpoint import restore_checkpoint

        assert jax.device_count() >= 4
        GB, SL, STEPS = 8, 16, 7

        def cfg(accum):
            return TrainConfig(
                model=llama.TINY, global_batch=GB, seq_len=SL,
                steps=STEPS, grad_accum=accum, shard_update=True,
                overlap_comm=True, ckpt_async=False)

        def run(trainer, start, stop, ckpt):
            state = trainer.init_state()
            if start > 0:
                state = restore_checkpoint(ckpt, state)
                assert state is not None
                assert int(jax.device_get(state["step"])) == start
            losses = []
            state, _ = trainer.fit(
                data_at(start, gb=GB, sl=SL), state=state, steps=stop,
                on_step=lambda i, m: losses.append(m["loss"]),
                ckpt_dir=ckpt,
            )
            return [float(jax.device_get(l)) for l in losses]

        mesh4 = mesh_of({"data": 4}, 4)
        mesh2 = mesh_of({"data": 2}, 2)
        baseline = run(Trainer(cfg(1), mesh4), 0, STEPS,
                       str(tmp_path / "base"))
        assert len(baseline) == STEPS

        accum2 = grad_accum_for_world(1, 4, 2, GB)
        assert accum2 == 2
        ck = str(tmp_path / "elastic")
        losses = run(Trainer(cfg(1), mesh4), 0, 3, ck)
        losses += run(Trainer(cfg(accum2), mesh2), 3, 5, ck)
        losses += run(Trainer(cfg(1), mesh4), 5, STEPS, ck)
        assert len(losses) == STEPS
        np.testing.assert_allclose(losses, baseline, rtol=2e-3, atol=2e-3)


class TestLogEveryNoDeviceSync:
    def _fetches(self):
        import kubedl_tpu.training.trainer as tmod

        return tmod.SCALAR_FETCHES

    def test_steps_between_logs_issue_no_blocking_transfer(self):
        tr = Trainer(make_cfg(steps=6, log_every=0), mesh_of({"data": 4}, 4))
        before = self._fetches()
        _, summary, _ = run_losses(tr, 6)
        # exactly two true barriers: the first step (first_step_seconds
        # clock) and the final step (stops the throughput clock) — the 4
        # steps in between must not fetch
        assert self._fetches() - before == 2
        assert summary["loss_log"] == []

    def test_log_every_cadence_fetches_and_records(self):
        tr = Trainer(make_cfg(steps=6, log_every=2), mesh_of({"data": 4}, 4))
        before = self._fetches()
        _, summary, _ = run_losses(tr, 6)
        # first + final + the log_every fetches at steps 2 and 4 (step 6
        # IS the final fetch, not a duplicate)
        assert self._fetches() - before == 4
        assert [s for s, _ in summary["loss_log"]] == [2, 4]
        assert all(np.isfinite(v) for _, v in summary["loss_log"])


class TestLongContextPolicy:
    def test_auto_upgrades_remat_and_chunks_loss(self):
        model = dataclasses.replace(
            llama.TINY, max_seq=8192, remat=True, remat_policy="dots_flash"
        )
        cfg = TrainConfig(model=model, global_batch=2, seq_len=4096,
                          steps=1, long_context_policy="auto")
        tr = Trainer(cfg, mesh_of({"data": 2}, 2))
        assert tr.cfg.model.remat_policy == "flash_rope"
        assert tr.cfg.model.loss_chunk == 512
        assert "remat_policy=flash_rope" in tr.long_context_policy_applied
        assert "loss_chunk=512" in tr.long_context_policy_applied

    def test_short_seq_and_off_leave_model_alone(self):
        model = dataclasses.replace(
            llama.TINY, max_seq=8192, remat=True, remat_policy="dots_flash"
        )
        short = Trainer(
            TrainConfig(model=model, global_batch=2, seq_len=128, steps=1),
            mesh_of({"data": 2}, 2),
        )
        assert short.cfg.model.remat_policy == "dots_flash"
        assert short.long_context_policy_applied == ""
        off = Trainer(
            TrainConfig(model=model, global_batch=2, seq_len=4096, steps=1,
                        long_context_policy="off"),
            mesh_of({"data": 2}, 2),
        )
        assert off.cfg.model.remat_policy == "dots_flash"


class TestGradBucketPlan:
    def test_every_leaf_in_exactly_one_bucket(self):
        sizes = [100, 5000, 3 * 2**20, 10 * 2**20, 512, 4096]
        plan = plan_grad_buckets(sizes, bucket_bytes=4 * 2**20)
        seen = sorted(i for b in plan.buckets for i in b)
        assert seen == list(range(len(sizes)))
        assert plan.total_bytes == sum(sizes)

    def test_buckets_respect_size_and_issue_order(self):
        sizes = [2 * 2**20] * 6
        plan = plan_grad_buckets(sizes, bucket_bytes=4 * 2**20)
        assert plan.n_buckets == 3
        for b in plan.buckets:
            assert sum(sizes[i] for i in b) <= 4 * 2**20
        # backward-readiness order: the LAST leaf's bucket issues first
        assert plan.buckets[0][0] == len(sizes) - 1

    def test_oversized_leaf_gets_its_own_bucket(self):
        plan = plan_grad_buckets([10 * 2**20, 100, 10 * 2**20],
                                 bucket_bytes=4 * 2**20)
        assert any(len(b) == 1 for b in plan.buckets)
        assert plan.n_buckets >= 2

    def test_scatter_flags_honor_min_bytes(self):
        sizes = [MIN_SCATTER_BYTES - 1, MIN_SCATTER_BYTES,
                 MIN_SCATTER_BYTES + 1]
        plan = plan_grad_buckets(sizes)
        assert plan.scatter == (False, True, True)
        assert plan.scattered_bytes == sum(sizes[1:])

    def test_bad_bucket_bytes_raises(self):
        with pytest.raises(ValueError):
            plan_grad_buckets([1024], bucket_bytes=0)

    def test_host_planning_within_tier1_budget(self):
        from scripts.scheduler_microbench import run_bucket_microbench

        out = run_bucket_microbench(iters=50)
        assert out["within_budget"], (
            f"bucket plan p95 {out['plan_ms_p95']} ms blew the "
            f"{out['budget_ms']} ms budget"
        )
