"""Worker entrypoint: what a TPUJob pod runs.

Usable two ways (matching the two container runtimes):
- subprocess: `python -m kubedl_tpu.training.entry`
- in-process: entrypoint string "kubedl_tpu.training.entry:train_main"

Reads the operator-injected bootstrap env (KUBEDL_*), initializes
`jax.distributed`, builds the mesh, **restores from the latest checkpoint**
(slice-granular restart-from-checkpoint, SURVEY.md §7 hard-part b: a gang
restart re-enters here and loses at most one save interval), trains with
periodic saves, and writes the final state to KUBEDL_MODEL_PATH (feeding
the ModelVersion lineage pipeline). The train config rides the env as JSON
under KUBEDL_TRAIN_CONFIG.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, Optional

from kubedl_tpu.utils.envguard import apply_env

#: last run's summary, for in-process harnesses (bench.py) to read back
LAST_SUMMARY: Optional[dict] = None

#: process-wide persistent-compile-cache event counters (jax's monitoring
#: listeners are global and cannot be unregistered, so ONE listener feeds
#: these and train_main reports per-run deltas — an in-process harness
#: calling train_main N times must not stack N listeners)
_CACHE_EVENTS = {"hits": 0, "misses": 0}
_CACHE_LISTENER_ON = False


def _ensure_cache_listener() -> None:
    global _CACHE_LISTENER_ON
    if _CACHE_LISTENER_ON:
        return
    _CACHE_LISTENER_ON = True
    import jax.monitoring

    def _on_event(event, **kw):
        if event.endswith("/cache_hits"):
            _CACHE_EVENTS["hits"] += 1
        elif event.endswith("/cache_misses"):
            _CACHE_EVENTS["misses"] += 1

    jax.monitoring.register_event_listener(_on_event)


def _model_preset(name: str):
    from kubedl_tpu.models import llama, moe

    if "moe" in name:
        return moe.preset(name)
    return llama.preset(name)


def train_main(env: Optional[Dict[str, str]] = None) -> int:
    global LAST_SUMMARY
    t_start = time.time()
    # startup attribution (BASELINE.md north star — the reference
    # instruments exactly this window, pkg/metrics/job_metrics.go:139-194):
    # each phase's wall seconds ride the worker summary so a slow cold
    # start is diagnosable from the pod log alone
    phases: Dict[str, float] = {}
    spawn_ts = float(os.environ.get("KUBEDL_SPAWN_TS", 0) or 0)
    if spawn_ts:
        phases["spawn_to_proc"] = max(t_start - spawn_ts, 0.0)
    # changed-vars-only environ writes: glibc setenv/putenv may realloc
    # the environ block, racing native getenv from XLA's persistent
    # worker threads (one process hosts every gang attempt).  A
    # replacement pod re-enters with an identical env, so the
    # steady-state restart path must not touch environ at all.
    apply_env(env)
    # import jax only after env is set (JAX_PLATFORMS etc.)
    from kubedl_tpu.utils.compile_cache import (
        cache_entry_count, enable_compilation_cache,
    )

    # before the first trace: a gang restart / resize / resume re-enters
    # here and must deserialize, not recompile, the unchanged train step
    cache_dir = enable_compilation_cache()
    cache_before = cache_entry_count(cache_dir)
    # comm/compute overlap (docs/performance.md "Sharded weight update &
    # overlap"): the sharded update leans on XLA's latency-hiding
    # scheduler to run the gradient reduce-scatter concurrently with
    # backward compute. TPU-only knobs, appended — never overwrite flags
    # the operator or user already set (their copy wins on conflict
    # because libtpu parses left to right, last occurrence winning).
    if "cpu" not in os.environ.get("JAX_PLATFORMS", "").lower():
        cur = os.environ.get("LIBTPU_INIT_ARGS", "")
        if "latency_hiding_scheduler" not in cur:
            os.environ["LIBTPU_INIT_ARGS"] = (
                "--xla_tpu_enable_latency_hiding_scheduler=true "
                "--xla_tpu_enable_async_collective_fusion=true "
                "--xla_tpu_enable_async_collective_fusion_fuse_all_gather"
                "=true " + cur
            ).strip()
    t0 = time.time()
    import jax

    # count persistent-cache hit/miss events IN THIS PROCESS (round-4
    # BENCH hole: "warm_compile_used" meant "an AOT executable exists",
    # which is also true when the warm thread silently recompiled for
    # 50s — only jax's own cache events distinguish served from rebuilt)
    _ensure_cache_listener()
    events_at_start = dict(_CACHE_EVENTS)

    from kubedl_tpu.api import constants
    from kubedl_tpu.parallel.mesh import initialize_from_env, mesh_from_env

    initialize_from_env()
    phases["jax_import"] = time.time() - t0

    # single-process jobs: bring the TPU client up in the background while
    # python pays for the heavy framework imports below (multi-process
    # jobs already initialized the backend via jax.distributed above)
    dev_thread = None
    if int(os.environ.get(constants.ENV_NUM_PROCESSES, "1")) <= 1:
        import threading

        dev_thread = threading.Thread(target=jax.devices, daemon=True,
                                      name="kubedl-devinit")
        dev_thread.start()
    t0 = time.time()
    from kubedl_tpu.training.checkpoint import restore_from_best
    from kubedl_tpu.training.data import SyntheticTokens
    from kubedl_tpu.training.trainer import TrainConfig, Trainer

    phases["imports"] = time.time() - t0
    t0 = time.time()
    if dev_thread is not None:
        dev_thread.join()
    jax.devices()
    phases["jax_device_init"] = time.time() - t0

    raw = os.environ.get("KUBEDL_TRAIN_CONFIG", "{}")
    opts = json.loads(raw)
    model = _model_preset(opts.get("model", "tiny"))
    import dataclasses

    for knob in ("remat_policy", "loss_chunk"):
        if knob in opts and hasattr(model, knob):
            model = dataclasses.replace(model, **{knob: opts[knob]})
    cfg = TrainConfig(
        model=model,
        global_batch=int(opts.get("global_batch", 8)),
        seq_len=int(opts.get("seq_len", min(128, model.max_seq))),
        steps=int(opts.get("steps", 5)),
        learning_rate=float(opts.get("learning_rate", 3e-4)),
        grad_accum=int(opts.get("grad_accum", 1)),
        attn_impl=opts.get("attn_impl", "auto"),
        context_parallel_impl=opts.get("context_parallel_impl", "ring"),
        microbatches=int(opts.get("microbatches", 0)),
        ckpt_every=int(opts.get("ckpt_every", 0)),
        ckpt_async=bool(opts.get("ckpt_async", True)),
        opt_moment_dtype=opts.get("opt_moment_dtype", "float32"),
        shard_update=bool(opts.get("shard_update", True)),
        overlap_comm=bool(opts.get("overlap_comm", True)),
        grad_bucket_mb=float(opts.get("grad_bucket_mb", 4.0)),
        log_every=int(opts.get("log_every", 0)),
        long_context_policy=opts.get("long_context_policy", "auto"),
    )
    # elastic resize (docs/elasticity.md): when the gang restarted at a
    # world size different from the one the job was tuned at, rescale
    # grad accumulation so the per-device microbatch stays at its tuned
    # size — global_batch (and the loss trajectory) is unchanged
    base_world = int(os.environ.get(constants.ENV_ELASTIC_BASE_WORLD, "0") or 0)
    world = int(os.environ.get(constants.ENV_NUM_PROCESSES, "1") or 1)
    # planner-owned meshes (docs/planning.md): rescale in data-parallel
    # units instead — a re-plan may have moved chips between data and
    # model axes, so the raw process count no longer tracks batch shards
    base_dp = int(os.environ.get(constants.ENV_ELASTIC_BASE_DP, "0") or 0)
    mesh_axes = os.environ.get(constants.ENV_MESH_AXES, "")
    if base_dp > 0 and mesh_axes:
        from kubedl_tpu.api.topology import MeshSpec
        from kubedl_tpu.elastic.resize import data_parallel_world

        base_world = base_dp
        world = data_parallel_world(MeshSpec.from_env(mesh_axes))
    if base_world > 0 and world != base_world:
        from kubedl_tpu.elastic.resize import grad_accum_for_world

        accum = grad_accum_for_world(
            cfg.grad_accum, base_world, world, cfg.global_batch
        )
        if accum != cfg.grad_accum:
            print(
                json.dumps({"elastic_grad_accum": accum, "world": world,
                            "base_world": base_world}),
                flush=True,
            )
            cfg = dataclasses.replace(cfg, grad_accum=accum)
    t0 = time.time()
    mesh = mesh_from_env()
    trainer = Trainer(cfg, mesh)
    phases["trainer_build"] = time.time() - t0
    # overlap the two big cold-start compiles: the train step AOT-compiles
    # in a background thread while init_state compiles+runs on this one
    trainer.warm_compile_async()

    out = os.environ.get(constants.ENV_MODEL_PATH, "")
    ckpt_dir = os.environ.get(constants.ENV_CKPT_DIR, "")
    if not ckpt_dir and out and cfg.ckpt_every:
        from kubedl_tpu.remote.client import is_remote_root as _remote

        if _remote(out):
            # a remote model root is a URL: deriving checkpoints/ under it
            # would write a literal `http:/...` tree into the cwd. Keep
            # periodic saves on fast local disk; the final publish uploads.
            import hashlib
            import tempfile

            ckpt_dir = os.path.join(
                tempfile.gettempdir(),
                "kubedl-ckpt-" + hashlib.sha256(out.encode()).hexdigest()[:16],
            )
        else:
            ckpt_dir = os.path.join(out, "checkpoints")

    # restore-from-latest: a gang restart resumes instead of retraining.
    # The fresh init doubles as the restore template (shardings/structure)
    # and is reused as-is on a cold start — init runs exactly once.
    t0 = time.time()
    state = trainer.init_state()
    # peer-replicated restore (docs/robustness.md "Async checkpointing"):
    # when the owning host's local shard dir is gone (node replacement),
    # pull the mirrored shards from the peer blob root before giving up
    ckpt_peer = os.environ.get(constants.ENV_CKPT_PEER, "")
    if ckpt_dir:
        restored = restore_from_best(
            ckpt_dir, state, sources=[s for s in (ckpt_peer,) if s]
        )
        if restored is not None:
            state = restored
            step = int(jax.device_get(state["step"]))
            print(json.dumps({"resumed_from_step": step}), flush=True)
    phases["state_init"] = time.time() - t0

    t0 = time.time()
    data_path = opts.get("data_path", "")
    if data_path:
        # real token file through the native prefetch loader (C++ ring;
        # numpy where no compiler exists) — batch assembly off the
        # critical path
        from kubedl_tpu.data import TokenFileDataset

        data = TokenFileDataset(
            data_path, cfg.global_batch, cfg.seq_len,
            seed=cfg.seed, token_bytes=int(opts.get("token_bytes", 4)),
        )
    else:
        data = SyntheticTokens(cfg.global_batch, cfg.seq_len, model.vocab_size)
    data_loader = getattr(data, "loader_name", "synthetic")
    phases["data_build"] = time.time() - t0
    first_step_wall = {}
    cancel = (env or {}).get("_KUBEDL_CANCEL")  # ThreadRuntime cancellation
    # fault injection (net-new vs reference, SURVEY.md §5 "No fault
    # injection anywhere"): die retryably ONCE at a given step — exercises
    # the slice-granular restart-from-checkpoint path end to end
    fault_step = int(os.environ.get("KUBEDL_FAULT_ONCE_AT_STEP", "-1"))
    fault_marker = os.environ.get("KUBEDL_FAULT_MARKER", "")

    # progress beacon (kubedl_tpu/watchdog/): a side thread stamps
    # {step, tokens, ts} to the operator-injected file so the watchdog can
    # tell a wedged step loop (ts fresh, step frozen) from a dead process
    # (everything frozen). Training never depends on the beacon.
    beacon = None
    beacon_file = os.environ.get(constants.ENV_BEACON_FILE, "")
    if beacon_file:
        from kubedl_tpu.watchdog.beacon import ProgressBeacon

        try:
            beat = float(os.environ.get(constants.ENV_BEACON_INTERVAL, "0.5"))
        except ValueError:
            beat = 0.5
        beacon = ProgressBeacon(beacon_file, interval=beat).start()
    tokens_per_step = float(cfg.global_batch * cfg.seq_len)
    from kubedl_tpu import chaos

    def on_step(i, metrics):
        if "t" not in first_step_wall:
            first_step_wall["t"] = time.time()
        if beacon is not None:
            beacon.step(i + 1, tokens=(i + 1) * tokens_per_step)
        if cancel is not None and getattr(cancel, "is_set", lambda: False)():
            raise SystemExit(137)  # retryable: gang restart requested
        if (
            fault_step >= 0
            and i == fault_step
            and fault_marker
            and not os.path.exists(fault_marker)
        ):
            with open(fault_marker, "w") as f:
                f.write("fired")
            raise SystemExit(137)
        if chaos.should_fail("trainer.step_stall"):
            # injected hang: wedge the STEP LOOP without exiting — the
            # beacon thread keeps stamping fresh ts, so the watchdog sees
            # the hang signature (not silent death). Only the kubelet's
            # cancel/kill gets us out. A latency-mode spec returns after
            # should_fail's own bounded sleep instead of entering this.
            while True:
                if cancel is not None and getattr(
                    cancel, "is_set", lambda: False
                )():
                    raise SystemExit(137)
                time.sleep(0.02)

    # a warm restart never waits long for the background AOT compile: the
    # plain jit deserializes the on-disk entry in seconds, so a stalled
    # compile thread is abandoned, not waited out. A cold start keeps the
    # unbounded join — the join IS the compile there. Warm is classified
    # by THIS process's cache events at decision time (init has compiled
    # by now: a cold run has already missed; entries_before>0 would
    # misclassify whenever the dir holds unrelated programs).
    # KUBEDL_WARM_JOIN_TIMEOUT: seconds; 0 = don't wait at all; negative
    # or malformed = unbounded.
    warm_join_timeout: Optional[float] = None
    looks_warm = (
        _CACHE_EVENTS["hits"] - events_at_start["hits"] > 0
        and _CACHE_EVENTS["misses"] - events_at_start["misses"] == 0
    )
    if looks_warm:
        try:
            warm_join_timeout = float(
                os.environ.get("KUBEDL_WARM_JOIN_TIMEOUT", "30")
            )
        except ValueError:
            warm_join_timeout = 30.0  # never let a bad env kill the job
        if warm_join_timeout < 0:
            warm_join_timeout = None
    # parameter-service mode (docs/elasticity.md "Parameter-service
    # mode"): instead of the synchronous gang, this worker pushes deltas
    # to / pulls shards from the PS tier at KUBEDL_PS_ADDR, so peer
    # preemptions never restart it. The sync path below is untouched.
    train_mode = opts.get("train_mode", "sync")
    ps_addr = os.environ.get(constants.ENV_PS_ADDR, "")
    try:
        if train_mode == "ps" and ps_addr:
            from kubedl_tpu.ps.server import PSClient

            worker_id = "worker-" + os.environ.get(
                constants.ENV_PROCESS_ID, "0"
            )
            push_every = int(
                os.environ.get(constants.ENV_PS_PUSH_EVERY, "0")
                or opts.get("ps_push_every", 1)
            )
            state, summary = trainer.fit_ps(
                iter(data),
                PSClient(ps_addr),
                worker_id,
                state=state,
                on_step=on_step,
                push_every=push_every,
            )
        else:
            state, summary = trainer.fit(
                iter(data),
                state=state,
                on_step=on_step,
                ckpt_dir=ckpt_dir or None,
                ckpt_every=cfg.ckpt_every,
                ckpt_peer=ckpt_peer,
                warm_join_timeout=warm_join_timeout,
            )
    finally:
        if beacon is not None:
            beacon.stop()  # flush the final step count
    summary["data_loader"] = data_loader  # native | numpy | synthetic
    summary["first_step_wall_time"] = first_step_wall.get("t", time.time())
    total = summary["first_step_wall_time"] - (spawn_ts or t_start)
    # phases must SUM to total_to_first_step (round-4 VERDICT: a 57s warm
    # stall sat in an uninstrumented window) — fold fit's own phases in
    # and surface whatever remains as an explicit residual
    phases["warm_compile_join"] = summary.get("warm_compile_join_s", 0.0)
    phases["pre_loop_sync"] = summary.get("pre_loop_sync_s", 0.0)
    phases["first_step"] = summary.get("first_step_seconds", 0.0)
    phases["unattributed"] = max(
        total - sum(v for k, v in phases.items() if k != "total_to_first_step"),
        0.0,
    )
    phases["total_to_first_step"] = total
    summary["startup_phases"] = {k: round(v, 3) for k, v in phases.items()}
    hits = _CACHE_EVENTS["hits"] - events_at_start["hits"]
    misses = _CACHE_EVENTS["misses"] - events_at_start["misses"]
    summary["compile_cache"] = {
        "dir": cache_dir,
        "entries_before": cache_before,
        "entries_after": cache_entry_count(cache_dir),
        "cache_hits": hits,
        "cache_misses": misses,
        # decided at resolve time inside fit (a timed-out warm thread
        # finishing late must not claim credit)
        "aot_executable_used": trainer._aot_used,
        # an AOT executable merely existing is NOT a warm start: every
        # compile this process requested must have been SERVED from the
        # persistent cache (hits observed, zero misses)
        "warm_compile_used": (
            trainer._aot_used and hits > 0 and misses == 0
        ),
    }
    LAST_SUMMARY = summary
    print(json.dumps({"worker_summary": summary}), flush=True)

    if out:
        from kubedl_tpu.remote.client import is_remote_root, upload_tree
        from kubedl_tpu.training.checkpoint import save_checkpoint

        step = int(jax.device_get(state["step"]))
        if is_remote_root(out):
            # a remote model root is a URL, not a directory: saving onto it
            # directly would create a literal `http:/host/...` tree in the
            # cwd (the r5 junk-tree bug). Save to a scratch dir and push
            # through the blob client instead.
            import tempfile

            with tempfile.TemporaryDirectory(prefix="kubedl-publish-") as tmp:
                save_checkpoint(tmp, state, step)
                n = upload_tree(tmp, out)
                print(f"published {n} blobs to {out}", flush=True)
        elif os.path.abspath(ckpt_dir or "") != os.path.abspath(out):
            # publish the final state at the model-path root — serving and
            # the ModelVersion build read `latest` from there, not from
            # checkpoints/
            save_checkpoint(out, state, step)
    return 0


if __name__ == "__main__":
    sys.exit(train_main())
