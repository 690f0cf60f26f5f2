"""Serving tests (reference analogue: controllers/serving suite): predictor
gating on artifact build, canary weight normalization, framework setters,
and a real end-to-end generate through the JAX server."""

import json
import time

import pytest

from kubedl_tpu.core.manager import ControllerManager
from kubedl_tpu.core.objects import PodPhase
from kubedl_tpu.core.store import ObjectStore
from kubedl_tpu.lineage.types import Model, ModelVersion, ModelVersionPhase
from kubedl_tpu.serving.controller import (
    LABEL_PREDICTOR,
    HTTP_PORT,
    InferenceController,
)
from kubedl_tpu.serving.types import (
    Framework,
    Inference,
    Predictor,
    TrafficPolicy,
)

from tests.helpers import PodDriver, env_of


def make_mv(store, name="mv1", model="m1", phase=ModelVersionPhase.SUCCEEDED,
            storage_root="/tmp/x"):
    mv = ModelVersion(model_name=model, storage_root=storage_root,
                      image=f"models/{model}:{name}", phase=phase)
    mv.metadata.name = name
    store.create(mv)
    return mv


def make_inference(store, predictors, framework=Framework.JAX, name="inf1"):
    inf = Inference(framework=framework, predictors=predictors)
    inf.metadata.name = name
    store.create(inf)
    return inf


def setup():
    store = ObjectStore()
    ctrl = InferenceController(store, local_addresses=True)
    return store, ctrl


class TestPredictorSync:
    def test_gated_on_artifact_build(self):
        store, ctrl = setup()
        make_mv(store, phase=ModelVersionPhase.IMAGE_BUILDING)
        make_inference(store, [Predictor(name="main", model_version="mv1")])
        ctrl.reconcile("default", "inf1")
        assert store.list("Pod") == []  # gated (reference :149-204)
        inf = store.get("Inference", "inf1")
        assert "waiting for artifact" in inf.predictor_statuses["main"].message
        # build completes -> pods appear
        def done(mv):
            mv.phase = ModelVersionPhase.SUCCEEDED
        store.update_with_retry("ModelVersion", "mv1", "default", done)
        ctrl.reconcile("default", "inf1")
        pods = store.list("Pod")
        assert [p.metadata.name for p in pods] == ["inf1-main-0"]

    def test_entry_service_and_scale(self):
        store, ctrl = setup()
        make_mv(store)
        make_inference(store, [Predictor(name="main", model_version="mv1",
                                         replicas=3)])
        ctrl.reconcile("default", "inf1")
        assert store.try_get("Service", "inf1", "default") is not None
        assert len(store.list("Pod")) == 3
        # scale down
        inf = store.get("Inference", "inf1")
        inf.predictors[0].replicas = 1
        store.update(inf)
        ctrl.reconcile("default", "inf1")
        assert len(store.list("Pod")) == 1

    def test_latest_version_tracking(self):
        store, ctrl = setup()
        mv = make_mv(store, name="mv2", model="m1")
        model = Model(latest_version="mv2")
        model.metadata.name = "m1"
        store.create(model)
        make_inference(store, [Predictor(name="main", model_name="m1")])
        ctrl.reconcile("default", "inf1")
        inf = store.get("Inference", "inf1")
        assert inf.predictor_statuses["main"].image == mv.image

    def test_jax_setter_env(self):
        store, ctrl = setup()
        make_mv(store, storage_root="/ckpts/m1")
        make_inference(store, [Predictor(name="main", model_version="mv1")])
        ctrl.reconcile("default", "inf1")
        pod = store.get("Pod", "inf1-main-0")
        env = env_of(pod)
        assert env["KUBEDL_MODEL_PATH"] == "/ckpts/m1"
        cfg = json.loads(env["KUBEDL_SERVE_CONFIG"])
        assert cfg["port"] == HTTP_PORT
        assert pod.spec.main_container().entrypoint == (
            "kubedl_tpu.serving.server:serve_main"
        )

    def test_tfserving_setter_env(self):
        store, ctrl = setup()
        make_mv(store)
        make_inference(store, [Predictor(name="main", model_version="mv1")],
                       framework=Framework.TF_SERVING)
        ctrl.reconcile("default", "inf1")
        env = env_of(store.get("Pod", "inf1-main-0"))
        assert env["MODEL_NAME"] == "m1"
        assert env["MODEL_BASE_PATH"] == "/models/m1"

    def test_removed_predictor_gc(self):
        store, ctrl = setup()
        make_mv(store)
        make_inference(store, [
            Predictor(name="a", model_version="mv1"),
            Predictor(name="b", model_version="mv1"),
        ])
        ctrl.reconcile("default", "inf1")
        assert len(store.list("Pod")) == 2
        inf = store.get("Inference", "inf1")
        inf.predictors = [p for p in inf.predictors if p.name == "a"]
        store.update(inf)
        ctrl.reconcile("default", "inf1")
        names = [p.metadata.name for p in store.list("Pod")]
        assert names == ["inf1-a-0"]


class TestTraffic:
    def test_canary_weights_normalized_over_ready(self):
        store, ctrl = setup()
        driver = PodDriver(store)
        make_mv(store)
        make_inference(store, [
            Predictor(name="stable", model_version="mv1", traffic_weight=90),
            Predictor(name="canary", model_version="mv1", traffic_weight=10),
        ])
        ctrl.reconcile("default", "inf1")
        # nothing ready yet -> no routes
        tp = store.get("TrafficPolicy", "inf1")
        assert tp.routes == []
        # only stable ready -> 100% stable (never route to dead canary)
        driver.run("inf1-stable-0")
        ctrl.reconcile("default", "inf1")
        tp = store.get("TrafficPolicy", "inf1")
        assert {r.predictor: r.weight for r in tp.routes} == {"stable": 100}
        # both ready -> 90/10
        driver.run("inf1-canary-0")
        ctrl.reconcile("default", "inf1")
        tp = store.get("TrafficPolicy", "inf1")
        weights = {r.predictor: r.weight for r in tp.routes}
        assert weights == {"stable": 90, "canary": 10}
        assert sum(weights.values()) == 100


class TestEndToEndServe:
    def test_generate_through_operator(self, tmp_path):
        """Train-less serve: publish a ModelVersion, create an Inference,
        wait for the predictor pod to run the real JAX server, hit HTTP."""
        import urllib.request

        from kubedl_tpu.operator import Operator, OperatorOptions
        from kubedl_tpu.runtime.executor import ThreadRuntime

        opts = OperatorOptions(
            local_addresses=True,
            artifact_registry_root=str(tmp_path / "reg"),
        )
        model_dir = tmp_path / "model"
        model_dir.mkdir()
        with Operator(opts, runtime=ThreadRuntime()) as op:
            mv = ModelVersion(model_name="m1", storage_root=str(model_dir),
                              phase=ModelVersionPhase.PENDING)
            mv.metadata.name = "mv1"
            op.store.create(mv)
            pred = Predictor(name="main", model_version="mv1")
            port = 18080
            pred.template.spec.main_container().set_env(
                "KUBEDL_SERVE_CONFIG", json.dumps({"port": port, "preset": "tiny"})
            )
            inf = Inference(framework=Framework.JAX, predictors=[pred])
            inf.metadata.name = "inf1"
            op.store.create(inf)

            # wait for the server pod to come up and answer
            deadline = time.time() + 60
            result = None
            while time.time() < deadline:
                try:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{port}/v1/generate",
                        data=json.dumps(
                            {"prompt_ids": [1, 2, 3], "max_tokens": 4}
                        ).encode(),
                        headers={"Content-Type": "application/json"},
                    )
                    with urllib.request.urlopen(req, timeout=5) as resp:
                        result = json.loads(resp.read())
                    break
                except Exception:
                    time.sleep(0.5)
            assert result is not None, "server never answered"
            assert len(result["token_ids"]) == 4
            assert result["prompt_len"] == 3
            tp = op.store.get("TrafficPolicy", "inf1")
            # serving pod is Running -> traffic routed to it
            assert any(r.predictor == "main" for r in tp.routes)


class TestContinuousBatching:
    def _reference_generate(self, engine, prompt, n):
        """Oracle: the original single-sequence decode_step loop."""
        import jax
        import jax.numpy as jnp

        from kubedl_tpu.models import llama

        cfg = engine.cfg
        decode = jax.jit(lambda p, c, t: llama.decode_step(p, c, t, cfg))
        cache = llama.init_cache(cfg, 1, engine.max_seq)
        logits = None
        for tok in prompt:
            logits, cache = decode(engine.params, cache,
                                   jnp.full((1, 1), int(tok), jnp.int32))
        out = []
        for _ in range(n):
            nxt = int(logits[0].argmax())
            out.append(nxt)
            logits, cache = decode(engine.params, cache,
                                   jnp.full((1, 1), nxt, jnp.int32))
        return out

    def test_batched_matches_single_sequence_oracle(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        try:
            prompt = [5, 9, 13]
            got = eng.generate(prompt, max_tokens=6)
            want = self._reference_generate(eng, prompt, 6)
            assert got["token_ids"] == want
            assert got["prompt_len"] == 3
        finally:
            eng.close()

    def test_concurrent_requests_interleave_and_match(self):
        import threading

        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=4, max_seq=64)
        try:
            prompts = [[1, 2], [7], [11, 3, 5], [2, 2, 2, 2]]
            want = [self._reference_generate(eng, p, 5) for p in prompts]
            results = [None] * len(prompts)

            def worker(i):
                results[i] = eng.generate(prompts[i], max_tokens=5)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            for i, r in enumerate(results):
                assert r is not None and r["token_ids"] == want[i], (i, r)
        finally:
            eng.close()

    def test_more_requests_than_slots_all_complete(self):
        import threading

        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        try:
            results = [None] * 5

            def worker(i):
                results[i] = eng.generate([i + 1], max_tokens=3)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(5)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert all(r is not None and len(r["token_ids"]) == 3
                       for r in results), results
        finally:
            eng.close()

    def test_temperature_sampling_varies(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=1, max_seq=64)
        try:
            outs = {tuple(eng.generate([3], max_tokens=8,
                                       temperature=2.0)["token_ids"])
                    for _ in range(5)}
            assert len(outs) > 1  # hot sampling is actually stochastic
        finally:
            eng.close()


def test_stats_endpoint_counts_requests():
    from kubedl_tpu.serving.server import LlamaEngine

    eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
    try:
        eng.generate([1, 2], max_tokens=3)
        eng.generate([3], max_tokens=2)
        st = eng.stats()
        assert st["requests"] == 2
        assert st["tokens_out"] == 5
        assert st["tokens_in"] == 3
        assert st["qps"] > 0 and st["max_batch"] == 2
    finally:
        eng.close()


class TestAutoscaler:
    """Closed-loop QPS autoscaling (the reference only stubs autoScale in
    its API; here the controller drives replicas from live load)."""

    def _setup(self, qps_by_pod, clock):
        from kubedl_tpu.core.objects import PodPhase
        from kubedl_tpu.core.store import ObjectStore
        from kubedl_tpu.lineage.types import ModelVersion, ModelVersionPhase
        from kubedl_tpu.serving.controller import InferenceController
        from kubedl_tpu.serving.types import AutoScaleSpec, Inference, Predictor

        store = ObjectStore()
        mv = ModelVersion(model_name="m", phase=ModelVersionPhase.SUCCEEDED,
                          image="m:v1")
        mv.metadata.name = "m-v1"
        store.create(mv)

        def probe(pod):
            return qps_by_pod.get(pod.metadata.name, 0.0)

        ctrl = InferenceController(store, local_addresses=True,
                                   qps_probe=probe, clock=clock)
        inf = Inference()
        inf.metadata.name = "svc"
        inf.predictors.append(Predictor(
            name="main", model_version="m-v1", replicas=1,
            autoscale=AutoScaleSpec(min_replicas=1, max_replicas=4,
                                    target_qps=10.0),
        ))
        store.create(inf)

        def run_pods():
            for p in store.list("Pod"):
                if p.status.phase != PodPhase.RUNNING:
                    def mut(o):
                        o.status.phase = PodPhase.RUNNING
                    store.update_with_retry("Pod", p.metadata.name,
                                            "default", mut)
        return store, ctrl, run_pods

    def test_scales_up_on_load_and_down_after_cooldown(self):
        t = {"now": 1000.0}
        qps = {}
        store, ctrl, run_pods = self._setup(qps, clock=lambda: t["now"])
        ctrl.reconcile("default", "svc")
        run_pods()
        ctrl.reconcile("default", "svc")
        pods = [p.metadata.name for p in store.list("Pod")]
        assert pods == ["svc-main-0"]
        # load arrives: 35 qps against target 10 -> 4 replicas (max-capped)
        qps["svc-main-0"] = 35.0
        ctrl.reconcile("default", "svc")
        assert len(store.list("Pod")) == 4
        assert any(e.reason == "Autoscaled" for e in store.list("Event"))
        run_pods()
        # load drops immediately: cooldown holds the fleet steady...
        qps["svc-main-0"] = 1.0
        ctrl.reconcile("default", "svc")
        assert len(store.list("Pod")) == 4
        # ...until the cooldown window passes
        t["now"] += 60.0
        ctrl.reconcile("default", "svc")
        assert len(store.list("Pod")) == 1

    def test_no_probe_means_clamp_only(self):
        store, ctrl, run_pods = self._setup({}, clock=lambda: 0.0)
        ctrl.qps_probe = None
        ctrl.reconcile("default", "svc")
        assert len(store.list("Pod")) == 1  # min_replicas clamp, no scaling


def test_windowed_qps_not_lifetime_average():
    """r2 review: the autoscale signal must track LIVE load — a long-idle
    engine then hit by a burst must report the burst, not ~0."""
    import time as _time

    from kubedl_tpu.serving.server import LlamaEngine

    eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
    try:
        # simulate a long-idle engine (backdate start + use a small window)
        eng._stats["started_at"] = _time.time() - 3600
        eng.qps_window_s = 5.0
        for _ in range(4):
            eng.generate([1], max_tokens=1)
        st = eng.stats()
        assert st["qps"] >= 0.5, st  # burst visible in the window
        assert st["lifetime_qps"] < 0.01, st  # the old signal would miss it
    finally:
        eng.close()


def test_probe_failure_never_scales_down(tmp_path):
    """r2 review: missing metrics must not justify deleting capacity."""
    import math

    from kubedl_tpu.core.objects import PodPhase
    from kubedl_tpu.core.store import ObjectStore
    from kubedl_tpu.lineage.types import ModelVersion, ModelVersionPhase
    from kubedl_tpu.serving.controller import InferenceController
    from kubedl_tpu.serving.types import AutoScaleSpec, Inference, Predictor

    store = ObjectStore()
    mv = ModelVersion(model_name="m", phase=ModelVersionPhase.SUCCEEDED)
    mv.metadata.name = "m-v1"
    store.create(mv)
    qps = {"value": 40.0, "fail": False}

    def probe(pod):
        if qps["fail"]:
            raise TimeoutError("probe timeout")
        return qps["value"]

    t = {"now": 0.0}
    ctrl = InferenceController(store, local_addresses=True, qps_probe=probe,
                               clock=lambda: t["now"])
    inf = Inference()
    inf.metadata.name = "svc2"
    inf.predictors.append(Predictor(
        name="main", model_version="m-v1", replicas=1,
        autoscale=AutoScaleSpec(min_replicas=1, max_replicas=4,
                                target_qps=10.0)))
    store.create(inf)
    ctrl.reconcile("default", "svc2")
    for p in store.list("Pod"):
        def mut(o):
            o.status.phase = PodPhase.RUNNING
        store.update_with_retry("Pod", p.metadata.name, "default", mut)
    ctrl.reconcile("default", "svc2")  # scales to 4 on load
    for p in store.list("Pod"):
        def mut(o):
            o.status.phase = PodPhase.RUNNING
        store.update_with_retry("Pod", p.metadata.name, "default", mut)
    assert len(store.list("Pod")) == 4
    # probes start failing under overload: fleet must HOLD, not shrink
    qps["fail"] = True
    t["now"] += 120.0
    ctrl.reconcile("default", "svc2")
    assert len(store.list("Pod")) == 4


class TestPrefill:
    """Batched prefill (round-3 #2): whole prompts in ONE forward, then
    per-row dynamic-slice cache updates in decode."""

    def test_prefill_matches_stepwise_decode(self):
        """Prefilling a prompt must leave the cache/logits exactly where
        feeding it token-by-token through decode_step_batched would."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from kubedl_tpu.models import llama

        cfg = llama.TINY
        params = llama.llama_init(jax.random.PRNGKey(0), cfg)
        prompt = [5, 9, 13, 2, 7]
        B, T = 2, 32

        # stepwise oracle: feed each prompt token through the decode step
        cache_a = llama.init_batched_cache(cfg, B, T)
        logits_a = None
        for tok in prompt:
            toks = jnp.zeros((B, 1), jnp.int32).at[0, 0].set(tok)
            logits_a, cache_a = llama.decode_step_batched(
                params, cache_a, toks, cfg
            )

        # prefill: one forward, row 1 inactive (length 0)
        cache_b = llama.init_batched_cache(cfg, B, T)
        toks = jnp.zeros((B, 8), jnp.int32).at[0, : len(prompt)].set(
            jnp.asarray(prompt)
        )
        lens = jnp.asarray([len(prompt), 0], jnp.int32)
        logits_b, cache_b = llama.prefill_batched(
            params, cache_b, toks, lens, cfg
        )

        assert int(cache_b["pos"][0]) == len(prompt)
        assert int(cache_b["pos"][1]) == 0  # inactive row untouched
        np.testing.assert_allclose(
            np.asarray(logits_a[0]), np.asarray(logits_b[0]),
            rtol=2e-4, atol=2e-4,
        )
        # row 0's cached K/V over the prompt span must agree
        np.testing.assert_allclose(
            np.asarray(cache_a["k"][:, 0, : len(prompt)]),
            np.asarray(cache_b["k"][:, 0, : len(prompt)]),
            rtol=2e-4, atol=2e-4,
        )
        # inactive row's cache really untouched (still zeros)
        assert float(jnp.abs(cache_b["k"][:, 1]).sum()) == 0.0

    def test_prefill_bucket_sizes(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=1, max_seq=64)
        try:
            assert eng._prefill_bucket(1) == 16
            assert eng._prefill_bucket(16) == 16
            assert eng._prefill_bucket(17) == 32
            assert eng._prefill_bucket(63) == 64
            assert eng._prefill_bucket(1000) == 64  # clamped to max_seq
        finally:
            eng.close()

    def test_long_prompt_single_tick(self):
        """A prompt near max_seq completes with 1 token without issue
        (prefill + a single decode step, not 60+ sequential steps)."""
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        try:
            prompt = list(range(1, 50))
            got = eng.generate(prompt, max_tokens=2)
            assert len(got["token_ids"]) == 2
            assert got["prompt_len"] == 49
        finally:
            eng.close()


def test_generate_timeout_frees_slot():
    """ADVICE r2 #5: an abandoned (timed-out) request must release its
    queue entry / batch row instead of occupying it until natural
    completion."""
    from kubedl_tpu.serving.server import LlamaEngine, _Slot

    eng = LlamaEngine(preset="tiny", max_batch=1, max_seq=64)
    try:
        # freeze the scheduler so the request can never complete
        with eng._cv:
            eng._stop = True
            eng._cv.notify_all()
        eng._thread.join(timeout=10)
        out = eng.generate([1, 2], max_tokens=4, timeout_s=0.2)
        assert out["error"] == "timed out"
        assert list(eng._waiting) == []  # queue entry released
        # row-occupying case: simulate a slot stuck mid-decode
        stuck = _Slot([1], 4, 0.0)
        eng._slots[0] = stuck
        out2 = eng.generate([3], max_tokens=1, timeout_s=0.2)
        assert out2["error"] == "timed out"
        assert list(eng._waiting) == []
    finally:
        eng._thread.join(timeout=1)


class TestInt8Quantization:
    """Weight-only int8 for serving (decode is HBM-bound; measured on
    v5e-1 Gemma-2B: b1 119 -> 199 tok/s, b8 793 -> 1218 tok/s)."""

    def test_quantize_roundtrip_accuracy(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from kubedl_tpu.models import llama

        cfg = llama.TINY
        p = llama.llama_init(jax.random.PRNGKey(0), cfg)
        qp = llama.quantize_params(p, cfg)
        # per-column symmetric: dequantized weights within 1/127 of scale
        w = np.asarray(p["layers"]["wq"], np.float32)
        dq = np.asarray(llama.deq(qp["layers"]["wq"]), np.float32)
        colmax = np.abs(w).max(axis=-2, keepdims=True)
        # int8 step + bf16 scale rounding (~2^-8 relative)
        bound = colmax / 127.0 + np.abs(w) * 2.0 ** -7 + 1e-6
        assert np.all(np.abs(w - dq) <= bound)
        # norms untouched
        assert qp["layers"]["attn_norm"] is p["layers"]["attn_norm"]

    def test_forward_decode_prefill_close_to_fp(self):
        import jax
        import jax.numpy as jnp

        from kubedl_tpu.models import llama

        cfg = llama.TINY
        p = llama.llama_init(jax.random.PRNGKey(0), cfg)
        qp = llama.quantize_params(p, cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab_size)
        lf = llama.llama_forward(p, toks, cfg)
        lq = llama.llama_forward(qp, toks, cfg)
        rel = float(jnp.abs(lf - lq).max() / (jnp.abs(lf).max() + 1e-9))
        assert rel < 0.1, rel
        # decode + prefill paths run with quantized params
        cache = llama.init_batched_cache(cfg, 2, 32)
        logits, cache = llama.decode_step_batched(qp, cache, toks[:, :1], cfg)
        assert logits.shape == (2, cfg.vocab_size)
        pre, _ = llama.prefill_batched(
            qp, llama.init_batched_cache(cfg, 2, 32), toks,
            jnp.array([16, 16]), cfg,
        )
        assert pre.shape == (2, cfg.vocab_size)
        # the single-sequence decode_step path accepts quantized params too
        sc = llama.init_cache(cfg, 2, 32)
        ls, _ = llama.decode_step(qp, sc, toks[:, :1], cfg)
        assert ls.shape == (2, cfg.vocab_size)

    def test_engine_serves_quantized(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          quantize="int8")
        try:
            got = eng.generate([5, 9, 13], max_tokens=6)
            assert len(got["token_ids"]) == 6
            assert got["prompt_len"] == 3
        finally:
            eng.close()
        import pytest as _pytest

        with _pytest.raises(ValueError, match="quantize"):
            LlamaEngine(preset="tiny", quantize="fp4")

    def test_tied_embeddings_quantized(self):
        """Gemma ties lm_head to the embedding: the quantized head path
        (deq(embed).T) must work too."""
        import jax
        import jax.numpy as jnp

        from kubedl_tpu.models import llama

        cfg = llama.TINY_GEMMA
        p = llama.llama_init(jax.random.PRNGKey(0), cfg)
        qp = llama.quantize_params(p, cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                  cfg.vocab_size)
        lf = llama.llama_forward(p, toks, cfg)
        lq = llama.llama_forward(qp, toks, cfg)
        rel = float(jnp.abs(lf - lq).max() / (jnp.abs(lf).max() + 1e-9))
        assert rel < 0.15, rel


def test_predictor_quantize_rides_serve_config():
    """quantize is a first-class Predictor field: the JAX setter plumbs it
    into KUBEDL_SERVE_CONFIG so a canary can A/B int8 vs full precision."""
    import json as _json

    store, ctrl = setup()
    make_mv(store)
    make_inference(store, [
        Predictor(name="fp", model_version="mv1"),
        Predictor(name="q8", model_version="mv1", quantize="int8"),
    ])
    ctrl.reconcile("default", "inf1")
    from tests.helpers import env_of as _env_of

    cfg_fp = _json.loads(_env_of(store.get("Pod", "inf1-fp-0"))["KUBEDL_SERVE_CONFIG"])
    cfg_q8 = _json.loads(_env_of(store.get("Pod", "inf1-q8-0"))["KUBEDL_SERVE_CONFIG"])
    assert cfg_fp["quantize"] == ""
    assert cfg_q8["quantize"] == "int8"


class TestShardedServing:
    """Mesh-sharded serving (BASELINE target 5: Gemma-2B on a v5e-4):
    weights megatron-shard over a tensor axis; greedy outputs must equal
    the single-device engine exactly."""

    def test_tensor_sharded_matches_unsharded(self):
        # exact equality holds on the fp32 TINY model; on bf16 hardware,
        # row-parallel psum reduction order can flip near-tie argmaxes
        from kubedl_tpu.serving.server import LlamaEngine

        eng1 = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        try:
            want = eng1.generate([5, 9, 13], max_tokens=6)["token_ids"]
        finally:
            eng1.close()
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          mesh_axes={"tensor": 4})
        try:
            got = eng.generate([5, 9, 13], max_tokens=6)
            assert got["token_ids"] == want
            # weights really are sharded over 4 devices
            wq = eng.params["layers"]["wq"]
            assert len(wq.sharding.device_set) == 4
        finally:
            eng.close()

    def test_sharded_plus_int8(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          mesh_axes={"tensor": 2}, quantize="int8")
        try:
            got = eng.generate([3, 7], max_tokens=5)
            assert len(got["token_ids"]) == 5
            q8 = eng.params["layers"]["wq"]["q8"]
            assert len(q8.sharding.device_set) == 2
        finally:
            eng.close()

    def test_mesh_rides_serve_config(self):
        """`mesh` in KUBEDL_SERVE_CONFIG reaches the engine (predictor
        templates set it for multi-chip serving hosts)."""
        from kubedl_tpu.serving.server import engine_kwargs

        kw = engine_kwargs(
            {"preset": "tiny", "mesh": {"tensor": 2}, "quantize": "int8",
             "max_batch": 3, "max_queue_depth": 8, "max_queue_age_s": 5.0},
            "/ckpts/m",
        )
        assert kw == {"preset": "tiny", "ckpt_dir": "/ckpts/m",
                      "max_batch": 3, "quantize": "int8",
                      "mesh_axes": {"tensor": 2},
                      "max_queue_depth": 8, "max_queue_age_s": 5.0,
                      "prefix_cache_mb": 64.0,
                      "kv_layout": "paged", "kv_block_size": 16,
                      "kv_blocks": 0, "spec_k": 0, "spec_draft": "ngram",
                      "kv_attention": "gather", "spec_candidates": 1,
                      "spec_draft_layers": 0, "spec_tree": False,
                      "prefill_chunk_tokens": 0,
                      "advertise_prefix_len": 8, "role": "colocated",
                      "model_version": "base"}
        defaults = engine_kwargs({}, "")
        assert defaults["mesh_axes"] is None
        # load-shedding budget defaults ride the config too
        assert defaults["max_queue_depth"] == 64
        assert defaults["max_queue_age_s"] == 30.0
        # prefix cache rides the config (0 disables it)
        assert defaults["prefix_cache_mb"] == 64.0
        assert engine_kwargs({"prefix_cache_mb": 0}, "")["prefix_cache_mb"] == 0.0


class TestSegmentPolicy:
    """Pure host-side tests of the segment-size bucket policy (no device
    work): the `up - need <= up // 4` round-up rule and the while-waiting
    cap that bounds admission latency."""

    def test_round_up_only_on_small_overshoot(self):
        from kubedl_tpu.serving.server import LlamaEngine

        seg = LlamaEngine.segment_size
        assert seg(32, 32) == 32  # exact
        assert seg(31, 32) == 32  # overshoot 1 <= 8: run 32, discard 1
        assert seg(24, 32) == 32  # overshoot 8 == 32 // 4: still up
        assert seg(23, 32) == 4   # overshoot 9 > 8: step down
        assert seg(7, 32) == 4    # up to 32 would waste 25 decodes
        assert seg(4, 32) == 4
        assert seg(3, 32) == 4    # up=4, overshoot 1 <= 1
        assert seg(2, 32) == 1    # up=4, overshoot 2 > 1: down to 1
        assert seg(1, 32) == 1

    def test_waiting_cap_clamps_need(self):
        from kubedl_tpu.serving.server import LlamaEngine

        seg = LlamaEngine.segment_size
        # cap=4 (requests waiting): long budgets still decode in 4s so
        # admission latency stays <= 4 tokens
        assert seg(100, 4) == 4
        assert seg(100, 32) == 32
        assert seg(3, 4) == 4
        assert seg(2, 4) == 1
        assert seg(1, 4) == 1

    def test_degenerate_inputs(self):
        from kubedl_tpu.serving.server import LlamaEngine

        seg = LlamaEngine.segment_size
        assert seg(0, 32) == 1    # need clamps to >= 1
        assert seg(100, 1) == 1   # cap dominates
        assert seg(5, 3) == 4     # need clamps to cap=3, then rounds to 4

    @pytest.mark.parametrize("need,waiting,mid_prefill,decoding,want", [
        # prefill work owed: a row mid-prompt is one short segment away
        # from its next chunk, as a waiting request is from its row
        (100, 0, 1, 12, (4, "prefill")),
        (100, 0, 7, 8, (4, "prefill")),
        (24, 0, 1, 2, (4, "prefill")),   # 24 would have rounded up to 32
        (100, 1, 0, 3, (4, "waiting")),
        (100, 3, 2, 1, (4, "waiting")),  # both: the older reason names it
        # as many rows mid-prompt as rows decoding, and nobody waits for a
        # row: the next chunk is one step away
        (100, 0, 1, 1, (1, "prefill")),
        (100, 0, 6, 2, (1, "prefill")),
        (3, 0, 4, 4, (1, "prefill")),
        (100, 1, 6, 2, (4, "waiting")),  # a row has to come free: 4 steps
        # the budgets alone already ask for no more: the cap shortens nothing
        (23, 0, 1, 2, (4, "")),
        (7, 2, 0, 2, (4, "")),
        (3, 0, 1, 2, (4, "")),
        (2, 0, 5, 9, (1, "")),
        (1, 1, 1, 1, (1, "")),
        (2, 0, 5, 1, (1, "")),
        # nothing owed: the budgets' own choice
        (100, 0, 0, 5, (32, "")),
        (24, 0, 0, 5, (32, "")),
        (23, 0, 0, 5, (4, "")),
        (2, 0, 0, 5, (1, "")),
    ])
    def test_owed_prefill_work_caps_the_segment(self, need, waiting,
                                                mid_prefill, decoding, want):
        from kubedl_tpu.serving.server import LlamaEngine

        assert LlamaEngine.choose_segment(
            need, waiting, mid_prefill, decoding) == want

    @pytest.mark.parametrize("need", [1, 2, 3, 4, 5, 7, 8, 23, 24, 31, 32,
                                      33, 100, 4000])
    def test_choice_is_the_parents_where_no_row_is_mid_prompt(self, need):
        """Nothing mid-prefill: `cap = 4 if waiting else 32`, as before,
        however many rows decode."""
        from kubedl_tpu.serving.server import LlamaEngine

        seg, choose = LlamaEngine.segment_size, LlamaEngine.choose_segment
        for decoding in (1, 16):
            assert choose(need, 0, 0, decoding) == (seg(need, 32), "")
            for waiting in (1, 9):
                assert choose(need, waiting, 0, decoding)[0] == seg(need, 4)
        # and rows mid-prompt that are fewer than the rows decoding ask
        # for what a waiting request asks for
        assert choose(need, 0, 1, 2)[0] == choose(need, 1, 0, 2)[0]
        assert LlamaEngine.SEGMENT_BUCKETS == (32, 4, 1)


class TestSegmentFromOwedPrefill:
    """The tick chooses its decode segment from the prefill work it still
    owes (PR 36): a row whose prompt is mid-way gets its next chunk after
    a short segment, as a waiting request gets its row. Ticks are driven
    by hand, so the order of dispatches is the schedule's alone."""

    @staticmethod
    def _logged(eng):
        """Record every prefill program and decode segment the engine
        dispatches, in order: ``("P", tokens)`` and ``("S", k)``."""
        log, runner = [], eng._runner
        prefill, segment = runner.prefill, runner.decode_segment

        def logged_prefill(params, toks, lens, *a, **kw):
            log.append(("P", int(lens[0])))
            return prefill(params, toks, lens, *a, **kw)

        def logged_segment(n_steps, *a, **kw):
            log.append(("S", n_steps))
            return segment(n_steps, *a, **kw)

        runner.prefill, runner.decode_segment = logged_prefill, logged_segment
        return log

    @staticmethod
    def _serve_b_behind_a(eng, a, b, b_version=""):
        """``a`` is decoding when ``b`` arrives; tick until both are done."""
        from kubedl_tpu.serving.server import _Slot

        sa, sb = _Slot(*a, 0.0), _Slot(*b, 0.0)
        sb.version = b_version
        with eng._cv:
            eng._waiting.append(sa)
        eng._loop_once()
        with eng._cv:
            eng._waiting.append(sb)
        for _ in range(400):
            if sa.done.is_set() and sb.done.is_set():
                break
            eng._loop_once()
        assert sa.done.is_set() and sb.done.is_set()
        return sa.result["token_ids"], sb.result["token_ids"]

    A = ([5, 9, 13], 100)             # decoding, a long budget
    B = (list(range(40, 88)), 8)      # 48 tokens: three chunks of 16

    @pytest.mark.parametrize("chunk,want", [
        # chunked: B's second and third chunk each follow a short segment
        # (one row mid-prompt, one decoding: one step); its last chunk
        # leaves nothing owed and the budgets choose again
        (16, [("P", 3), ("S", 32), ("P", 16), ("S", 1), ("P", 16), ("S", 1),
              ("P", 16), ("S", 32)]),
        # whole prompts in one program owe nothing after their prefill
        (0, [("P", 3), ("S", 32), ("P", 48), ("S", 32), ("S", 32)]),
    ])
    def test_stub_engine_dispatch_order(self, chunk, want):
        from scripts.scheduler_microbench import build_stub_engine

        eng = build_stub_engine(max_batch=4, max_seq=256, kv_layout="paged",
                                prefill_chunk_tokens=chunk)
        try:
            log = self._logged(eng)
            out_a, out_b = self._serve_b_behind_a(eng, self.A, self.B)
            assert (len(out_a), len(out_b)) == (100, 8)
            assert log[:len(want)] == want
            pipe = eng.pipeline_stats()
            shorts = sum(1 for kind, k in log if kind == "S" and k == 1)
            assert pipe["segments_short"] == {
                "waiting": 0, "prefill": 2 if chunk else 0}
            assert pipe["segments_by_k"]["1"] == shorts
            assert sum(pipe["segments_by_k"].values()) == sum(
                1 for kind, _k in log if kind == "S") == pipe["segments"]
        finally:
            eng.close()

    def test_another_versions_row_is_owed_its_prefill(self):
        """Two versions co-resident, whole prompts: B's version has the
        next tick, so its prefill is one segment of A's away, and that
        segment is the short one. Rows of every version count."""
        from scripts.scheduler_microbench import build_stub_engine

        eng = build_stub_engine(max_batch=4, max_seq=256, kv_layout="paged",
                                prefill_chunk_tokens=0)
        try:
            with eng._cv:
                # sorts before "base": the round-robin gives base the tick
                # in which B is admitted
                eng._versions["a-canary"] = eng.params
            log = self._logged(eng)
            out_a, out_b = self._serve_b_behind_a(eng, self.A, self.B,
                                                  b_version="a-canary")
            assert (len(out_a), len(out_b)) == (100, 8)
            assert log[:5] == [("P", 3), ("S", 32), ("S", 1), ("P", 48),
                               ("S", 4)]
            assert eng.pipeline_stats()["segments_short"] == {
                "waiting": 0, "prefill": 1}
        finally:
            eng.close()

    def test_a_waiting_request_still_names_the_short_segment(self):
        """Three requests on two rows: the third waits for a row, and the
        segments it shortens are counted under the older reason."""
        from kubedl_tpu.serving.server import _Slot
        from scripts.scheduler_microbench import build_stub_engine

        eng = build_stub_engine(max_batch=2, max_seq=256, kv_layout="paged",
                                prefill_chunk_tokens=16)
        try:
            slots = [_Slot([7, 8, 9 + j], 40, 0.0) for j in range(3)]
            with eng._cv:
                eng._waiting.extend(slots)
            for _ in range(400):
                if all(s.done.is_set() for s in slots):
                    break
                eng._loop_once()
            assert all(len(s.result["token_ids"]) == 40 for s in slots)
            short = eng.pipeline_stats()["segments_short"]
            assert short["waiting"] > 0 and short["prefill"] == 0
            assert eng.metrics.segment_lengths.value(
                k="4", short="waiting") == short["waiting"]
        finally:
            eng.close()

    def test_speculative_ticks_choose_no_segment(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=128, spec_k=2,
                          prefill_chunk_tokens=16, prefix_cache_mb=0)
        try:
            out = eng.generate(list(range(3, 40)), max_tokens=12)
            assert len(out["token_ids"]) == 12
            pipe = eng.pipeline_stats()
            assert pipe["segments"] > 0
            assert set(pipe["segments_by_k"].values()) == {0}
            assert pipe["segments_short"] == {"waiting": 0, "prefill": 0}
        finally:
            eng.close()

    def test_second_chunk_follows_a_short_segment_and_tokens_hold(self):
        """On the CPU preset, whole: while A decodes, B's two-chunk prompt
        gets its second chunk after a 1-step segment where the parent's
        rule (`cap = 4 if waiting else 32`) put a 32-step one, and both
        rules serve the same tokens."""
        from kubedl_tpu.serving.server import LlamaEngine

        a, b = ([5, 9, 13], 60), (list(range(40, 70)), 6)  # 30 = 16 + 14
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=256,
                          prefill_chunk_tokens=16, prefix_cache_mb=0)
        try:
            TestChainAcrossPrefill()._freeze(eng)
            log = self._logged(eng)
            mine = self._serve_b_behind_a(eng, a, b)
            mine_log = list(log)
            del log[:]
            eng.choose_segment = lambda need, waiting, _mid, _dec: (
                LlamaEngine.segment_size(need, 4 if waiting else 32), "")
            parents = self._serve_b_behind_a(eng, a, b)
        finally:
            eng.close()
        assert mine == parents
        assert [len(t) for t in mine] == [60, 6]
        assert mine_log[:5] == [("P", 3), ("S", 32), ("P", 16), ("S", 1),
                                ("P", 14)]
        assert log[:5] == [("P", 3), ("S", 32), ("P", 16), ("S", 32),
                           ("P", 14)]


class TestChainAcrossPrefill:
    """The device token chain across interleaved prefills: merged on
    device when row sets allow (no host round trip), rebuilt from host
    tokens when the generation goes stale."""

    def _freeze(self, eng):
        """Stop the background scheduler so the test drives ticks."""
        with eng._cv:
            eng._stop = True
            eng._cv.notify_all()
        eng._thread.join(timeout=10)
        eng._stop = False

    def _drive(self, eng, slots, max_ticks=200):
        n = 0
        while not all(s.done.is_set() for s in slots):
            eng._loop_once()
            n += 1
            assert n < max_ticks, "pipeline did not converge"

    def test_interleaved_prefill_merges_chain_on_device(self):
        """A prefill landing mid-generation must NOT force the running
        row's token feed through the host: the sampled first token is
        merged into the device chain and both outputs stay exact."""
        from kubedl_tpu.serving.server import LlamaEngine, _Slot

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        oracle = TestContinuousBatching()
        try:
            self._freeze(eng)
            a = _Slot([5, 9, 13], 12, 0.0)
            with eng._cv:
                eng._waiting.append(a)
            eng._loop_once()  # prefill A + segment 1 in flight
            b = _Slot([7], 6, 0.0)  # arrives mid-generation
            with eng._cv:
                eng._waiting.append(b)
            self._drive(eng, [a, b])
            assert a.result["token_ids"] == oracle._reference_generate(
                eng, [5, 9, 13], 12
            )
            assert b.result["token_ids"] == oracle._reference_generate(
                eng, [7], 6
            )
            assert eng.pipeline_stats()["chain_rebuilds"] == 0
        finally:
            eng.close()

    def test_stale_chain_rebuilt_from_host_tokens(self):
        """A `_prefill_gen` bump invalidates the chain: the next tick must
        flush the in-flight segment (its values feed `next_input`), rebuild
        the token feed host-side, and still produce exact output."""
        from kubedl_tpu.serving.server import LlamaEngine, _Slot

        eng = LlamaEngine(preset="tiny", max_batch=1, max_seq=64)
        oracle = TestContinuousBatching()
        try:
            self._freeze(eng)
            a = _Slot([5, 9, 13], 10, 0.0)
            with eng._cv:
                eng._waiting.append(a)
            eng._loop_once()  # prefill + segment 1 in flight, chain live
            assert eng._chain is not None
            eng._prefill_gen += 1  # stale: what recovery paths produce
            self._drive(eng, [a])
            assert a.result["token_ids"] == oracle._reference_generate(
                eng, [5, 9, 13], 10
            )
            pipe = eng.pipeline_stats()
            assert pipe["chain_rebuilds"] >= 1
            assert eng.metrics.chain_rebuilds.value() >= 1.0
        finally:
            eng.close()


def test_scheduler_recovers_after_segment_failure():
    """Injected segment failure: the in-flight request fails, the donated
    cache + deferred segment are dropped safely, pipeline counters reset
    (the r5 stats-drift fix), and the NEXT request serves exactly."""
    from kubedl_tpu.serving.server import LlamaEngine

    eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
    oracle = TestContinuousBatching()
    try:
        orig = eng._runner._segment_fn
        state = {"armed": True}

        def boom(k, greedy):
            fn = orig(k, greedy)

            def wrapped(*a, **kw):
                if state["armed"]:
                    state["armed"] = False
                    raise RuntimeError("injected segment failure")
                return fn(*a, **kw)

            return wrapped

        eng._runner._segment_fn = boom
        r1 = eng.generate([5, 9], max_tokens=6, timeout_s=60)
        assert "injected segment failure" in r1.get("error", ""), r1
        r2 = eng.generate([5, 9, 13], max_tokens=6, timeout_s=60)
        assert r2["token_ids"] == oracle._reference_generate(
            eng, [5, 9, 13], 6
        )
        pipe = eng.pipeline_stats()
        assert pipe["errors"] == 1
        assert pipe["inflight"] == 0
        # post-recovery accounting describes the recovered engine only
        assert pipe["ticks"] >= 1
        assert eng.metrics.scheduler_errors.value() == 1.0
    finally:
        eng.close()


def test_pipeline_stats_and_metrics_endpoint():
    """Pipeline accounting is visible end to end: stats() carries the
    per-tick timings, and /metrics exports the Prometheus family."""
    import threading
    import urllib.request
    from http.server import ThreadingHTTPServer

    from kubedl_tpu.serving.server import LlamaEngine, make_handler

    eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
    try:
        eng.generate([1, 2, 3], max_tokens=8)
        eng.generate([4], max_tokens=8)
        st = eng.stats()
        pipe = st["pipeline"]
        assert pipe["ticks"] >= 1 and pipe["segments"] >= 1
        for k in ("dispatch_ms_avg", "harvest_ms_avg", "host_ms_avg",
                  "overlap_ratio", "dispatch_ms_p50", "tick_ms_p50"):
            assert k in pipe, (k, pipe)
        assert st["queued"] == 0

        srv = ThreadingHTTPServer(
            ("127.0.0.1", 0), make_handler(eng, "tiny")
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            port = srv.server_address[1]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                text = r.read().decode()
                ctype = r.headers["Content-Type"]
        finally:
            srv.shutdown()
            srv.server_close()
        assert ctype.startswith("text/plain")
        assert "kubedl_tpu_serving_segments" in text
        assert "kubedl_tpu_serving_dispatch_ms_bucket" in text
        assert "kubedl_tpu_serving_overlap_ratio" in text
    finally:
        eng.close()


def test_queued_backlog_blocks_scale_down():
    """Dict-shaped probes (the full /v1/stats payload) feed the
    autoscaler; a backlog of queued requests vetoes scale-down even when
    completion-rate QPS looks idle."""
    from kubedl_tpu.core.objects import PodPhase
    from kubedl_tpu.core.store import ObjectStore
    from kubedl_tpu.lineage.types import ModelVersion, ModelVersionPhase
    from kubedl_tpu.serving.controller import InferenceController
    from kubedl_tpu.serving.types import AutoScaleSpec, Inference, Predictor

    store = ObjectStore()
    mv = ModelVersion(model_name="m", phase=ModelVersionPhase.SUCCEEDED)
    mv.metadata.name = "m-v1"
    store.create(mv)
    load = {"qps": 35.0, "queued": 0}

    def probe(pod):
        return dict(load)

    t = {"now": 0.0}
    ctrl = InferenceController(store, local_addresses=True, qps_probe=probe,
                               clock=lambda: t["now"])
    inf = Inference()
    inf.metadata.name = "svc3"
    inf.predictors.append(Predictor(
        name="main", model_version="m-v1", replicas=1,
        autoscale=AutoScaleSpec(min_replicas=1, max_replicas=4,
                                target_qps=10.0)))
    store.create(inf)

    def run_pods():
        for p in store.list("Pod"):
            if p.status.phase != PodPhase.RUNNING:
                def mut(o):
                    o.status.phase = PodPhase.RUNNING
                store.update_with_retry("Pod", p.metadata.name, "default",
                                        mut)

    ctrl.reconcile("default", "svc3")
    run_pods()
    ctrl.reconcile("default", "svc3")  # dict probe drives scale-up
    assert len(store.list("Pod")) == 4
    run_pods()
    # QPS collapses because replicas saturate — but requests are QUEUED:
    # the backlog must veto the scale-down, cooldown or not
    load.update(qps=1.0, queued=6)
    t["now"] += 120.0
    ctrl.reconcile("default", "svc3")
    assert len(store.list("Pod")) == 4
    # backlog drains -> scale-down proceeds
    load.update(queued=0)
    t["now"] += 120.0
    ctrl.reconcile("default", "svc3")
    assert len(store.list("Pod")) == 1


class TestSchedulerMicrobench:
    """Tier-1 guard on host-side scheduler overhead: with the device
    stubbed out, per-tick time IS host overhead — regressions fail here
    instead of waiting for a full bench run."""

    def test_host_tick_overhead_within_budget(self):
        from scripts.scheduler_microbench import (
            TICK_BUDGET_MS,
            run_microbench,
        )

        out = run_microbench(requests=8, max_tokens=16, max_batch=4)
        assert out["tokens"] == 8 * 16
        assert out["tick_ms_p50"] <= TICK_BUDGET_MS, out
        assert out["within_budget"], out

    def test_prefix_match_graft_within_budget(self):
        """The prefix-cache admission path (observe + longest-prefix
        match + graft dispatch) is pure host work — it must fit the same
        per-tick envelope or reuse pays its savings back as overhead."""
        from scripts.scheduler_microbench import (
            PREFIX_BUDGET_MS,
            run_prefix_microbench,
        )

        out = run_prefix_microbench(requests=8, max_tokens=8, max_batch=4)
        assert out["hits"] == 8, out  # every request rode the cache
        assert out["tokens_saved"] >= 8 * out["prefix_len"]
        assert out["tick_ms_p50"] <= PREFIX_BUDGET_MS, out
        assert out["match_graft_ms"] <= PREFIX_BUDGET_MS, out
        assert out["within_budget"], out

    def test_paged_block_table_within_budget(self):
        """The paged layout's extra host work — mirror re-upload per
        dispatch plus allocator alloc/free on admission/finalize — must
        fit the same per-tick envelope, and the pool must drain back to
        empty (no block leaks) once every request completes."""
        from scripts.scheduler_microbench import (
            PAGED_BUDGET_MS,
            run_paged_microbench,
        )

        out = run_paged_microbench(requests=8, max_tokens=16, max_batch=4)
        assert out["tokens"] == 8 * 16
        assert out["blocks_leaked"] == 0, out
        assert out["tick_ms_p50"] <= PAGED_BUDGET_MS, out
        assert out["mirror_upload_ms"] <= PAGED_BUDGET_MS, out
        assert out["within_budget"], out

    def test_chunked_admission_within_budget(self):
        """The FIFO chunk scheduler (continuous batching) is pure host
        arithmetic on top of the paged tick — it must fit the same
        per-tick envelope, dispatch exactly ceil(len/budget) chunks per
        request, and leak no blocks."""
        from scripts.scheduler_microbench import (
            CHUNKED_BUDGET_MS,
            run_chunked_admission_microbench,
        )

        out = run_chunked_admission_microbench(
            requests=8, prompt_len=48, max_tokens=8, max_batch=4
        )
        assert out["tokens"] == 8 * 8
        assert out["chunks"] == 8 * 3  # 48 tokens / 16-token budget
        assert out["blocks_leaked"] == 0, out
        assert out["tick_ms_p50"] <= CHUNKED_BUDGET_MS, out
        assert out["within_budget"], out

    def test_mid_prefill_rows_keep_the_tick_within_budget(self):
        """The same gate with two rows decoding while twelve prompts are
        mid-way and nobody waits for a row: the tick counts the prefill
        work it owes and runs short segments for it (one step while the
        rows mid-prompt outnumber the rows decoding, then four), at no
        host cost."""
        from scripts.scheduler_microbench import (
            CHUNKED_BUDGET_MS,
            run_chunked_admission_microbench,
        )

        out = run_chunked_admission_microbench(
            requests=12, prompt_len=48, max_tokens=8, max_batch=16,
            decoders=2, decoder_tokens=100,
        )
        assert out["tokens"] == 12 * 8 + 2 * 100
        assert out["chunks"] == 12 * 3 + 2
        assert out["segments_short"]["waiting"] == 0, out
        assert out["segments_short"]["prefill"] >= 30, out
        assert out["segments_by_k"]["1"] >= 20, out
        assert out["segments_by_k"]["4"] >= 5, out
        assert out["blocks_leaked"] == 0, out
        assert out["tick_ms_p50"] <= CHUNKED_BUDGET_MS, out
        assert out["within_budget"], out

    def test_tracing_disarmed_within_budget(self):
        """Every hot path calls TRACER unconditionally; with tracing
        disarmed the call must stay a near-free attribute test — an
        allocation or lock sneaking onto that path would tax every
        scheduler tick and router dispatch fleet-wide."""
        from scripts.scheduler_microbench import (
            TRACING_DISARMED_US,
            run_tracing_microbench,
        )

        out = run_tracing_microbench(calls=50_000)
        assert out["span_us"] <= TRACING_DISARMED_US, out
        assert out["begin_finish_us"] <= TRACING_DISARMED_US, out
        assert out["record_us"] <= TRACING_DISARMED_US, out
        assert out["within_budget"], out


class TestPrefixReuse:
    """Device-resident prefix KV cache (docs/serving.md "Prefix cache"):
    suffix-only prefill must be EXACTLY equivalent to full prefill for
    greedy decoding — causal attention's KV at position p depends only
    on tokens <= p, so a grafted cached prefix changes nothing."""

    def _freeze(self, eng):
        with eng._cv:
            eng._stop = True
            eng._cv.notify_all()
        eng._thread.join(timeout=10)
        eng._stop = False

    def test_suffix_prefill_matches_full_prefill(self):
        """Model-level equivalence: extract a row's prefix KV, graft it
        into a fresh cache, prefill only the suffix — same last-token
        logits, same cache contents over the valid span, same pos."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from kubedl_tpu.models import llama

        cfg = llama.TINY
        params = llama.llama_init(jax.random.PRNGKey(0), cfg)
        B, T = 2, 64
        prompt = list(range(1, 21))  # 20 tokens: prefix 12 + suffix 8
        toks = np.zeros((B, 32), np.int32)
        toks[0, :20] = prompt
        lens = jnp.asarray(np.array([20, 0], np.int32))
        cache = llama.init_batched_cache(cfg, B, T)
        full_logits, full_cache = llama.prefill_batched(
            params, cache, jnp.asarray(toks), lens, cfg
        )
        # entry payload: first 16 positions of row 0 (12 valid + pad)
        k, v = llama.extract_prefix_from_row(full_cache, 0, 16)
        cache2 = llama.init_batched_cache(cfg, B, T)
        cache2 = llama.copy_prefix_into_row(cache2, k, v, 0, 12)
        assert int(cache2["pos"][0]) == 12
        suf = np.zeros((B, 16), np.int32)
        suf[0, :8] = prompt[12:]
        suf_logits, suf_cache = llama.prefill_batched_from(
            params, cache2, jnp.asarray(suf),
            jnp.asarray(np.array([8, 0], np.int32)),
            jnp.asarray(np.array([12, 0], np.int32)), cfg,
        )
        np.testing.assert_allclose(
            np.asarray(suf_logits[0]), np.asarray(full_logits[0]),
            rtol=2e-4, atol=2e-4,
        )
        assert int(suf_cache["pos"][0]) == 20
        np.testing.assert_allclose(
            np.asarray(suf_cache["k"][:, 0, :20]),
            np.asarray(full_cache["k"][:, 0, :20]),
            rtol=2e-4, atol=2e-4,
        )

    def test_greedy_equivalence_cache_on_vs_off(self):
        """Acceptance bar: with a shared >=8-token prefix, cache-on
        greedy token ids are bit-identical to cache-off, and the cache
        actually engaged (hits + tokens saved)."""
        from kubedl_tpu.serving.server import LlamaEngine

        shared = list(range(3, 15))  # 12-token shared system prompt
        prompts = [shared + [100 + j, 200 + j] for j in range(5)]
        ref = LlamaEngine(preset="tiny", max_seq=128, max_batch=4,
                          prefix_cache_mb=0)
        try:
            want = [ref.generate(p, max_tokens=6)["token_ids"]
                    for p in prompts]
        finally:
            ref.close()
        eng = LlamaEngine(preset="tiny", max_seq=128, max_batch=4,
                          prefix_cache_mb=8, prefix_min_len=8)
        try:
            got = [eng.generate(p, max_tokens=6) for p in prompts]
            assert [r["token_ids"] for r in got] == want
            st = eng.stats()["prefix_cache"]
            assert st["hits"] >= 1 and st["tokens_saved"] > 0
            assert st["pinned"] == 0  # every pin released at harvest
            # later requests actually rode the graft
            assert any(r["cached_prefix_len"] > 0 for r in got)
        finally:
            eng.close()

    def test_tagged_request_caches_on_first_sight(self):
        """`cache_prefix=True` (the HTTP body tag) inserts the prompt's
        prefix without waiting for min_seen repeats."""
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_seq=128, max_batch=2,
                          prefix_cache_mb=8, prefix_min_len=8)
        try:
            p = list(range(5, 20))
            eng.generate(p, max_tokens=2, cache_prefix=True)
            st = eng.stats()["prefix_cache"]
            assert st["inserts"] == 1 and st["entries"] == 1
            r = eng.generate(p + [42], max_tokens=2)
            assert r["cached_prefix_len"] >= 8
        finally:
            eng.close()

    def test_timeout_vacation_releases_pin(self):
        """Regression (satellite): a request that times out while its
        row is mid-prefill must release the prefix-cache pin its graft
        took — a leaked refcount blocks eviction forever."""
        import threading

        import numpy as np

        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_seq=64, max_batch=1,
                          prefix_cache_mb=8, prefix_min_len=4)
        try:
            self._freeze(eng)  # test drives admission; prefill never runs
            L, _, _, KV, hd = eng._runner.pool_shape
            k = np.zeros((L, 16, KV, hd), np.float32)
            prefix = [1, 2, 3, 4, 5, 6]
            assert eng._pcache.insert(prefix, k, k.copy(), len(prefix))
            entry = eng._pcache._entries[tuple(prefix)]
            t = threading.Thread(
                target=eng.generate,
                args=(prefix + [7, 8],),
                kwargs={"max_tokens": 4, "timeout_s": 0.3},
            )
            t.start()
            # wait for the request to queue, then admit it: the match
            # pins the entry and the graft lands in row 0
            deadline = time.time() + 5
            while time.time() < deadline:
                with eng._cv:
                    eng._admit_locked()
                    if eng._slots[0] is not None:
                        break
                time.sleep(0.01)
            assert eng._slots[0] is not None
            assert entry.refs == 1 and eng._slots[0].cached_len == len(prefix)
            t.join(timeout=10)  # generate() times out and vacates
            assert not t.is_alive()
            assert entry.refs == 0, "vacated slot leaked its prefix pin"
            assert eng._slots[0] is None and list(eng._waiting) == []
        finally:
            with eng._cv:
                eng._stop = True
                eng._cv.notify_all()

    def test_graft_overflow_falls_back_to_full_prefill(self):
        """A graft whose start + suffix bucket would spill past max_seq
        must be dropped (dynamic_update_slice CLAMPS: the suffix would
        land at the wrong positions) — the row full-prefills instead and
        the output stays exact."""
        from kubedl_tpu.serving.server import LlamaEngine

        # max_seq=32: a 20-token prefix + 16-token min bucket overflows
        ref = LlamaEngine(preset="tiny", max_seq=32, max_batch=1,
                          prefix_cache_mb=0)
        eng = LlamaEngine(preset="tiny", max_seq=32, max_batch=1,
                          prefix_cache_mb=8, prefix_min_len=4)
        try:
            shared = list(range(2, 22))  # 20 tokens
            a = shared + [101]
            b = shared + [102]
            want = [ref.generate(p, max_tokens=4)["token_ids"]
                    for p in (a, b)]
            got = [eng.generate(p, max_tokens=4) for p in (a, b)]
            assert [r["token_ids"] for r in got] == want
            # the graft was dropped, not misplaced
            assert all(r["cached_prefix_len"] == 0 for r in got)
            assert eng._pcache.stats()["pinned"] == 0
        finally:
            ref.close()
            eng.close()


class TestProbeFailureSurfacing:
    """Consecutive stats-probe failures must SURFACE (NotReady condition +
    event + metric), not silently drop the pod out of the QPS math."""

    def _setup(self, probe):
        from kubedl_tpu.core.objects import PodPhase
        from kubedl_tpu.core.store import ObjectStore
        from kubedl_tpu.lineage.types import ModelVersion, ModelVersionPhase
        from kubedl_tpu.observability.metrics import ServingMetrics
        from kubedl_tpu.serving.controller import InferenceController
        from kubedl_tpu.serving.types import AutoScaleSpec, Inference, Predictor

        store = ObjectStore()
        mv = ModelVersion(model_name="m", phase=ModelVersionPhase.SUCCEEDED,
                          image="m:v1")
        mv.metadata.name = "m-v1"
        store.create(mv)
        metrics = ServingMetrics()
        ctrl = InferenceController(store, local_addresses=True,
                                   qps_probe=probe, metrics=metrics)
        inf = Inference()
        inf.metadata.name = "svc"
        inf.predictors.append(Predictor(
            name="main", model_version="m-v1", replicas=1,
            autoscale=AutoScaleSpec(min_replicas=1, max_replicas=2,
                                    target_qps=10.0)))
        store.create(inf)
        ctrl.reconcile("default", "svc")
        for p in store.list("Pod"):
            def mut(o):
                o.status.phase = PodPhase.RUNNING
            store.update_with_retry("Pod", p.metadata.name, "default", mut)
        return store, ctrl, metrics

    def test_consecutive_failures_flip_not_ready_and_back(self):
        state = {"fail": True}

        def probe(pod):
            if state["fail"]:
                raise TimeoutError("stats probe timeout")
            return {"qps": 1.0, "queued": 0}

        store, ctrl, metrics = self._setup(probe)
        thresh = ctrl.PROBE_NOTREADY_THRESHOLD
        for i in range(thresh - 1):
            ctrl.reconcile("default", "svc")
            inf = store.get("Inference", "svc")
            assert inf.predictor_statuses["main"].not_ready == []
        ctrl.reconcile("default", "svc")  # threshold crossing
        inf = store.get("Inference", "svc")
        st = inf.predictor_statuses["main"]
        assert st.not_ready == ["svc-main-0"]
        assert "NotReady" in st.message
        events = [e for e in store.list("Event")
                  if e.reason == "ReplicaNotReady"]
        assert len(events) == 1  # fires once at the crossing, no spam
        assert metrics.probe_failures.value(pod="svc-main-0") == float(thresh)
        assert metrics.replicas_not_ready.value(inference="svc") == 1.0
        # a later reconcile past the threshold does NOT re-fire the event
        ctrl.reconcile("default", "svc")
        events = [e for e in store.list("Event")
                  if e.reason == "ReplicaNotReady"]
        assert len(events) == 1
        # probe recovers: condition clears
        state["fail"] = False
        ctrl.reconcile("default", "svc")
        inf = store.get("Inference", "svc")
        assert inf.predictor_statuses["main"].not_ready == []
        assert metrics.replicas_not_ready.value(inference="svc") == 0.0

    def test_deleted_pod_counter_pruned(self):
        def probe(pod):
            raise TimeoutError("down")

        store, ctrl, _ = self._setup(probe)
        for _ in range(3):
            ctrl.reconcile("default", "svc")
        assert ctrl._probe_failures.get("svc-main-0", 0) >= 3
        store.try_delete("Pod", "svc-main-0", "default")
        ctrl.reconcile("default", "svc")
        assert "svc-main-0" not in ctrl._probe_failures


class TestDrainBeforeDelete:
    """Scale-down/GC with a drain window: the controller tells the replica
    to drain (hook + annotation), waits for idle stats or the grace, and
    only then deletes — in-flight decodes are never severed."""

    def _setup(self, clock, stats, drained_pods, grace=30.0):
        from kubedl_tpu.api import constants
        from kubedl_tpu.core.objects import PodPhase
        from kubedl_tpu.core.store import ObjectStore
        from kubedl_tpu.lineage.types import ModelVersion, ModelVersionPhase
        from kubedl_tpu.serving.controller import InferenceController
        from kubedl_tpu.serving.types import Inference, Predictor

        store = ObjectStore()
        mv = ModelVersion(model_name="m", phase=ModelVersionPhase.SUCCEEDED,
                          image="m:v1")
        mv.metadata.name = "m-v1"
        store.create(mv)

        def probe(pod):
            return stats[pod.metadata.name]

        def hook(pod):
            drained_pods.append(pod.metadata.name)

        ctrl = InferenceController(store, local_addresses=True,
                                   qps_probe=probe, clock=clock,
                                   drain_grace_s=grace, drain_hook=hook)
        inf = Inference()
        inf.metadata.name = "svc"
        inf.predictors.append(Predictor(name="main", model_version="m-v1",
                                        replicas=2))
        store.create(inf)
        ctrl.reconcile("default", "svc")
        for p in store.list("Pod"):
            def mut(o):
                o.status.phase = PodPhase.RUNNING
            store.update_with_retry("Pod", p.metadata.name, "default", mut)
        return store, ctrl

    def test_waits_for_idle_then_deletes(self):
        from kubedl_tpu.api import constants

        t = {"now": 100.0}
        stats = {"svc-main-0": {"active_slots": 0, "queued": 0},
                 "svc-main-1": {"active_slots": 2, "queued": 1}}
        drained = []
        store, ctrl = self._setup(lambda: t["now"], stats, drained)

        def shrink(o):
            o.predictors[0].replicas = 1
        store.update_with_retry("Inference", "svc", "default", shrink)
        # first sight: drain signal + annotation, pod NOT deleted
        requeue = ctrl.reconcile("default", "svc")
        pods = {p.metadata.name for p in store.list("Pod")}
        assert pods == {"svc-main-0", "svc-main-1"}
        assert drained == ["svc-main-1"]
        pod = store.get("Pod", "svc-main-1")
        assert constants.ANNOTATION_DRAIN_STARTED in pod.metadata.annotations
        assert any(e.reason == "Draining" for e in store.list("Event"))
        assert requeue == 1.0  # fast requeue while a drain is pending
        # still busy inside the grace: the pod survives another pass
        t["now"] += 1.0
        ctrl.reconcile("default", "svc")
        assert len(store.list("Pod")) == 2
        assert drained == ["svc-main-1"]  # hook fires once, not per pass
        # replica reports idle -> deleted before the grace expires
        stats["svc-main-1"] = {"active_slots": 0, "queued": 0}
        ctrl.reconcile("default", "svc")
        pods = {p.metadata.name for p in store.list("Pod")}
        assert pods == {"svc-main-0"}

    def test_grace_expiry_deletes_busy_pod(self):
        t = {"now": 100.0}
        stats = {"svc-main-0": {"active_slots": 0, "queued": 0},
                 "svc-main-1": {"active_slots": 2, "queued": 5}}
        store, ctrl = self._setup(lambda: t["now"], stats, [], grace=30.0)

        def shrink(o):
            o.predictors[0].replicas = 1
        store.update_with_retry("Inference", "svc", "default", shrink)
        ctrl.reconcile("default", "svc")
        assert len(store.list("Pod")) == 2
        t["now"] += 31.0  # grace expired: availability wins, delete anyway
        ctrl.reconcile("default", "svc")
        assert {p.metadata.name for p in store.list("Pod")} == {"svc-main-0"}

    def test_zero_grace_preserves_delete_on_sight(self):
        t = {"now": 0.0}
        stats = {"svc-main-0": {"active_slots": 0, "queued": 0},
                 "svc-main-1": {"active_slots": 9, "queued": 9}}
        drained = []
        store, ctrl = self._setup(lambda: t["now"], stats, drained, grace=0.0)

        def shrink(o):
            o.predictors[0].replicas = 1
        store.update_with_retry("Inference", "svc", "default", shrink)
        ctrl.reconcile("default", "svc")
        assert {p.metadata.name for p in store.list("Pod")} == {"svc-main-0"}
        assert drained == []  # no drain dance when the window is off


class TestModelLifecycle:
    """Engine-side weight hot-swap (docs/serving.md "Model lifecycle"):
    a second parameter tree rides the same jitted functions, requests
    pick their version at admission, retired trees evict only after the
    last referencing row drains, and every failure mode of the
    ``serving.weight_swap`` chaos site leaves the old version serving —
    never a torn state."""

    PROMPT = [3, 1, 4, 1, 5, 9]

    def _save_scaled(self, eng, tmp_path, tag, scale):
        """A real checkpoint whose weights provably differ from init."""
        import jax

        from kubedl_tpu.models import llama
        from kubedl_tpu.training.checkpoint import save_checkpoint

        params = llama.llama_init(jax.random.PRNGKey(0), eng.cfg)
        params = jax.tree_util.tree_map(lambda x: x * scale, params)
        d = str(tmp_path / tag)
        save_checkpoint(d, {"params": params}, 1)
        return d

    def test_hot_swap_serves_both_versions_bit_identically(self, tmp_path):
        from kubedl_tpu.serving.server import LlamaEngine, UnknownModelVersion

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        try:
            base_out = eng.generate(list(self.PROMPT), max_tokens=8)
            d = self._save_scaled(eng, tmp_path, "v2", 1.5)
            eng.load_version("v2", d)
            eng.load_version("v2", d)  # idempotent
            assert eng.versions()["loaded"] == ["base", "v2"]
            v2_out = eng.generate(list(self.PROMPT), max_tokens=8,
                                  model_version="v2")
            assert v2_out["model_version"] == "v2"
            assert v2_out["token_ids"] != base_out["token_ids"]
            # the default version is UNTOUCHED by co-residency
            again = eng.generate(list(self.PROMPT), max_tokens=8)
            assert again["token_ids"] == base_out["token_ids"]
            assert again["model_version"] == "base"
            with pytest.raises(UnknownModelVersion):
                eng.generate([1], max_tokens=2, model_version="nope")
        finally:
            eng.close()

    def test_concurrent_two_version_traffic_each_bit_identical(self, tmp_path):
        import threading

        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=4, max_seq=64)
        try:
            d = self._save_scaled(eng, tmp_path, "v2", 2.0)
            eng.load_version("v2", d)
            ref = {
                "base": eng.generate(list(self.PROMPT), max_tokens=8),
                "v2": eng.generate(list(self.PROMPT), max_tokens=8,
                                   model_version="v2"),
            }
            results = []

            def worker(ver):
                for _ in range(3):
                    r = eng.generate(list(self.PROMPT), max_tokens=8,
                                     model_version="" if ver == "base"
                                     else ver)
                    results.append((ver, r))

            threads = [threading.Thread(target=worker, args=(v,))
                       for v in ("base", "v2", "base", "v2")]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert len(results) == 12
            for ver, r in results:
                # versions NEVER cross-contaminate, even interleaved in
                # the same batch window
                assert r["token_ids"] == ref[ver]["token_ids"], ver
                assert r["model_version"] == ver
        finally:
            eng.close()

    def test_retire_evicts_after_drain_default_fenced(self, tmp_path):
        from kubedl_tpu.serving.server import LlamaEngine, UnknownModelVersion

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        try:
            d = self._save_scaled(eng, tmp_path, "v2", 1.5)
            eng.load_version("v2", d)
            with pytest.raises(ValueError):
                eng.retire_version("base")  # the default cannot retire
            assert eng.retire_version("v2") is True
            assert eng.retire_version("ghost") is False
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if eng.versions()["loaded"] == ["base"]:
                    break
                eng.generate([2], max_tokens=1)  # admission pass evicts
            assert eng.versions()["loaded"] == ["base"]
            assert eng.versions()["retiring"] == []
            # a retired version is gone for NEW requests
            with pytest.raises(UnknownModelVersion):
                eng.generate([1], max_tokens=2, model_version="v2")
        finally:
            eng.close()

    def test_failed_load_leaves_old_version_serving(self, tmp_path):
        """The weight_swap contract: corrupt artifact, truncated step, or
        an injected mid-swap crash — the load FAILS, the serving tree is
        untouched, outputs stay bit-identical."""
        import json as _json

        from kubedl_tpu.chaos import FaultInjected, FaultPlan, FaultSpec
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        try:
            before = eng.generate(list(self.PROMPT), max_tokens=8)
            # missing artifact: no checkpoint at all under the dir
            with pytest.raises(ValueError):
                eng.load_version("v2", str(tmp_path / "empty"))
            # truncated artifact: manifest present, shard file missing
            torn = tmp_path / "torn" / "step-00000001"
            torn.mkdir(parents=True)
            (torn / "meta.json").write_text(_json.dumps(
                {"step": 1, "nprocs": 1, "leaves": {}}))
            (tmp_path / "torn" / "latest").write_text("step-00000001")
            with pytest.raises(ValueError):
                eng.load_version("v2", str(tmp_path / "torn"))
            # mid-swap crash: the chaos site fires inside the build
            good = self._save_scaled(eng, tmp_path, "good", 1.5)
            with FaultPlan(7, sites={
                "serving.weight_swap": [FaultSpec.nth(1)],
            }) as plan:
                with pytest.raises(FaultInjected):
                    eng.load_version("v2", good)
            assert plan.faults("serving.weight_swap") == 1
            assert eng.versions()["loaded"] == ["base"]  # no torn state
            after = eng.generate(list(self.PROMPT), max_tokens=8)
            assert after["token_ids"] == before["token_ids"]
            # and the SAME dir loads fine once the fault clears
            eng.load_version("v2", good)
            assert "v2" in eng.versions()["loaded"]
        finally:
            eng.close()

    def test_corrupt_restore_at_engine_start(self, tmp_path):
        """Engine START under weight_swap chaos / torn checkpoints: an
        injected fault fails the constructor cleanly (supervisor
        restarts, old pod keeps serving); a torn latest step falls back
        to the previous good one instead of serving random weights."""
        import jax

        from kubedl_tpu.chaos import FaultInjected, FaultPlan, FaultSpec
        from kubedl_tpu.models import llama
        from kubedl_tpu.serving.server import LlamaEngine
        from kubedl_tpu.training.checkpoint import save_checkpoint

        with FaultPlan(11, sites={
            "serving.weight_swap": [FaultSpec.nth(1)],
        }):
            with pytest.raises(FaultInjected):
                LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        # torn newest step: restore falls back to the good step 1
        eng0 = LlamaEngine(preset="tiny", max_batch=2, max_seq=64)
        try:
            params = llama.llama_init(jax.random.PRNGKey(0), eng0.cfg)
            params = jax.tree_util.tree_map(lambda x: x * 3.0, params)
            d = str(tmp_path / "ck")
            save_checkpoint(d, {"params": params}, 1)
            want = None
            eng1 = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                               ckpt_dir=d)
            try:
                want = eng1.generate(list(self.PROMPT), max_tokens=8)
            finally:
                eng1.close()
            import json as _json
            import pathlib

            torn = pathlib.Path(d) / "step-00000002"
            torn.mkdir()
            (torn / "meta.json").write_text(_json.dumps(
                {"step": 2, "nprocs": 1, "leaves": {}}))
            (pathlib.Path(d) / "latest").write_text("step-00000002")
            eng2 = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                               ckpt_dir=d)
            try:
                got = eng2.generate(list(self.PROMPT), max_tokens=8)
                assert got["token_ids"] == want["token_ids"]
            finally:
                eng2.close()
        finally:
            eng0.close()
