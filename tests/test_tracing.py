"""Tracing tests (TPU addition per SURVEY.md §5 — no reference analogue).

Grown with the distributed-tracing work (docs/observability.md): trace
identity + header propagation, router→engine context hops against stub
replicas, hedge/retry/fallback span tagging, the flight-recorder response
shape, SLO burn-rate math under a fake clock, and exemplar rendering."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from kubedl_tpu.observability.slo import (
    DEFAULT_ALERTS,
    BurnAlert,
    SLOTracker,
    alerts_from_config,
)
from kubedl_tpu.observability.tracing import (
    TRACE_HEADER,
    TRACER,
    TraceContext,
    Tracer,
    build_span_tree,
    new_span_id,
    new_trace_id,
    parse_trace_header,
    span_to_dict,
    trace_for_job,
)

from tests.helpers import make_tpujob
from tests.test_engine import make_engine, submit_and_reconcile


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts with an empty, armed ring and leaves it so."""
    TRACER.clear()
    TRACER.enabled = True
    yield
    TRACER.clear()
    TRACER.enabled = True


class TestTracer:
    def test_span_records_duration_and_attrs(self):
        t = Tracer()
        with t.span("work", key="v") as attrs:
            attrs["late"] = 1
        (s,) = t.spans("work")
        assert s.duration >= 0
        assert s.attrs == {"key": "v", "late": 1}

    def test_ring_capacity(self):
        t = Tracer(capacity=8)
        for i in range(20):
            with t.span(f"s{i}"):
                pass
        assert len(t.spans()) == 8
        assert t.spans()[0].name == "s12"

    def test_disabled_is_free(self):
        t = Tracer()
        t.enabled = False
        with t.span("skipped"):
            pass
        assert t.spans() == []


class TestTraceIdentity:
    def test_two_tracers_share_the_epoch_timebase(self):
        """Spans recorded by INDEPENDENT tracers (different processes in
        production) must land on one wall-clock timeline — the whole
        premise of scripts/tracemerge.py."""
        t1, t2 = Tracer(), Tracer()
        wall0 = time.time()
        with t1.span("a"):
            pass
        with t2.span("b"):
            pass
        wall1 = time.time()
        (a,), (b,) = t1.spans("a"), t2.spans("b")
        assert wall0 - 1.0 <= a.ts <= wall1 + 1.0
        assert wall0 - 1.0 <= b.ts <= wall1 + 1.0
        assert abs(a.ts - b.ts) < 1.0  # same timebase, not per-process zero

    def test_header_round_trip(self):
        ctx = TraceContext(new_trace_id(), new_span_id())
        back = parse_trace_header(ctx.to_header())
        assert back is not None
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id
        assert back.sampled

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-short-short-01",
        "00-" + "g" * 32 + "-" + "0" * 16 + "-01",  # non-hex
    ])
    def test_malformed_header_parses_to_none(self, bad):
        assert parse_trace_header(bad) is None

    def test_trace_for_job_is_deterministic(self):
        a, b = trace_for_job("uid-1"), trace_for_job("uid-1")
        assert a.trace_id == b.trace_id and a.span_id == b.span_id
        assert trace_for_job("uid-2").trace_id != a.trace_id

    def test_record_parents_under_context(self):
        t = Tracer()
        ctx = TraceContext(new_trace_id(), new_span_id())
        sid = t.record("child", duration=0.1, trace=ctx)
        (s,) = t.spans("child")
        assert s.span_id == sid
        assert s.trace_id == ctx.trace_id
        assert s.parent_id == ctx.span_id
        # explicit parent_id and span_id win over the context defaults
        forced = new_span_id()
        t.record("forced", trace=ctx, parent_id="p" * 16, span_id=forced)
        (f,) = t.spans("forced")
        assert f.span_id == forced and f.parent_id == "p" * 16

    def test_record_wall_ts_pins_the_epoch_timestamp(self):
        t = Tracer()
        t.record("pinned", duration=2.0, wall_ts=1000.0)
        (s,) = t.spans("pinned")
        assert s.ts == 1000.0

    def test_build_span_tree_roots_and_order(self):
        root_id, kid_id = new_span_id(), new_span_id()
        spans = [
            {"name": "kid", "span_id": kid_id, "parent_id": root_id,
             "ts": 2.0},
            {"name": "root", "span_id": root_id, "parent_id": "", "ts": 1.0},
            # self-parented (job.submit idiom) and orphaned spans are roots
            {"name": "selfp", "span_id": "s" * 16, "parent_id": "s" * 16,
             "ts": 0.5},
            {"name": "orphan", "span_id": new_span_id(),
             "parent_id": "missing-parent00", "ts": 3.0},
        ]
        tree = build_span_tree(spans)
        assert [n["name"] for n in tree] == ["selfp", "root", "orphan"]
        assert [c["name"] for c in tree[1]["children"]] == ["kid"]

    def test_disarmed_calls_are_inert(self):
        t = Tracer()
        t.enabled = False
        h = t.begin("x", parent=TraceContext(new_trace_id(), new_span_id()))
        h.finish(late=1)
        with t.span("y"):
            pass
        assert t.record("z", duration=1.0) == ""
        assert t.spans() == []


class TestEngineIntegration:
    def test_reconcile_emits_span(self):
        engine, store, _ = make_engine()
        submit_and_reconcile(engine, store, make_tpujob("traced"))
        spans = TRACER.spans("reconcile")
        assert spans and spans[-1].attrs["job"] == "default/traced"

    def test_job_milestones_share_the_job_trace(self):
        """submit/gang_bind spans land in the deterministic per-job
        trace, rooted at the self-parented job.submit span."""
        engine, store, _ = make_engine()
        job = make_tpujob("ladder")
        submit_and_reconcile(engine, store, job, times=4)
        uid = store.get(job.KIND, "ladder").metadata.uid
        ctx = trace_for_job(uid)
        spans = {s.name: s for s in TRACER.trace_spans(ctx.trace_id)}
        assert spans["job.submit"].span_id == ctx.span_id
        assert spans["job.gang_bind"].parent_id == ctx.span_id
        tree = TRACER.span_tree(ctx.trace_id)
        assert tree and tree[0]["name"] == "job.submit"
        assert "job.gang_bind" in {c["name"] for c in tree[0]["children"]}

    def test_milestones_emitted_once_per_job(self):
        engine, store, _ = make_engine()
        job = make_tpujob("once")
        # re-reconciling must not duplicate milestone spans
        submit_and_reconcile(engine, store, job, times=5)
        uid = store.get(job.KIND, "once").metadata.uid
        names = [s.name for s in
                 TRACER.trace_spans(trace_for_job(uid).trace_id)]
        assert len(names) == len(set(names)), names


# ---------------------------------------------------------------------------
# a traced request's tree and its first_tokens record come from one set of stamps
# ---------------------------------------------------------------------------

class TestServingEngineStamps:
    @pytest.mark.parametrize("chunk", [0, 16])
    def test_queue_wait_and_prefill_spans_are_the_records_parts(self, chunk):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=128,
                          prefill_chunk_tokens=chunk, prefix_cache_mb=0)
        try:
            reply = eng.generate(list(range(40, 81)), max_tokens=4, debug_trace=True)
            untraced = eng.generate(list(range(1, 6)), max_tokens=2)
            records = eng.pipeline_stats()["first_tokens"]
        finally:
            eng.close()
        assert "trace" not in untraced and len(records) == 2  # traced or not
        _seq, queue, backlog, chunks, first, n_chunks, segments, steps = records[0]
        (root,) = reply["trace"]["spans"]
        spans = {c["name"]: c for c in root["children"]}
        assert root["name"] == "engine.request"
        # rounded to the microsecond when stored, each end once
        assert spans["engine.queue_wait"]["duration_ms"] == pytest.approx(queue, abs=0.001)
        assert spans["engine.prefill"]["duration_ms"] == pytest.approx(
            chunks + first, abs=0.002)
        # the admission's work starts where the queue wait ends, inside the backlog
        assert spans["engine.admission"]["ts"] == pytest.approx(
            spans["engine.queue_wait"]["ts"] + queue / 1e3, abs=2e-6)
        assert spans["engine.admission"]["duration_ms"] <= backlog + 0.001
        assert spans["engine.prefill"]["ts"] == pytest.approx(
            spans["engine.queue_wait"]["ts"] + (queue + backlog) / 1e3, abs=3e-6)
        attrs = spans["engine.prefill"]["attrs"]
        assert (attrs["chunks"], attrs["segments"], attrs["steps"]) == (
            n_chunks, segments, steps) == (3 if chunk else 1, 0, 0)
        assert queue + backlog + chunks + first == pytest.approx(reply["ttft_ms"], abs=0.01)


# ---------------------------------------------------------------------------
# router → engine context propagation against stub replicas
# ---------------------------------------------------------------------------

class _TraceStubHandler(BaseHTTPRequestHandler):
    def log_message(self, *a):  # quiet
        pass

    def _json(self, code, payload, headers=None):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        if self.path.startswith("/v1/trace"):
            self._json(200, {"enabled": True,
                             "spans": self.server.trace_spans})
            return
        self._json(200, {"queued": 0, "shed_recent": 0, "draining": False})

    def do_POST(self):
        n = int(self.headers.get("Content-Length", "0"))
        req = json.loads(self.rfile.read(n) or b"{}")
        beh = self.server.behavior
        if self.path == "/v1/cancel":
            self._json(200, {"cancelled": True})
            return
        self.server.calls.append(
            {"req": req, "trace_header": self.headers.get(TRACE_HEADER)}
        )
        shots = beh.get("fail_first", 0)
        if len(self.server.calls) <= shots:
            self._json(503, {"error": "busy", "shed": True,
                             "reason": "overloaded"}, {"Retry-After": "1"})
            return
        if beh.get("delay"):
            time.sleep(beh["delay"])
        self._json(200,
                   {"token_ids": [1, 2, 3], "served_by": self.server.name})


def _trace_stub(name, **behavior):
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _TraceStubHandler)
    srv.name = name
    srv.behavior = behavior
    srv.calls = []
    srv.trace_spans = []
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


@pytest.fixture
def trace_fleet():
    servers = {}

    def make(name, **behavior):
        servers[name] = _trace_stub(name, **behavior)
        return servers[name]

    yield make, servers
    for s in servers.values():
        s.shutdown()
        s.server_close()


class TestRouterPropagation:
    def _router(self, servers, **kw):
        from kubedl_tpu.serving.router import ServingRouter

        kw.setdefault("hedge_enabled", False)
        kw.setdefault("affinity_prefix_len", 0)
        return ServingRouter(
            [(n, "127.0.0.1", s.server_port) for n, s in servers.items()],
            **kw)

    def test_forward_carries_the_trace_header(self, trace_fleet):
        make, servers = trace_fleet
        a = make("a")
        r = self._router(servers)
        caller = TraceContext(new_trace_id(), new_span_id())
        code, _, _ = r.handle_generate(
            {"prompt_ids": [1, 2], "max_tokens": 4}, 5000, trace=caller)
        assert code == 200
        sent = parse_trace_header(a.calls[0]["trace_header"])
        assert sent is not None
        assert sent.trace_id == caller.trace_id
        # the header names the FORWARD span, and the chain reads
        # caller -> router.request -> router.forward
        (root,) = TRACER.spans("router.request")
        (fwd,) = TRACER.spans("router.forward")
        assert root.parent_id == caller.span_id
        assert fwd.parent_id == root.span_id
        assert sent.span_id == fwd.span_id
        assert fwd.attrs["replica"] == "a"
        assert fwd.attrs["result"] == "ok"

    def test_retry_spans_share_the_trace_and_tag_the_attempt(
            self, trace_fleet):
        make, servers = trace_fleet
        make("a", fail_first=1)  # primary sheds once, failover retries
        make("b")
        r = self._router(servers)
        code, _, _ = r.handle_generate(
            {"prompt_ids": [1], "max_tokens": 4}, 5000)
        assert code == 200
        fwds = TRACER.spans("router.forward")
        assert len(fwds) == 2
        assert {f.attrs["retry"] for f in fwds} == {0, 1}
        assert len({f.trace_id for f in fwds}) == 1
        shed, won = sorted(fwds, key=lambda f: f.attrs["retry"])
        assert shed.attrs["result"] == "ReplicaShedding"
        assert won.attrs["result"] == "ok"

    def test_hedge_spans_tagged_winner_and_loser(self, trace_fleet):
        make, servers = trace_fleet
        make("a", delay=0.8)  # least-loaded tie-break makes "a" primary
        make("b")
        r = self._router(servers, hedge_enabled=True, hedge_floor_ms=50.0,
                         hedge_default_ms=80.0)
        code, payload, _ = r.handle_generate(
            {"prompt_ids": [7] * 8, "max_tokens": 4}, 8000)
        assert code == 200 and payload["served_by"] == "b"
        # the loser's span is recorded when its slow attempt resolves
        deadline = time.monotonic() + 3.0
        while (time.monotonic() < deadline
               and len(TRACER.spans("router.forward")) < 2):
            time.sleep(0.02)
        fwds = TRACER.spans("router.forward")
        assert len(fwds) == 2
        outcomes = {f.attrs["replica"]: f.attrs.get("outcome") for f in fwds}
        assert outcomes == {"a": "loser", "b": "winner"}
        assert len({f.trace_id for f in fwds}) == 1

    def test_fallback_leg_is_traced(self, trace_fleet):
        make, servers = trace_fleet
        dec = make("dec")
        dead = make("pre")
        port = dead.server_port
        dead.shutdown()
        dead.server_close()  # prefill leg: connection refused
        del servers["pre"]
        from kubedl_tpu.serving.router import ServingRouter

        r = ServingRouter(
            [{"name": "pre", "port": port, "role": "prefill"},
             {"name": "dec", "port": dec.server_port, "role": "decode"}],
            hedge_enabled=False, affinity_prefix_len=0)
        code, payload, _ = r.handle_generate(
            {"prompt_ids": [1, 2], "max_tokens": 4}, 5000)
        assert code == 200 and payload["served_by"] == "dec"
        (root,) = TRACER.spans("router.request")
        (leg,) = TRACER.spans("router.prefill_leg")
        (fb,) = TRACER.spans("router.fallback")
        assert leg.parent_id == root.span_id
        assert fb.parent_id == root.span_id
        assert fb.attrs["reason"] == "disagg_leg_failed"
        assert fb.trace_id == root.trace_id

    def test_flight_recorder_response_shape(self, trace_fleet):
        make, servers = trace_fleet
        a = make("a")
        # the replica's /v1/trace contribution nests under its forward span
        r = self._router(servers)
        code, payload, _ = r.handle_generate(
            {"prompt_ids": [1], "max_tokens": 4,
             "debug": {"trace": True}}, 5000)
        assert code == 200
        rec = payload["trace"]
        (root,) = TRACER.spans("router.request")
        assert rec["trace_id"] == root.trace_id
        assert rec["spans"][0]["name"] == "router.request"
        kids = {c["name"] for c in rec["spans"][0]["children"]}
        assert "router.forward" in kids

    def test_flight_recorder_merges_replica_spans(self, trace_fleet):
        make, servers = trace_fleet
        a = make("a")
        r = self._router(servers)
        caller = TraceContext(new_trace_id(), new_span_id())
        # seed the stub's /v1/trace with an engine-side span parented
        # under the forward context the router will send
        code, _, _ = r.handle_generate(
            {"prompt_ids": [1], "max_tokens": 4}, 5000, trace=caller)
        assert code == 200
        sent = parse_trace_header(a.calls[0]["trace_header"])
        a.trace_spans = [{
            "name": "engine.request", "trace_id": sent.trace_id,
            "span_id": new_span_id(), "parent_id": sent.span_id,
            "ts": time.time(), "duration_ms": 1.0, "attrs": {},
        }]
        code, payload, _ = r.handle_generate(
            {"prompt_ids": [1], "max_tokens": 4,
             "debug": {"trace": True}}, 5000)
        assert code == 200
        tree = payload["trace"]["spans"]
        # the stub serves the seeded span for any trace query; the flight
        # recorder must surface it in the merged tree
        all_names = set()

        def walk(nodes):
            for n in nodes:
                all_names.add(n["name"])
                walk(n["children"])

        walk(tree)
        assert "engine.request" in all_names


class TestSLOBurnRate:
    def _tracker(self, clock, alerts=DEFAULT_ALERTS):
        return SLOTracker(objective=0.999, latency_objective_ms=100.0,
                          alerts=alerts, clock=clock)

    def test_burn_rate_math_under_fake_clock(self):
        now = [1000.0]
        t = self._tracker(lambda: now[0])
        for _ in range(20):
            t.observe(ok=True, latency_ms=10.0)
        assert t.burn_rate(300.0) == 0.0
        for _ in range(20):
            t.observe(ok=False, latency_ms=10.0)
        # 20 bad / 40 total over every window -> 0.5 / 0.001 = 500x
        assert t.burn_rate(300.0) == pytest.approx(500.0)
        assert t.burning(DEFAULT_ALERTS[0])

    def test_latency_breach_counts_as_bad(self):
        now = [1000.0]
        t = self._tracker(lambda: now[0])
        assert t.observe(ok=True, latency_ms=50.0) is True
        assert t.observe(ok=True, latency_ms=500.0) is False  # 200 but slow
        snap = t.snapshot()
        assert snap["requests"] == 2 and snap["bad"] == 1

    def test_outage_flips_gauges_and_time_clears_them(self):
        """The acceptance drill: an injected outage flips
        kubedl_tpu_slo_burning to 1; the outage ending (time passing
        under a fake clock) clears it without new traffic."""
        now = [10_000.0]
        t = self._tracker(lambda: now[0])
        for _ in range(10):
            t.observe(ok=False, latency_ms=5.0, trace_id="f" * 32)
        text = t.metrics.registry.render()
        assert 'kubedl_tpu_slo_burning{severity="page"} 1.0' in text
        assert t.snapshot()["burning"]["page"] is True
        assert t.last_bad_trace_id == "f" * 32
        # outage over: advance past the long window, no new events
        now[0] += DEFAULT_ALERTS[0].long_s + t.bucket_s + 1.0
        t.refresh()
        text = t.metrics.registry.render()
        assert 'kubedl_tpu_slo_burning{severity="page"} 0.0' in text
        assert t.snapshot()["burning"]["page"] is False

    def test_short_window_alone_does_not_fire(self):
        """Multi-window discipline: a blip that has not yet burned the
        LONG window must not page."""
        now = [50_000.0]
        alerts = (BurnAlert("page", 10.0, 1000.0, 14.4),)
        t = self._tracker(lambda: now[0], alerts=alerts)
        # long window full of good traffic...
        for _ in range(99):
            t.observe(ok=True, latency_ms=1.0)
            now[0] += 5.0
        # ...then a 1-bucket burst of errors
        t.observe(ok=False, latency_ms=1.0)
        assert t.burn_rate(10.0) >= 14.4
        assert t.burn_rate(1000.0) < 14.4
        assert not t.burning(alerts[0])

    def test_alerts_from_config(self):
        assert alerts_from_config(None) == DEFAULT_ALERTS
        (a,) = alerts_from_config([{"severity": "ticket", "short_s": 60,
                                    "long_s": 600, "threshold": 2.5}])
        assert a == BurnAlert("ticket", 60.0, 600.0, 2.5)

    def test_exemplar_links_metrics_to_a_retrievable_trace(self):
        """A burning SLO's histogram exemplar must resolve to a trace the
        ring buffer can serve via /v1/trace."""
        now = [1000.0]
        t = self._tracker(lambda: now[0])
        tid = new_trace_id()
        TRACER.record("router.request", duration=0.2,
                      trace=TraceContext(tid, ""))
        t.observe(ok=False, latency_ms=42.0, trace_id=tid)
        text = t.metrics.registry.render()
        assert f'# {{trace_id="{tid}"}} 42.0' in text
        spans = TRACER.trace_spans(tid)
        assert spans and spans[0].name == "router.request"

    def test_router_feeds_slo_and_stats(self, trace_fleet):
        make, servers = trace_fleet
        make("a")
        from kubedl_tpu.serving.router import ServingRouter

        r = ServingRouter(
            [("a", "127.0.0.1", servers["a"].server_port)],
            hedge_enabled=False, affinity_prefix_len=0,
            slo={"objective": 0.99, "latency_objective_ms": 60_000.0})
        code, _, _ = r.handle_generate(
            {"prompt_ids": [1], "max_tokens": 4}, 5000)
        assert code == 200
        snap = r.stats()["slo"]
        assert snap["objective"] == 0.99
        assert snap["requests"] == 1 and snap["bad"] == 0
        text = r.metrics.registry.render()
        assert 'kubedl_tpu_slo_requests{result="good"} 1.0' in text
