"""Multi-tenant QoS (kubeai_tpu/qos/, docs/qos.md): the priority-class
lattice and proxy-side resolution, the class-aware weighted-fair
admission queue (deficit round-robin over bounded tenant lanes),
class-aware shedding and per-class queue-wait budgets, the preemptible
batch tier (marker detection, engine-side seizure, proxy resume), the
/debug/qos surface, the preemption-storm trigger, loadgen's
--priority-mix, and the full drill (batch flood vs interactive p99
TTFT with byte-correct resume) as the tier-1 e2e."""

import json
import queue as stdqueue
import threading
import time
import types
import urllib.error
import urllib.request

import pytest

from kubeai_tpu.metrics import default_registry
from kubeai_tpu.qos import (
    CLASSES,
    QoSQueue,
    is_preempt_event,
    normalize_priority,
    rank,
    resolve_priority,
    tenant_default_class,
)
from kubeai_tpu.qos.stats import qos_snapshot, record_preemption


def counter(name, labels=None):
    return default_registry.get(name).value(labels=labels)


# ---------------------------------------------------------------------------
# Priority classes + resolution


class TestClasses:
    def test_lattice_order(self):
        assert CLASSES == ("interactive", "standard", "batch")
        assert rank("interactive") < rank("standard") < rank("batch")
        # Unknown strings rank with standard (engine-side leniency).
        assert rank("bogus") == rank("standard")

    def test_normalize_is_lenient(self):
        assert normalize_priority(" Interactive ") == "interactive"
        assert normalize_priority("BATCH") == "batch"
        assert normalize_priority("platinum") == ""
        assert normalize_priority("") == ""
        assert normalize_priority(None) == ""

    def test_resolution_precedence(self, monkeypatch):
        monkeypatch.setenv("KUBEAI_QOS_TENANT_CLASS", "t1=batch")
        # header > body > tenant default > standard
        assert resolve_priority("interactive", "batch", "t1") == "interactive"
        assert resolve_priority("", "Interactive", "t1") == "interactive"
        assert resolve_priority("", "", "t1") == "batch"
        assert resolve_priority("", "", "t2") == "standard"
        assert resolve_priority("", "", "") == "standard"

    def test_explicit_invalid_raises(self):
        with pytest.raises(ValueError, match="X-Priority"):
            resolve_priority("platinum", "", "")
        with pytest.raises(ValueError, match="priority"):
            resolve_priority("", "golden", "")

    def test_tenant_default_class_map(self, monkeypatch):
        monkeypatch.setenv(
            "KUBEAI_QOS_TENANT_CLASS", "abc=interactive, def=BATCH, bad=gold"
        )
        assert tenant_default_class("abc") == "interactive"
        assert tenant_default_class("def") == "batch"
        assert tenant_default_class("bad") == ""  # unknown class ignored
        assert tenant_default_class("zzz") == ""
        assert tenant_default_class("") == ""


# ---------------------------------------------------------------------------
# QoSQueue: class order, DRR fairness, bounded lanes, shed, budgets


def mk_req(priority="standard", tenant="", tokens=4, arrival=None):
    return types.SimpleNamespace(
        priority=priority,
        tenant=tenant,
        prompt_ids=[0] * tokens,
        arrival=time.monotonic() if arrival is None else arrival,
    )


def drain(q):
    out = []
    while True:
        try:
            out.append(q.get_nowait())
        except stdqueue.Empty:
            return out


class TestQueue:
    def test_strict_class_order(self):
        q = QoSQueue()
        b = mk_req("batch")
        s = mk_req("standard")
        i = mk_req("interactive")
        for r in (b, s, i):
            q.put_nowait(r)
        assert drain(q) == [i, s, b]
        assert q.qsize() == 0

    def test_fifo_within_a_lane(self):
        q = QoSQueue()
        reqs = [mk_req("standard", tenant="t") for _ in range(5)]
        for r in reqs:
            q.put_nowait(r)
        assert drain(q) == reqs

    def test_unknown_class_folds_to_standard(self):
        q = QoSQueue()
        r = mk_req("platinum")
        q.put_nowait(r)
        assert q.peek_priority() == "standard"
        assert q.get_nowait() is r

    def test_drr_rotates_lanes_not_arrival_order(self):
        """Tenant a's burst arrives first; with quantum 1 every serve
        exhausts the lane's deficit, so service alternates lanes instead
        of draining a's burst while b starves."""
        q = QoSQueue(quantum=1)
        a1, a2 = mk_req(tenant="a", tokens=1), mk_req(tenant="a", tokens=1)
        b1, b2 = mk_req(tenant="b", tokens=1), mk_req(tenant="b", tokens=1)
        for r in (a1, a2, b1, b2):
            q.put_nowait(r)
        assert drain(q) == [a1, b1, a2, b2]

    def test_drr_charges_prompt_cost(self):
        """A tenant submitting 8x-costlier prompts gets proportionally
        fewer serves per rotation: weighted fairness in prompt tokens,
        not request counts."""
        q = QoSQueue(quantum=4)
        big = [mk_req(tenant="big", tokens=8) for _ in range(4)]
        small = [mk_req(tenant="small", tokens=1) for _ in range(8)]
        for r in big + small:
            q.put_nowait(r)
        first5 = [q.get_nowait() for _ in range(5)]
        assert sum(1 for r in first5 if r.tenant == "small") == 4
        assert sum(1 for r in first5 if r.tenant == "big") == 1
        # Everything still drains (no starvation either way).
        assert len(drain(q)) == 7

    def test_lanes_fold_to_other_past_topk(self):
        q = QoSQueue(topk=2)
        q.put_nowait(mk_req(tenant="t1"))
        q.put_nowait(mk_req(tenant="t2"))
        q.put_nowait(mk_req(tenant="t3"))
        q.put_nowait(mk_req(tenant="t4"))
        lanes = q.snapshot()["per_class"]["standard"]["lanes"]
        assert set(lanes) == {"t1", "t2", "__other__"}
        assert lanes["__other__"]["depth"] == 2
        assert len(drain(q)) == 4

    def test_class_aware_shedding(self):
        """maxsize 8: batch refuses at 50% (4), standard at 85%
        (ceil(6.8) = 7), interactive only at the hard cap — batch sheds
        first, interactive last."""
        q = QoSQueue(maxsize=8)
        for _ in range(4):
            q.put_nowait(mk_req("batch"))
        with pytest.raises(stdqueue.Full):
            q.put_nowait(mk_req("batch"))
        for _ in range(3):
            q.put_nowait(mk_req("standard"))
        with pytest.raises(stdqueue.Full):
            q.put_nowait(mk_req("standard"))
        q.put_nowait(mk_req("interactive"))
        with pytest.raises(stdqueue.Full):
            q.put_nowait(mk_req("interactive"))
        snap = q.snapshot()
        assert snap["per_class"]["batch"]["shed"] == 1
        assert snap["per_class"]["standard"]["shed"] == 1
        assert snap["per_class"]["interactive"]["shed"] == 1
        assert q.qsize() == 8

    def test_peek_outranks_backlog(self):
        q = QoSQueue()
        q.put_nowait(mk_req("batch"))
        assert q.peek_priority() == "batch"
        assert not q.outranks("batch")  # same class does not outrank
        q.put_nowait(mk_req("standard"))
        assert q.peek_priority() == "standard"
        assert q.outranks("batch")
        assert not q.outranks("interactive")
        # A shed batch client waits behind everything; an interactive
        # one only behind its own class.
        assert q.backlog_at_or_above("batch") == 2
        assert q.backlog_at_or_above("interactive") == 0

    def test_budget_sweep_drops_only_expired_classes(self, monkeypatch):
        monkeypatch.setenv("KUBEAI_QOS_BUDGET_BATCH", "0.5")
        q = QoSQueue()
        stale = mk_req("batch", arrival=100.0)
        fresh = mk_req("batch", arrival=109.8)
        old_interactive = mk_req("interactive", arrival=100.0)  # no budget
        for r in (stale, fresh, old_interactive):
            q.put_nowait(r)
        dropped = q.sweep_budgets(now=110.0)
        assert dropped == [stale]
        assert q.snapshot()["per_class"]["batch"]["budget_drops"] == 1
        # Rate limit: an immediate re-sweep is a no-op.
        assert q.sweep_budgets(now=110.1) == []
        remaining = drain(q)
        assert len(remaining) == 2
        assert fresh in remaining and old_interactive in remaining

    def test_empty_queue_raises_empty(self):
        q = QoSQueue()
        with pytest.raises(stdqueue.Empty):
            q.get_nowait()
        assert q.peek_priority() is None


# ---------------------------------------------------------------------------
# Preemption marker (exact mirror of the handoff marker's discipline)


class TestPreemptMarker:
    def test_detects_marker_chunk(self):
        ev = (
            b'data: {"choices": [{"index": 0, "text": "", '
            b'"finish_reason": "preempted"}]}\n\n'
        )
        assert is_preempt_event(ev)

    def test_token_text_containing_word_is_not_marker(self):
        ev = (
            b'data: {"choices": [{"index": 0, "text": "got preempted", '
            b'"finish_reason": null}]}\n\n'
        )
        assert not is_preempt_event(ev)

    def test_done_and_junk_are_not_markers(self):
        assert not is_preempt_event(b"data: [DONE]\n\n")
        assert not is_preempt_event(b"data: preempted not json\n\n")
        assert not is_preempt_event(b": comment preempted\n\n")

    def test_markers_are_mutually_exclusive(self):
        """A handoff marker must never read as a preemption marker or
        vice versa — a flight is handed off OR preempted, never both,
        and the two resume paths differ (exclusion vs none)."""
        from kubeai_tpu.disagg.handoff import is_handoff_event

        handoff = (
            b'data: {"choices": [{"index": 0, "text": "", '
            b'"finish_reason": "handoff"}]}\n\n'
        )
        preempt = (
            b'data: {"choices": [{"index": 0, "text": "", '
            b'"finish_reason": "preempted"}]}\n\n'
        )
        assert is_handoff_event(handoff) and not is_preempt_event(handoff)
        assert is_preempt_event(preempt) and not is_handoff_event(preempt)


# ---------------------------------------------------------------------------
# Stats surface: storm trigger, /debug/qos snapshot


class TestStats:
    def test_snapshot_shape(self):
        doc = qos_snapshot()
        assert doc["classes"] == list(CLASSES)
        for key in ("preemptions", "preempted_tokens", "resumes",
                    "proxy_requests", "storm_window_preemptions"):
            assert key in doc

    def test_handle_qos_request_routes(self):
        from kubeai_tpu.qos import handle_qos_request

        assert handle_qos_request("/debug/other", {}) is None
        status, ctype, body = handle_qos_request("/debug/qos", {})
        assert status == 200 and ctype == "application/json"
        assert json.loads(body)["classes"] == list(CLASSES)

    def test_preemption_storm_trigger(self, monkeypatch):
        from kubeai_tpu.obs.incidents import (
            IncidentRecorder,
            install_recorder,
            uninstall_recorder,
        )

        monkeypatch.setenv("KUBEAI_QOS_STORM_COUNT", "3")
        monkeypatch.setenv("KUBEAI_QOS_STORM_WINDOW", "10")
        rec = IncidentRecorder(
            sources={"probe": lambda: {}}, incident_dir="",
            debounce_seconds=300.0,
        )
        install_recorder(rec)
        try:
            # Two in-window preemptions: churn, not yet a storm.
            record_preemption(5, now=1e9)
            record_preemption(5, now=1e9 + 1)
            assert rec.wait_idle()
            assert not [
                i for i in rec.snapshot()
                if i["trigger"] == "qos_preemption_storm"
            ]
            record_preemption(5, now=1e9 + 2)
            assert rec.wait_idle()
            storms = [
                i for i in rec.snapshot()
                if i["trigger"] == "qos_preemption_storm"
            ]
            assert len(storms) == 1
            assert storms[0]["detail"]["preemptions_in_window"] == 3
        finally:
            uninstall_recorder(rec)
            rec.stop()


# ---------------------------------------------------------------------------
# loadgen --priority-mix parsing


class TestPriorityMix:
    def test_parse(self):
        from benchmarks.loadgen import parse_priority_mix

        assert parse_priority_mix("interactive:2,batch:8") == [
            ("interactive", 2.0), ("batch", 8.0),
        ]
        assert parse_priority_mix("Standard") == [("standard", 1.0)]

    def test_parse_rejects_unknown_class_and_bad_weights(self):
        from benchmarks.loadgen import parse_priority_mix

        with pytest.raises(ValueError, match="priority-mix class"):
            parse_priority_mix("platinum:2")
        with pytest.raises(ValueError, match="weight"):
            parse_priority_mix("batch:x")
        with pytest.raises(ValueError, match="positive"):
            parse_priority_mix("batch:0")
        with pytest.raises(ValueError, match="empty"):
            parse_priority_mix(" , ")


# ---------------------------------------------------------------------------
# Engine-level: class-aware admission, preemption, budgets, Retry-After


def mk_params(**kw):
    from kubeai_tpu.engine.sampling import SamplingParams

    kw.setdefault("temperature", 0.0)
    kw.setdefault("max_tokens", 4)
    return SamplingParams(**kw)


@pytest.fixture(scope="module")
def qos_engine():
    """One REAL single-slot engine server: with exactly one decode slot
    every batch-vs-interactive contention is deterministic."""
    from kubeai_tpu.engine.core import EngineConfig, build_test_engine
    from kubeai_tpu.engine.server import EngineServer

    eng = build_test_engine(
        engine_config=EngineConfig(
            max_slots=1, max_seq_len=2048, prefill_buckets=(16, 32),
            decode_chunk=2, max_queue=16,
        )
    )
    srv = EngineServer(eng, "q1", host="127.0.0.1", port=0)
    srv.start()
    eng.generate(eng.tokenizer.encode("warm"), mk_params(), timeout=120)
    yield eng, srv
    srv.stop()


def sse_post(port, body, path="/v1/completions", headers=None, timeout=60):
    """POST a streaming request; returns the (text, finish_reason) event
    shapes plus '[DONE]'. Blocks until the stream ends."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
    out = []
    for block in raw.replace(b"\r\n", b"\n").split(b"\n\n"):
        if not block.startswith(b"data: "):
            continue
        payload = block[6:].decode()
        if payload == "[DONE]":
            out.append("[DONE]")
            continue
        c = json.loads(payload)["choices"][0]
        out.append((c.get("text"), c.get("finish_reason")))
    return out


def await_cond(cond, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out awaiting {msg}")


# The tiny CPU test model decodes ~1k tok/s, so "long" means hundreds
# of tokens: enough wall-clock in the slot for an interactive arrival
# to land mid-decode deterministically.
BATCH_BODY = {
    "model": "q1", "prompt": "the long batch job", "stream": True,
    "temperature": 0, "max_tokens": 400,
}


class TestEnginePreemption:
    def test_interactive_seizes_preemptible_batch_slot(self, qos_engine):
        """Slots full of preemptible batch work + an interactive arrival
        = the batch stream finishes early with the `preempted` marker
        (a direct client sees it verbatim; the proxy would withhold it
        and resume) and the interactive request is served immediately
        instead of waiting out 24 tokens of bulk decode."""
        eng, srv = qos_engine
        pre_before = counter("kubeai_qos_preemptions_total")
        tok_before = counter("kubeai_qos_preempted_tokens_total")
        got: list = []

        def run_batch():
            got.extend(sse_post(
                srv.port, BATCH_BODY,
                headers={"X-Priority": "batch", "X-Preemptible": "1"},
            ))

        t = threading.Thread(target=run_batch, daemon=True)
        t.start()
        await_cond(
            lambda: counter("kubeai_engine_active_slots") >= 1,
            msg="batch stream occupying the slot",
        )
        shape = sse_post(
            srv.port, dict(BATCH_BODY, prompt="quick question", max_tokens=4),
            headers={"X-Priority": "interactive"},
        )
        assert shape[-1] == "[DONE]"
        t.join(timeout=30)
        assert not t.is_alive(), "preempted batch stream never ended"
        fins = [fr for s in got if isinstance(s, tuple) for fr in [s[1]] if fr]
        assert fins == ["preempted"], f"expected the preempt marker, got {fins}"
        assert got[-1] == "[DONE]"
        assert counter("kubeai_qos_preemptions_total") == pre_before + 1
        assert counter("kubeai_qos_preempted_tokens_total") >= tok_before

    def test_handoff_planned_flight_is_never_preempted(self, qos_engine):
        """Exclusivity: X-Preemptible alongside X-Handoff-Planned is
        ignored — a flight is handed off OR preempted, never both. The
        interactive arrival waits for the batch stream instead."""
        eng, srv = qos_engine
        pre_before = counter("kubeai_qos_preemptions_total")
        got: list = []

        def run_batch():
            got.extend(sse_post(
                srv.port, BATCH_BODY,
                headers={
                    "X-Priority": "batch", "X-Preemptible": "1",
                    "X-Handoff-Planned": "1",
                },
            ))

        t = threading.Thread(target=run_batch, daemon=True)
        t.start()
        await_cond(
            lambda: counter("kubeai_engine_active_slots") >= 1,
            msg="batch stream occupying the slot",
        )
        shape = sse_post(
            srv.port, dict(BATCH_BODY, prompt="quick question", max_tokens=2),
            headers={"X-Priority": "interactive"},
        )
        assert shape[-1] == "[DONE]"
        t.join(timeout=60)
        assert not t.is_alive()
        fins = [fr for s in got if isinstance(s, tuple) for fr in [s[1]] if fr]
        assert fins == ["length"], (
            f"handoff-planned flight was preempted: {fins}"
        )
        assert counter("kubeai_qos_preemptions_total") == pre_before

    def test_non_preemptible_batch_is_never_preempted(self, qos_engine):
        """Without the proxy's X-Preemptible stamp (non-replayable
        request), batch work runs to completion even with interactive
        waiting."""
        eng, srv = qos_engine
        pre_before = counter("kubeai_qos_preemptions_total")
        got: list = []

        def run_batch():
            got.extend(sse_post(
                srv.port, BATCH_BODY,
                headers={"X-Priority": "batch"},
            ))

        t = threading.Thread(target=run_batch, daemon=True)
        t.start()
        await_cond(
            lambda: counter("kubeai_engine_active_slots") >= 1,
            msg="batch stream occupying the slot",
        )
        sse_post(
            srv.port, dict(BATCH_BODY, prompt="quick question", max_tokens=2),
            headers={"X-Priority": "interactive"},
        )
        t.join(timeout=60)
        fins = [fr for s in got if isinstance(s, tuple) for fr in [s[1]] if fr]
        assert fins == ["length"]
        assert counter("kubeai_qos_preemptions_total") == pre_before

    def test_queue_wait_budget_errors_expired_batch(self, qos_engine, monkeypatch):
        """A queued batch request past KUBEAI_QOS_BUDGET_BATCH is dropped
        with the budget error instead of waiting forever behind a busy
        slot; interactive (no budget set) keeps waiting."""
        eng, srv = qos_engine
        monkeypatch.setenv("KUBEAI_QOS_BUDGET_BATCH", "0.3")
        drops_before = counter("kubeai_qos_budget_drops_total", {"class": "batch"})
        occupier = eng.submit(
            eng.tokenizer.encode("hold the slot"),
            mk_params(max_tokens=1600),
            priority="interactive",
        )
        try:
            await_cond(
                lambda: counter("kubeai_engine_active_slots") >= 1,
                msg="occupier admitted",
            )
            batch = eng.submit(
                eng.tokenizer.encode("bulk"), mk_params(), priority="batch",
            )
            deadline = time.monotonic() + 10
            ev = None
            while time.monotonic() < deadline:
                try:
                    ev = batch.out.get(timeout=1)
                    break
                except stdqueue.Empty:
                    continue
            assert ev is not None, "budget sweep never fired"
            assert ev[0] == "error" and "budget" in ev[1], ev
            assert counter(
                "kubeai_qos_budget_drops_total", {"class": "batch"}
            ) == drops_before + 1
        finally:
            occupier.cancelled.set()
            await_cond(
                lambda: counter("kubeai_engine_active_slots") == 0,
                msg="engine drained",
            )

    def test_shed_batch_gets_429_with_scaled_retry_after(self, qos_engine):
        """Batch sheds at 50% of max_queue (8 of 16) with a Retry-After
        scaled by the backlog it would sit behind; the engine's
        qos_retry_after math matches what the header carries."""
        eng, srv = qos_engine
        occupier = eng.submit(
            eng.tokenizer.encode("hold the slot"),
            mk_params(max_tokens=1600),
            priority="interactive",
        )
        queued = []
        try:
            await_cond(
                lambda: counter("kubeai_engine_active_slots") >= 1,
                msg="occupier admitted",
            )
            for _ in range(8):
                queued.append(eng.submit(
                    eng.tokenizer.encode("bulk"), mk_params(), priority="batch",
                ))
            with pytest.raises(stdqueue.Full):
                eng.submit(
                    eng.tokenizer.encode("bulk"), mk_params(), priority="batch",
                )
            # 8 queued batch ahead, 1 slot: 1 + 8//1 = 9 seconds.
            assert eng.qos_retry_after("batch") == 9
            # Interactive skips the batch backlog entirely.
            assert eng.qos_retry_after("interactive") == 1
            body = json.dumps(dict(BATCH_BODY, stream=False)).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/completions", data=body,
                headers={"Content-Type": "application/json", "X-Priority": "batch"},
            )
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=10)
            assert exc.value.code == 429
            assert exc.value.headers.get("Retry-After") == "9"
        finally:
            for r in queued:
                r.cancelled.set()
            occupier.cancelled.set()
            await_cond(
                lambda: counter("kubeai_engine_active_slots") == 0
                and eng.queue_depth() == 0,
                msg="engine drained",
            )

    def test_engine_serves_debug_qos(self, qos_engine):
        eng, srv = qos_engine
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/qos", timeout=10
        ) as r:
            doc = json.load(r)
        assert doc["classes"] == list(CLASSES)
        assert set(doc["queue"]["per_class"]) == set(CLASSES)
        assert doc["queue"]["maxsize"] == 16


# ---------------------------------------------------------------------------
# Proxy + engine e2e: resolution at the boundary, preempt-resume replay


@pytest.fixture(scope="module")
def qos_stack(qos_engine):
    from kubeai_tpu.api import model_types as mt
    from kubeai_tpu.api.core_types import KIND_POD
    from kubeai_tpu.api.model_types import Model, ModelSpec
    from kubeai_tpu.config.system import System
    from kubeai_tpu.controller.controller import ModelReconciler
    from kubeai_tpu.loadbalancer.balancer import LoadBalancer
    from kubeai_tpu.proxy.handler import ModelProxy
    from kubeai_tpu.proxy.modelclient import ModelClient
    from kubeai_tpu.proxy.server import OpenAIServer
    from kubeai_tpu.runtime.store import ObjectMeta, Store

    eng, srv = qos_engine
    store = Store()
    system = System().default_and_validate()
    system.allow_pod_address_override = True
    rec = ModelReconciler(store, system)
    rec.start()
    lb = LoadBalancer(store, allow_pod_address_override=True)
    lb.start()
    mc = ModelClient(store)
    proxy = ModelProxy(mc, lb, max_retries=2, await_timeout=10)
    api = OpenAIServer(proxy, mc, host="127.0.0.1", port=0)
    api.start()
    store.create(
        mt.KIND_MODEL,
        Model(
            meta=ObjectMeta(name="q1"),
            spec=ModelSpec(
                url="hf://qos/model", resource_profile="cpu:1",
                replicas=1, min_replicas=1,
            ),
        ),
    )
    await_cond(
        lambda: len(store.list(KIND_POD, selector={mt.LABEL_MODEL: "q1"})) == 1,
        msg="model pod",
    )
    [pod] = store.list(KIND_POD, selector={mt.LABEL_MODEL: "q1"})

    def forge(p):
        p.status.ready = True
        p.status.pod_ip = "127.0.0.1"
        p.meta.annotations[mt.ANNOTATION_MODEL_POD_IP] = "127.0.0.1"
        p.meta.annotations[mt.ANNOTATION_MODEL_POD_PORT] = str(srv.port)

    store.mutate(KIND_POD, pod.meta.name, forge)
    await_cond(lambda: lb.get_all_addresses("q1"), msg="endpoint")
    yield api
    api.stop()
    lb.stop()
    rec.stop()


class TestProxyE2E:
    def test_invalid_priority_is_400_at_the_proxy(self, qos_stack):
        api = qos_stack
        body = json.dumps({"model": "q1", "prompt": "x", "max_tokens": 2}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{api.port}/openai/v1/completions", data=body,
            headers={"Content-Type": "application/json", "X-Priority": "platinum"},
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 400
        assert b"invalid X-Priority" in exc.value.read()

    def test_header_beats_body_and_body_is_consumed(self, qos_stack):
        api = qos_stack
        inter_before = counter(
            "kubeai_qos_proxy_requests_total", {"class": "interactive"}
        )
        body = json.dumps({
            "model": "q1", "prompt": "x", "max_tokens": 2,
            "temperature": 0, "priority": "batch",
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{api.port}/openai/v1/completions", data=body,
            headers={
                "Content-Type": "application/json", "X-Priority": "interactive",
            },
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
            r.read()
        assert counter(
            "kubeai_qos_proxy_requests_total", {"class": "interactive"}
        ) == inter_before + 1

    def test_body_priority_field_resolves(self, qos_stack):
        api = qos_stack
        batch_before = counter(
            "kubeai_qos_proxy_requests_total", {"class": "batch"}
        )
        body = json.dumps({
            "model": "q1", "prompt": "x", "max_tokens": 2,
            "temperature": 0, "priority": "batch",
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{api.port}/openai/v1/completions", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
            r.read()
        assert counter(
            "kubeai_qos_proxy_requests_total", {"class": "batch"}
        ) == batch_before + 1

    def test_operator_serves_debug_qos(self, qos_stack):
        api = qos_stack
        with urllib.request.urlopen(
            f"http://127.0.0.1:{api.port}/debug/qos", timeout=10
        ) as r:
            doc = json.load(r)
        assert doc["classes"] == list(CLASSES)
        assert "proxy_requests" in doc

    def test_preempted_batch_stream_resumes_byte_identical(self, qos_stack, qos_engine):
        """The tentpole's proof at test scale: a long preemptible batch
        stream through the proxy is seized mid-decode by an interactive
        arrival, parked, re-dispatched with its replay cursor, and the
        client sees ONE stream identical in shape to an uncontended run
        — zero duplicated and zero dropped events — with the preemption
        span on the proxy timeline."""
        eng, srv = qos_engine
        api = qos_stack
        body = dict(BATCH_BODY)

        reference = sse_post(
            api.port, body, path="/openai/v1/completions",
            headers={"X-Priority": "batch"},
        )
        assert reference[-1] == "[DONE]" and len(reference) > 5
        assert all(fr != "preempted" for s in reference
                   if isinstance(s, tuple) for fr in [s[1]])

        pre_before = counter("kubeai_qos_preemptions_total")
        res_before = counter("kubeai_qos_resumes_total")
        rid = "qos-e2e-preempt-1"
        got: list = []
        errs: list = []

        def run_batch():
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{api.port}/openai/v1/completions",
                    data=json.dumps(body).encode(),
                    headers={
                        "Content-Type": "application/json",
                        "X-Priority": "batch", "X-Request-ID": rid,
                    },
                )
                with urllib.request.urlopen(req, timeout=120) as resp:
                    raw = resp.read()
                for block in raw.replace(b"\r\n", b"\n").split(b"\n\n"):
                    if not block.startswith(b"data: "):
                        continue
                    payload = block[6:].decode()
                    if payload == "[DONE]":
                        got.append("[DONE]")
                        continue
                    c = json.loads(payload)["choices"][0]
                    got.append((c.get("text"), c.get("finish_reason")))
            except Exception as e:
                errs.append(e)

        gen_before = counter("kubeai_engine_generated_tokens_total")
        t = threading.Thread(target=run_batch, daemon=True)
        t.start()
        # MID-decode: the slot is taken and tokens are flowing, so the
        # proxy has delivered events before the seizure (an arrival in the
        # instant after admission preempts a stream that has delivered
        # none, and the span below would carry a cursor of 0).
        await_cond(
            lambda: counter("kubeai_engine_active_slots") >= 1
            and counter("kubeai_engine_generated_tokens_total") >= gen_before + 8,
            msg="batch stream occupying the slot and decoding",
        )
        shape = sse_post(
            api.port, dict(body, prompt="quick question", max_tokens=4),
            path="/openai/v1/completions",
            headers={"X-Priority": "interactive"},
        )
        assert shape[-1] == "[DONE]"
        t.join(timeout=120)
        assert not t.is_alive(), "batch stream never completed after preemption"
        assert not errs, f"batch stream errored: {errs}"
        assert counter("kubeai_qos_preemptions_total") >= pre_before + 1
        assert counter("kubeai_qos_resumes_total") >= res_before + 1
        assert got == reference, (
            "resumed stream duplicated or dropped events vs the "
            "uncontended reference"
        )
        # The proxy timeline carries the preemption span with the cursor.
        timeline = None
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and timeline is None:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{api.port}/debug/requests?id={rid}",
                timeout=5,
            ) as resp:
                doc = json.loads(resp.read())
            for tl in doc.get("requests", []):
                if tl.get("component") == "proxy" and tl.get("request_id") == rid:
                    timeline = tl
            time.sleep(0.05)
        assert timeline is not None, "proxy timeline not recorded"
        phases = {p["name"]: p for p in timeline["phases"]}
        assert "preempted" in phases, f"no preempted span in {sorted(phases)}"
        assert phases["preempted"]["attrs"]["delivered_events"] >= 1
        assert timeline["outcome"] == "ok"


# ---------------------------------------------------------------------------
# The full e2e: batch flood vs interactive p99 with byte-correct resume.


def test_qos_drill_fast():
    from benchmarks.qos_drill import run

    summary = run(fast=True, verbose=False)
    assert summary["ok"]
    assert summary["preemption"]["preemptions"] >= 1
    assert summary["preemption"]["resumes"] >= 1
    assert summary["surfaces"]["storm_incident_id"]
