"""Tenant-attributed observability (kubeai_tpu/obs/tenants.py): hashed
identity, the bounded top-K accountant (eviction into __other__ with
conservation), rolling-window shares + flood detection, canary
exclusion, the request meter's usage parsing, and the serving-path
integrations — the /debug index, /debug/tenants on both servers, the
tenant filter on /debug/requests, the include_usage terminal-path fix,
and the full drill (real proxy + engine + heavy hitter) as the tier-1
e2e."""

import json
import threading
import urllib.request

import pytest

from kubeai_tpu.obs.recorder import FlightRecorder, handle_debug_request
from kubeai_tpu.obs.tenants import (
    ANONYMOUS,
    LATENCY_BUCKETS,
    OTHER,
    M_T_REQUESTS,
    M_T_TOKENS,
    RequestMeter,
    TenantAccountant,
    default_accountant,
    extract_tenant,
    hash_tenant_key,
    sanitize_tenant,
)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def mk_accountant(**kw):
    kw.setdefault("topk", 4)
    kw.setdefault("window_seconds", 60.0)
    kw.setdefault("flood_share", 0.5)
    kw.setdefault("flood_min", 4.0)
    kw.setdefault("clock", FakeClock())
    return TenantAccountant(**kw)


# ---------------------------------------------------------------------------
# Identity


def test_hashed_id_is_stable_across_restarts():
    # Pinned literals: the hash is unsalted sha256 by contract, so the
    # SAME key maps to the SAME id in every process, forever — the
    # join key dashboards and incident timelines rely on.
    assert hash_tenant_key("abc") == "ba7816bf8f01cfea"
    assert hash_tenant_key("loadgen-a-key") == "868b853fa87d19a8"
    assert hash_tenant_key("abc") == hash_tenant_key("abc")
    assert len(hash_tenant_key("x" * 500)) == 16


def test_extract_tenant_precedence_and_fallbacks():
    # Bearer wins over X-API-Key; headers are case-insensitive.
    assert extract_tenant({"Authorization": "Bearer abc"}) == hash_tenant_key("abc")
    assert extract_tenant({"authorization": "bearer abc"}) == hash_tenant_key("abc")
    assert extract_tenant({"X-API-Key": "abc"}) == hash_tenant_key("abc")
    assert extract_tenant({"x-api-key": "abc"}) == hash_tenant_key("abc")
    assert (
        extract_tenant({"Authorization": "Bearer tok", "X-API-Key": "other"})
        == hash_tenant_key("tok")
    )
    # Non-bearer auth schemes fall through to the API key, then anonymous.
    assert (
        extract_tenant({"Authorization": "Basic dXNlcg==", "X-API-Key": "k"})
        == hash_tenant_key("k")
    )
    assert extract_tenant({"Authorization": "Basic dXNlcg=="}) == ANONYMOUS
    assert extract_tenant({}) == ANONYMOUS
    assert extract_tenant({"Authorization": "Bearer   "}) == ANONYMOUS
    # The raw key never appears in the derived id.
    assert "secret" not in extract_tenant({"X-API-Key": "secret"})


def test_sanitize_tenant():
    assert sanitize_tenant("abc-DEF_1.2") == "abc-DEF_1.2"
    assert sanitize_tenant('evil"\nvalue{}') == "evilvalue"
    assert len(sanitize_tenant("x" * 200)) == 64


# ---------------------------------------------------------------------------
# Accountant: sketch, eviction, conservation


def test_topk_eviction_folds_into_other_and_conserves_sums():
    a = mk_accountant(topk=2)
    a.record_request("t1", "ok", 0.1, prompt_tokens=10, completion_tokens=5)
    a.record_request("t1", "ok", 0.1, prompt_tokens=10, completion_tokens=5)
    a.record_request("t2", "error", 0.2, prompt_tokens=7, completion_tokens=0)
    before = a.totals()
    # Capacity is 2 identified tenants; t3 evicts the min-weight (t2).
    a.record_request("t3", "ok", 0.1, prompt_tokens=3, completion_tokens=1)
    after = a.totals()
    assert after["prompt_tokens"] == before["prompt_tokens"] + 3
    assert after["completion_tokens"] == before["completion_tokens"] + 1
    rep = a.report()
    rows = {r["tenant"]: r for r in rep["tenants"]}
    assert "t2" not in rows
    assert rows[OTHER]["tokens"]["prompt"] == 7
    assert rows[OTHER]["outcomes"] == {"error": 1}
    assert rep["evictions"] == 1
    # The metric series moved too: t2's labeled series is gone, its
    # value landed on __other__.
    assert M_T_REQUESTS.value({"tenant": "t2", "outcome": "error"}) == 0.0
    assert M_T_REQUESTS.value({"tenant": OTHER, "outcome": "error"}) >= 1.0
    assert M_T_TOKENS.value({"tenant": OTHER, "kind": "prompt"}) >= 7.0
    # Space-saving: the newcomer inherits the victim's weight, so a
    # persistent heavy hitter (t1, weight 2) is never the next victim.
    a.record_request("t4", "ok", 0.1)
    rows = {r["tenant"]: r for r in a.report()["tenants"]}
    assert "t1" in rows, "heavy hitter evicted before lighter newcomers"


def test_eviction_fold_does_not_inflate_other_window_share():
    """A victim's LIFETIME counts folding into __other__ must not read
    as __other__ *window* traffic — that would dilute every real
    tenant's share exactly during long-tail key churn and mask a
    genuine flood."""
    clock = FakeClock()
    a = mk_accountant(topk=3, window_seconds=60.0, clock=clock)
    # Tenant v accumulates a large lifetime OUTSIDE the current window.
    for _ in range(1000):
        a.record_request("v", "ok", 0.1, prompt_tokens=1)
    clock.advance(120)
    a.tick()  # snapshot AFTER v's burst: the eventual window baseline
    clock.advance(30)
    # Fresh window traffic: a real hitter plus key churn — n2 evicts
    # the min-weight tenant n1 (v at weight 1000 and hitter at 9 are
    # safe) and n1's LIFETIME folds into __other__.
    for _ in range(9):
        a.record_request("hitter", "ok", 0.1)
    a.record_request("n1", "ok", 0.1)  # fills the third slot
    a.record_request("n2", "ok", 0.1)  # evicts n1 -> fold
    # Advance far enough that the post-burst snapshot STARTS the window
    # (the construction-time seed gets pruned), while the fresh traffic
    # stays inside it.
    clock.advance(35)
    a.tick()
    st = a._window_state
    total = sum(s["window_requests"] for s in st.values())
    # 9 (hitter) + 1 (n2); n1's single in-window request is dropped by
    # the fold's baseline shift (documented undercount) — crucially,
    # neither v's 1000 out-of-window history nor n1's lifetime shows
    # up as __other__ window traffic.
    assert total == 10, st
    assert st["hitter"]["share"] == pytest.approx(0.9)
    assert st["v"]["window_requests"] == 0
    assert st[OTHER]["window_requests"] == 0


def test_observe_usage_total_only_shape():
    a = mk_accountant()
    m = RequestMeter("t", accountant=a)
    # Prompt-heavy usage without completion_tokens: completion must be
    # total - prompt, not total.
    m.observe_usage({"prompt_tokens": 900, "total_tokens": 1000})
    assert (m.prompt_tokens, m.completion_tokens) == (900, 100)
    m2 = RequestMeter("t", accountant=a)
    m2.observe_usage({"prompt_tokens": 7, "total_tokens": 7})  # embeddings
    assert (m2.prompt_tokens, m2.completion_tokens) == (7, 0)
    # Malformed (total < prompt): clamp at 0 — a negative completion
    # count would DECREMENT the token counter.
    m3 = RequestMeter("t", accountant=a)
    m3.observe_usage({"prompt_tokens": 100, "total_tokens": 0})
    assert (m3.prompt_tokens, m3.completion_tokens) == (100, 0)


def test_anonymous_rides_free_and_is_never_evicted():
    a = mk_accountant(topk=1)
    a.record_request(ANONYMOUS, "ok", 0.1)
    a.record_request("t1", "ok", 0.1)
    a.record_request("t2", "ok", 0.1)  # evicts t1, never anonymous
    rows = {r["tenant"]: r for r in a.report()["tenants"]}
    assert ANONYMOUS in rows and "t2" in rows and "t1" not in rows
    # Empty/garbage tenant ids collapse to anonymous, not new series.
    a.record_request("", "ok", 0.1)
    rows = {r["tenant"]: r for r in a.report()["tenants"]}
    assert rows[ANONYMOUS]["requests"]["total"] == 2


def test_concurrent_accounting_conserves_token_totals():
    """8 threads hammer the accountant (more tenants than top-K slots,
    so folds race with records); every token must land exactly once,
    in a tracked row or in __other__."""
    a = mk_accountant(topk=3)
    n_threads, per_thread = 8, 200
    barrier = threading.Barrier(n_threads)

    def work(k):
        barrier.wait()
        for i in range(per_thread):
            a.record_request(
                f"tenant-{(k * 7 + i) % 11}", "ok", 0.05,
                prompt_tokens=3, completion_tokens=2,
            )
            a.record_cost(f"tenant-{(k * 3 + i) % 11}", 0.5, 1.5)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    totals = a.totals()
    assert totals["requests"] == total
    assert totals["prompt_tokens"] == 3 * total
    assert totals["completion_tokens"] == 2 * total
    assert abs(totals["slot_seconds"] - 0.5 * total) < 1e-6
    assert abs(totals["kv_page_seconds"] - 1.5 * total) < 1e-6
    # The exported counter series conserve the same sum across folds
    # (tracked rows + whatever landed on __other__).
    req_sum = sum(
        v for key, v in M_T_REQUESTS.snapshot().items()
        if dict(key).get("tenant", "").startswith("tenant-")
        or dict(key).get("tenant") == OTHER
    )
    assert req_sum >= total  # >= : the process-global registry is shared


# ---------------------------------------------------------------------------
# Rolling window, shares, flood


class _AlwaysLeader:
    def __init__(self):
        self.is_leader = threading.Event()
        self.is_leader.set()


def test_window_shares_and_flood_trigger(tmp_path):
    from kubeai_tpu.obs.incidents import (
        IncidentRecorder,
        install_recorder,
        uninstall_recorder,
    )

    clock = FakeClock()
    a = mk_accountant(topk=8, window_seconds=30.0, flood_min=5.0, clock=clock)
    rec = IncidentRecorder(
        sources={}, incident_dir=str(tmp_path), election=_AlwaysLeader(),
        debounce_seconds=1.0, clock=clock,
    )
    install_recorder(rec)
    try:
        for _ in range(3):
            a.record_request("small", "ok", 0.1)
        clock.advance(5)
        a.tick()
        st = a._window_state
        assert st["small"]["share"] == 1.0
        assert st["small"]["window_requests"] == 3
        # Below the floor (3 < 5): no flood even at share 1.0.
        assert not [
            i for i in rec.snapshot() if i["trigger"] == "tenant_flood"
        ]
        # The hitter arrives: 9 of 12 window requests.
        for _ in range(9):
            a.record_request("hog", "ok", 0.1)
        clock.advance(5)
        a.tick()
        rec.wait_idle()
        floods = [i for i in rec.snapshot() if i["trigger"] == "tenant_flood"]
        assert floods, "flood not detected"
        assert floods[0]["detail"]["tenant"] == "hog"
        assert floods[0]["detail"]["share"] == 0.75
        rep = a.report()
        assert rep["flood"]["last"]["tenant"] == "hog"
        # The window slides: once the burst ages out, share decays.
        clock.advance(31)
        a.tick()
        assert a._window_state["hog"]["window_requests"] == 0
    finally:
        uninstall_recorder(rec)
        rec.stop()


def test_flood_never_fires_for_the_other_bucket(tmp_path):
    from kubeai_tpu.obs.incidents import (
        IncidentRecorder,
        install_recorder,
        uninstall_recorder,
    )

    clock = FakeClock()
    # topk=1: the long tail all folds into __other__, which dominates
    # the window — but a mixture of small tenants is not one hitter.
    a = mk_accountant(topk=1, flood_min=2.0, clock=clock)
    rec = IncidentRecorder(
        sources={}, incident_dir=str(tmp_path), election=_AlwaysLeader(),
        clock=clock,
    )
    install_recorder(rec)
    try:
        for i in range(20):
            a.record_request(f"tail-{i}", "ok", 0.1)
        clock.advance(2)
        a.tick()
        rec.wait_idle()
        floods = [i for i in rec.snapshot() if i["trigger"] == "tenant_flood"]
        # The only possible crossing is the last-tracked tail tenant or
        # __other__; __other__ must never be named a flood.
        assert all(f["detail"]["tenant"] != OTHER for f in floods)
        # anonymous is equally a mixture (every unauthenticated
        # client): a window it dominates is not one hitter either.
        for _ in range(50):
            a.record_request(ANONYMOUS, "ok", 0.1)
        clock.advance(2)
        a.tick()
        rec.wait_idle()
        assert all(
            f["detail"].get("tenant") != ANONYMOUS
            for f in rec.snapshot()
            if f["trigger"] == "tenant_flood"
        )
    finally:
        uninstall_recorder(rec)
        rec.stop()


def test_window_p95_and_attainment_buckets():
    clock = FakeClock()
    a = mk_accountant(clock=clock)
    a.ttft_threshold_s = 2.0
    # 9 fast + 1 slow: p95 lands in the slow bucket, attainment 0.9.
    for _ in range(9):
        a.record_request("t", "ok", 0.3, ttft_s=0.2)
    a.record_request("t", "ok", 40.0, ttft_s=35.0)
    clock.advance(5)
    a.tick()
    st = a._window_state["t"]
    assert st["e2e_p95_s"] == 60.0  # bucket upper bound covering 40s
    assert st["ttft_attainment"] == pytest.approx(0.9)
    assert st["e2e_attainment"] == pytest.approx(0.9)
    assert 2.0 in LATENCY_BUCKETS and 30.0 in LATENCY_BUCKETS


def test_canary_requests_are_excluded():
    a = mk_accountant()
    m = RequestMeter("t1", canary=True, accountant=a)
    m.observe_usage({"prompt_tokens": 10, "completion_tokens": 5})
    m.finish("ok")
    assert a.totals()["requests"] == 0
    assert a.report()["canary_excluded"] == 1


# ---------------------------------------------------------------------------
# RequestMeter: usage parsing, stripping, idempotence


def test_meter_observes_and_strips_injected_usage_chunk():
    a = mk_accountant()
    m = RequestMeter("t1", accountant=a)
    m.strip_usage = True
    token_ev = b'data: {"choices": [{"text": "hi", "finish_reason": null}]}\n\n'
    usage_ev = (
        b'data: {"choices": [], "usage": {"prompt_tokens": 12, '
        b'"completion_tokens": 4, "total_tokens": 16}}\n\n'
    )
    assert m.observe_event(token_ev) is False
    assert m.observe_event(b"data: [DONE]\n\n") is False
    assert m.observe_event(usage_ev) is True  # strip: injected
    assert (m.prompt_tokens, m.completion_tokens) == (12, 4)
    # Client-requested usage (no injection): observed but NOT stripped.
    m2 = RequestMeter("t1", accountant=a)
    assert m2.observe_event(usage_ev) is False
    assert m2.usage_seen
    # Generated text containing the word "usage" must not confuse it.
    m3 = RequestMeter("t1", accountant=a)
    m3.strip_usage = True
    tricky = b'data: {"choices": [{"text": "\\"usage\\"", "finish_reason": null}]}\n\n'
    assert m3.observe_event(tricky) is False
    assert not m3.usage_seen


def test_meter_parses_buffered_json_body_and_finishes_once():
    a = mk_accountant()
    m = RequestMeter("t1", accountant=a)
    body = json.dumps({
        "choices": [{"text": "hello"}],
        "usage": {"prompt_tokens": 6, "completion_tokens": 4, "total_tokens": 10},
    }).encode()
    m.feed(body[:10])
    m.feed(body[10:])
    m.first_byte()
    m.parse_body()
    m.finish("ok")
    m.finish("error")  # idempotent: first outcome wins
    rows = {r["tenant"]: r for r in a.report()["tenants"]}
    assert rows["t1"]["tokens"] == {
        "prompt": 6, "completion": 4, "window_prompt": 0, "window_completion": 0,
    }
    assert rows["t1"]["outcomes"] == {"ok": 1}


def test_sse_flush_tail_delivers_unterminated_final_event():
    """The passthrough SSE path flushes a clean-EOF trailing remainder
    (a third-party engine's final event may lack the terminating blank
    line); the replay path keeps the strict discard (default)."""
    from kubeai_tpu.proxy.recovery import sse_events

    chunks = [b"data: a\n\n", b"data: [DONE]\n", b""]

    def reader_for(items):
        it = iter(items)
        return lambda: next(it)

    strict = list(sse_events(reader_for(chunks)))
    assert strict == [b"data: a\n\n"]
    flushed = list(sse_events(reader_for(chunks), flush_tail=True))
    assert flushed == [b"data: a\n\n", b"data: [DONE]\n"]


def test_meter_feed_drops_buffer_past_cap():
    import kubeai_tpu.obs.tenants as T

    a = mk_accountant()
    m = RequestMeter("t", accountant=a)
    big = b"x" * (T.BODY_PARSE_CAP // 2 + 1)
    m.feed(big)
    m.feed(big)  # crosses the cap: buffered bytes are released
    assert m._buf == []
    m.parse_body()  # over-cap: no parse, no crash
    assert not m.usage_seen


def test_reset_drops_state_and_series():
    a = mk_accountant()
    a.record_request("zz-reset-probe", "ok", 0.1, prompt_tokens=5)
    assert M_T_REQUESTS.value({"tenant": "zz-reset-probe", "outcome": "ok"}) == 1.0
    a.reset()
    assert a.totals()["requests"] == 0
    assert M_T_REQUESTS.value({"tenant": "zz-reset-probe", "outcome": "ok"}) == 0.0
    # Post-reset recording works and the window baseline is re-seeded:
    # the very first tick must see the new traffic.
    a.record_request("zz-reset-probe", "ok", 0.1)
    a._clock.advance(1)
    a.tick()
    assert a._window_state["zz-reset-probe"]["window_requests"] == 1


# ---------------------------------------------------------------------------
# /debug surfaces (unit level)


def test_debug_requests_tenant_filter():
    rec = FlightRecorder()
    rec.record_timeline({"request_id": "r1", "attrs": {"tenant": "t-a"}, "component": "proxy"})
    rec.record_timeline({"request_id": "r2", "attrs": {"tenant": "t-b"}, "component": "proxy"})
    rec.record_timeline({"request_id": "r3", "attrs": {}, "component": "proxy"})
    code, _, body = handle_debug_request(
        "/debug/requests", "tenant=t-a", recorder=rec
    )
    assert code == 200
    reqs = json.loads(body)["requests"]
    assert [r["request_id"] for r in reqs] == ["r1"]


def test_debug_index_lists_server_specific_endpoints():
    from kubeai_tpu.obs.recorder import debug_index_response

    _, _, body = debug_index_response("operator")
    op = {e["path"] for e in json.loads(body)["endpoints"]}
    assert "/debug/tenants" in op and "/debug/slo" in op
    assert "/debug/pipeline" not in op
    _, _, body = debug_index_response("engine")
    en = {e["path"] for e in json.loads(body)["endpoints"]}
    assert "/debug/pipeline" in en and "/debug/tenants" in en
    assert "/debug/slo" not in en
    for e in json.loads(body)["endpoints"]:
        assert e["description"].strip()


# ---------------------------------------------------------------------------
# Engine server integration: /debug routes + include_usage terminal path


@pytest.fixture(scope="module")
def engine_server():
    from kubeai_tpu.engine.core import EngineConfig, build_test_engine
    from kubeai_tpu.engine.server import EngineServer

    eng = build_test_engine(
        engine_config=EngineConfig(
            # Room for a stream that no machine finishes inside the deadline
            # test's 0.4 s (400 tokens took 0.36-0.40 s on an idle sandbox).
            max_slots=2, max_seq_len=2048, prefill_buckets=(16, 32),
            max_queue=8, decode_chunk=2,
        )
    )
    srv = EngineServer(eng, "tenants-m1", host="127.0.0.1", port=0)
    srv.start()
    # Warm the compile cache so deadline timing below is about decode.
    from kubeai_tpu.engine.sampling import SamplingParams

    eng.generate(
        eng.tokenizer.encode("warm"),
        SamplingParams(temperature=0.0, max_tokens=4), timeout=180,
    )
    yield srv
    srv.stop()


def _engine_post(srv, body, headers=None, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _events(raw: bytes):
    return [
        json.loads(b[6:])
        for b in raw.split(b"\n\n")
        if b.startswith(b"data: ") and b[6:].strip() != b"[DONE]"
    ]


def test_engine_debug_index_and_tenants_route(engine_server):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{engine_server.port}/debug", timeout=10
    ) as r:
        doc = json.load(r)
    assert doc["server"] == "engine"
    assert any(e["path"] == "/debug/tenants" for e in doc["endpoints"])
    with urllib.request.urlopen(
        f"http://127.0.0.1:{engine_server.port}/debug/tenants", timeout=10
    ) as r:
        view = json.load(r)
    assert "tenants" in view and "topk" in view


def test_engine_cost_attribution_via_tenant_header(engine_server):
    default_accountant.reset()
    with _engine_post(
        engine_server,
        {"model": "tenants-m1", "prompt": "count", "max_tokens": 4, "temperature": 0},
        headers={"X-KubeAI-Tenant": "cost-tenant"},
    ) as r:
        body = json.load(r)
    assert body["usage"]["completion_tokens"] == 4
    rows = {r_["tenant"]: r_ for r_ in default_accountant.report()["tenants"]}
    assert "cost-tenant" in rows
    cost = rows["cost-tenant"]["cost"]
    assert cost["slot_seconds"] > 0
    assert cost["kv_page_seconds"] >= cost["slot_seconds"]  # >= 1 page held
    # Un-attributed requests record no cost.
    before = default_accountant.totals()["slot_seconds"]
    with _engine_post(
        engine_server,
        {"model": "tenants-m1", "prompt": "count", "max_tokens": 2, "temperature": 0},
    ) as r:
        r.read()
    assert default_accountant.totals()["slot_seconds"] == before


def test_stream_deadline_abort_still_delivers_usage(engine_server):
    """Satellite: include_usage must arrive on EVERY terminal path —
    this stream is deadline-aborted mid-decode (the scheduler sweep
    frees the slot and emits an error event), and the usage chunk must
    still precede the error."""
    with _engine_post(
        engine_server,
        {
            "model": "tenants-m1", "prompt": "count forever", "stream": True,
            "max_tokens": 1900, "temperature": 0,
            "stream_options": {"include_usage": True},
        },
        headers={"X-Request-Deadline": "0.4"},
        timeout=60,
    ) as r:
        raw = r.read()
    evs = _events(raw)
    errors = [e for e in evs if "error" in e]
    usages = [e for e in evs if isinstance(e.get("usage"), dict) and not e.get("choices")]
    assert errors, f"stream was not deadline-aborted: {evs[-2:]}"
    assert "deadline" in errors[0]["error"]["message"]
    assert usages, "deadline-aborted stream delivered no usage block"
    u = usages[0]["usage"]
    assert u["prompt_tokens"] > 0
    # Best-effort: the tokens emitted before the abort are accounted.
    n_tokens = sum(1 for e in evs if e.get("choices") and e["choices"][0].get("text"))
    assert u["completion_tokens"] >= max(n_tokens - 1, 0)
    assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]


def test_stream_ok_path_usage_unchanged(engine_server):
    with _engine_post(
        engine_server,
        {
            "model": "tenants-m1", "prompt": "short", "stream": True,
            "max_tokens": 3, "temperature": 0,
            "stream_options": {"include_usage": True},
        },
    ) as r:
        raw = r.read()
    evs = _events(raw)
    usages = [e for e in evs if isinstance(e.get("usage"), dict)]
    assert len(usages) == 1
    assert usages[0]["choices"] == []
    assert usages[0]["usage"]["completion_tokens"] == 3


# ---------------------------------------------------------------------------
# The full e2e: real proxy + engine + weighted mix + heavy hitter.


def test_tenant_drill_fast():
    from benchmarks.tenant_drill import run

    summary = run(fast=True, verbose=False)
    assert summary["ok"]
    assert summary["conservation"]["completion_tokens"] > 0
    assert summary["flood"]["incident_id"]
    assert summary["canary_excluded"]
