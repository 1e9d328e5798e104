"""A request's first token, measured from inside (ISSUE 53): `receive` on
the serving thread, the queue wait by what it waited for, the delivery from
hand-over to bytes written, the collector's runs, and one clock with a
profiler trace. Everything here runs the tiny test engine on the CPU: what
is held is WHERE a second is filed and that the parts add up, never how
long anything takes."""

import gc
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from kubeai_tpu.engine.core import EngineConfig, EventQueue, build_test_engine
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.metrics import default_registry
from kubeai_tpu.obs import default_recorder
from kubeai_tpu.obs import perf as perf_obs
from kubeai_tpu.obs.perf import PipelineStallTracker, default_profiler, handle_perf_request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench"))
from readers import prefill_start_lag  # noqa: E402

CAUSES = ("turn", "slots", "pages")
GREEDY = dict(temperature=0.0)


def _by_cause():
    m = default_registry.get("kubeai_engine_queue_wait_by_cause_seconds_total")
    return {c: m.value({"cause": c}) for c in CAUSES}


def _hist(name):
    """(sum, count) over every series of a histogram."""
    snap = default_registry.get(name).snapshot()
    return sum(v[1] for v in snap.values()), sum(v[2] for v in snap.values())


def _counter(name, **labels):
    return default_registry.get(name).value(labels)


def _drain(req, timeout=60):
    """A request's events up to its terminal one."""
    events = []
    while not events or events[-1][0] not in ("done", "error"):
        events += req.out.get_many(timeout=timeout)
    return events


def _timeline(req, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for tl in default_recorder.snapshot():
            if tl["span_id"] == req.trace.ctx.span_id:
                return tl
        time.sleep(0.02)
    raise AssertionError("no timeline for the request")


def _phase(tl, name):
    return next(p for p in tl["phases"] if p["name"] == name)


def _queue_ms(tl):
    """(the queue phase's ms, its attrs' three parts)."""
    q = _phase(tl, "queue")
    return q["duration_ms"], tuple(q["attrs"][f"{c}_ms"] for c in CAUSES)


def _engine(**cfg):
    eng = build_test_engine(EngineConfig(**{"max_seq_len": 128, "prefill_buckets": (16, 32, 64), "decode_chunk": 4, **cfg}))
    eng.start()
    return eng


def _submit(eng, n_prompt=8, max_tokens=8, **kw):
    return eng.submit(list(range(1, n_prompt + 1)), SamplingParams(max_tokens=max_tokens, **GREEDY), **kw)


# -- the queue wait, by what it was for ------------------------------------


def test_a_lone_requests_wait_is_all_turn():
    eng = _engine(max_slots=2)
    try:
        _drain(_submit(eng))  # compiles
        req = _submit(eng)
        _drain(req)
        total, (turn, slots, pages) = _queue_ms(_timeline(req))
        assert slots == 0 and pages == 0
        assert turn == pytest.approx(total, abs=0.002) and turn > 0
    finally:
        eng.stop()


def test_a_request_behind_a_busy_slot_waits_for_slots():
    """One slot. While request 0 holds it, `first` and `second` arrive: the
    slot goes to `first` when 0 ends, and `second` waits through the whole
    of `first` in rounds that ended with every slot busy."""
    eng = _engine(max_slots=1)
    try:
        _drain(_submit(eng))  # compiles
        holder = _submit(eng, max_tokens=24)
        holder.out.get(timeout=60)  # it holds the slot
        first, second = _submit(eng, max_tokens=24), _submit(eng, max_tokens=8)
        for r in (holder, first, second):
            _drain(r)
        tl_first, tl_second = _timeline(first), _timeline(second)
        total, (turn, slots, pages) = _queue_ms(tl_second)
        assert pages == 0
        assert slots >= _phase(tl_first, "decode")["duration_ms"] > 0
        assert turn + slots == pytest.approx(total, abs=0.005)
        # ... and most of what `first` waited was for the slot too.
        assert _queue_ms(tl_first)[1][1] > 0
    finally:
        eng.stop()


def test_a_request_the_pool_cannot_back_yet_waits_for_pages():
    """Two slots, a pool that backs one such prompt with its budget: the
    second request finds a free slot in every round and is deferred on the
    pool until the first one's pages come back."""
    eng = _engine(max_slots=2, page_size=16, num_pages=11, prefix_cache_min=0)
    try:
        _drain(_submit(eng, n_prompt=40, max_tokens=60))  # compiles; 7 of the 10 pages
        a = _submit(eng, n_prompt=40, max_tokens=60)
        a.out.get(timeout=60)  # it holds its pages
        b = eng.submit(list(range(50, 90)), SamplingParams(max_tokens=60, **GREEDY))
        for r in (a, b):
            assert _drain(r)[-1][0] == "done"
        total, (turn, slots, pages) = _queue_ms(_timeline(b))
        assert slots == 0 and pages > 0
        assert turn + pages == pytest.approx(total, abs=0.005)
    finally:
        eng.stop()


def test_the_three_series_divide_the_histograms_sum():
    """A mixed run: more requests than slots, of two sizes, one cancelled
    while queued. Over it the three series add up to the histogram's sum,
    and every request's three parts to its own `queue` phase."""
    eng = _engine(max_slots=2, page_size=16, num_pages=13, prefix_cache_min=0)
    try:
        _drain(_submit(eng, n_prompt=40, max_tokens=4))  # compiles the 64 bucket
        _drain(_submit(eng, n_prompt=8, max_tokens=4))  # and the 16 bucket
        time.sleep(0.1)  # their accounting lies behind
        before, (sum0, n0) = _by_cause(), _hist("kubeai_engine_queue_wait_seconds")
        reqs = [_submit(eng, n_prompt=40 if i % 3 == 0 else 8, max_tokens=40 if i % 3 == 0 else 12) for i in range(9)]
        reqs[-1].cancelled.set()  # never reaches a slot: divided up to its end
        for r in reqs[:-1]:
            assert _drain(r)[-1][0] == "done"
        timelines = [_timeline(r) for r in reqs]
        after, (sum1, n1) = _by_cause(), _hist("kubeai_engine_queue_wait_seconds")
        assert n1 - n0 == len(reqs)
        moved = {c: after[c] - before[c] for c in CAUSES}
        assert sum(moved.values()) == pytest.approx(sum1 - sum0, rel=1e-6)
        assert moved["slots"] > 0 and all(v >= 0 for v in moved.values())
        for tl in timelines:
            total, parts = _queue_ms(tl)
            assert sum(parts) == pytest.approx(total, abs=0.005) and min(parts) >= 0
        assert [p["name"] for p in timelines[-1]["phases"]] == ["queue"] and timelines[-1]["outcome"] == "cancelled"
    finally:
        eng.stop()


def test_rounds_are_looked_back_over_and_forgotten_in_bulk():
    """`_queue_parts` on hand-made rounds: the wait between the first round
    after the arrival and the last before the dispatch goes by what each
    round stopped at, the rest is `turn`; the list is cut in bulk."""
    eng = build_test_engine(EngineConfig(max_slots=1, max_seq_len=64, prefill_buckets=(16,)))
    clock = iter([10.0, 11.0, 13.0, 16.0, 20.0])
    stops = ["slots", "pages", "slots", "empty"]
    real = time.monotonic
    try:
        time.monotonic = lambda: next(clock)
        for stopped in stops + ["empty"]:
            eng._stamp_round()
            eng._round_stopped = stopped
    finally:
        time.monotonic = real
    assert [r[0] for r in eng._rounds] == [10.0, 11.0, 13.0, 16.0, 20.0]
    # Arrived at 10.5 (R1 = 11), dispatched at 20.5 (Rk = 20): 11->13 pages, 13->16 slots, 16->20 nothing.
    turn, slots, pages = eng._queue_parts(10.5, 20.5)
    assert (slots, pages) == (3.0, 2.0) and turn == pytest.approx(0.5 + 4.0 + 0.5)
    # Admitted in the round it first met: all turn.
    assert eng._queue_parts(16.5, 20.25) == (pytest.approx(3.75), 0.0, 0.0)
    assert eng._queue_parts(20.5, 20.75) == (0.25, 0.0, 0.0)
    for _ in range(2 * eng.ROUNDS_KEPT):
        eng._stamp_round()
    assert eng.ROUNDS_KEPT <= len(eng._rounds) <= 2 * eng.ROUNDS_KEPT


# -- receive and deliver: the serving thread's two stages --------------------


@pytest.fixture(scope="module")
def served():
    from kubeai_tpu.engine.server import EngineServer

    eng = build_test_engine(EngineConfig(max_slots=4, max_seq_len=256, decode_chunk=4))
    seen: list = []
    submit = eng.submit

    def spying_submit(*a, **kw):
        req = submit(*a, **kw)
        seen.append(req)
        return req

    eng.submit = spying_submit
    eng.start()
    srv = EngineServer(eng, "m", host="127.0.0.1", port=0)
    srv.start()
    _post(srv.port, {"prompt": "warm up", "max_tokens": 12, "temperature": 0, "stream": True})
    yield srv, seen
    srv.stop()
    eng.stop()


def _post(port, body, path="/v1/completions"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_receive_is_observed_once_a_submitted_request(served):
    srv, seen = served
    _, n0 = _hist("kubeai_engine_receive_seconds")
    assert _post(srv.port, {"prompt": "hello there", "max_tokens": 4, "temperature": 0})[0] == 200
    assert _hist("kubeai_engine_receive_seconds")[1] == n0 + 1
    for refused in ({"max_tokens": 4}, {"prompt": "x", "max_tokens": 0}, {"prompt": "x", "n": 99}):
        assert _post(srv.port, refused)[0] == 400
    assert _hist("kubeai_engine_receive_seconds")[1] == n0 + 1
    tl = _timeline(seen[-1])
    receive = tl["phases"][0]
    assert receive["name"] == "receive" and tl["phases"][1]["name"] == "queue"
    assert receive["attrs"]["prompt_tokens"] == len(seen[-1].prompt_ids) >= len("hello there")
    assert receive["attrs"]["body_bytes"] > 20
    assert receive["duration_ms"] >= 0
    assert receive["start_ms"] + receive["duration_ms"] == pytest.approx(tl["start_ms"], abs=0.01)


def test_delivery_is_counted_once_a_streamed_request(served):
    srv, seen = served
    first0 = _counter("kubeai_engine_deliver_writes_total", which="first")
    later0 = _counter("kubeai_engine_deliver_writes_total", which="later")
    lag0 = _counter("kubeai_engine_deliver_lag_seconds_total", which="first")
    status, raw = _post(srv.port, {"prompt": "stream me", "max_tokens": 16, "temperature": 0, "stream": True})
    assert status == 200 and raw.rstrip().endswith(b"data: [DONE]")
    req = seen[-1]
    deadline = time.monotonic() + 10
    while _counter("kubeai_engine_deliver_writes_total", which="first") == first0 and time.monotonic() < deadline:
        time.sleep(0.01)  # the serving thread files its record after its last write
    assert _counter("kubeai_engine_deliver_writes_total", which="first") == first0 + 1
    # 16 tokens in chunks of 4: the first token alone, then a hand-over a chunk.
    assert 1 <= _counter("kubeai_engine_deliver_writes_total", which="later") - later0 <= 16
    assert _counter("kubeai_engine_deliver_lag_seconds_total", which="first") >= lag0
    tl = _timeline(req)
    deadline = time.monotonic() + 10
    while "deliver_first_ms" not in _phase(tl, "decode")["attrs"] and time.monotonic() < deadline:
        time.sleep(0.01)
    attrs = _phase(tl, "decode")["attrs"]
    assert 0 <= attrs["deliver_first_ms"] <= attrs["deliver_max_ms"] < tl["duration_ms"]
    assert 0 <= attrs["deliver_mean_ms"] <= attrs["deliver_max_ms"]
    # A response that is not streamed has no write a hand-over: nothing moves.
    assert _post(srv.port, {"prompt": "whole", "max_tokens": 4, "temperature": 0})[0] == 200
    time.sleep(0.05)
    assert _counter("kubeai_engine_deliver_writes_total", which="first") == first0 + 1


def test_the_oldest_hand_overs_stamp_goes_to_the_reader():
    q = EventQueue()
    q.put_many(["a"], 1.0)
    q.put_many(["b", "c"], 2.0)
    assert q.get_many() == ["a", "b", "c"] and q.taken_at == 1.0 and q.handed_at is None
    q.put("error")  # a single event comes with no stamp
    assert q.get_many() == ["error"] and q.taken_at is None
    q.put_many(["d"], 3.0)
    assert q.get_many() == ["d"] and q.taken_at == 3.0


def test_a_wakes_stamp_is_its_first_events_under_contention():
    """Writers hand over events that ARE their stamps, readers take what is
    there: whatever the interleaving, `taken_at` is the stamp of the first
    event taken (the oldest hand-over not yet read), and nothing is lost."""
    n_pairs, n_puts = 6, 400
    queues = [EventQueue() for _ in range(n_pairs)]
    bad: list = []
    taken = [0] * n_pairs

    def write(q):
        for i in range(n_puts):
            stamp = float(i + 1)
            q.put_many([stamp, stamp + 0.25, stamp + 0.5], stamp)

    def read(n):
        while taken[n] < 3 * n_puts:
            evs = queues[n].get_many(timeout=20)
            if queues[n].taken_at != evs[0] or evs != sorted(evs):
                bad.append((queues[n].taken_at, evs[:4]))
            taken[n] += len(evs)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write, args=(q,)) for q in queues]
        threads += [threading.Thread(target=read, args=(n,)) for n in range(n_pairs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not bad and taken == [3 * n_puts] * n_pairs


# -- the collector ------------------------------------------------------------


def test_a_collection_is_on_the_counter_and_on_the_step_it_fell_in():
    perf_obs.gc_watch.install()
    try:
        created = time.monotonic()
        tracker = PipelineStallTracker()
        with tracker.segment("emit"):
            gc.collect()
        step = tracker.end_step("decode_chunk")
        # The forced collection, and whatever the interpreter collected of
        # its own accord since the tracker began to look.
        assert 0 < step["gc_ms"] <= (time.monotonic() - created) * 1000
        assert "gc_ms" not in tracker.end_step("decode_chunk")  # the next step had none
    finally:
        perf_obs.gc_watch.remove()
    (slow,) = tracker.slowest_steps()
    assert slow["gc_ms"] > 0 and "gc_ms" not in slow["ms"] and slow["total_ms"] == pytest.approx(slow["ms"]["emit"], abs=0.01)

    eng = _engine(max_slots=2)
    try:
        _drain(_submit(eng))  # compiles
        gen2 = _counter("kubeai_engine_gc_seconds_total", generation="2")
        req = _submit(eng, max_tokens=100)
        req.out.get(timeout=60)
        default_recorder.clear()
        gc.collect()
        _drain(req)
        deadline = time.monotonic() + 5
        while _counter("kubeai_engine_gc_seconds_total", generation="2") == gen2 and time.monotonic() < deadline:
            time.sleep(0.01)  # the loop moves it once an iteration
        assert _counter("kubeai_engine_gc_seconds_total", generation="2") > gen2
        assert any(s.get("gc_ms", 0) > 0 for s in default_recorder.engine_steps() if s["kind"] == "decode_chunk")
    finally:
        eng.stop()
    assert perf_obs.gc_watch._on_gc not in gc.callbacks or perf_obs.gc_watch._users > 0


# -- a profiler's trace: one clock, and the events of a request ----------------


def _host_events(xplane):
    from jax.profiler import ProfileData

    return [
        (ln.name, ev.name, dict(ev.stats), ev.start_ns, ev.duration_ns)
        for plane in ProfileData.from_file(xplane).planes if plane.name.startswith("/host:")
        for ln in plane.lines for ev in ln.events
    ]


def test_a_capture_holds_a_requests_events_and_its_own_ends_on_this_hosts_clock(served, monkeypatch, tmp_path):
    srv, seen = served
    monkeypatch.setenv("KUBEAI_DEBUG_PROFILE", "1")
    monkeypatch.setattr(default_profiler, "root", str(tmp_path))
    box = {}
    captured, release = threading.Event(), threading.Event()

    def capture():
        box["reply"] = handle_perf_request("/debug/profile", "seconds=0.2&python_tracer=0", engine=None)
        captured.set()
        release.wait()  # a thread that ended would lend its id, and its line's name, to a later one

    from jax.profiler import TraceAnnotation

    for _ in range(3):  # a loaded machine may not serve the request inside 0.2 s
        captured.clear()
        threading.Thread(target=capture, daemon=True).start()
        deadline = time.monotonic() + 5
        while not TraceAnnotation.is_enabled() and time.monotonic() < deadline:
            time.sleep(0.001)
        sent = time.monotonic()
        status, _ = _post(srv.port, {"prompt": "in the window", "max_tokens": 6, "temperature": 0, "stream": True})
        assert captured.wait(timeout=60)
        code, _, body = box["reply"]
        assert status == 200 and code == 200
        doc = json.loads(body)
        t0, t1 = doc["window_monotonic"]
        assert abs((t1 - t0) - 0.2) < 0.05
        rid = seen[-1].trace.rid
        events = [e for e in _host_events(doc["xplane"]) if e[2].get("rid") == rid]
        if {"req.admit", "serve.receive", "serve.write", "sched.prefill"} <= {e[1] for e in events}:
            break
    else:
        raise AssertionError(f"three captures, none held the request's events: {sorted({e[1] for e in events})}")
    release.set()
    assert rid == "r" + seen[-1].trace.ctx.trace_id[:8]
    by_name = {e[1]: e for e in events}
    admit = by_name["req.admit"]
    assert admit[0] == "engine-loop" and by_name["sched.prefill"][0] == "engine-loop"
    assert admit[2]["waited_ms"] == pytest.approx(sum(admit[2][f"{c}_ms"] for c in CAUSES), abs=1e-6)
    assert by_name["sched.prefill"][2]["calls"] == 1 and by_name["sched.prefill"][2]["kind"] == "group"
    assert by_name["serve.write"][0] == "engine-serve" and by_name["serve.write"][2]["lag_ms"] >= 0
    assert by_name["serve.receive"][2]["prompt_tokens"] == len(seen[-1].prompt_ids)
    # One clock: the window's two stamps put this host's monotonic reads on
    # the trace's, so the request's events lie between its send and now.
    window = next(e for e in _host_events(doc["xplane"]) if e[1] == "profile.window")
    to_trace_ns = lambda mono: window[3] + (mono - t0) * 1e9  # noqa: E731
    assert abs((window[3] + window[4]) - to_trace_ns(t1)) < 5e6
    assert to_trace_ns(sent) - 1e6 <= admit[3] <= to_trace_ns(time.monotonic())


def test_no_event_costs_more_than_one_annotation(served, monkeypatch):
    """With no profiler running an event is one call through
    `_trace_annotation`: at most one per admission, receive, write and
    collection, whatever else the request does."""
    srv, seen = served
    made: dict[str, int] = {}

    class Null:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

        is_enabled = staticmethod(lambda: False)

    def counting(name, **attrs):
        made[name] = made.get(name, 0) + 1
        assert all(isinstance(v, (int, float, str)) for v in attrs.values()), (name, attrs)
        return Null()

    monkeypatch.setattr(perf_obs, "_trace_annotation", lambda: counting)
    writes0 = sum(_counter("kubeai_engine_deliver_writes_total", which=w) for w in ("first", "later"))
    collections = []
    gc.callbacks.append(lambda phase, info: collections.append(phase) if phase == "stop" else None)
    try:
        status, _ = _post(srv.port, {"prompt": "count my events", "max_tokens": 12, "temperature": 0, "stream": True})
        gc.collect()
        time.sleep(0.1)
    finally:
        gc.callbacks.pop()
    assert status == 200
    writes = sum(_counter("kubeai_engine_deliver_writes_total", which=w) for w in ("first", "later")) - writes0
    assert made["req.admit"] == 1 and made["serve.receive"] == 1
    assert 1 <= made["serve.write"] == writes
    assert 1 <= made.get("host.gc", 0) <= len(collections)


# -- the reader of the wait behind the chunk in flight --------------------------


MS = 1_000_000


def test_prefill_calls_find_their_runs_in_order():
    calls = [
        ("group", 10 * MS, 1 * MS, 1),
        ("chunk", 20 * MS, 2 * MS, 3),  # three programs queued by one call
        ("group", 23 * MS, 1 * MS, 1),  # dispatched while the chunk call's programs still run
    ]
    runs = {
        "group": [(5 * MS, 2 * MS), (15 * MS, 3 * MS), (40 * MS, 3 * MS)],  # the first ran before any call began
        "chunk": [(25 * MS, 4 * MS), (29 * MS, 4 * MS), (33 * MS, 4 * MS)],
    }
    found, edge, lost = prefill_start_lag.match(calls, runs, 0, 100 * MS)
    assert (edge, lost) == (0, 0)
    assert sorted(found) == [(3 * MS, 12 * MS), (4 * MS, 3 * MS), (16 * MS, 3 * MS)]
    out = prefill_start_lag.summary(calls, runs, 0, 100 * MS)
    assert out["lag_ms_mean"] == pytest.approx((3 + 4 + 16) / 3) and out["lag_ms_max"] == 16
    assert out["run_ms_mean"] == pytest.approx(6.0) and out["longest_run_ms"] == 4
    assert prefill_start_lag.value(out) == out["lag_ms_mean"]
    # A run that began before its call's dispatch ended waited for nothing.
    assert prefill_start_lag.match([("group", 10 * MS, 5 * MS, 1)], {"group": [(12 * MS, MS)]}, 0, 100 * MS)[0] == [(0, MS)]


def test_a_call_at_the_traces_edge_is_dropped_not_lost():
    calls = [("group", t * MS, MS, 1) for t in (10, 30, 50, 95)]
    runs = {"group": [(12 * MS, 5 * MS), (33 * MS, 5 * MS), (52 * MS, 5 * MS), (98 * MS, 5 * MS)]}  # the last ends outside
    found, edge, lost = prefill_start_lag.match(calls, runs, 0, 100 * MS)
    assert (len(found), edge, lost) == (3, 1, 0)
    assert prefill_start_lag.value(prefill_start_lag.summary(calls, runs, 0, 100 * MS)) == pytest.approx((1 + 2 + 1) / 3)
    # No run left at all for the last call: the trace ended first.
    runs["group"].pop()
    assert prefill_start_lag.match(calls, runs, 0, 100 * MS)[1:] == (1, 0)
    # A call outside the interval is none of the interval's calls.
    assert prefill_start_lag.summary(calls, runs, 20 * MS, 100 * MS)["calls"] == 3


def test_nothing_is_read_where_under_nine_in_ten_calls_found_a_run():
    calls = [("group", t * MS, MS, 1) for t in range(10, 90, 10)] + [("chunk", 15 * MS, MS, 1)]
    runs = {"group": [(t * MS + 2 * MS, MS) for t in range(10, 90, 10)], "chunk": []}  # the chunk program's name moved
    out = prefill_start_lag.summary(calls, runs, 0, 100 * MS)
    assert (out["calls"], out["found"], out["edge"]) == (9, 8, 0)
    assert prefill_start_lag.value(out) is None
    assert prefill_start_lag.value({}) is None and prefill_start_lag.value(prefill_start_lag.summary([], {}, 0, MS)) is None
    calls.append(("group", 90 * MS, MS, 1))
    runs["group"].append((92 * MS, MS))
    assert prefill_start_lag.value(prefill_start_lag.summary(calls, runs, 0, 100 * MS)) == pytest.approx(1.0)
