"""The scheduler loop's segments on a running engine (CPU, tiny model):
the per-cause seconds of /debug/pipeline sum to the loop's wall time, and
a profiler capture of the running engine holds the same segments as
``sched.*`` events, with their attrs, on the scheduler thread's line."""

import glob
import os
import threading
import time

import pytest

from kubeai_tpu.engine.core import build_test_engine
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.obs import default_recorder
from kubeai_tpu.obs import perf as perf_obs


def _generate(eng, n_requests=3, max_tokens=24, prompt=b"hello there, engine"):
    threads = [
        threading.Thread(
            target=eng.generate,
            args=(list(prompt) * (1 + i), SamplingParams(temperature=0.0, max_tokens=max_tokens)),
            kwargs={"timeout": 120},
        )
        for i in range(n_requests)
    ]
    for t in threads:
        t.start()
    return threads


def test_causes_sum_to_the_loops_wall_time():
    eng = build_test_engine()
    eng.start()
    try:
        # Warm every program first: the compile of a first call is loop
        # time too, but the window below should hold a steady loop.
        for t in _generate(eng):
            t.join()
        counter = eng._stall._counter
        read = lambda: {c: counter.value(labels={"cause": c}) for c in perf_obs.STALL_CAUSES}  # noqa: E731
        before, t0 = read(), time.monotonic()
        for t in _generate(eng):
            t.join()
        time.sleep(0.3)  # some idle iterations too
        after, t1 = read(), time.monotonic()
        by_cause = {c: after[c] - before[c] for c in before}
        # Within 2% of the wall time, plus the one segment (an idle wait
        # of 50 ms at most) that either read may have caught open.
        assert sum(by_cause.values()) == pytest.approx(t1 - t0, rel=0.02, abs=0.06), by_cause
        for cause in ("sweep", "admit", "prefill", "dispatch", "host_overlap", "fetch_wait", "emit", "idle"):
            assert by_cause[cause] > 0, (cause, by_cause)
        rep = eng.pipeline_report()
        assert set(rep["causes"]) == set(perf_obs.STALL_CAUSES)
        # What lies under no named segment is `other`, and it is little.
        assert rep["coverage"] == pytest.approx(1.0 - rep["causes"]["other"]["fraction"], abs=0.02), rep
        assert rep["coverage"] >= 0.9, rep
        assert by_cause["other"] < 0.1 * (t1 - t0), by_cause
        assert sum(c["fraction"] for c in rep["causes"].values()) == pytest.approx(1.0, abs=1e-3)
        # A decode chunk's step record carries the stamps of its iteration.
        chunk = next(s for s in reversed(default_recorder.engine_steps()) if s["kind"] == "decode_chunk")
        assert chunk["fetch_wait_ms"] > 0 and chunk["emit_ms"] > 0
        assert chunk["dur_ms"] >= chunk["fetch_wait_ms"]
    finally:
        eng.stop()


def test_one_emit_segment_and_one_observation_a_chunk():
    """`host_work_per_chunk_ms` divides the seconds under `sweep` + `admit`
    + `dispatch` + `host_overlap` + `emit` by the count of `decode_chunk`
    observations: a chunk's delivery, however many requests it hands
    their tokens to, is still ONE `emit` segment that brackets all of it
    and ONE observation."""
    from kubeai_tpu.metrics import default_registry

    eng = build_test_engine()
    emits: list = []
    segment = eng._stall.segment

    def counting(cause, **attrs):
        seg = segment(cause, **attrs)
        if cause == "emit":
            emits.append((seg, attrs))
        return seg

    eng._stall.segment = counting
    observed = lambda: sum(  # noqa: E731
        n for key, (_, _, n) in eng.m_step.snapshot().items() if ("phase", "decode_chunk") in key
    )
    handovers = default_registry.counter("kubeai_engine_emit_handovers_total")
    generated = default_registry.counter("kubeai_engine_generated_tokens_total")
    emit_s = lambda: eng._stall._counter.value(labels={"cause": "emit"})  # noqa: E731
    eng.start()
    try:
        for t in _generate(eng):  # compile first
            t.join()
        time.sleep(0.2)
        default_recorder.clear()
        n0, o0, h0, g0, e0 = len(emits), observed(), handovers.value(), generated.value(), emit_s()
        for t in _generate(eng, n_requests=4, max_tokens=40):
            t.join()
        time.sleep(0.2)
    finally:
        eng.stop()
    chunks = len(emits) - n0
    assert chunks >= 5
    assert observed() - o0 == chunks
    # ... and ONE step record, which carries that segment's time.
    steps = [s for s in default_recorder.engine_steps() if s["kind"] == "decode_chunk"]
    assert len(steps) == chunks and all(s["emit_ms"] > 0 for s in steps)
    # The segment covers the whole delivery: its seconds are the counter's.
    assert sum(seg.seconds for seg, _ in emits[n0:]) == pytest.approx(emit_s() - e0, rel=1e-6, abs=1e-6)
    # What it announces on the trace is still the chunk's tokens, K a live slot.
    assert all(a["tokens"] % eng.cfg.decode_chunk == 0 and a["tokens"] > 0 for _, a in emits[n0:])
    # ... and a request was handed its tokens once a chunk, not once a token:
    # its first token alone, then a hand-over for each chunk it decoded in.
    tokens, handed = generated.value() - g0, handovers.value() - h0
    assert tokens >= 4 * eng.cfg.decode_chunk
    assert 4 <= handed <= 4 + (tokens - 4) / eng.cfg.decode_chunk + 4


@pytest.mark.parametrize("python_tracer", ["default", "0"])
def test_profiler_capture_holds_the_segments_on_the_scheduler_line(tmp_path, python_tracer):
    from jax.profiler import ProfileData

    # The call chooses the tracer (/debug/profile?python_tracer=0|1).
    choice = {} if python_tracer == "default" else {"python_tracer": False}

    eng = build_test_engine()
    eng.start()
    try:
        for t in _generate(eng):  # compile outside the capture
            t.join()
        box = {}
        cap = threading.Thread(
            target=lambda: box.update(perf_obs.ProfilerCapture(str(tmp_path)).capture(1.0, **choice))
        )
        cap.start()
        time.sleep(0.2)
        for t in _generate(eng, n_requests=3, max_tokens=16):
            t.join()
        cap.join()
    finally:
        eng.stop()
    files = glob.glob(os.path.join(box["trace_dir"], "**", "*.xplane.pb"), recursive=True)
    assert len(files) == 1, files
    # The reply names what a reader cannot guess.
    assert box["xplane"] == files[0] and box["window_event"] == "profile.window"
    assert box["python_tracer"] is (python_tracer == "default")
    data = ProfileData.from_file(files[0])
    host = next(p for p in data.planes if p.name == "/host:CPU")
    lines = [ln for ln in host.lines if any(ev.name.startswith("sched.") for ev in ln.events)]
    # One thread writes them, and the trace calls its line by the name
    # the loop gave the thread.
    assert [ln.name for ln in lines] == ["engine-loop"], [ln.name for ln in host.lines]
    events: dict[str, list[dict]] = {}
    for ev in lines[0].events:
        if ev.name.startswith("sched."):
            events.setdefault(ev.name, []).append(dict(ev.stats))
    for name in ("sched.sweep", "sched.admit", "sched.prefill", "sched.dispatch",
                 "sched.host_overlap", "sched.fetch_wait", "sched.emit", "sched.idle"):
        assert name in events, sorted(events)
    prefill = events["sched.prefill"][0]
    assert {"kind", "bucket", "batch", "tokens", "cached", "pad"} <= set(prefill), prefill
    assert prefill["kind"] in ("group", "chunk") and prefill["tokens"] > 0
    if prefill["kind"] == "group":  # whole [rows, bucket] programs
        assert (prefill["tokens"] + prefill["pad"]) % prefill["bucket"] == 0, prefill
    dispatch = events["sched.dispatch"][0]
    assert dispatch["steps"] == eng.cfg.decode_chunk and 1 <= dispatch["active"] <= eng.cfg.max_slots
    assert events["sched.emit"][0]["tokens"] >= 1
    assert "admitted" in events["sched.host_overlap"][0]
    # No annotation per token or per slot: a handful per loop iteration.
    n_iter = len(events["sched.sweep"])
    assert sum(len(v) for v in events.values()) <= 10 * n_iter
    # The traced interval is an event of the trace either way; with the
    # Python tracer (the default) the capture's sleep is one too, which is
    # what the benchmark's accepted harness takes its window from.
    names = {ev.name for ln in host.lines for ev in ln.events}
    assert "profile.window" in names
    assert ("$time sleep" in names) == (python_tracer == "default")
    assert any(n.startswith("$") and ".py:" in n for n in names) == (python_tracer == "default")
