"""Perf X-ray suite (kubeai_tpu/obs/perf.py + engine wiring):

- MFU/roofline formulas vs the hand-computed 8b-int8 numbers from
  docs/benchmarks.md (the doc's prose math is now code — these tests
  pin the two to each other),
- stall-attribution math on fake-clock scripted step records (exact
  /debug/pipeline percentages),
- the shared TokenRateWindow: the engine gauge and the fleet
  collector's counter-delta tok/s agree by construction, including the
  idle→busy transition where the old deque implementation spiked,
- profiler-capture smoke on CPU (403 when ungated, single-flight 409,
  artifact on disk, gang fan-out op),
- perf_gate pass / regress / schema-invalid, API and CLI.
"""

import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeai_tpu.metrics import default_registry
from kubeai_tpu.models.base import ModelConfig
from kubeai_tpu.obs import perf as perf_obs
from kubeai_tpu.obs.perf import (
    PerfModel,
    PipelineStallTracker,
    ProfilerBusy,
    TokenRateWindow,
    default_profiler,
    device_constants,
    handle_perf_request,
    param_counts,
)


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


FLAGSHIP_8B = ModelConfig(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
    dtype="bfloat16",
)


# ---------------------------------------------------------------------------
# Roofline / MFU accounting vs docs/benchmarks.md hand-computed values.


class TestPerfModel:
    def test_8b_int8_matches_docs(self):
        """docs/benchmarks.md: ~8.03e9 params, ~8.0 GB int8 weights,
        ~9.8 ms weight-read step floor at 819 GB/s, ~4.7-4.9k tok/s
        roofline at 48 slots, MFU ~10% at the measured 1,225 tok/s."""
        pm = PerfModel.from_model_config(FLAGSHIP_8B, quantization="int8")
        assert 7.9e9 < pm.param_count < 8.2e9
        assert pm.flops_per_token == 2 * pm.active_params
        assert 7.9e9 < pm.weight_bytes < 8.2e9
        floor_ms = pm.step_floor_seconds(819) * 1e3
        assert 9.5 < floor_ms < 10.1
        roof = pm.roofline_tokens_per_sec(48, 819)
        assert 4400 < roof < 5100
        mfu = pm.mfu(1225.0, 197e12)
        assert 0.095 < mfu < 0.105  # the doc's "MFU ~10%" at r4

    def test_dense_total_equals_active(self):
        total, active = param_counts(FLAGSHIP_8B)
        assert total == active

    def test_moe_active_below_total(self):
        mc = ModelConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=4096,
            num_layers=4, num_heads=8, num_kv_heads=8,
            num_experts=8, num_experts_per_tok=2,
        )
        total, active = param_counts(mc)
        assert active < total
        pm = PerfModel.from_model_config(mc)
        assert pm.flops_per_token == 2 * active
        # Weight-read roofline costs every RESIDENT expert.
        assert pm.weight_bytes == total * 2  # bf16

    def test_tied_embeddings_counted_once(self):
        tied = ModelConfig(vocab_size=1000, hidden_size=64, tie_word_embeddings=True)
        untied = ModelConfig(vocab_size=1000, hidden_size=64)
        assert param_counts(tied)[0] == param_counts(untied)[0] - 1000 * 64

    def test_measured_weight_bytes_override(self):
        pm = PerfModel.from_model_config(FLAGSHIP_8B, weight_bytes=5e9)
        assert pm.weight_bytes == 5e9

    def test_device_constants(self):
        env = device_constants("TPU v5 lite")
        assert env.peak_flops == 197e12 and env.hbm_gbps == 819
        env = device_constants("TPU v5p chip")
        assert env.peak_flops == 459e12 and env.hbm_gbps == 2765
        env = device_constants("cpu")
        assert env.peak_flops is None and env.hbm_gbps is None
        # Unknown device: MFU/roofline read 0, never a made-up number.
        pm = PerfModel.from_model_config(FLAGSHIP_8B)
        assert pm.mfu(1000.0, env.peak_flops) == 0.0
        assert pm.roofline_tokens_per_sec(48, env.hbm_gbps) is None


# ---------------------------------------------------------------------------
# Stall attribution: scripted fake-clock records -> exact percentages.


class TestStallTracker:
    @staticmethod
    def _run(tr, clock, cause, ms, **attrs):
        with tr.segment(cause, **attrs) as seg:
            clock.advance(ms / 1000.0)
        return seg

    def test_scripted_fractions_exact(self):
        clock = FakeClock()
        tr = PipelineStallTracker(window=60.0, clock=clock)
        counter = tr._counter
        base = counter.value(labels={"cause": "fetch_wait"})
        for _ in range(10):
            for cause, ms in (
                ("dispatch", 1.0), ("host_overlap", 2.0), ("fetch_wait", 6.0), ("emit", 1.0),
            ):
                self._run(tr, clock, cause, ms)
            step = tr.end_step("decode_chunk")
            # The step record holds the same stamps the counter saw.
            step.pop("other", None)  # the scripted second since the last chunk
            assert step == pytest.approx(
                {"dispatch": 1.0, "host_overlap": 2.0, "fetch_wait": 6.0, "emit": 1.0}
            )
            clock.advance(1.0)
        seg = self._run(tr, clock, "prefill", 10.0, kind="group", bucket=64, batch=2)
        assert seg.seconds == pytest.approx(0.010) and seg.t1 - seg.t0 == seg.seconds
        assert tr.end_step("prefill_group") == pytest.approx({"prefill": 10.0, "other": 1000.0})
        rep = tr.report()
        # 110 ms under segments; the scripted second between chunks lies
        # under none and is `other`.
        assert rep["accounted_ms"] == pytest.approx(10110.0)
        causes = rep["causes"]
        assert causes["other"]["ms"] == pytest.approx(10000.0)
        assert causes["dispatch"]["ms"] == pytest.approx(10.0)
        assert causes["host_overlap"]["ms"] == pytest.approx(20.0)
        assert causes["fetch_wait"]["ms"] == pytest.approx(60.0)
        assert causes["emit"]["ms"] == pytest.approx(10.0)
        assert causes["prefill"]["ms"] == pytest.approx(10.0)
        # The acceptance shape: per-cause fractions sum to ~1.0 and
        # match the scripted scenario exactly.
        assert causes["fetch_wait"]["fraction"] == pytest.approx(60 / 10110, abs=1e-4)
        assert causes["host_overlap"]["fraction"] == pytest.approx(20 / 10110, abs=1e-4)
        assert sum(c["fraction"] for c in causes.values()) == pytest.approx(1.0, abs=1e-3)
        assert rep["dominant_cause"] == "other"
        assert rep["interpretation"].startswith("99% other")
        assert rep["steps"] == {"decode_chunk": 10, "prefill_group": 1}
        # The fleet-visible counter saw the same seconds.
        assert counter.value(labels={"cause": "fetch_wait"}) - base == pytest.approx(0.060)
        # 110 ms under a named segment in a span of 10.11 s.
        assert rep["coverage"] == pytest.approx(0.110 / 10.110, abs=1e-3)

    def test_nested_segments_are_disjoint_and_cover(self):
        """A segment opened inside another suspends the outer one: the
        causes of one iteration are disjoint and sum to its wall time."""
        clock = FakeClock()
        tr = PipelineStallTracker(window=60.0, clock=clock)
        t_begin = clock()
        self._run(tr, clock, "sweep", 0.5)
        with tr.segment("admit") as admit:
            clock.advance(0.002)
            self._run(tr, clock, "kv_transfer", 3.0, tokens=40)
            clock.advance(0.001)
            inner = self._run(tr, clock, "prefill", 20.0, kind="chunk", cached=128)
            clock.advance(0.001)
        self._run(tr, clock, "dispatch", 1.5, active=2, steps=8)
        self._run(tr, clock, "idle", 50.0)
        wall_ms = (clock() - t_begin) * 1000
        step = tr.end_step("decode_chunk")
        assert step == pytest.approx(
            {"sweep": 0.5, "admit": 4.0, "kv_transfer": 3.0, "prefill": 20.0,
             "dispatch": 1.5, "idle": 50.0, "other": 0.0}
        )
        assert sum(step.values()) == pytest.approx(wall_ms)
        # The outer segment's own stamps still span the inner ones.
        assert admit.seconds == pytest.approx(0.027) and inner.seconds == pytest.approx(0.020)
        rep = tr.report()
        assert rep["accounted_ms"] == pytest.approx(wall_ms)
        assert rep["coverage"] == pytest.approx(1.0)
        assert tr._stack == []

    def test_segment_closes_on_exception(self):
        clock = FakeClock()
        tr = PipelineStallTracker(window=60.0, clock=clock)
        with pytest.raises(RuntimeError):
            with tr.segment("sweep"):
                clock.advance(0.004)
                raise RuntimeError("injected")
        assert tr._stack == []
        assert tr.end_step("decode_chunk") == pytest.approx({"sweep": 4.0})

    def test_window_prunes(self):
        clock = FakeClock()
        tr = PipelineStallTracker(window=30.0, clock=clock)
        self._run(tr, clock, "emit", 1.0)
        clock.advance(31.0)
        assert tr.report()["accounted_ms"] == 0.0
        assert "dominant_cause" not in tr.report()

    def test_empty_report_shape(self):
        tr = PipelineStallTracker(window=10.0, clock=FakeClock())
        rep = tr.report()
        assert rep["accounted_ms"] == 0.0
        assert set(rep["causes"]) == set(perf_obs.STALL_CAUSES)
        assert all(c["fraction"] == 0.0 for c in rep["causes"].values())
        assert rep["slowest_steps"] == []

    def test_slowest_steps_keeps_the_slowest(self):
        """Of 20 steps the 8 slowest stay, slowest first, each with its
        kind, its age and its ms by cause; the 60 s window does not hold
        them back."""
        clock = FakeClock()
        tr = PipelineStallTracker(window=60.0, clock=clock)
        for i in range(20):
            self._run(tr, clock, "fetch_wait", 10 + (i * 7) % 20)  # 10..29 ms, each once
            self._run(tr, clock, "emit", 1)
            tr.end_step("decode_chunk" if i % 2 else "prefill_group")
        slow = tr.report()["slowest_steps"]
        assert [s["ms"]["fetch_wait"] for s in slow] == [29, 28, 27, 26, 25, 24, 23, 22]
        assert all(s["total_ms"] == pytest.approx(s["ms"]["fetch_wait"] + 1) for s in slow)
        assert {s["kind"] for s in slow} == {"decode_chunk", "prefill_group"}
        # A step slower than the least kept one takes its place ...
        self._run(tr, clock, "dispatch", 26.5)
        tr.end_step("decode_chunk")
        slow = tr.slowest_steps()
        assert [round(s["total_ms"], 1) for s in slow] == [30, 29, 28, 27, 26.5, 26, 25, 24]
        assert slow[4]["ms"]["dispatch"] == 26.5 and slow[4]["age_s"] == 0.0
        # ... and a quicker one does not get in (a comparison, no sort).
        self._run(tr, clock, "emit", 5)
        tr.end_step("decode_chunk")
        assert [s["total_ms"] for s in tr.slowest_steps()] == [s["total_ms"] for s in slow]
        # Past the report's 60 s window they are still there, with their age.
        clock.advance(120.0)
        rep = tr.report()
        assert rep["accounted_ms"] == 0.0 and len(rep["slowest_steps"]) == 8
        assert all(120.0 <= s["age_s"] <= 121.0 for s in rep["slowest_steps"])

    def test_slowest_steps_forgets_after_its_horizon(self):
        clock = FakeClock()
        tr = PipelineStallTracker(clock=clock)
        for _ in range(tr.SLOWEST_KEPT):
            self._run(tr, clock, "fetch_wait", 500)  # a stall, eight times
            tr.end_step("decode_chunk")
        self._run(tr, clock, "idle", 1000.0 * tr.SLOWEST_HORIZON + 1.0)  # no request for ten minutes
        # The first step after the horizon gets in though it is under the
        # old floor: the stalls are forgotten, and nobody had to ask. The
        # wait for a request is not part of how slow it was.
        self._run(tr, clock, "emit", 2)
        tr.end_step("decode_chunk")
        assert [s["total_ms"] for s in tr.slowest_steps()] == [2.0]
        clock.advance(tr.SLOWEST_HORIZON + 1.0)
        assert tr.slowest_steps() == [] and tr.report()["slowest_steps"] == []


# ---------------------------------------------------------------------------
# Shared token-rate window: engine gauge vs fleet counter-delta.


class TestTokenRateWindow:
    def test_idle_to_busy_agrees_with_counter_delta(self):
        """The regression this class exists to fix: after idle, the old
        engine deque attributed the first chunk's tokens to ~zero
        elapsed time (a spike); the fleet's counter-delta never did.
        Both views now share one implementation and must agree at every
        sample point."""
        clock = FakeClock()
        eng = TokenRateWindow(span=10.0, clock=clock)  # engine: increments
        fleet = TokenRateWindow(span=0.0, clock=clock)  # fleet: per-scrape delta
        total = 0
        eng.add(500)
        total += 500
        fleet.observe_total(total)
        assert eng.rate() == 0.0  # first sample anchors — no spike
        assert fleet.rate() == 0.0
        for _ in range(5):
            clock.advance(1.0)
            eng.add(100)
            total += 100
            fleet.observe_total(total)
            assert eng.rate() == pytest.approx(fleet.rate())
        assert eng.rate() == pytest.approx(100.0)

    def test_counter_reset_reanchors(self):
        clock = FakeClock()
        w = TokenRateWindow(span=60.0, clock=clock)
        w.observe_total(1000)
        clock.advance(5)
        w.observe_total(200)  # engine restarted: counter went backwards
        assert w.rate() == 0.0
        clock.advance(5)
        w.observe_total(300)
        assert w.rate() == pytest.approx(20.0)

    def test_prune_keeps_anchor_pair(self):
        clock = FakeClock()
        w = TokenRateWindow(span=10.0, clock=clock)
        for _ in range(20):
            clock.advance(1.0)
            w.add(50)
        # Window spans ~10s of samples (anchor + 10-11 in-window).
        assert len(w) <= 12
        assert w.rate() == pytest.approx(50.0)
        w.reset()
        assert w.rate() == 0.0 and len(w) == 0

    def test_fleet_collector_uses_shared_window(self):
        from kubeai_tpu.autoscaler import fleet

        assert fleet.TokenRateWindow is TokenRateWindow

    def test_fleet_scrape_idle_busy_no_spike(self):
        """Fleet-side view of the same transition: a first scrape after
        a burst anchors instead of reporting the burst over dt=0."""
        from kubeai_tpu.autoscaler.fleet import FleetCollector

        class StubLB:
            def get_all_addresses(self, model):
                return ["a:1"]

        page = (
            "kubeai_engine_queue_depth 0\nkubeai_engine_active_slots 1\n"
            "kubeai_engine_slots_total 8\nkubeai_engine_kv_pages_used 5\n"
            "kubeai_engine_kv_pages_cached 0\nkubeai_engine_kv_pages_total 100\n"
            "kubeai_engine_generated_tokens_total {gt}\n"
        )
        clock = FakeClock()
        texts = {"a:1": page.format(gt=5000)}
        col = FleetCollector(
            StubLB(), clock=clock, fetch=lambda addr: texts[addr]
        )
        agg = col.collect(["m1"])["m1"]["aggregate"]
        assert agg["tokens_per_second"] == 0.0  # anchor only
        texts["a:1"] = page.format(gt=5300)
        clock.advance(10)
        agg = col.collect(["m1"])["m1"]["aggregate"]
        assert agg["tokens_per_second"] == 30.0
        # busy -> idle: the very next scrape reads 0 (per-collect delta
        # semantics — the engine gauge resets on idle, and the fleet
        # view must not decay the old burst across a longer window).
        clock.advance(10)
        agg = col.collect(["m1"])["m1"]["aggregate"]
        assert agg["tokens_per_second"] == 0.0


# ---------------------------------------------------------------------------
# Engine wiring e2e (CPU, tiny model): enriched step records, the
# /debug/pipeline report, and the MFU/roofline gauges on /metrics.


class TestEngineWiring:
    def test_pipeline_report_and_enriched_steps(self):
        from kubeai_tpu.engine.core import build_test_engine
        from kubeai_tpu.engine.sampling import SamplingParams
        from kubeai_tpu.obs import default_recorder

        eng = build_test_engine()
        assert isinstance(eng._rate_window, TokenRateWindow)
        eng.start()
        try:
            ids, text, fin = eng.generate(
                list(b"hello there"), SamplingParams(temperature=0.0, max_tokens=6),
                timeout=120,
            )
            assert fin.completion_tokens > 0
            # The "done" event is delivered BEFORE the chunk's stall
            # record lands (emission precedes accounting by design —
            # clients must not wait on bookkeeping): poll briefly.
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                rep = eng.pipeline_report()
                if rep["steps"].get("decode_chunk", 0) >= 1:
                    break
                time.sleep(0.01)
            assert rep["accounted_ms"] > 0
            assert sum(
                c["fraction"] for c in rep["causes"].values()
            ) == pytest.approx(1.0, abs=1e-3)
            assert rep["steps"].get("decode_chunk", 0) >= 1
            for key in ("mfu", "roofline_fraction", "tokens_per_second"):
                assert key in rep
            # Step records carry the uniform breakdown.
            chunk = next(
                s for s in default_recorder.engine_steps()
                if s["kind"] == "decode_chunk"
            )
            for key in ("dispatch_ms", "host_overlap_ms", "fetch_wait_ms", "emit_ms"):
                assert key in chunk, key
            # HTTP route (the engine server wires srv.engine through).
            code, ctype, body = handle_perf_request("/debug/pipeline", "", engine=eng)
            assert code == 200 and ctype == "application/json"
            doc = json.loads(body)
            assert "causes" in doc and "mfu" in doc
        finally:
            eng.stop()

    def test_tokens_per_handover_beside_the_emit_cause(self):
        """/debug/engine -> perf.tokens_per_handover: generated tokens a
        hand-over to a request. An admission's first token is one of one;
        a decode chunk's tokens go over together, so the ratio says how
        often that engaged (1.0: never), and the `emit` cause of a chunk
        is still one segment and one `decode_chunk` observation."""
        from kubeai_tpu.engine.core import build_test_engine
        from kubeai_tpu.engine.sampling import SamplingParams

        eng = build_test_engine()
        assert eng._perf_debug_section()["tokens_per_handover"] is None  # nothing handed over yet
        emit_segments = []
        segment = eng._stall.segment

        def counting(cause, **attrs):
            if cause == "emit":
                emit_segments.append(attrs)
            return segment(cause, **attrs)

        eng._stall.segment = counting
        chunks = lambda: sum(  # noqa: E731
            n for key, (_, _, n) in eng.m_step.snapshot().items() if ("phase", "decode_chunk") in key
        )
        c0 = chunks()
        eng.start()
        try:
            K = eng.cfg.decode_chunk
            _, _, fin = eng.generate(
                list(b"hello there"), SamplingParams(temperature=0.7, seed=3, max_tokens=1 + 2 * K), timeout=120,
            )
            time.sleep(0.2)
        finally:
            eng.stop()
        n = fin.completion_tokens
        tokens, handovers = eng._handed
        assert tokens == n and n > 1
        # The first token alone, then at most a hand-over a chunk.
        assert 2 <= handovers <= 1 + -(-(n - 1) // K)
        assert eng._perf_debug_section()["tokens_per_handover"] == round(n / handovers, 3) > 1.0
        assert chunks() - c0 == len(emit_segments) >= handovers - 1
        assert "kubeai_engine_emit_handovers_total" in default_registry.render()

    def test_mfu_roofline_gauges_on_metrics_page(self):
        from kubeai_tpu.engine.core import build_test_engine

        eng = build_test_engine()
        text = default_registry.render()
        assert "kubeai_engine_mfu" in text
        assert "kubeai_engine_roofline_fraction" in text
        assert "kubeai_engine_stall_seconds_total" in text
        # CPU: constants unresolved -> honest zeros, never invented.
        assert eng._mfu() == 0.0
        assert eng._roofline_fraction() == 0.0
        section = eng._perf_debug_section()
        assert section["flops_per_token"] == 2 * param_counts(eng.model_config)[1]
        assert section["weight_bytes"] > 0
        assert "stall" in section

    def test_debug_engine_serves_the_paged_kernel_blocks(self, monkeypatch):
        """The (kv pages, queries) pair ops/paged_attention.py chose for
        each call shape it traced reaches a reader through the perf
        section of GET /debug/engine."""
        from kubeai_tpu.engine.core import build_test_engine
        from kubeai_tpu.obs.recorder import handle_debug_request
        from kubeai_tpu.ops import paged_attention

        shape = "B=32 S=1 H=28 Kv=4 pages=32x64 bfloat16"
        monkeypatch.setattr(paged_attention, "chosen_blocks", {shape: (8, 1)})
        eng = build_test_engine()
        try:
            code, _, body = handle_debug_request("/debug/engine", "limit=1")
            assert code == 200
            assert json.loads(body)["perf"]["paged_kernel_blocks"] == {shape: [8, 1]}
        finally:
            eng.stop()

    def test_debug_engine_serves_the_chunk_kernel_tiles(self, monkeypatch):
        """Likewise the (query rows, keys) ops/chunk_attention.py was given
        for each call shape of more than one row a slot; the hit share
        beside it is empty for a family that counts no attention pairs."""
        from kubeai_tpu.engine.core import build_test_engine
        from kubeai_tpu.obs.recorder import handle_debug_request
        from kubeai_tpu.ops import chunk_attention

        shape = "B=1 S=2048 H=28 Kv=4 pages=256x64 window=4096"
        monkeypatch.setattr(chunk_attention, "chosen_tiles", {shape: {"query_tile": 256, "kv_block": 256}})
        eng = build_test_engine()
        try:
            code, _, body = handle_debug_request("/debug/engine", "limit=1")
            assert code == 200
            perf = json.loads(body)["perf"]
            assert perf["chunk_kernel_tiles"] == {shape: {"query_tile": 256, "kv_block": 256}}
            assert perf["chunk_kernel_hit_share"] == {}
        finally:
            eng.stop()

    def test_stop_unregisters_perf_section(self):
        """stop() must unpin the engine from the process-global debug
        registry (it holds the KV pool + jit caches via the bound
        method) — without clobbering a newer engine's registration."""
        from kubeai_tpu.engine.core import build_test_engine
        from kubeai_tpu.obs.recorder import _engine_debug_sections

        eng = build_test_engine()
        assert _engine_debug_sections.get("perf") is eng._perf_section_fn
        eng.stop()
        assert _engine_debug_sections.get("perf") is None
        eng2 = build_test_engine()
        eng.stop()  # stale owner's repeat stop must not evict eng2
        assert _engine_debug_sections.get("perf") is eng2._perf_section_fn
        eng2.stop()

    def test_pipeline_without_engine(self):
        code, _, body = handle_perf_request("/debug/pipeline", "", engine=None)
        assert code == 200
        assert json.loads(body) == {"available": False, "reason": "no engine attached"}


# ---------------------------------------------------------------------------
# On-demand profiler capture (CPU smoke).


class TestProfilerCapture:
    def test_403_when_ungated(self, monkeypatch):
        monkeypatch.delenv("KUBEAI_DEBUG_PROFILE", raising=False)
        code, _, body = handle_perf_request("/debug/profile", "seconds=0.05", engine=None)
        assert code == 403
        assert "KUBEAI_DEBUG_PROFILE" in json.loads(body)["error"]["message"]

    def test_smoke_capture_writes_artifacts(self, monkeypatch, tmp_path):
        monkeypatch.setenv("KUBEAI_DEBUG_PROFILE", "1")
        monkeypatch.setattr(default_profiler, "root", str(tmp_path))
        code, _, body = handle_perf_request("/debug/profile", "seconds=0.05", engine=None)
        assert code == 200
        doc = json.loads(body)
        assert doc["trace_dir"].startswith(str(tmp_path))
        assert os.path.isdir(doc["trace_dir"])
        assert doc["files"] >= 1 and doc["bytes"] > 0
        assert doc["gang_fanout"] == 0

    def test_bad_seconds_400(self, monkeypatch):
        monkeypatch.setenv("KUBEAI_DEBUG_PROFILE", "1")
        code, _, _ = handle_perf_request("/debug/profile", "seconds=banana", engine=None)
        assert code == 400

    def test_bad_python_tracer_400(self, monkeypatch):
        monkeypatch.setenv("KUBEAI_DEBUG_PROFILE", "1")
        code, _, body = handle_perf_request("/debug/profile", "seconds=0.05&python_tracer=off", engine=None)
        assert code == 400 and "python_tracer" in json.loads(body)["error"]["message"]

    @pytest.mark.parametrize("python_tracer", ["0", "1"])
    def test_the_call_chooses_the_tracer_and_the_reply_names_the_file(self, monkeypatch, tmp_path, python_tracer):
        """One .xplane.pb, named by the reply, that holds the capture's own
        span and the scheduler's segments whichever tracer ran beside."""
        from jax.profiler import ProfileData

        monkeypatch.setenv("KUBEAI_DEBUG_PROFILE", "1")
        monkeypatch.setattr(default_profiler, "root", str(tmp_path))
        tr = PipelineStallTracker()
        stop = threading.Event()

        def loop():  # what the scheduler thread does, without an engine
            while not stop.is_set():
                with tr.segment("emit", tokens=3):
                    time.sleep(0.005)

        th = threading.Thread(target=loop, daemon=True)
        th.start()
        try:
            code, _, body = handle_perf_request(
                "/debug/profile", f"seconds=0.3&python_tracer={python_tracer}", engine=None
            )
        finally:
            stop.set()
            th.join()
        assert code == 200
        doc = json.loads(body)
        assert doc["python_tracer"] is (python_tracer == "1") and doc["window_event"] == "profile.window"
        assert doc["files"] == 1 and doc["xplane"].startswith(doc["trace_dir"]) and doc["xplane"].endswith(".xplane.pb")
        events = [
            (ev.name, dict(ev.stats)) for plane in ProfileData.from_file(doc["xplane"]).planes
            if plane.name.startswith("/host:") for ln in plane.lines for ev in ln.events
        ]
        names = {n for n, _ in events}
        assert "profile.window" in names and "sched.emit" in names
        assert next(st for n, st in events if n == "sched.emit")["tokens"] == 3
        assert ("$time sleep" in names) == (python_tracer == "1")

    def test_single_flight_409(self, monkeypatch, tmp_path):
        monkeypatch.setenv("KUBEAI_DEBUG_PROFILE", "1")
        monkeypatch.setattr(default_profiler, "root", str(tmp_path))
        started = threading.Event()
        results = {}

        orig_capture = default_profiler.capture

        def slow_capture(seconds, engine=None, out_dir=None, **kw):
            # Signal once the lock is held, without burning a real trace
            # for the whole window.
            started.set()
            return orig_capture(seconds, engine=engine, out_dir=out_dir, **kw)

        monkeypatch.setattr(default_profiler, "capture", slow_capture)

        def first():
            results["first"] = handle_perf_request(
                "/debug/profile", "seconds=0.8", engine=None
            )

        t = threading.Thread(target=first, daemon=True)
        t.start()
        assert started.wait(timeout=30)
        time.sleep(0.1)  # let the first capture take the lock
        code, _, body = handle_perf_request("/debug/profile", "seconds=0.05", engine=None)
        t.join(timeout=30)
        assert code == 409
        assert results["first"][0] == 200

    def test_gang_leader_fans_out(self):
        """Rank 0 broadcasts a 'profile' op over the dispatch control
        channel before starting its own trace."""
        from kubeai_tpu.engine.core import build_test_engine

        eng = build_test_engine()
        published = []

        class StubPublisher:
            n_followers = 2

            def publish(self, op, scalars, arrays):
                published.append((op, scalars))

        eng._publisher = StubPublisher()
        try:
            n = eng.broadcast_profile(1.5, "/tmp/trace-dir")
            assert n == 2
            assert published == [
                ("profile", {"seconds": 1.5, "dir": "/tmp/trace-dir"})
            ]
        finally:
            eng._publisher = None

    def test_follower_capture_dir_suffixed_by_rank(self, monkeypatch):
        """Followers suffix the broadcast dir with their rank so ranks
        sharing a host/mount can't clobber each other's artifacts."""
        captured = {}

        def fake_capture(seconds, engine=None, out_dir=None):
            captured["dir"] = out_dir
            captured["done"] = threading.Event()
            captured["done"].set()
            return {}

        monkeypatch.setattr(default_profiler, "capture", fake_capture)
        perf_obs.start_background_capture(0.1, "/tmp/shared/profile-x")
        deadline = time.monotonic() + 10
        while "dir" not in captured and time.monotonic() < deadline:
            time.sleep(0.01)
        assert captured["dir"] == "/tmp/shared/profile-x-rank0"

    def test_follower_profile_op(self, monkeypatch):
        """A follower receiving the fan-out op starts a background
        capture and keeps replaying (the next op still executes)."""
        from kubeai_tpu.engine.core import build_test_engine

        eng = build_test_engine()
        calls = []
        monkeypatch.setattr(
            perf_obs, "start_background_capture",
            lambda seconds, out_dir: calls.append((seconds, out_dir)),
        )

        class FakeFollower:
            def __init__(self):
                self.ops = [
                    ("profile", {"seconds": 2.5, "dir": "/tmp/d"}, {}),
                    ("stop", {}, {}),
                ]

            def recv(self):
                return self.ops.pop(0)

        eng.run_follower(FakeFollower())
        assert calls == [(2.5, "/tmp/d")]


# ---------------------------------------------------------------------------
# Perf regression gate.

from benchmarks.perf_gate import (  # noqa: E402
    EXPECTED_METRIC,
    gate,
    load_bench,
    main as perf_gate_main,
    validate,
)


def bench_doc(value, preset="8b-int8", **kw):
    doc = {
        "metric": EXPECTED_METRIC,
        "value": value,
        "unit": "tok/s",
        "vs_baseline": round(value / 285.25, 3),
        "preset": preset,
    }
    doc.update(kw)
    return doc


class TestPerfGate:
    def test_schema_valid(self):
        assert validate(bench_doc(1225.18, mfu_pct=9.99)) == []

    def test_schema_invalid_cases(self):
        assert any("metric" in e for e in validate({"value": 1.0}))
        assert any("unit" in e for e in validate(bench_doc(1.0) | {"unit": "rps"}))
        assert any("value" in e for e in validate(bench_doc(1.0) | {"value": "fast"}))
        assert any("preset" in e for e in validate(bench_doc(1.0, preset="")))
        assert any("failed run" in e for e in validate(bench_doc(0.0) | {"error": "boom"}))
        assert any("> 0" in e for e in validate(bench_doc(0.0)))

    def test_pass_within_tolerance(self):
        ok, report = gate(bench_doc(1150), [bench_doc(1225)])
        assert ok and report["verdict"] == "pass"

    def test_20pct_toks_regression_fails(self):
        ok, report = gate(bench_doc(980), [bench_doc(1225)])
        assert not ok
        assert any("tok/s regressed" in r for r in report["regressions"])

    def test_mfu_regression_fails(self):
        ok, report = gate(
            bench_doc(1220, mfu_pct=6.0), [bench_doc(1225, mfu_pct=10.0)]
        )
        assert not ok
        assert any("MFU regressed" in r for r in report["regressions"])

    def test_rate_controlled_ttft_regression_fails(self):
        ok, report = gate(
            bench_doc(1220, rate_controlled={"p50_ttft_ms": 900.0}),
            [bench_doc(1225, rate_controlled={"p50_ttft_ms": 400.0})],
        )
        assert not ok
        assert any("TTFT regressed" in r for r in report["regressions"])

    def test_cpu_fallback_and_other_presets_excluded(self):
        baselines = [
            bench_doc(5000, note="accelerator init hung; CPU fallback (not a TPU number)"),
            bench_doc(4000, preset="1.3b"),
            bench_doc(0.0) | {"error": "all presets failed"},
        ]
        ok, report = gate(bench_doc(100), baselines)
        assert ok  # nothing comparable -> baseline-setting pass
        assert report["baselines_considered"] == 0

    def test_cli_synthetic_pair(self, tmp_path):
        """`make perf-gate` semantics on a synthetic pair: pass, then an
        injected 20% tok/s regression exits nonzero, then schema-invalid
        exits 2. Both envelope shapes (driver wrapper + raw line)."""
        base = tmp_path / "BENCH_r01.json"
        base.write_text(json.dumps(
            {"n": 1, "parsed": bench_doc(1000.0, mfu_pct=10.0)}
        ))
        good = tmp_path / "BENCH_r02.json"
        good.write_text(json.dumps(bench_doc(950.0, mfu_pct=9.5)))
        glob_arg = str(tmp_path / "BENCH_r*.json")
        assert perf_gate_main([str(good), "--baseline-glob", glob_arg]) == 0
        # No explicit candidate: the newest round is gated vs the rest.
        assert perf_gate_main(["--baseline-glob", glob_arg]) == 0

        good.write_text(json.dumps(bench_doc(790.0)))  # -21% injected
        assert perf_gate_main(["--baseline-glob", glob_arg]) == 1

        bad = tmp_path / "BENCH_r03.json"
        bad.write_text(json.dumps({"metric": "wrong", "value": 100}))
        assert perf_gate_main([str(bad), "--baseline-glob", glob_arg]) == 2

    def test_load_bench_unwraps_driver_envelope(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"n": 4, "rc": 0, "parsed": bench_doc(1225.18)}))
        assert load_bench(str(p))["value"] == 1225.18
