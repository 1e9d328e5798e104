"""Perf X-ray: roofline/MFU accounting, step-pipeline stall attribution,
and on-demand device profiler capture.

Four pieces, all dependency-free (jax is imported lazily and only by the
profiler capture):

- **PerfModel** — model FLOPs/token and weight-bytes/token computed ONCE
  from ModelConfig. This is the single source of truth for the roofline
  math that used to live as prose in docs/benchmarks.md (8b-int8: ~8 GB
  int8 weights / ~819 GB/s v5e HBM = ~9.8 ms/step floor = ~4.9k tok/s at
  48 slots) and as ad-hoc constants in bench.py / profile_engine.py.
  ``PEAK_FLOPS`` / ``HBM_GBPS`` are the shared per-device tables.
- **TokenRateWindow** — the sliding-window tokens/sec implementation
  shared by the engine's ``kubeai_engine_tokens_per_second`` gauge and
  the fleet collector's counter-delta derivation. Both store cumulative
  totals and report (last-first)/(span); the first sample only ANCHORS
  the window, so an idle→busy transition cannot report a spike the
  fleet's counter-delta view would never show.
- **PipelineStallTracker** — aggregates the engine's enriched step
  records (dispatch / host-overlap / fetch-wait / emit / prefill) over a
  sliding window into the ``GET /debug/pipeline`` stall report and the
  ``kubeai_engine_stall_seconds_total{cause}`` counter.
- **ProfilerCapture** + ``handle_perf_request`` — ``GET
  /debug/profile?seconds=N`` starts a ``jax.profiler`` trace (single-
  flight; opt-in via ``KUBEAI_DEBUG_PROFILE=1``, mirroring the
  ``/debug/faults`` arming gate) and returns the artifact path; on a
  gang, rank 0 fans the capture out to followers over the existing
  dispatch control channel so every rank's trace covers the same window.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass

from kubeai_tpu.metrics import default_registry

log = logging.getLogger("kubeai_tpu.obs.perf")

# ---------------------------------------------------------------------------
# Device constant tables (shared by bench.py, profile_engine.py, and the
# engine's live MFU/roofline gauges — previously two drifting copies).

# Peak bf16 matmul FLOP/s per chip by TPU generation (public specs).
PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}

# HBM bandwidth (GB/s) per chip generation (public specs).
HBM_GBPS = {
    "v5 lite": 819,
    "v5e": 819,
    "v5p": 2765,
    "v6 lite": 1640,
    "v6e": 1640,
    "v4": 1228,
}


@dataclass(frozen=True)
class DeviceEnv:
    """Resolved perf constants for one device kind. ``peak_flops`` /
    ``hbm_gbps`` are None when the device is unknown (CPU, new chip):
    MFU/roofline then read 0 rather than inventing a denominator."""

    kind: str = ""
    peak_flops: float | None = None
    hbm_gbps: float | None = None
    # As jax reports them for this process (detect_device); empty/0 when
    # the constants were looked up by kind alone.
    platform: str = ""
    visible_devices: int = 0


def device_constants(device_kind: str) -> DeviceEnv:
    """Match a jax ``device_kind`` string (e.g. "TPU v5 lite") against
    the constant tables by substring, longest key first ("v5 lite" must
    win over "v5")."""
    kl = str(device_kind).lower()
    peak = next(
        (v for k, v in sorted(PEAK_FLOPS.items(), key=lambda kv: -len(kv[0])) if k in kl),
        None,
    )
    hbm = next(
        (v for k, v in sorted(HBM_GBPS.items(), key=lambda kv: -len(kv[0])) if k in kl),
        None,
    )
    return DeviceEnv(kind=str(device_kind), peak_flops=peak, hbm_gbps=hbm)


def detect_device() -> DeviceEnv:
    """DeviceEnv for the current process's first local device, with the
    platform and device count jax reports (lazy jax import)."""
    import dataclasses

    import jax

    dev = jax.local_devices()[0]
    return dataclasses.replace(
        device_constants(dev.device_kind),
        platform=dev.platform,
        visible_devices=len(jax.devices()),
    )


# ---------------------------------------------------------------------------
# Roofline / MFU accounting.

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def param_counts(mc) -> tuple[float, float]:
    """(total, active) parameter counts from a ModelConfig, analytically.
    Dense families have total == active; MoE counts every expert as
    resident (weight-read roofline: a batched decode step touches all
    experts) but only the routed top-k as active (FLOPs/token)."""
    D, F, L, V = mc.hidden_size, mc.intermediate_size, mc.num_layers, mc.vocab_size
    H, Kv, h = mc.num_heads, mc.num_kv_heads, mc.head_dim_
    attn = D * H * h + 2 * D * Kv * h + H * h * D
    if getattr(mc, "qkv_bias", False):
        attn += (H + 2 * Kv) * h
    mlp = 3 * D * F
    norms = 2 * D + (2 * D if getattr(mc, "post_norms", False) else 0)
    E = getattr(mc, "num_experts", 0)
    if E:
        k = mc.num_experts_per_tok
        router = D * E
        layer_total = attn + norms + E * mlp + router
        layer_active = attn + norms + k * mlp + router
    else:
        layer_total = layer_active = attn + norms + mlp
    embed = V * D
    head = 0 if getattr(mc, "tie_word_embeddings", False) else V * D
    fixed = embed + head + D
    return float(fixed + L * layer_total), float(fixed + L * layer_active)


@dataclass(frozen=True)
class PerfModel:
    """Per-model roofline constants, computed once. ``flops_per_token``
    is the standard decode estimate 2 * active params (attention adds a
    few % at seq<=1k — same convention as docs/benchmarks.md);
    ``weight_bytes`` is what one decode step must stream from HBM."""

    param_count: float  # resident params (weight-read roofline)
    active_params: float  # params touched per token (FLOPs)
    flops_per_token: float
    weight_bytes: float

    @classmethod
    def from_model_config(cls, mc, quantization: str = "", weight_bytes: float | None = None) -> "PerfModel":
        """*weight_bytes*, when given (e.g. measured off the live param
        tree), overrides the analytic estimate; otherwise params are
        costed at 1 byte for int8 weight-only quantization, else the
        model dtype's width."""
        total, active = param_counts(mc)
        if weight_bytes is None:
            per_param = 1 if quantization == "int8" else _DTYPE_BYTES.get(mc.dtype, 2)
            weight_bytes = total * per_param
        return cls(
            param_count=total,
            active_params=active,
            flops_per_token=2.0 * active,
            weight_bytes=float(weight_bytes),
        )

    def step_floor_seconds(self, hbm_gbps: float) -> float:
        """Weight-read floor for ONE decode step (the whole batch shares
        the read, which is why batch is 'nearly free' until HBM fills)."""
        return self.weight_bytes / (hbm_gbps * 1e9)

    def roofline_tokens_per_sec(self, batch: int, hbm_gbps: float | None) -> float | None:
        """Output tok/s if decode were purely weight-read-bound at this
        batch size (None when the device bandwidth is unknown)."""
        if not hbm_gbps or batch <= 0:
            return None
        return batch / self.step_floor_seconds(hbm_gbps)

    def mfu(self, tokens_per_sec: float, peak_flops: float | None) -> float:
        """Model FLOPs utilization (fraction of peak) at a decode rate."""
        if not peak_flops:
            return 0.0
        return tokens_per_sec * self.flops_per_token / peak_flops


# ---------------------------------------------------------------------------
# Shared sliding-window token rate.


class TokenRateWindow:
    """Sliding-window rate over a cumulative count. One implementation
    for BOTH consumers that used to disagree during idle→busy
    transitions:

    - the engine's goodput gauge (``add(n)`` per decode chunk), and
    - the fleet collector's per-endpoint counter-delta tok/s
      (``observe_total(counter_value)`` per scrape).

    Samples are (t, cumulative_total); rate = (last-first)/(t_last-t_0).
    The FIRST sample only anchors the window — its tokens were produced
    before the window opened, so attributing them to ~zero elapsed time
    (the old engine deque did exactly that on the first busy chunk after
    idle) reported a spike the counter-delta view never showed. A total
    that goes BACKWARDS (engine restart resetting the counter) re-anchors
    instead of reporting a negative rate."""

    def __init__(self, span: float = 10.0, clock=time.monotonic):
        self.span = span
        self._clock = clock
        self._lock = threading.Lock()
        self._samples: deque[tuple[float, float]] = deque()
        self._total = 0.0

    def add(self, n: float, now: float | None = None) -> None:
        with self._lock:
            self._total += n
            self._observe_locked(self._total, now)

    def observe_total(self, total: float, now: float | None = None) -> None:
        with self._lock:
            self._observe_locked(float(total), now)

    def _observe_locked(self, total: float, now: float | None) -> None:
        now = self._clock() if now is None else now
        if self._samples and total < self._samples[-1][1]:
            self._samples.clear()  # counter reset: re-anchor
        self._total = total
        self._samples.append((now, total))
        cutoff = now - self.span
        # Keep at least two samples: the oldest retained one is the
        # anchor just before (or at) the window edge, so the delta is
        # always measured over a real elapsed span.
        while len(self._samples) > 2 and self._samples[1][0] <= cutoff:
            self._samples.popleft()

    def rate(self, now: float | None = None) -> float:
        with self._lock:
            if len(self._samples) < 2:
                return 0.0
            t0, c0 = self._samples[0]
            t1, c1 = self._samples[-1]
            return (c1 - c0) / (t1 - t0) if t1 > t0 else 0.0

    def reset(self) -> None:
        """Drop the window (engine idle: the gauge must read 0, and the
        next busy chunk must re-anchor rather than span the idle gap)."""
        with self._lock:
            self._samples.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._samples)


# ---------------------------------------------------------------------------
# Stall attribution.

# The uniform timing breakdown every scheduler step record maps onto
# (segments are DISJOINT wall-time slices — the engine measures each
# directly rather than deriving any as an interval difference, so the
# per-cause seconds can be summed without double-counting):
#   dispatch      argument upload + broadcast + async jit call
#   host_overlap  first-token emission for admitted requests + aux work
#                 between a dispatch and its fetch — time the pipelining
#                 successfully hid behind device compute
#   fetch_wait    pure host block inside device_get (device compute +
#                 result transfer outlasting the overlapped host work)
#   emit          detokenize / stop-check / client delivery
#   prefill       prefill dispatch calls (group and chunked)
#   kv_transfer   KV restore admissions (engine/kvstate.py): blob
#                 validation + page upload + slot rebuild on the
#                 scheduler thread — the import cost restore pays
#                 instead of the prefill cost replay would
STALL_CAUSES = ("dispatch", "host_overlap", "fetch_wait", "emit", "prefill", "kv_transfer")

_INTERPRET = {
    "fetch_wait": (
        "host blocked in device_get — host-bound on the device round-trip: "
        "device compute + result transfer outlast the overlapped host work"
    ),
    "host_overlap": (
        "host-bound between dispatch and fetch: admissions/aux/emission "
        "work dominates the chunk turnaround (the device is likely idle "
        "waiting for the next dispatch)"
    ),
    "dispatch": "host-bound on dispatch: argument upload/broadcast dominates",
    "emit": "host-bound on emission: detokenize/stop-check/delivery dominates",
    "prefill": "prefill-bound: prompt processing dominates the window",
    "kv_transfer": (
        "restore-bound: KV page import (blob upload + slot rebuild) "
        "dominates — resumes are arriving faster than pages can be "
        "imported; check kubeai_kv_restore_seconds and the break-even "
        "floor (KUBEAI_KV_BREAKEVEN_TOKENS)"
    ),
}


class PipelineStallTracker:
    """Sliding-window aggregation of enriched scheduler step records into
    a stall-attribution report ('where does decode wall-time go'). The
    engine records one entry per decode chunk / prefill call; ``report``
    answers ``GET /debug/pipeline``. Per-cause totals also feed the
    ``kubeai_engine_stall_seconds_total{cause}`` counter so the fleet
    collector and SLO layers see the same attribution fleet-wide."""

    def __init__(self, window: float = 60.0, clock=time.monotonic, registry=None):
        self.window = window
        self._clock = clock
        self._lock = threading.Lock()
        # (t, kind, {cause: ms})
        self._records: deque[tuple[float, str, dict]] = deque()
        reg = registry or default_registry
        self._counter = reg.counter(
            "kubeai_engine_stall_seconds_total",
            "scheduler step wall time by stall cause (dispatch | "
            "host_overlap | fetch_wait | emit | prefill | kv_transfer) — "
            "the aggregate behind GET /debug/pipeline",
        )

    def record_decode(
        self,
        dispatch_ms: float,
        host_overlap_ms: float,
        fetch_wait_ms: float,
        emit_ms: float,
        now: float | None = None,
    ) -> None:
        self._record(
            "decode_chunk",
            {
                "dispatch": max(dispatch_ms, 0.0),
                "host_overlap": max(host_overlap_ms, 0.0),
                "fetch_wait": max(fetch_wait_ms, 0.0),
                "emit": max(emit_ms, 0.0),
            },
            now,
        )

    def record_prefill(self, kind: str, dur_ms: float, now: float | None = None) -> None:
        self._record(kind, {"prefill": max(dur_ms, 0.0)}, now)

    def record_kv_transfer(self, dur_ms: float, now: float | None = None) -> None:
        self._record("kv_restore", {"kv_transfer": max(dur_ms, 0.0)}, now)

    def _record(self, kind: str, causes: dict, now: float | None) -> None:
        now = self._clock() if now is None else now
        for cause, ms in causes.items():
            if ms:
                self._counter.inc(ms / 1000.0, labels={"cause": cause})
        with self._lock:
            self._records.append((now, kind, causes))
            self._prune_locked(now)

    def _prune_locked(self, now: float) -> None:
        cutoff = now - self.window
        while self._records and self._records[0][0] < cutoff:
            self._records.popleft()

    def report(self, now: float | None = None) -> dict:
        """The /debug/pipeline payload: per-cause ms + fraction of
        accounted step time (fractions sum to 1.0 by construction),
        step counts by kind, and a human interpretation of the dominant
        cause. ``coverage`` is accounted time / observed wall span — the
        remainder is scheduler idle (or work between records)."""
        now = self._clock() if now is None else now
        with self._lock:
            self._prune_locked(now)
            records = list(self._records)
        cause_ms = {c: 0.0 for c in STALL_CAUSES}
        steps: dict[str, int] = {}
        for _, kind, causes in records:
            steps[kind] = steps.get(kind, 0) + 1
            for cause, ms in causes.items():
                cause_ms[cause] = cause_ms.get(cause, 0.0) + ms
        accounted = sum(cause_ms.values())
        out: dict = {
            "window_seconds": self.window,
            "steps": steps,
            "accounted_ms": round(accounted, 3),
            "causes": {
                c: {
                    "ms": round(ms, 3),
                    "fraction": round(ms / accounted, 4) if accounted else 0.0,
                }
                for c, ms in cause_ms.items()
            },
        }
        if records:
            span = now - records[0][0]
            if span > 0:
                out["coverage"] = round(min(accounted / (span * 1000.0), 1.0), 4)
        if accounted:
            dominant = max(cause_ms, key=lambda c: cause_ms[c])
            out["dominant_cause"] = dominant
            pct = round(100.0 * cause_ms[dominant] / accounted)
            out["interpretation"] = f"{pct}% {dominant} → {_INTERPRET[dominant]}"
        return out


# ---------------------------------------------------------------------------
# On-demand device profiler capture.


def profiling_enabled() -> bool:
    """Whether /debug/profile may start a device trace. Off by default —
    a trace burns device attention and disk, so it requires the explicit
    ``KUBEAI_DEBUG_PROFILE=1`` opt-in (mirroring the /debug/faults
    arming gate). Re-read per request so tests can toggle it."""
    return os.environ.get("KUBEAI_DEBUG_PROFILE", "") in ("1", "true", "yes")


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (the profiler is process-global
    jax state — overlapping traces would corrupt each other)."""


class ProfilerCapture:
    """Single-flight jax.profiler trace capture. ``capture`` blocks for
    the requested window (the HTTP handler thread is per-connection, so
    blocking is fine) and returns the artifact summary. Works on CPU —
    tier-1 smokes the whole path without an accelerator."""

    def __init__(self, root: str | None = None):
        self._lock = threading.Lock()
        self.root = root or os.environ.get(
            "KUBEAI_PROFILE_DIR", "/tmp/kubeai-profiles"
        )

    def capture(self, seconds: float, engine=None, out_dir: str | None = None) -> dict:
        if not self._lock.acquire(blocking=False):
            raise ProfilerBusy("a profile capture is already in flight")
        try:
            out_dir = out_dir or os.path.join(
                self.root, time.strftime("profile-%Y%m%d-%H%M%S")
            )
            os.makedirs(out_dir, exist_ok=True)
            fanout = 0
            if engine is not None:
                # Gang leader: followers start their own capture of the
                # same window over the existing dispatch control channel
                # (best-effort — a degraded gang still profiles rank 0).
                try:
                    fanout = engine.broadcast_profile(seconds, out_dir)
                except Exception as e:
                    log.warning("profile gang fan-out failed: %s", e)
            import jax

            jax.profiler.start_trace(out_dir)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            files = 0
            total = 0
            for r, _, fs in os.walk(out_dir):
                for f in fs:
                    files += 1
                    try:
                        total += os.path.getsize(os.path.join(r, f))
                    except OSError:
                        pass
            return {
                "trace_dir": out_dir,
                "seconds": seconds,
                "files": files,
                "bytes": total,
                "gang_fanout": fanout,
            }
        finally:
            self._lock.release()


default_profiler = ProfilerCapture()


def start_background_capture(seconds: float, out_dir: str | None = None) -> None:
    """Gang-follower side of the fan-out: run a capture on a daemon
    thread so the dispatch replay loop keeps running — the replayed
    decode work is exactly what the trace should cover. Best-effort:
    a busy/failed capture is a log line, never a dead follower.

    The broadcast *out_dir* is suffixed with this process's rank: on a
    shared mount (or a single-host multi-process gang) every rank would
    otherwise write the same plugins/profile/<timestamp>/<hostname>
    artifact paths and silently clobber each other's trace."""
    if out_dir:
        try:
            import jax

            out_dir = f"{out_dir}-rank{jax.process_index()}"
        except Exception:  # pragma: no cover - backend init failure
            pass

    def run():
        try:
            default_profiler.capture(seconds, out_dir=out_dir)
        except ProfilerBusy:
            log.warning("profile fan-out ignored: capture already in flight")
        except Exception:
            log.exception("follower profile capture failed")

    threading.Thread(target=run, name="profile-capture", daemon=True).start()


# ---------------------------------------------------------------------------
# HTTP surface (mounted by the engine server's /debug router).

PERF_DEBUG_PATHS = ("/debug/pipeline", "/debug/profile")


def handle_perf_request(path: str, query: str = "", engine=None) -> tuple[int, str, bytes] | None:
    """Route a GET to the perf X-ray surface. Returns (status,
    content_type, body) or None when *path* is not a perf route.

    - ``/debug/pipeline`` — the windowed stall-attribution report (plus
      live MFU/roofline context when an engine is attached).
    - ``/debug/profile?seconds=N`` — start a jax.profiler trace for N
      seconds (default 2, clamped to [0.05, 120]); 403 unless
      ``KUBEAI_DEBUG_PROFILE=1``, 409 while a capture is in flight.
    """
    import json
    from urllib.parse import parse_qs

    if path == "/debug/pipeline":
        if engine is None:
            body = {"available": False, "reason": "no engine attached"}
        else:
            body = engine.pipeline_report()
        return 200, "application/json", json.dumps(body).encode()
    if path == "/debug/profile":
        if not profiling_enabled():
            return 403, "application/json", json.dumps({
                "error": {
                    "message": "device profiling over HTTP is disabled; set "
                               "KUBEAI_DEBUG_PROFILE=1 on this process to enable",
                    "type": "invalid_request_error",
                }
            }).encode()
        q = parse_qs(query or "")
        try:
            seconds = float((q.get("seconds") or ["2"])[0])
        except ValueError:
            return 400, "application/json", json.dumps(
                {"error": {"message": "seconds must be a number"}}
            ).encode()
        seconds = min(max(seconds, 0.05), 120.0)
        try:
            result = default_profiler.capture(seconds, engine=engine)
        except ProfilerBusy as e:
            return 409, "application/json", json.dumps(
                {"error": {"message": str(e), "type": "conflict"}}
            ).encode()
        except Exception as e:  # profiler unavailable on this backend
            return 500, "application/json", json.dumps(
                {"error": {"message": f"profile capture failed: {e}"}}
            ).encode()
        return 200, "application/json", json.dumps(result).encode()
    return None
