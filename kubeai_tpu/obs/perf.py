"""Perf X-ray: roofline/MFU accounting, step-pipeline stall attribution,
and on-demand device profiler capture.

Five pieces, all dependency-free (jax is imported lazily: by the profiler
capture, and for the TraceAnnotation of a scheduler segment):

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
- **PipelineStallTracker** — ``segment(cause)`` stamps each segment of
  the scheduler loop once and feeds the ``GET /debug/pipeline`` report,
  the ``kubeai_engine_stall_seconds_total{cause}`` counter, the engine's
  step records and the ``sched.<cause>`` events of a profiler trace.
- **GcWatch** — the interpreter's cyclic collector on the record: one
  ``gc.callbacks`` function while an engine loop runs, feeding
  ``kubeai_engine_gc_seconds_total{generation}``, the ``gc_ms`` of the
  step a collection fell in, and a ``host.gc`` event of a profiler trace.
- **ProfilerCapture** + ``handle_perf_request`` — ``GET
  /debug/profile?seconds=N`` starts a ``jax.profiler`` trace (single-
  flight; opt-in via ``KUBEAI_DEBUG_PROFILE=1``, mirroring the
  ``/debug/faults`` arming gate) and returns the artifact path; on a
  gang, rank 0 fans the capture out to followers over the existing
  dispatch control channel so every rank's trace covers the same window.
"""

from __future__ import annotations

import gc
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
    """(total, active) parameter counts of a ModelConfig, analytically:
    its family's (kubeai_tpu/models/__init__.py::SEAM, `param_counts`)."""
    from kubeai_tpu.models import family  # lazily: the families import jax

    return family(mc).param_counts(mc)


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
    # FLOPs of one (query, key) pair inside the mask in one layer (score
    # and weighted value over every query head): set for a family whose
    # engine counts its masked pairs (kubeai_engine_attn_pairs_total), so
    # that MFU includes the attention its masks leave; 0: the few % the
    # docstring above speaks of are left out.
    attn_flops_per_pair: float = 0.0

    @classmethod
    def from_model_config(cls, mc, quantization: str = "", weight_bytes: float | None = None) -> "PerfModel":
        """*weight_bytes*, when given (e.g. measured off the live param
        tree), overrides the analytic estimate; otherwise params are
        costed at 1 byte for int8 weight-only quantization, else the
        model dtype's width."""
        from kubeai_tpu.models import family  # lazily: the families import jax

        total, active = param_counts(mc)
        if weight_bytes is None:
            per_param = 1 if quantization == "int8" else _DTYPE_BYTES.get(mc.dtype, 2)
            weight_bytes = total * per_param
        return cls(
            param_count=total,
            active_params=active,
            flops_per_token=2.0 * active,
            weight_bytes=float(weight_bytes),
            # The engine counts pairs for a family with a window pool (SEAM, `window_pool_tokens`).
            attn_flops_per_pair=4.0 * mc.num_heads * mc.head_dim_ if family(mc).window_pool_tokens(mc) else 0.0,
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

    def mfu(self, tokens_per_sec: float, peak_flops: float | None, pairs_per_sec: float = 0.0) -> float:
        """Model FLOPs utilization (fraction of peak) at a decode rate,
        with the masked attention of *pairs_per_sec* (query, key) pairs
        summed over layers where the engine counts them."""
        if not peak_flops:
            return 0.0
        return (tokens_per_sec * self.flops_per_token + pairs_per_sec * self.attn_flops_per_pair) / peak_flops


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

# The scheduler loop's segments: one vocabulary for the counter
# ``kubeai_engine_stall_seconds_total{cause}``, GET /debug/pipeline, the
# step records of /debug/engine and the ``sched.<cause>`` events the loop
# writes into a profiler trace (host spans on the device trace's clock).
# Segments are DISJOINT wall-time slices of the scheduler thread — each is
# stamped once, at its two ends, by ``PipelineStallTracker.segment`` — and
# with ``other``, the time between one segment's end stamp and the next
# one's start stamp, the per-cause seconds sum to the loop's wall time:
#   sweep         deadline / QoS-budget / KV-park sweeps at the top of an
#                 iteration, the recompile counter at its end
#   admit         queue drain and slot + KV page planning (the prefill and
#                 kv_transfer segments nest inside and are not counted twice)
#   prefill       prefill dispatch calls (group and chunked)
#   kv_transfer   KV restore admissions (engine/kvstate.py): blob
#                 validation + page upload + slot rebuild — the import cost
#                 restore pays instead of the prefill cost replay would
#   dispatch      argument upload + broadcast + async jit call of a chunk
#   host_overlap  first-token emission for admitted requests + aux work
#                 between a dispatch and its fetch — time the pipelining
#                 successfully hid behind device compute
#   fetch_wait    pure host block inside device_get: a chunk's results in
#                 _process_chunk, an admission round's first tokens in
#                 _emit_admitted (device compute + result transfer
#                 outlasting the overlapped host work)
#   emit          detokenize / stop-check / client delivery
#   idle          nothing to do: the wait for the next request
#   other         under no segment (a trace shows nothing there): the
#                 loop's statements between segments, and what falls on
#                 them — the wait to get the interpreter lock back from
#                 the serving threads after an emission woke them up,
#                 recovery after a failed step
STALL_CAUSES = (
    "sweep", "admit", "prefill", "kv_transfer", "dispatch", "host_overlap",
    "fetch_wait", "emit", "idle", "other",
)

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
    "sweep": "host-bound on the per-iteration sweeps (deadlines, QoS budgets, KV park)",
    "admit": "host-bound on admission: queue drain and KV page planning dominate",
    "idle": "idle: the scheduler mostly waits for requests",
    "other": (
        "under no segment: the scheduler thread mostly waits for the "
        "interpreter lock (serving threads delivering tokens) or recovers"
    ),
}

_annotation = None


def _trace_annotation():
    """jax.profiler.TraceAnnotation (a TraceMe: a flag test while no
    profiler runs), resolved once; a null context where jax is absent."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation as _annotation
        except Exception:  # pragma: no cover - jax is a hard dependency of the engine
            import contextlib

            _annotation = lambda name, **attrs: contextlib.nullcontext()  # noqa: E731
    return _annotation


def trace_mark(name: str, **attrs) -> None:
    """A mark on the calling thread's line of a profiler trace: an event
    with *attrs* and no length, where something ENDED whose length is one
    of the attrs (a flag test while no profiler runs, as every event
    through ``_trace_annotation``)."""
    with _trace_annotation()(name, **attrs):
        pass


class GcWatch:
    """The cyclic collector's runs, stamped by one ``gc.callbacks``
    function that is installed while some engine loop runs (``install`` /
    ``remove``, counted). A collection stops every Python thread of the
    process, whichever thread set it off, so its seconds are the
    process's: ``seconds`` by generation and ``total`` are cumulative and
    written by the callback alone (collections never overlap). The
    callback takes NO lock and feeds no metric: a collection can begin
    between any two bytecodes of its thread, also inside a metric's own
    locked region. ``flush`` (the scheduler loop, once an iteration: one
    comparison where nothing was collected) moves what is new to
    ``kubeai_engine_gc_seconds_total{generation}``; a tracker reads
    ``total`` at ``end_step`` for the step's ``gc_ms``; while a profiler
    runs, a ``host.gc`` event lies on the collecting thread's line."""

    def __init__(self, registry=None):
        self.seconds = [0.0, 0.0, 0.0]
        self.total = 0.0
        self._fed = [0.0, 0.0, 0.0]
        self._fed_total = 0.0
        self._open = None  # (start stamp, annotation) of the collection under way
        self._users = 0
        self._lock = threading.Lock()  # install / remove / flush; never the callback
        self._counter = (registry or default_registry).counter(
            "kubeai_engine_gc_seconds_total",
            "seconds the interpreter's cyclic garbage collector ran, by generation "
            "(every Python thread of the process stands still meanwhile); diagnosis: "
            "a stall with no collection beside it was not the collector's",
        )

    def install(self) -> None:
        with self._lock:
            self._users += 1
            if self._users == 1:
                gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        with self._lock:
            self._users -= 1
            if self._users == 0 and self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = _trace_annotation()("host.gc", generation=info["generation"])
            ann.__enter__()
            self._open = (time.monotonic(), ann)
        elif self._open is not None:
            (t0, ann), self._open = self._open, None
            seconds = time.monotonic() - t0
            self.seconds[info["generation"]] += seconds
            self.total += seconds
            if getattr(ann, "is_enabled", bool)():  # a profiler runs: what it freed, on the event
                ann.set_metadata(collected=info["collected"])
            ann.__exit__(None, None, None)

    def flush(self) -> None:
        if self.total == self._fed_total or not self._lock.acquire(blocking=False):
            return
        try:
            self._fed_total = self.total
            for gen, seconds in enumerate(self.seconds):
                if seconds > self._fed[gen]:
                    self._counter.inc(seconds - self._fed[gen], labels={"generation": str(gen)})
                    self._fed[gen] = seconds
        finally:
            self._lock.release()


gc_watch = GcWatch()


class Segment:
    """One stamped slice of the scheduler thread's time (see
    ``PipelineStallTracker.segment``). After exit: ``t0``/``t1`` are the
    stamps and ``seconds`` the time between them."""

    __slots__ = ("_tracker", "cause", "_ann", "t0", "t1", "seconds", "_own", "_resumed")

    def __init__(self, tracker: "PipelineStallTracker", cause: str, attrs: dict):
        self._tracker = tracker
        self.cause = cause
        self._ann = _trace_annotation()(f"sched.{cause}", **attrs)
        self.t0 = self.t1 = self.seconds = self._own = self._resumed = 0.0

    def __enter__(self) -> "Segment":
        self._ann.__enter__()
        tracker = self._tracker
        stack = tracker._stack
        self.t0 = self._resumed = now = tracker._clock()
        if stack:  # the enclosing segment stops counting while this one runs
            stack[-1]._own += now - stack[-1]._resumed
        elif tracker._last_end is not None:
            # Since the last segment ended: the same two stamps, no third.
            tracker._add("other", now - tracker._last_end, now)
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        stack = self._tracker._stack
        self.t1 = now = self._tracker._clock()
        self.seconds = now - self.t0
        stack.pop()
        if stack:
            stack[-1]._resumed = now
        else:
            self._tracker._last_end = now
        self._tracker._add(self.cause, self._own + now - self._resumed, now)
        self._ann.__exit__(*exc)


class PipelineStallTracker:
    """Where the scheduler thread's wall time goes. The loop wraps each of
    its segments in ``segment(cause)``; the one pair of stamps taken there
    feeds the ``kubeai_engine_stall_seconds_total{cause}`` counter, the
    sliding window behind ``GET /debug/pipeline`` (``report``), the step
    record the engine hands to /debug/engine (``end_step``) and, while a
    profiler runs, a ``sched.<cause>`` event on the scheduler thread's
    line of the trace."""

    SLOWEST_KEPT = 8  # steps shown under ``slowest_steps``
    SLOWEST_HORIZON = 600.0  # seconds a slow step is remembered

    def __init__(self, window: float = 60.0, clock=time.monotonic, registry=None):
        self.window = window
        self._clock = clock
        self._lock = threading.Lock()
        # (t_end, cause, ms) per segment; (t, kind, None) per finished step
        self._records: deque[tuple[float, str, float | None]] = deque()
        self._stack: list[Segment] = []  # open segments (scheduler thread only)
        self._last_end: float | None = None  # end stamp of the last outermost segment
        self._step: dict[str, float] = {}  # ms by cause since the last end_step
        self._gc_seen = gc_watch.total  # the collector's seconds at the last end_step
        # The few slowest steps of the last ten minutes, (total ms, end
        # stamp, kind, ms by cause): what an operator asks after a stall.
        # ``_slow_floor`` is what a step must outlast to get in, until the
        # oldest kept one passes the horizon (``_slow_expires``).
        self._slowest: list[tuple[float, float, str, dict[str, float]]] = []
        self._slow_floor, self._slow_expires = 0.0, float("inf")
        reg = registry or default_registry
        self._counter = reg.counter(
            "kubeai_engine_stall_seconds_total",
            "scheduler thread wall time by segment (sweep | admit | prefill | "
            "kv_transfer | dispatch | host_overlap | fetch_wait | emit | idle | "
            "other): disjoint, together the loop's whole wall time — the aggregate "
            "behind GET /debug/pipeline and the sched.<cause> events of a profiler trace",
        )

    def segment(self, cause: str, **attrs) -> Segment:
        """Context manager around one segment of the scheduler loop.
        *attrs* go onto the trace event only. A segment opened inside
        another suspends the outer one: seconds are never counted twice."""
        return Segment(self, cause, attrs)

    def end_step(self, kind: str) -> dict[str, float]:
        """Close the current step's record: ms by cause of the segments
        that ended since the last call, counted in the window as one step
        of *kind* (decode_chunk | prefill_group | prefill_chunked |
        kv_restore). ``gc_ms`` beside the causes, where the cyclic
        collector ran since the last call (GcWatch): inside the causes'
        time, not beside it."""
        step, self._step = self._step, {}
        now = self._clock()
        total = sum(step.values()) - step.get("idle", 0.0)  # a wait for requests is no slow step
        gc_total = gc_watch.total
        if gc_total != self._gc_seen:
            step["gc_ms"] = (gc_total - self._gc_seen) * 1000.0
            self._gc_seen = gc_total
        with self._lock:
            self._records.append((now, kind, None))
            self._prune_locked(now)
            if total > self._slow_floor or now > self._slow_expires:
                self._keep_slow_locked(total, now, kind, step)
        return step

    def _keep_slow_locked(self, total: float, now: float, kind: str, step: dict[str, float]) -> None:
        """Off the hot path: only a step slower than the least kept one, or
        the first after a kept one passed the horizon, gets here."""
        kept = [e for e in self._slowest if e[1] >= now - self.SLOWEST_HORIZON]
        kept.append((total, now, kind, dict(step)))
        kept.sort(key=lambda e: -e[0])
        self._set_slowest_locked(kept[: self.SLOWEST_KEPT])

    def _set_slowest_locked(self, kept: list) -> None:
        self._slowest = kept
        self._slow_floor = kept[-1][0] if len(kept) == self.SLOWEST_KEPT else 0.0
        self._slow_expires = min((e[1] for e in kept), default=float("inf")) + self.SLOWEST_HORIZON

    def slowest_steps(self, now: float | None = None) -> list[dict]:
        """The slowest steps of the last ten minutes, slowest first:
        kind, seconds since the step ended (``age_s``; ``end_monotonic`` is
        the stamp itself, this host's CLOCK_MONOTONIC), total ms (``idle``
        left out), ms by cause of the segments that ended inside the
        step and, where the cyclic collector ran inside it, ``gc_ms``."""
        now = self._clock() if now is None else now
        with self._lock:
            kept = [e for e in self._slowest if e[1] >= now - self.SLOWEST_HORIZON]
            if len(kept) != len(self._slowest):  # forgotten: the floor falls with them
                self._set_slowest_locked(kept)
        return [
            {
                "kind": kind, "age_s": round(now - end, 3), "end_monotonic": round(end, 6),
                "total_ms": round(total, 3),
                "ms": {c: round(ms, 3) for c, ms in step.items() if c != "gc_ms"},
                **({"gc_ms": round(step["gc_ms"], 3)} if "gc_ms" in step else {}),
            }
            for total, end, kind, step in kept
        ]

    def _add(self, cause: str, seconds: float, now: float) -> None:
        seconds = max(seconds, 0.0)
        if seconds:
            self._counter.inc(seconds, labels={"cause": cause})
        ms = seconds * 1000.0
        self._step[cause] = self._step.get(cause, 0.0) + ms
        with self._lock:
            self._records.append((now, cause, ms))
            self._prune_locked(now)

    def _prune_locked(self, now: float) -> None:
        cutoff = now - self.window
        while self._records and self._records[0][0] < cutoff:
            self._records.popleft()

    def report(self, now: float | None = None) -> dict:
        """The /debug/pipeline payload: per-cause ms + fraction of
        accounted time (fractions sum to 1.0 by construction), step
        counts by kind, and a human interpretation of the dominant
        cause. ``coverage`` is the share of the observed wall span that
        lay under a named segment (everything but ``other``)."""
        now = self._clock() if now is None else now
        with self._lock:
            self._prune_locked(now)
            records = list(self._records)
        cause_ms = {c: 0.0 for c in STALL_CAUSES}
        steps: dict[str, int] = {}
        first = None  # when the oldest segment of the window began
        for t, name, ms in records:
            if ms is None:
                steps[name] = steps.get(name, 0) + 1
                continue
            cause_ms[name] = cause_ms.get(name, 0.0) + ms
            if first is None:
                first = t - ms / 1000.0
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
        if first is not None and now > first:
            named = accounted - cause_ms["other"]
            out["coverage"] = round(min(named / ((now - first) * 1000.0), 1.0), 4)
        if accounted:
            dominant = max(cause_ms, key=lambda c: cause_ms[c])
            out["dominant_cause"] = dominant
            pct = round(100.0 * cause_ms[dominant] / accounted)
            out["interpretation"] = f"{pct}% {dominant} → {_INTERPRET[dominant]}"
        out["slowest_steps"] = self.slowest_steps(now)
        return out


# ---------------------------------------------------------------------------
# On-demand device profiler capture.


PROFILE_WINDOW_EVENT = "profile.window"  # the capture's own span, on the capturing thread's line


def profiling_enabled() -> bool:
    """Whether /debug/profile may start a device trace. Off by default —
    a trace burns device attention and disk, so it requires the explicit
    ``KUBEAI_DEBUG_PROFILE=1`` opt-in (mirroring the /debug/faults
    arming gate). Re-read per request so tests can toggle it."""
    return os.environ.get("KUBEAI_DEBUG_PROFILE", "") in ("1", "true", "yes")


class _TraceSession:
    """One profiler trace, started on construction; ``stop(out_dir)`` writes
    it as ``<out_dir>/plugins/profile/<time>/<host>.xplane.pb``, where
    TensorBoard's profile plugin and ``jax.profiler.ProfileData`` look.

    Only the .xplane.pb is written: ``jax.profiler.stop_trace`` also
    converts the whole trace to a trace-viewer JSON and gzips it, which on a
    v5e took most of the 23 s a 4 s capture of a 7B engine needed after its
    traced seconds (PERF.md, PR 24). *python_tracer* is the call's choice
    (``/debug/profile?python_tracer=0|1``): the Python tracer hooks every
    call of every Python thread of the process, and the device planes and
    the host's TraceMe events (the scheduler's ``sched.*``,
    ``profile.window``) are there without it; it is on by default because
    the benchmark's ``--trace 1`` takes the traced interval from that
    tracer's record of the capture's sleep. Where this jax has no
    ``ProfilerSession`` to stop by hand, it is ``start_trace`` /
    ``stop_trace`` after all. ``stop()`` returns the .xplane.pb's path
    (None where it cannot tell)."""

    def __init__(self, jax, out_dir: str, python_tracer: bool = True):
        self._jax, self._out_dir, self._session = jax, out_dir, None
        options = None
        if not python_tracer:
            try:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
            except AttributeError:  # a jax without ProfileOptions
                options = None
        try:
            from jax._src.lib import _profiler

            self._session = _profiler.ProfilerSession(options) if options else _profiler.ProfilerSession()
        except Exception:  # no such class here, or it refused: the public pair
            jax.profiler.start_trace(out_dir, **({"profiler_options": options} if options else {}))

    def stop(self) -> str | None:
        if self._session is None:
            self._jax.profiler.stop_trace()
            import glob

            found = glob.glob(os.path.join(self._out_dir, "plugins", "profile", "*", "*.xplane.pb"))
            return max(found, key=os.path.getmtime, default=None)
        xspace = self._session.stop()  # the trace, as a serialized XSpace
        import socket

        run_dir = os.path.join(
            self._out_dir, "plugins", "profile", time.strftime("%Y_%m_%d_%H_%M_%S")
        )
        os.makedirs(run_dir, exist_ok=True)
        path = os.path.join(run_dir, socket.gethostname() + ".xplane.pb")
        with open(path, "wb") as f:
            f.write(xspace)
        return path


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

    def capture(self, seconds: float, engine=None, out_dir: str | None = None,
                python_tracer: bool = True) -> dict:
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

            t_start = time.monotonic()
            session = _TraceSession(jax, out_dir, python_tracer)
            t_traced = time.monotonic()
            try:
                # The traced interval, as an event of the trace itself: a
                # reader takes its window from here, not from the first
                # and last device operation.
                with _trace_annotation()(PROFILE_WINDOW_EVENT, seconds=seconds):
                    window = [time.monotonic()]
                    time.sleep(seconds)
                    window.append(time.monotonic())
            finally:
                t_stop = time.monotonic()
                xplane = session.stop()  # collects and writes: the long part
                t_written = time.monotonic()
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
                # What a reader of the trace needs and cannot guess: the
                # file, the host event that spans the traced interval, and
                # whether the Python tracer ran beside it.
                "xplane": xplane,
                "window_event": PROFILE_WINDOW_EVENT,
                # This host's CLOCK_MONOTONIC just inside that event's two
                # ends: any monotonic stamp of this host (a timeline's
                # phases, slowest_steps[].end_monotonic, a client's own
                # records) goes onto the trace's clock by these alone.
                "window_monotonic": window,
                "python_tracer": bool(python_tracer),
                # What the capture cost beyond the traced seconds.
                "start_seconds": round(t_traced - t_start, 3),
                "stop_seconds": round(t_written - t_stop, 3),
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
    - ``/debug/profile?seconds=N&python_tracer=0|1`` — start a
      jax.profiler trace for N seconds (default 2, clamped to [0.05,
      120]), with the Python tracer (default) or without; 403 unless
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
        tracer = (q.get("python_tracer") or ["1"])[0]
        if tracer not in ("0", "1"):
            return 400, "application/json", json.dumps(
                {"error": {"message": "python_tracer must be 0 or 1"}}
            ).encode()
        try:
            result = default_profiler.capture(seconds, engine=engine, python_tracer=tracer == "1")
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
