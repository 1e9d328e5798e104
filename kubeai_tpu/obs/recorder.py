"""Flight recorder: bounded ring buffers of completed request timelines
and engine scheduler steps, plus the /debug HTTP surface.

Two inputs, two disciplines:

- ``record_timeline(dict)`` — already-assembled timelines (the proxy's
  SpanBuilder). Direct append under the ring lock.
- ``submit(RequestTrace)`` — raw stamp collections from the engine
  scheduler. The scheduler thread only enqueues; a daemon worker
  assembles marks/token-times into phase spans off-thread, keeping
  span construction out of the decode loop entirely (the ISSUE's
  "record timestamps in the scheduler loop, assemble spans
  off-thread" contract).

The ``/debug`` endpoints both HTTP servers mount:

- ``/debug/requests[?limit=N&id=X]`` — most-recent-first request
  timelines (phase breakdown: where did this request's time go).
- ``/debug/engine[?limit=N]`` — last N scheduler step records (batch
  composition, token counts, kernel flavor, pages in use).
- ``/debug/trace[?limit=N]`` — Chrome trace-event JSON
  (https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU)
  loadable directly in Perfetto / chrome://tracing: one lane per
  request, one lane for the scheduler steps.
"""

from __future__ import annotations

import json
import queue
import threading
import time
from collections import deque
from urllib.parse import parse_qs

from kubeai_tpu.obs.trace import RequestTrace

DEFAULT_TIMELINES = 1024
DEFAULT_STEPS = 512


class FlightRecorder:
    def __init__(self, capacity: int = DEFAULT_TIMELINES, step_capacity: int = DEFAULT_STEPS):
        self._lock = threading.Lock()
        self._timelines: deque[dict] = deque(maxlen=capacity)
        self._steps: deque[dict] = deque(maxlen=step_capacity)
        self._q: "queue.Queue[RequestTrace]" = queue.Queue()
        self._worker: threading.Thread | None = None
        self._worker_lock = threading.Lock()

    # -- ingest ------------------------------------------------------------

    def record_timeline(self, timeline: dict) -> None:
        with self._lock:
            self._timelines.append(timeline)
        # Subscribers (the OTLP exporter) see every recorded timeline;
        # hooks must be O(1) non-blocking (the exporter's is a bounded
        # enqueue) and a raising hook loses only its own copy.
        for fn in list(_timeline_hooks):
            try:
                fn(timeline)
            except Exception:
                pass

    def submit(self, tr: RequestTrace, observe=None) -> None:
        """Enqueue a finished RequestTrace for off-thread assembly
        (scheduler-thread-safe: one queue put). *observe*, if given,
        runs on the worker thread with the trace before assembly — the
        seam for O(tokens) metric derivation (per-token TPOT observes)
        that must stay off the scheduler thread."""
        self._ensure_worker()
        self._q.put((tr, observe))

    def record_step(self, **fields) -> None:
        """Append one scheduler step record (cheap: dict build + deque
        append; deque appends are atomic under the GIL)."""
        fields.setdefault("t_ms", round(time.time() * 1000, 3))
        self._steps.append(fields)

    def _ensure_worker(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            return
        with self._worker_lock:
            if self._worker is not None and self._worker.is_alive():
                return
            self._worker = threading.Thread(
                target=self._drain, name="flight-recorder", daemon=True
            )
            self._worker.start()

    def _drain(self) -> None:
        while True:
            tr, observe = self._q.get()
            try:
                if observe is not None:
                    observe(tr)
                timeline = assemble_request_trace(tr)
                self.record_timeline(timeline)
                # For amend_decode: what the serving thread learns after
                # this goes onto the dict in the ring. Either it has set
                # `late` by now or it will find `timeline` set.
                tr.timeline = timeline
                if tr.late is not None:
                    _amend_decode(timeline, tr.late)
            except Exception:
                pass  # a malformed trace must never kill the worker
            finally:
                self._q.task_done()

    # -- read --------------------------------------------------------------

    def snapshot(self, limit: int | None = None, wait: float = 1.0) -> list[dict]:
        """Most-recent-first timelines. Waits (bounded) for the assembly
        queue to drain so a caller that just finished a request sees it."""
        deadline = time.monotonic() + wait
        while self._q.unfinished_tasks and time.monotonic() < deadline:
            time.sleep(0.002)
        with self._lock:
            out = list(self._timelines)
        out.reverse()
        return out[:limit] if limit else out

    def engine_steps(self, limit: int | None = None) -> list[dict]:
        out = list(self._steps)
        out.reverse()
        return out[:limit] if limit else out

    def clear(self) -> None:
        with self._lock:
            self._timelines.clear()
        self._steps.clear()

    # -- export ------------------------------------------------------------

    def chrome_trace(self, limit: int | None = None) -> dict:
        """Chrome trace-event JSON (``X`` complete events, µs units).
        Each request timeline gets its own tid lane; the scheduler step
        records land on a dedicated lane so per-request phases line up
        against batch composition in Perfetto."""
        events: list[dict] = []
        timelines = self.snapshot(limit)
        for tid, tl in enumerate(timelines, start=1):
            name = f"{tl.get('component', '?')} {tl.get('request_id', '')}".strip()
            events.append({
                "ph": "M", "pid": 1, "tid": tid, "name": "thread_name",
                "args": {"name": name},
            })
            events.append({
                "ph": "X", "pid": 1, "tid": tid,
                "name": f"request:{tl.get('outcome') or '?'}",
                "ts": round(tl["start_ms"] * 1000, 1),
                "dur": round(tl["duration_ms"] * 1000, 1),
                "args": {
                    "trace_id": tl.get("trace_id", ""),
                    "model": tl.get("model", ""),
                    **tl.get("attrs", {}),
                },
            })
            for ph in tl.get("phases", []):
                events.append({
                    "ph": "X", "pid": 1, "tid": tid, "name": ph["name"],
                    "ts": round(ph["start_ms"] * 1000, 1),
                    "dur": round(ph["duration_ms"] * 1000, 1),
                    "args": ph.get("attrs", {}),
                })
        steps = self.engine_steps()
        if steps:
            events.append({
                "ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
                "args": {"name": "engine scheduler"},
            })
            for st in steps:
                args = {k: v for k, v in st.items() if k not in ("t_ms", "dur_ms", "kind")}
                dur_ms = st.get("dur_ms", 0.0)
                events.append({
                    "ph": "X", "pid": 1, "tid": 0,
                    "name": st.get("kind", "step"),
                    # t_ms is stamped when the step is RECORDED (its
                    # end); the complete-event ts is its start.
                    "ts": round((st["t_ms"] - dur_ms) * 1000, 1),
                    "dur": round(dur_ms * 1000, 1),
                    "args": args,
                })
                # Counter tracks: stalls and occupancy visible INLINE on
                # the timeline (Perfetto renders "C" events as graphs),
                # not only in the /debug/pipeline aggregate.
                ts_end = round(st["t_ms"] * 1000, 1)
                if st.get("kind") != "decode_chunk":
                    continue
                slots = st.get("slots")
                if isinstance(slots, (list, tuple)):
                    events.append({
                        "ph": "C", "pid": 1, "name": "slot occupancy",
                        "ts": ts_end, "args": {"active": len(slots)},
                    })
                if "pages_total" in st and "pages_used" in st:
                    events.append({
                        "ph": "C", "pid": 1, "name": "free KV pages",
                        "ts": ts_end,
                        "args": {"free": st["pages_total"] - st["pages_used"]},
                    })
                if "fetch_wait_ms" in st:
                    events.append({
                        "ph": "C", "pid": 1, "name": "fetch_wait_ms",
                        "ts": ts_end, "args": {"ms": st["fetch_wait_ms"]},
                    })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


def _amend_decode(timeline: dict, attrs: dict) -> None:
    """*attrs* into *timeline*'s ``decode`` phase, as a new dict put in
    the old one's place: a reader serializing the timeline meanwhile
    keeps the dict it began with."""
    for phase in timeline["phases"]:
        if phase["name"] == "decode":
            phase["attrs"] = {**phase["attrs"], **attrs}


def amend_decode(tr: RequestTrace, attrs: dict) -> None:
    """Attrs of the ``decode`` phase that are known only after the
    scheduler let the trace go (the serving thread's delivery record: its
    last write comes after the request's end). They go onto the trace, for
    an assembly still to come, and onto the assembled timeline where there
    is one: of the two threads at least one sees what the other wrote."""
    tr.late = attrs
    timeline = tr.timeline
    if timeline is not None:
        _amend_decode(timeline, attrs)


def assemble_request_trace(tr: RequestTrace) -> dict:
    """RequestTrace (raw marks + token stamps) -> timeline dict with the
    canonical engine phases:

    - ``receive`` entry of the POST -> submit (body read, JSON parse,
      tokenization), only where the serving thread stamped its entry;
      it lies BEFORE the timeline's ``start_ms``
    - ``queue``   submit -> prefill dispatch: the admission turn, then
      slots, then pages (attrs ``turn_ms`` / ``slots_ms`` / ``pages_ms``
      add up to the phase)
    - ``prefill`` prefill dispatch -> first emitted token
    - ``decode``  first token -> terminal (attrs carry per-token
      offsets, so TTFT/TPOT percentiles are recomputable from the
      recorded timeline alone — bench.py does exactly that; and, of a
      streamed request, ``deliver_*_ms``: hand-over to bytes written)
    """
    base = tr.t0_wall - tr.t0_mono

    def ms(t_mono: float) -> float:
        return round((base + t_mono) * 1000, 3)

    end = tr.end_mono if tr.end_mono is not None else time.monotonic()
    phases: list[dict] = []
    t_prefill = tr.first_mark("prefill")
    t_first_tok = tr.tokens[0] if tr.tokens else None
    if tr.received is not None:
        phases.append({
            "name": "receive",
            "start_ms": ms(tr.received),
            "duration_ms": round((tr.t0_mono - tr.received) * 1000, 3),
            "attrs": {k: tr.attrs[k] for k in ("body_bytes", "prompt_tokens") if k in tr.attrs},
        })
    phases.append({
        "name": "queue",
        "start_ms": ms(tr.t0_mono),
        "duration_ms": round(((t_prefill if t_prefill is not None else end) - tr.t0_mono) * 1000, 3),
        "attrs": {} if tr.queue_parts is None else {
            k: round(s * 1000, 3) for k, s in zip(("turn_ms", "slots_ms", "pages_ms"), tr.queue_parts)
        },
    })
    if t_prefill is not None:
        phases.append({
            "name": "prefill",
            "start_ms": ms(t_prefill),
            "duration_ms": round(
                ((t_first_tok if t_first_tok is not None else end) - t_prefill) * 1000, 3
            ),
            "attrs": {k: tr.attrs[k] for k in ("prompt_tokens", "reuse_tokens") if k in tr.attrs},
        })
    if t_first_tok is not None:
        gaps = [
            (b - a) * 1000 for a, b in zip(tr.tokens, tr.tokens[1:])
        ]
        decode_attrs: dict = {
            "tokens": len(tr.tokens),
            # Offsets from request start (ms): TTFT = offsets[0], TPOT =
            # consecutive diffs. Rounded to keep /debug payloads small.
            "token_offsets_ms": [
                round((t - tr.t0_mono) * 1000, 2) for t in tr.tokens
            ],
        }
        if gaps:
            decode_attrs["tpot_ms_mean"] = round(sum(gaps) / len(gaps), 3)
        if tr.late is not None:
            decode_attrs.update(tr.late)
        phases.append({
            "name": "decode",
            "start_ms": ms(t_first_tok),
            "duration_ms": round((end - t_first_tok) * 1000, 3),
            "attrs": decode_attrs,
        })
    return {
        "trace_id": tr.ctx.trace_id,
        "span_id": tr.ctx.span_id,
        "request_id": tr.ctx.request_id,
        "component": tr.component,
        "model": tr.model,
        "outcome": tr.outcome or "unknown",
        "start_ms": ms(tr.t0_mono),
        "duration_ms": round((end - tr.t0_mono) * 1000, 3),
        "attrs": {k: v for k, v in tr.attrs.items()},
        "phases": phases,
    }


default_recorder = FlightRecorder()

# Process-global timeline subscribers: every FlightRecorder instance
# (the default one, per-test ones) feeds them, so an installed OTLP
# exporter sees spans no matter which recorder assembled them.
_timeline_hooks: list = []


def add_timeline_hook(fn) -> None:
    if fn not in _timeline_hooks:
        _timeline_hooks.append(fn)


def remove_timeline_hook(fn) -> None:
    try:
        _timeline_hooks.remove(fn)
    except ValueError:
        pass


# ---------------------------------------------------------------------------
# Shared /debug HTTP surface (mounted by both the operator's OpenAI
# server and the engine server).

DEBUG_PATHS = ("/debug/requests", "/debug/engine", "/debug/trace")

# Extra named sections merged into the /debug/engine payload (e.g. the
# cold-start phase timeline). Providers are zero-arg callables returning
# JSON-able values; latest registration per key wins, and a failing
# provider drops only its own section — the debug plane must never 500
# because one data source broke.
_engine_debug_sections: dict[str, object] = {}


def register_engine_debug_section(key: str, fn) -> None:
    _engine_debug_sections[key] = fn


def unregister_engine_debug_section(key: str, fn) -> None:
    """Remove *fn* IF it is still the current provider for *key* — the
    seam a dying owner (a stopped engine) uses so this process-global
    dict stops pinning it, without clobbering a newer owner's
    registration (mirrors CallbackGauge.clear_callback)."""
    if _engine_debug_sections.get(key) is fn:
        _engine_debug_sections.pop(key, None)


def handle_debug_request(
    path: str, query: str = "", recorder: FlightRecorder | None = None
) -> tuple[int, str, bytes] | None:
    """Route a GET to the debug surface. Returns (status, content_type,
    body) or None when *path* is not a debug route."""
    rec = recorder or default_recorder
    q = parse_qs(query or "")

    def intq(name, default):
        try:
            return int(q[name][0])
        except (KeyError, ValueError, IndexError):
            return default

    if path == "/debug/requests":
        limit = intq("limit", 50)
        wanted = (q.get("id") or [None])[0]
        tenant = (q.get("tenant") or [None])[0]
        tls = rec.snapshot(limit=None if (wanted or tenant) else limit)
        if wanted:
            tls = [
                t for t in tls
                if wanted in (t.get("trace_id"), t.get("request_id"))
            ]
        if tenant:
            # Tenant-attributed timelines (the proxy/engine stamp the
            # hashed tenant id into span attrs): one tenant's requests
            # isolated from the ring in one GET.
            tls = [
                t for t in tls
                if (t.get("attrs") or {}).get("tenant") == tenant
            ]
        if wanted or tenant:
            tls = tls[:limit]
        body = json.dumps({"requests": tls}).encode()
        return 200, "application/json", body
    if path == "/debug/engine":
        payload = {"steps": rec.engine_steps(intq("limit", 100))}
        # Snapshot: install() can register a section from another
        # thread (a parked replica's attach) mid-GET — iterating the
        # live dict would raise "changed size during iteration".
        for key, fn in list(_engine_debug_sections.items()):
            try:
                payload[key] = fn()
            except Exception:
                pass
        body = json.dumps(payload).encode()
        return 200, "application/json", body
    if path == "/debug/trace":
        body = json.dumps(rec.chrome_trace(intq("limit", 200))).encode()
        return 200, "application/json", body
    return None


# ---------------------------------------------------------------------------
# The /debug index: one GET listing every debug surface a server mounts
# with a one-line description — ten-plus endpoints exist and were only
# discoverable via docs. Keyed by which server ("operator" | "engine")
# serves each route; descriptions stay one line by contract (the full
# story lives in docs/observability.md).

DEBUG_INDEX: tuple[tuple[str, str, str], ...] = (
    ("/debug/requests", "both",
     "completed request timelines, most recent first (?limit=&id=&tenant=)"),
    ("/debug/engine", "both",
     "last scheduler step records: batch composition, tokens, kernel, KV pages (?limit=)"),
    ("/debug/trace", "both",
     "Chrome trace-event JSON for Perfetto: request lanes + scheduler lane (?limit=)"),
    ("/debug/faults", "both",
     "fault-injection failpoints: list armed faults; arm/disarm via ?set=/?clear= (gated by KUBEAI_DEBUG_FAULTS)"),
    ("/debug/incidents", "both",
     "incident black box: triggered cross-layer snapshots (?id= for the full document; operator-side)"),
    ("/debug/canary", "both",
     "synthetic canary prober state per model (operator-side)"),
    ("/debug/tenants", "both",
     "per-tenant usage metering: rolling-window share, tokens, latency attainment, cost proxies, heavy-hitter ranking"),
    ("/debug/qos", "both",
     "QoS scheduling: per-class queue depth/wait/shed, per-tenant fair-share deficits, preemption + resume counters"),
    ("/debug/endpoints", "operator",
     "per-model circuit-breaker view: endpoint states, consecutive failures, in-flight"),
    ("/debug/routing", "operator",
     "CHWBL ring snapshot + recent pick distribution per model"),
    ("/debug/health", "operator",
     "latency health scoring: per-endpoint TTFT p95/EWMA, pick weights, slow-start ramp, soft-ejection state"),
    ("/debug/autoscaler", "operator",
     "scaling decision audit: one record per tick per model/pool (?limit=&model=)"),
    ("/debug/fleet", "operator",
     "fleet saturation: per-endpoint engine scrapes, per-model aggregates, capacity headroom"),
    ("/debug/slo", "operator",
     "SLO monitor report: attainment + burn rate per objective over the rolling window"),
    ("/debug/history", "both",
     "embedded time-series history: tiered metric trajectories with gap markers (?series=&since=&step=)"),
    ("/debug/forecast", "both",
     "predictive telemetry: per-model forecast curves, prediction intervals, accuracy, anomaly state (?model=; operator-side)"),
    ("/debug/logs", "both",
     "recent WARNING+ structured log records with trace correlation (?level=&since=&trace=&limit=)"),
    ("/debug/pipeline", "engine",
     "windowed decode stall attribution (dispatch/host_overlap/fetch_wait/emit) + live MFU/roofline"),
    ("/debug/profile", "engine",
     "on-demand jax.profiler device trace (?seconds=; gated by KUBEAI_DEBUG_PROFILE)"),
)


def debug_index_response(server: str) -> tuple[int, str, bytes]:
    """The ``GET /debug`` payload for one server kind ("operator" |
    "engine"): every route it mounts, with descriptions."""
    endpoints = [
        {"path": p, "description": desc}
        for p, kind, desc in DEBUG_INDEX
        if kind in ("both", server)
    ]
    body = json.dumps({
        "server": server,
        "endpoints": endpoints,
        "docs": "docs/observability.md",
    }).encode()
    return 200, "application/json", body
