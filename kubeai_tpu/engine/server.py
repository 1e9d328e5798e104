"""OpenAI-compatible HTTP server wrapping the engine.

The in-pod API surface the reference expects from its engine containers
(vLLM-compatible; ref: internal/modelcontroller/engine_vllm.go probes on
:8000, internal/vllmclient/client.go adapter RPCs):

    GET  /health /healthz /readyz     liveness+readiness
    GET  /metrics                     Prometheus text (queue depth etc.)
    GET  /v1/models                   served model + loaded adapters
    POST /v1/completions              (+ SSE streaming)
    POST /v1/chat/completions         (+ SSE streaming)
    POST /v1/load_lora_adapter        {lora_name, lora_path}
    POST /v1/unload_lora_adapter      {lora_name}

Implementation is stdlib ThreadingHTTPServer: each connection gets a
thread that blocks on the engine's per-request event queue — the engine
itself runs a single scheduler thread, so concurrency here is I/O-bound
fan-in, which Python threads handle fine.
"""

from __future__ import annotations

import argparse
import json
import dataclasses
import os
import queue
import re
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from kubeai_tpu.engine.core import Engine, EventQueue, _name_os_thread
from kubeai_tpu.engine import kvstate
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.faults import FaultError, fault, handle_faults_request, set_thread_scope
from kubeai_tpu.metrics import default_registry
from kubeai_tpu.metrics.buildinfo import set_build_info
from kubeai_tpu.obs import (
    debug_index_response,
    extract_context,
    handle_canary_request,
    handle_debug_request,
    handle_forecast_request,
    handle_history_request,
    handle_incident_request,
    handle_logs_request,
    handle_tenant_request,
    install_log_ring,
)
from kubeai_tpu.obs.logs import (
    bind_log_context,
    get_logger,
    set_log_context,
    setup_logging,
)
from kubeai_tpu.obs.otel import maybe_start_exporter, uninstall_exporter
from kubeai_tpu.obs.history import (
    HistoryStore,
    RegistrySampler,
    history_dir_default,
    install_history,
    installed_history,
    uninstall_history,
)
from kubeai_tpu.obs import perf as perf_obs
from kubeai_tpu.obs.perf import handle_perf_request
from kubeai_tpu.obs.recorder import amend_decode
from kubeai_tpu.obs.tenants import TENANT_HEADER, sanitize_tenant
from kubeai_tpu.qos import (
    DEFAULT_CLASS,
    PREEMPTIBLE_HEADER,
    PRIORITY_HEADER,
    handle_qos_request,
    normalize_priority,
)

log = get_logger("kubeai_tpu.engine.server")

# Retry-After hint (seconds) on 429 backpressure responses.
RETRY_AFTER_HINT = "1"

# Disaggregated serving (docs/disaggregation.md): a prefill-role
# replica caps streamed generations at its handoff budget and marks the
# capped finish with this reason — the proxy's cutover signal. Decode-
# role replicas serve uncapped and accept resumed (X-Resume-Tokens)
# work; both are plain metadata on an otherwise identical server.
M_HANDOFF_CAPPED = default_registry.counter(
    "kubeai_engine_handoff_capped_total",
    "streamed generations a prefill-role replica capped at its handoff "
    "budget (finish_reason rewritten to 'handoff' for the proxy cutover)",
)
M_RESUMED = default_registry.counter(
    "kubeai_engine_resumed_requests_total",
    "requests arriving with X-Resume-Tokens (decode-side of a handoff, "
    "or a mid-stream crash replay): the deterministic prefix is "
    "regenerated here and the proxy suppresses it",
)
# A first token's two stages on this side of the scheduler (PERF.md
# section 3, "engine server"), each stamped by the serving thread.
M_RECEIVE = default_registry.histogram(
    "kubeai_engine_receive_seconds",
    "entry of a completions POST (before the body is read) to submit() "
    "returned: body read, JSON parse, tokenization; once per submitted "
    "request, never for one refused before it",
)
M_DELIVER_LAG = default_registry.counter(
    "kubeai_engine_deliver_lag_seconds_total",
    "streamed responses: seconds from the scheduler's hand-over of events "
    "to the serving thread's write of their frames done (the wake, the "
    "framing, the socket), summed over writes; which=first is a request's "
    "first such write, later the rest",
)
M_DELIVER_WRITES = default_registry.counter(
    "kubeai_engine_deliver_writes_total",
    "the writes kubeai_engine_deliver_lag_seconds_total sums over, by which",
)
_FIRST, _LATER = {"which": "first"}, {"which": "later"}


class EngineServer:
    def __init__(
        self,
        engine: Engine | None,
        model_name: str,
        host: str = "0.0.0.0",
        port: int = 8000,
        drain_grace: float = 30.0,
        role: str = "",
        handoff_budget: int = 0,
    ):
        # Disaggregated phase role ("prefill" | "decode" | "" unified).
        # Prefill replicas cap streamed generations at handoff_budget
        # tokens and finish them with reason "handoff" (the proxy's
        # cutover marker); decode replicas differ only in the label.
        self.role = role
        self.handoff_budget = handoff_budget if role == "prefill" else 0
        # engine=None is a PARKED replica: the process holds warmed
        # compiled programs (shared compile cache + --park-config) but
        # no weights; /readyz stays 503 until a POST /v1/attach streams
        # a model in and flips it ready. Scale-from-zero attaches to a
        # parked pod instead of cold-spawning a process.
        self.engine = engine
        self.model_name = model_name
        self._attach_lock = threading.Lock()
        self._attach_state = "parked" if engine is None else "attached"
        # Park-time --park-config warm in flight (BackgroundWarm):
        # attach joins it first, so an early attach can't duplicate the
        # same compilations concurrently.
        self.park_warm = None
        self.adapters: dict[str, str] = {}  # name -> path
        self._adapters_lock = threading.Lock()
        # Graceful drain: once set, /readyz goes 503 (k8s stops routing),
        # new inference gets 429 + Retry-After, and in-flight generations
        # get up to drain_grace seconds to finish before the hard stop
        # fails whatever remains (via the engine's _fail_inflight).
        self.draining = threading.Event()
        self.drain_grace = drain_grace
        self._stop_lock = threading.Lock()
        self._stopped = False
        # Set once stop() completes; the CLI main blocks on it so a
        # SIGTERM-initiated drain actually exits the process.
        self.stopped_event = threading.Event()
        handler = _make_handler(self)
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.port = self.httpd.server_port
        # Address peers use to fetch parked KV (GET /v1/kv/<key>). A
        # wildcard bind is unreachable as a connect target, so fall back
        # to loopback (right for in-process test stacks); real pods set
        # KUBEAI_KV_ADVERTISE to their pod IP.
        host_adv = os.environ.get("KUBEAI_KV_ADVERTISE", "") or (
            "127.0.0.1" if host in ("", "0.0.0.0", "::") else host
        )
        self.kv_advertise = f"{host_adv}:{self.port}"
        self._thread: threading.Thread | None = None
        # Engine-local telemetry flight recorder (only when this process
        # doesn't already run one — in-process test stacks colocate an
        # operator whose store then serves both servers). Ownership is
        # tracked so stop() only tears down what start() installed.
        self._history = None
        self._history_sampler = None
        self._otel = None

    def start(self):
        set_build_info("engine")
        # WARNING+ ring from server start (not first /debug/logs GET), so
        # early failures are already captured when someone comes looking.
        install_log_ring()
        self._otel = maybe_start_exporter("kubeai-engine")
        if installed_history() is None:
            self._history = HistoryStore(
                history_dir=os.path.join(history_dir_default(), "engine"),
            )
            self._history_sampler = RegistrySampler(self._history)
            install_history(self._history)
            self._history_sampler.start()
        if self.engine is not None:
            # Stamp the engine's parked-KV source address so export
            # offers point resuming peers back at THIS server's
            # /v1/kv/<key> route.
            self.engine.kv_advertise = self.kv_advertise
            # Per-replica failpoint scope: the scheduler thread adopts
            # it so engine.step/kv_export/kv_import fire @<port> twins
            # just like the handler threads' sites do.
            self.engine.fault_scope = str(self.port)
            self.engine.start()
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self._thread.start()
        tl = getattr(self.engine, "cold_start_timeline", None)
        if tl is not None:
            tl.ready()
        log.info("engine server for %s on :%d", self.model_name, self.port)

    def stop(self):
        """Idempotent hard stop. Ordering matters: stop ADMISSION first
        (draining flag), then the engine — engine.stop() fails in-flight
        requests, so live stream handlers see terminal events and finish
        their responses — and shut the HTTP server down LAST, inside a
        finally so a failing engine.stop() can never leak the serving
        thread (the old shutdown-then-stop order raced handlers still
        blocked on event queues that would never produce)."""
        with self._stop_lock:
            if self._stopped:
                return
            self._stopped = True
        self.draining.set()
        try:
            if self.engine is not None:
                self.engine.stop()
        finally:
            if self._history_sampler is not None:
                self._history_sampler.stop()
                self._history_sampler = None
            if self._history is not None:
                # Identity-checked: a newer owner's install survives.
                uninstall_history(self._history)
                self._history = None
            if self._otel is not None:
                self._otel.stop()
                uninstall_exporter(self._otel)
                self._otel = None
            self.httpd.shutdown()
            self.stopped_event.set()

    def drain(self, grace: float | None = None) -> None:
        """SIGTERM path: stop admission, let in-flight generations finish
        up to the drain budget, then stop() (which fails the rest)."""
        grace = self.drain_grace if grace is None else grace
        self.draining.set()
        if self.engine is None:
            return self.stop()  # parked: nothing in flight by definition
        log.info(
            "engine draining: %d active slots, %d queued, grace %.1fs",
            self.engine.active_slots(), self.engine.queue_depth(), grace,
        )
        deadline = time.monotonic() + grace
        # requests_in_system(), not queue+slots: the latter has a blind
        # window while a request is mid-admission (popped off the queue,
        # not yet registered in a slot) that would end the drain early
        # and hard-fail a request that was milliseconds from decoding.
        while self.engine.requests_in_system() > 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        leftover = self.engine.requests_in_system()
        if leftover:
            log.warning("drain budget expired with %d requests in flight", leftover)
        self.stop()

    def install_signal_handlers(self) -> None:
        """SIGTERM (kubelet shutdown) drains instead of killing mid-
        stream. Main-thread only (signal module constraint); the drain
        itself runs on a worker thread so the handler returns fast."""
        import signal

        def _on_term(signum, frame):
            threading.Thread(target=self.drain, daemon=True).start()

        signal.signal(signal.SIGTERM, _on_term)

    def attach(self, args_list: list[str], warmup: bool | None = None) -> tuple[bool, str]:
        """Attach a model to a parked replica: parse engine-server args
        (the Model's pod args), stream the weights in on a worker
        thread, warm up, and flip /readyz — the scale-from-zero path
        that skips process spawn + jax init + (with a warm cache)
        compilation. Returns (accepted, message); the load itself is
        asynchronous, readiness is the completion signal."""
        with self._attach_lock:
            if self.engine is not None:
                return False, f"model {self.model_name!r} already attached"
            if self._attach_state == "attaching":
                return False, "attach already in progress"
            self._attach_state = "attaching"
        if warmup is None:
            # Warm up by default: the parked pod exists to make ready
            # mean ready — with a park-warmed cache the warmup is reads.
            warmup = os.environ.get("KUBEAI_ATTACH_WARMUP", "1") == "1"

        def run():
            try:
                if self.park_warm is not None:
                    # Let the park-time warm finish writing the cache:
                    # building now would re-compile the same programs
                    # concurrently instead of reading them.
                    self.park_warm.join()
                parser = make_engine_arg_parser(require_model=True)
                a, unknown = parser.parse_known_args(args_list)
                if unknown:
                    log.info("attach ignoring unknown args: %s", unknown)
                if a.model is None:
                    raise ValueError("attach args must include --model")
                engine, name = build_engine_from_args(a, warmup=warmup)
                engine.kv_advertise = self.kv_advertise
                engine.fault_scope = str(self.port)
                engine.start()
                with self._attach_lock:
                    self.model_name = name
                    self.engine = engine
                    self._attach_state = "attached"
                tl = getattr(engine, "cold_start_timeline", None)
                if tl is not None:
                    tl.ready()
                log.info("parked replica attached model %s", name)
            except BaseException as e:  # incl. argparse SystemExit
                log.exception("attach failed")
                with self._attach_lock:
                    # Failed attaches are retryable: the pod stays
                    # not-ready (visible in /readyz + /health), the
                    # controller/operator decides whether to retry the
                    # attach or delete the pod.
                    self._attach_state = f"failed: {e}"

        threading.Thread(target=run, name="engine-attach", daemon=True).start()
        return True, "attaching"

    _ADAPTER_NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]{0,128}$")

    def load_adapter(self, name: str, path: str) -> tuple[bool, str]:
        if not self._ADAPTER_NAME_RE.match(name or ""):
            return False, f"invalid adapter name {name!r}"
        with self._adapters_lock:
            if name in self.adapters and self.adapters[name] != path:
                return False, f"adapter {name} already loaded from {self.adapters[name]}"
            self.adapters[name] = path
        loader = getattr(self.engine, "load_adapter", None)
        if loader is not None:
            try:
                loader(name, self._resolve_adapter_path(name, path))
            except Exception as e:
                with self._adapters_lock:
                    self.adapters.pop(name, None)
                return False, str(e)
        return True, "ok"

    @staticmethod
    def _resolve_adapter_path(name: str, path: str) -> str:
        """The engine stages remote adapter sources itself now (each
        gang rank must stage independently — a path staged by rank 0 is
        meaningless on a follower host); the name was validated against
        a strict charset by load_adapter. Kept as a passthrough seam."""
        return path

    def unload_adapter(self, name: str) -> tuple[bool, str]:
        with self._adapters_lock:
            existed = self.adapters.pop(name, None) is not None
        unloader = getattr(self.engine, "unload_adapter", None)
        if existed and unloader is not None:
            unloader(name)
        # Idempotency-tolerant like the reference client
        # (ref: internal/vllmclient/client.go:30-73).
        return True, "ok" if existed else "adapter was not loaded"


def _cancel_all(reqs) -> None:
    for r in reqs:
        r.cancelled.set()


def _make_handler(srv: EngineServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def setup(self):
            # Once a connection: the line a profiler trace gives this
            # thread (its serve.* events; who runs beside engine-loop).
            _name_os_thread("engine-serve")
            super().setup()

        def log_message(self, fmt, *args):
            log.debug("%s " + fmt, self.address_string(), *args)

        # ---- helpers ----

        def _json(self, code: int, obj, headers: dict | None = None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _error(self, code: int, msg: str, etype: str = "invalid_request_error", headers: dict | None = None):
            self._json(code, {"error": {"message": msg, "type": etype}}, headers=headers)

        def _saturated(self, msg: str = "engine saturated", retry_after: int | None = None):
            """Backpressure response: 429 + Retry-After + OpenAI-shaped
            body. A bare 503 invited synchronized retry storms — 429
            tells SDKs (which all implement jittered backoff for it)
            this is load, not failure. *retry_after* overrides the flat
            hint with the class-backlog-scaled one (Engine.qos_retry_after)
            so a shed batch client backs off longer than a shed
            interactive one."""
            return self._error(
                429, msg + "; retry after backoff", "rate_limit_error",
                headers={"Retry-After": str(retry_after) if retry_after else RETRY_AFTER_HINT},
            )

        def _read_body(self):
            n = int(self.headers.get("Content-Length", 0))
            return self.rfile.read(n)

        # ---- routes ----

        def do_GET(self):
            # Scope every failpoint fired on this handler thread to THIS
            # replica's port: fault("X") also fires "X@<port>", so chaos
            # schedules can target one replica of an in-process fleet.
            set_thread_scope(srv.port)
            path, _, query = self.path.partition("?")
            if path in ("/health", "/healthz"):
                body = {"status": "ok", "model": srv.model_name}
                if srv.role:
                    body["role"] = srv.role
                if srv.engine is None:
                    body["parked"] = True
                    body["attach"] = srv._attach_state
                self._json(200, body)
            elif path == "/readyz":
                # Readiness is distinct from liveness: not-ready until
                # the engine's scheduler loop is accepting work, so k8s
                # probes stop routing to pods whose engine is down — and
                # 503 the moment a drain starts, so routing stops BEFORE
                # the pod disappears.
                if srv.engine is None:
                    # Parked (or mid-attach): alive but serving nothing.
                    self._json(503, {"status": "parked", "attach": srv._attach_state})
                elif srv.draining.is_set():
                    self._json(503, {"status": "draining", "model": srv.model_name})
                elif srv.engine.is_ready():
                    ready = {"status": "ok", "model": srv.model_name}
                    if srv.role:
                        ready["role"] = srv.role
                    self._json(200, ready)
                else:
                    self._json(503, {"status": "engine not ready", "model": srv.model_name})
            elif path in ("/debug", "/debug/"):
                code, ctype, body = debug_index_response("engine")
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path.startswith("/debug/"):
                # Perf X-ray routes get the live engine (stall window,
                # gang profile fan-out); the shared recorder routes and
                # failpoints are process-global.
                resp = (
                    handle_faults_request(path, query)
                    or handle_perf_request(path, query, engine=srv.engine)
                    # Incident/canary surfaces answer "not installed"
                    # here — the black box lives operator-side, but an
                    # in-process stack (tests, the drill) may install
                    # one globally, and the route must exist either way.
                    or handle_incident_request(path, query)
                    or handle_canary_request(path, query)
                    # An engine process's accountant carries its own
                    # cost accumulations (slot/page-seconds by tenant).
                    or handle_tenant_request(path, query)
                    # QoS queue breakdown: the live engine's class/lane
                    # depths, deficits, preemption + resume counters.
                    or handle_qos_request(path, query)
                    or handle_history_request(path, query)
                    # Forecasting is operator-side; this answers an
                    # honest "not installed here" 404 on engines.
                    or handle_forecast_request(path, query)
                    or handle_logs_request(path, query)
                    or handle_debug_request(path, query)
                )
                if resp is None:
                    return self._error(404, f"no route {path}")
                code, ctype, body = resp
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path == "/metrics":
                # Occupancy gauges (KV pages, HBM) are callback gauges —
                # render() evaluates them at collect time, nothing to
                # refresh first.
                body = default_registry.render().encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path == "/v1/models":
                models = [{"id": srv.model_name, "object": "model", "owned_by": "kubeai-tpu"}]
                for name in sorted(srv.adapters):
                    models.append(
                        {"id": name, "object": "model", "owned_by": "kubeai-tpu",
                         "parent": srv.model_name}
                    )
                self._json(200, {"object": "list", "data": models})
            elif path.startswith("/v1/kv/"):
                # Parked-KV fetch (docs/robustness.md "State restore"):
                # the decode/resume replica pulls a preempted or handed-
                # off request's serialized pages from the replica that
                # parked them. A miss (expired, evicted, restarted) is
                # DEFINITIVE — the caller falls back to replay, so this
                # route never blocks or retries.
                key = path[len("/v1/kv/"):]
                entry = srv.engine.kv_park.get(key) if srv.engine is not None else None
                if entry is None:
                    return self._error(404, f"no parked KV under {key!r}")
                blob = entry.blob
                kvstate.M_KV_TRANSFER.inc(len(blob), labels={"direction": "tx"})
                self.send_response(200)
                self.send_header("Content-Type", "application/octet-stream")
                self.send_header("Content-Length", str(len(blob)))
                self.end_headers()
                self.wfile.write(blob)
            else:
                self._error(404, f"no route {path}")

        def do_POST(self):
            received = time.monotonic()  # the `receive` stage begins: before the body is read
            set_thread_scope(srv.port)  # per-replica failpoint twins
            path = self.path.split("?")[0]
            # Correlation id propagated by the proxy (X-Request-ID): one
            # grep finds a request's proxy AND engine log lines.
            # Sanitized — the engine port is reachable in-cluster without
            # the proxy, and a raw header in log lines enables forging.
            from kubeai_tpu.proxy.apiutils import sanitize_request_id

            rid = sanitize_request_id(self.headers.get("X-Request-ID", ""))
            # Trace context: the proxy stamps `traceparent` (W3C) on the
            # hop; absent that, the trace id derives from X-Request-ID
            # so proxy- and engine-side timelines still join.
            trace_ctx = extract_context(self.headers, fallback_request_id=rid)
            # Handler threads serve exactly one request: REPLACE the log
            # context so a pooled thread never leaks the prior request's ids.
            set_log_context(
                trace_id=trace_ctx.trace_id,
                span_id=trace_ctx.span_id,
                request_id=rid,
                model=srv.model_name,
            )
            if rid and path.startswith("/v1/"):
                log.info("request id=%s engine=%s path=%s", rid, srv.model_name, path)
            # Remaining end-to-end budget stamped by the proxy (seconds);
            # converted to an absolute monotonic deadline HERE so queue
            # wait counts against it.
            deadline = None
            dl_hdr = self.headers.get("X-Request-Deadline", "")
            if dl_hdr:
                try:
                    deadline = time.monotonic() + max(float(dl_hdr), 0.0)
                except ValueError:
                    pass  # unparseable deadline = no deadline
            # Mid-stream replay hint: the proxy already delivered this
            # many stream events to the client from a replica that died
            # mid-stream, and is re-running the (deterministic) request
            # here — it suppresses that prefix of OUR stream, so the
            # client sees one seamless continuation. The engine's job
            # is to regenerate identically (prompt prefill may hit the
            # shared-prefix cache); the hint is surfaced for logs and
            # the flight recorder.
            # Tenant attribution: the proxy's internal header carries
            # the HASHED tenant id (never a raw credential); the
            # scheduler prices the request's slot/page-seconds to it.
            # Absent (direct clients, canary probes) = un-attributed.
            tenant = sanitize_tenant(self.headers.get(TENANT_HEADER, ""))
            # QoS class: the proxy validates, strips, and restamps
            # X-Priority (like the tenant header), so whatever arrives
            # here is trusted. Lenient parse — this port is
            # cluster-internal, and header drift (old proxy, a test
            # harness) should degrade to standard, not 400.
            priority = normalize_priority(self.headers.get(PRIORITY_HEADER, "")) or DEFAULT_CLASS
            bind_log_context(tenant=tenant, qos_class=priority)
            # Preemptible stamp: only the proxy sets it (replayable
            # batch streams), and never together with a planned handoff
            # — a request is handed off OR preempted in a flight, not
            # both; the engine enforces the exclusion again here.
            preemptible = (
                self.headers.get(PREEMPTIBLE_HEADER) == "1"
                and self.headers.get("X-Handoff-Planned") != "1"
            )
            resume_tokens = 0
            rt_hdr = self.headers.get("X-Resume-Tokens", "")
            if rt_hdr:
                try:
                    resume_tokens = max(int(rt_hdr), 0)
                except ValueError:
                    pass
                if resume_tokens:
                    M_RESUMED.inc()
                    log.info(
                        "request id=%s is a resumed stream: %d events "
                        "already delivered upstream", rid, resume_tokens,
                    )
            try:
                raw = self._read_body()
                body = json.loads(raw or b"{}")
            except json.JSONDecodeError as e:
                return self._error(400, f"invalid JSON: {e}")
            if srv.draining.is_set() and path.startswith("/v1/") and path != "/v1/models":
                # Drain admission stop: in-flight work finishes, new work
                # goes elsewhere (the proxy retries another replica).
                return self._saturated("server is draining")
            if path == "/v1/attach":
                # Parked-replica attach: args are the engine pod's CLI
                # args (Model.spec.args included); model/served_model_name
                # are accepted as conveniences for hand-driven attaches.
                args_list = [str(x) for x in (body.get("args") or [])]
                if body.get("model") and "--model" not in args_list:
                    args_list = ["--model", str(body["model"])] + args_list
                if body.get("served_model_name") and "--served-model-name" not in args_list:
                    args_list += ["--served-model-name", str(body["served_model_name"])]
                ok, msg = srv.attach(args_list)
                return self._json(202 if ok else 409, {"status": msg})
            if srv.engine is None and path.startswith("/v1/"):
                return self._error(
                    503, "no model attached (parked replica)", "service_unavailable"
                )
            try:
                if path == "/v1/completions":
                    self._completions(
                        body, chat=False, trace_ctx=trace_ctx, deadline=deadline,
                        resume_tokens=resume_tokens, tenant=tenant,
                        priority=priority, preemptible=preemptible,
                        received=received, body_bytes=len(raw),
                    )
                elif path == "/v1/chat/completions":
                    self._completions(
                        body, chat=True, trace_ctx=trace_ctx, deadline=deadline,
                        resume_tokens=resume_tokens, tenant=tenant,
                        priority=priority, preemptible=preemptible,
                        received=received, body_bytes=len(raw),
                    )
                elif path == "/v1/embeddings":
                    self._embeddings(body)
                elif path == "/v1/load_lora_adapter":
                    ok, msg = srv.load_adapter(body.get("lora_name", ""), body.get("lora_path", ""))
                    self._json(200 if ok else 400, {"status": msg})
                elif path == "/v1/unload_lora_adapter":
                    ok, msg = srv.unload_adapter(body.get("lora_name", ""))
                    self._json(200, {"status": msg})
                else:
                    self._error(404, f"no route {path}")
            except BrokenPipeError:
                pass
            except Exception as e:  # pragma: no cover
                log.exception("request failed")
                try:
                    self._error(500, str(e), "internal_error")
                except Exception:
                    pass

        # ---- inference ----

        def _embeddings(self, body: dict):
            inputs = body.get("input")
            if inputs is None:
                return self._error(400, "input is required")
            if isinstance(inputs, str):
                inputs = [inputs]
            if not isinstance(inputs, list) or not inputs:
                return self._error(400, "input must be a string or list of strings")
            tok = srv.engine.tokenizer
            if all(isinstance(x, int) for x in inputs):
                prompts = [list(inputs)]  # one pre-tokenized input
            elif all(isinstance(x, str) for x in inputs):
                prompts = [tok.encode(t) for t in inputs]
            elif all(
                isinstance(x, list) and all(isinstance(i, int) for i in x) for x in inputs
            ):
                prompts = [list(x) for x in inputs]  # batch of token arrays
            else:
                return self._error(
                    400, "input must be a string, list of strings, or token array(s)"
                )
            if any(not p for p in prompts):
                return self._error(400, "input entries must be non-empty")
            try:
                vecs = srv.engine.embed(prompts)
            except ValueError as e:
                return self._error(400, str(e))
            import base64

            fmt = body.get("encoding_format", "float")
            data = []
            for i, v in enumerate(vecs):
                if fmt == "base64":
                    emb = base64.b64encode(v.astype("<f4").tobytes()).decode()
                else:
                    emb = [float(x) for x in v]
                data.append({"object": "embedding", "index": i, "embedding": emb})
            n_tokens = sum(len(p) for p in prompts)
            self._json(200, {
                "object": "list",
                "data": data,
                "model": srv.model_name,
                "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
            })

        def _parse_prompt(self, prompt):
            """OpenAI `prompt` accepts a string, a token-id list, a
            single-element list of either, or (unsupported here) a batch.
            Returns (text, ids) with exactly one set, or (None, None) after
            sending an error response."""
            if isinstance(prompt, list):
                if len(prompt) == 0:
                    self._error(400, "prompt must not be empty")
                    return None, None
                if all(isinstance(x, int) for x in prompt):
                    return None, list(prompt)
                if len(prompt) > 1:
                    self._error(400, "batched prompts are not supported")
                    return None, None
                prompt = prompt[0]
                if isinstance(prompt, list):
                    if not all(isinstance(x, int) for x in prompt):
                        self._error(400, "prompt token ids must be integers")
                        return None, None
                    return None, list(prompt)
            if not isinstance(prompt, str):
                self._error(400, "prompt must be a string or token id list")
                return None, None
            return prompt, None

        def _completions(self, body: dict, chat: bool, trace_ctx=None, deadline=None, resume_tokens=0, tenant="",
                         priority: str = DEFAULT_CLASS, preemptible: bool = False,
                         received: float | None = None, body_bytes: int = 0):
            tok = srv.engine.tokenizer
            # Belt over the proxy stamp: preemption resumes via the
            # stream-replay cursor, so a non-streaming body can never
            # be preemptible.
            preemptible = preemptible and bool(body.get("stream"))
            prompt_ids = None
            if chat:
                messages = body.get("messages")
                if not isinstance(messages, list) or not messages:
                    return self._error(400, "messages is required")
                prompt_text = tok.apply_chat_template(messages, add_generation_prompt=True)
            else:
                prompt = body.get("prompt")
                if prompt is None:
                    return self._error(400, "prompt is required")
                prompt_text, prompt_ids = self._parse_prompt(prompt)
                if prompt_text is None and prompt_ids is None:
                    return  # _parse_prompt already sent the error

            stop = body.get("stop") or ()
            if isinstance(stop, str):
                stop = (stop,)
            max_tokens = body.get("max_tokens", body.get("max_completion_tokens"))
            if max_tokens is None:
                # OpenAI defaults: completions=16, chat=engine default.
                max_tokens = 16 if not chat else srv.engine.cfg.default_max_tokens
            elif not isinstance(max_tokens, int) or max_tokens < 1:
                return self._error(400, "max_tokens must be a positive integer")
            # Prefill-role replica: cap STREAMED generations at the
            # handoff budget — a deterministic stream the proxy will
            # cut over to a decode replica anyway must not hold a
            # prefill-pool decode slot for its full length. The capped
            # finish is marked finish_reason "handoff" so the proxy can
            # tell it from a genuine length finish; a generation that
            # completes within budget keeps its real reason and never
            # hands off. Gated on the proxy's X-Handoff-Planned intent:
            # a stream that reached this replica WITHOUT a planned
            # cutover (ineligible request failing open here because the
            # decode pool is gone, or a direct client) must serve whole
            # — capping it would truncate the client at K tokens with a
            # marker nobody consumes. Non-streaming bodies always pass
            # uncapped.
            handoff_cap = False
            if (
                srv.handoff_budget > 0
                and body.get("stream")
                and self.headers.get("X-Handoff-Planned") == "1"
                and max_tokens > srv.handoff_budget
            ):
                max_tokens = srv.handoff_budget
                handoff_cap = True
                # (Counted at the finish rewrite, not here: a stream
                # that stops naturally within budget was never capped.)
            def num(key, default):
                # OpenAI documents these as "number or null": an explicit
                # JSON null must mean the default, not float(None).
                v = body.get(key)
                return default if v is None else v

            bias_raw = body.get("logit_bias") or {}
            if not isinstance(bias_raw, dict):
                return self._error(400, "logit_bias must be an object")
            if len(bias_raw) > srv.engine.cfg.max_logit_bias:
                # Silent truncation would drop bans without a signal.
                return self._error(
                    400,
                    f"logit_bias supports at most "
                    f"{srv.engine.cfg.max_logit_bias} entries on this engine",
                )
            logit_bias = []
            for k, v in bias_raw.items():
                try:
                    tok_id = int(k)
                    val = float(v)
                except (TypeError, ValueError):
                    return self._error(
                        400, "logit_bias keys must be token ids, values numbers"
                    )
                # Explicit finite+range gate: NaN slips through a
                # min/max clamp (comparisons are False) and negative
                # ids would wrap to the end of the vocab in the device
                # scatter.
                if tok_id < 0 or not (val == val) or val in (float("inf"), float("-inf")):
                    return self._error(
                        400, "logit_bias requires token ids >= 0 and finite values"
                    )
                logit_bias.append((tok_id, max(-100.0, min(100.0, val))))
            # OpenAI logprobs: completions spells it `logprobs: <int>` (0
            # is a VALID request: chosen-token logprobs with zero
            # alternatives), chat spells it `logprobs: true` with the
            # alternative count in `top_logprobs`.
            lp_field = body.get("logprobs")
            want_logprobs = lp_field is not None and lp_field is not False
            if chat:
                top_n = body.get("top_logprobs") or 0
                if top_n and not want_logprobs:
                    return self._error(
                        400, "logprobs must be set to true if top_logprobs is used"
                    )
            else:
                top_n = lp_field if isinstance(lp_field, int) and not isinstance(lp_field, bool) else 0
            if not isinstance(top_n, int) or isinstance(top_n, bool) or top_n < 0:
                return self._error(400, "top_logprobs must be a non-negative integer")
            if top_n > srv.engine.cfg.top_logprobs_k:
                return self._error(
                    400,
                    f"at most {srv.engine.cfg.top_logprobs_k} alternative "
                    "logprobs are supported on this engine",
                )
            params = SamplingParams(
                temperature=float(num("temperature", 1.0)),
                top_p=float(num("top_p", 1.0)),
                top_k=int(num("top_k", 0)),
                max_tokens=int(max_tokens),
                stop=tuple(stop),
                seed=body.get("seed"),
                logprobs=want_logprobs,
                presence_penalty=float(num("presence_penalty", 0.0)),
                frequency_penalty=float(num("frequency_penalty", 0.0)),
                logit_bias=tuple(logit_bias),
            )
            if prompt_ids is None:
                prompt_ids = tok.encode(prompt_text)
            # A request whose model field names a loaded adapter runs with
            # that adapter (the operator proxy rewrites model_adapter ids
            # to the bare adapter name before forwarding).
            requested = str(body.get("model", ""))
            adapter = requested if requested in srv.adapters else None
            # n > 1: one engine request per choice (the cross-slot prefix
            # cache makes the shared prompt nearly free for choices 2..n).
            # A set seed derives seed+i per choice — identical-seed
            # submissions would produce n copies of one sample.
            n_choices = body.get("n")
            if n_choices is None:
                n_choices = 1
            if (
                not isinstance(n_choices, int)
                or isinstance(n_choices, bool)
                or not 1 <= n_choices <= 16
            ):
                return self._error(400, "n must be an integer between 1 and 16")
            if params.seed is not None and (
                not isinstance(params.seed, int) or isinstance(params.seed, bool)
            ):
                return self._error(400, "seed must be an integer")
            # echo / stream_options validate BEFORE the submit loop: a
            # 400 after submitting would leave up to n live generations
            # with no consumer, burning slots/KV pages per malformed
            # request (ADVICE r5 medium).
            echo_val = body.get("echo")
            if echo_val is not None and not isinstance(echo_val, bool):
                return self._error(400, "echo must be a boolean")
            so = body.get("stream_options")
            if so is not None and not isinstance(so, dict):
                return self._error(400, "stream_options must be an object")
            if so is not None and not body.get("stream"):
                return self._error(400, "stream_options requires stream: true")
            so = so or {}
            if deadline is not None and time.monotonic() >= deadline:
                # Budget already spent before admission: refuse rather
                # than enqueue work whose caller has given up.
                return self._error(504, "deadline exceeded", "timeout_error")
            # KV page serialization (docs/robustness.md "State restore"):
            # single-choice streams that the proxy may preempt or hand
            # off park their pages at the cut, and a resume that arrives
            # with the proxy's X-KV-* offer imports that state instead
            # of replaying the prefix. Both legs are best-effort — any
            # failure below degrades to the PR-14 replay path on the
            # same stream, invisible to the client.
            park_kv = ""
            restore_state = None
            restore_key = ""
            if body.get("stream") and n_choices == 1:
                if handoff_cap:
                    park_kv = "handoff"
                elif preemptible:
                    park_kv = "preempt"
                restore_state, restore_key = self._acquire_restore(
                    prompt_ids, params, adapter, deadline,
                )
            reqs = []
            try:
                for i in range(n_choices):
                    p_i = params
                    if i > 0 and params.seed is not None:
                        p_i = dataclasses.replace(params, seed=params.seed + i)
                    # Each choice is its own engine request: same trace,
                    # one child span per choice.
                    r = srv.engine.submit(
                        prompt_ids, p_i, adapter=adapter, trace_ctx=trace_ctx,
                        deadline=deadline, tenant=tenant,
                        priority=priority, preemptible=preemptible,
                        park_kv=park_kv, restore=restore_state,
                        restore_key=restore_key, received=received,
                    )
                    if received is not None:
                        # The `receive` stage ends: body read, JSON parse
                        # and tokenization lie behind this thread.
                        took = time.monotonic() - received
                        M_RECEIVE.observe(took)
                        perf_obs.trace_mark(
                            "serve.receive", rid=r.trace.rid if r.trace is not None else "",
                            prompt_tokens=len(prompt_ids), ms=took * 1e3,
                        )
                    if r.trace is not None:
                        r.trace.model = srv.model_name
                        r.trace.attrs["body_bytes"] = body_bytes
                        if n_choices > 1:
                            r.trace.attrs["choice"] = i
                        if resume_tokens:
                            r.trace.attrs["resume_tokens"] = resume_tokens
                    reqs.append(r)
            except ValueError as e:
                _cancel_all(reqs)
                return self._error(400, str(e))
            except queue.Full:
                # Saturation mid-loop (n > 1): the already-submitted
                # sibling choices MUST be cancelled or they decode for a
                # response that will never be written.
                _cancel_all(reqs)
                return self._saturated(
                    retry_after=srv.engine.qos_retry_after(priority)
                )
            except BaseException:
                # Any other early exit (engine stopping, injected fault,
                # handler thread dying): same sibling-leak hazard.
                _cancel_all(reqs)
                raise

            rid = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
            created = int(time.time())
            # OpenAI `echo` (completions only): prepend the prompt text
            # to every choice. Prompt logprobs are not computed
            # (documented limit, like top-N alternatives).
            echo_text = ""
            if not chat and echo_val:
                echo_text = (
                    prompt_text if prompt_text is not None
                    else self._decode_safe(prompt_ids)
                )
            if body.get("stream"):
                self._stream_response(
                    reqs, rid, created, chat, want_logprobs, echo_text, top_n,
                    include_usage=bool(so.get("include_usage")),
                    handoff_cap=handoff_cap,
                    prompt_tokens_hint=len(prompt_ids),
                )
            else:
                self._full_response(
                    reqs, rid, created, chat, want_logprobs, echo_text, top_n,
                    deadline=deadline,
                )

        def _acquire_restore(self, prompt_ids, params, adapter, deadline):
            """Resolve the proxy's X-KV-* resume offer into a decoded
            RestoreState, or (None, "") to fall back to replay. Every
            failure here is SOFT — the request still runs, it just
            regenerates the deterministic prefix instead of importing
            it — so the client stream is identical either way."""
            eng = srv.engine
            key = self.headers.get(kvstate.KV_KEY_HEADER, "")
            if not key or not eng._kv_enabled():
                return None, ""
            source = self.headers.get(kvstate.KV_SOURCE_HEADER, "")
            try:
                tokens = int(self.headers.get(kvstate.KV_TOKENS_HEADER, "") or 0)
            except ValueError:
                tokens = 0
            t0 = time.monotonic()
            entry = eng.kv_park.get(key)
            blob = entry.blob if entry is not None else None
            if blob is None:
                if not source or source == eng.kv_advertise:
                    # Same-replica resume whose park expired or was
                    # evicted: a definitive miss.
                    kvstate.M_KV_IMPORT.inc(labels={"outcome": "miss"})
                    return None, ""
                if tokens < kvstate.breakeven_tokens():
                    # Break-even routing: below this prefix length,
                    # replaying costs less than a cross-replica fetch +
                    # device upload (docs/robustness.md has the math).
                    # Not a miss — a deliberate decision, so no counter.
                    return None, ""
                remaining = None if deadline is None else deadline - time.monotonic()
                blob = kvstate.fetch_blob(source, key, remaining)
                if blob is None:
                    kvstate.M_KV_IMPORT.inc(labels={"outcome": "miss"})
                    return None, ""
            try:
                # Serving-thread leg of the import failpoint: `corrupt`
                # mangles the acquired blob (the checksums below must
                # catch it), `error` aborts the acquire outright.
                blob = fault("engine.kv_import", payload=blob)
                state = kvstate.decode_state(
                    blob,
                    expect_model_fp=eng._kv_fp,
                    expect_request_fp=kvstate.request_fingerprint(
                        prompt_ids, params, adapter
                    ),
                )
            except FaultError:
                kvstate.M_KV_IMPORT.inc(labels={"outcome": "error"})
                return None, ""
            except kvstate.KVFormatError as e:
                kvstate.M_KV_IMPORT.inc(labels={"outcome": "corrupt"})
                log.warning(
                    "parked KV %s rejected (%s); resuming via replay", key, e
                )
                return None, ""
            kvstate.M_KV_RESTORE_SECONDS.observe(
                time.monotonic() - t0, labels={"phase": "acquire"}
            )
            return state, key

        def _decode_safe(self, ids) -> str:
            try:
                return srv.engine.tokenizer.decode(list(ids))
            except Exception:
                return ""

        def _token_text(self, token_id: int) -> str:
            """The token's OWN text (OpenAI logprobs semantics) — NOT the
            stream delta, which can be empty or combined when the
            detokenizer holds back partial UTF-8 / stop-string windows."""
            return self._decode_safe([token_id])

        def _top_entries(self, top, top_n, chat):
            """Format the engine's [(token_id, logprob), ...] top-N for
            the OpenAI response shape (chat: list of objects; legacy
            completions: token-text -> logprob map)."""
            if not top_n or not top:
                return None
            pairs = top[:top_n]
            if chat:
                return [
                    {"token": self._token_text(tid), "logprob": lp}
                    for tid, lp in pairs
                ]
            # Legacy completions shape is a text->logprob map: distinct
            # token ids can decode to the SAME text (byte fallbacks), and
            # pairs arrive sorted best-first — keep the best per text
            # rather than letting a later worse entry overwrite it.
            out = {}
            for tid, lp in pairs:
                out.setdefault(self._token_text(tid), lp)
            return out

        def _full_response(self, reqs, rid, created, chat, want_logprobs=False, echo_text="", top_n=0, deadline=None):
            choices = []
            prompt_tokens = 0
            completion_tokens = 0
            for idx, req in enumerate(reqs):
                chunks, pieces, fin = [], [], None
                while True:
                    wait = 600.0
                    if deadline is not None:
                        # The handler waits only as long as the budget:
                        # the scheduler's own sweep frees the slot, but
                        # the HTTP response must not outwait it.
                        wait = min(wait, max(deadline - time.monotonic(), 0.0) + 1.0)
                    try:
                        ev = req.out.get(timeout=wait)
                    except queue.Empty:
                        _cancel_all(reqs)
                        return self._error(504, "generation timed out", "timeout_error")
                    if ev[0] == "token":
                        chunks.append(ev[2])
                        if ev[1] >= 0:  # -1 marks a text-only flush
                            pieces.append((
                                ev[1],
                                ev[3] if len(ev) > 3 else None,
                                ev[4] if len(ev) > 4 else None,
                            ))
                    elif ev[0] == "done":
                        fin = ev[1]
                        break
                    else:
                        _cancel_all(reqs)
                        if ev[1] == Engine.DEADLINE_MSG:
                            # Scheduler aborted past the request deadline.
                            return self._error(504, ev[1], "timeout_error")
                        return self._error(500, ev[1], "internal_error")
                text = "".join(chunks)
                prompt_tokens = fin.prompt_tokens  # same prompt per choice
                completion_tokens += fin.completion_tokens
                if chat:
                    choice = {
                        "index": idx,
                        "message": {"role": "assistant", "content": text},
                        "finish_reason": fin.reason,
                    }
                    if want_logprobs:
                        content = []
                        for tid, lp, top in pieces:
                            if lp is None:
                                continue
                            entry = {"token": self._token_text(tid), "logprob": lp}
                            if top_n:
                                entry["top_logprobs"] = self._top_entries(top, top_n, chat) or []
                            content.append(entry)
                        choice["logprobs"] = {"content": content}
                else:
                    choice = {"index": idx, "text": echo_text + text, "finish_reason": fin.reason}
                    if want_logprobs:
                        kept = [(tid, lp, top) for tid, lp, top in pieces if lp is not None]
                        choice["logprobs"] = {
                            "tokens": [self._token_text(tid) for tid, _, _ in kept],
                            "token_logprobs": [lp for _, lp, _ in kept],
                            "top_logprobs": (
                                [self._top_entries(top, top_n, chat) or {} for _, _, top in kept]
                                if top_n
                                else None
                            ),
                        }
                choices.append(choice)
            usage = {
                "prompt_tokens": prompt_tokens,
                "completion_tokens": completion_tokens,
                "total_tokens": prompt_tokens + completion_tokens,
            }
            obj = "chat.completion" if chat else "text_completion"
            self._json(200, {
                "id": rid, "object": obj, "created": created,
                "model": srv.model_name, "choices": choices, "usage": usage,
            })

        def _stream_response(self, reqs, rid, created, chat, want_logprobs=False, echo_text="", top_n=0, include_usage=False, handoff_cap=False, prompt_tokens_hint=0):
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def send_chunk(payload: str):
                # Failpoint "kill-after-N-tokens": arming
                # engine.stream=error:1:skip=N severs the response after
                # the Nth SSE event left this replica — the chaos seam
                # for mid-stream replica death (proxy replay under test).
                # The thread's fault scope (set at do_POST entry) makes
                # this also fire engine.stream@<port>, the per-replica
                # twin (engine.stream@<port>=slow:... = one straggler
                # in a multi-replica single-process drill fleet).
                fault("engine.stream")
                data = f"data: {payload}\n\n".encode()
                frames.append(f"{len(data):x}\r\n".encode() + data + b"\r\n")

            # The frames of the events taken at one wake leave in ONE
            # write: a decode chunk hands a request its tokens at once
            # (Engine._hand_over), and a system call a frame would undo
            # that. The bytes and their order are those of a write a frame.
            frames: list[bytes] = []
            # Delivery, measured where it ends: `taken` is (the stamp of
            # the hand-over this thread woke for, its events) until their
            # frames are written; `lag` the seconds from stamp to written
            # as [first write's, all writes', writes, longest].
            taken: tuple[float, int] | None = None
            lag = [0.0, 0.0, 0, 0.0]
            trace_rid = reqs[0].trace.rid if reqs[0].trace is not None else ""

            def flush():
                nonlocal taken
                if frames:
                    data = b"".join(frames)
                    frames.clear()
                    self.wfile.write(data)
                    self.wfile.flush()
                    if taken is not None:
                        seconds = time.monotonic() - taken[0]
                        if not lag[2]:
                            lag[0] = seconds
                        lag[1] += seconds
                        lag[2] += 1
                        lag[3] = max(lag[3], seconds)
                        perf_obs.trace_mark("serve.write", rid=trace_rid, events=taken[1], lag_ms=seconds * 1e3)
                taken = None

            def delivered():
                """The stream ended: its writes onto the two counters, by
                which, and onto the `decode` phase of its timelines."""
                first, total, writes, longest = lag
                if not writes:
                    return
                M_DELIVER_LAG.inc(first, labels=_FIRST)
                M_DELIVER_WRITES.inc(1, labels=_FIRST)
                if writes > 1:
                    M_DELIVER_LAG.inc(total - first, labels=_LATER)
                    M_DELIVER_WRITES.inc(writes - 1, labels=_LATER)
                attrs = {
                    "deliver_first_ms": round(first * 1e3, 3),
                    "deliver_mean_ms": round(total / writes * 1e3, 3),
                    "deliver_max_ms": round(longest * 1e3, 3),
                }
                for r in reqs:
                    if r.trace is not None:
                        amend_decode(r.trace, attrs)

            obj = "chat.completion.chunk" if chat else "text_completion"

            # n > 1 choices decode concurrently; their events interleave
            # into one SSE stream tagged by choice index (OpenAI's n>1
            # stream shape) via a merge queue fed by one pump per choice.
            merged: "EventQueue[tuple[int, tuple]]" = EventQueue()

            def pump(idx, r):
                # Short poll + cancellation check: a cancelled request's
                # slot frees with NO terminal event (deliver=False), so a
                # long blocking get would strand this thread for the full
                # timeout after every client disconnect (review r5).
                waited = 0.0
                while True:
                    try:
                        evs = r.out.get_many(timeout=1.0)
                    except queue.Empty:
                        if r.cancelled.is_set():
                            return
                        waited += 1.0
                        if waited >= 600.0:
                            merged.put((idx, ("error", "generation timed out")))
                            return
                        continue
                    waited = 0.0
                    merged.put_many([(idx, ev) for ev in evs], r.out.taken_at)
                    if evs[-1][0] in ("done", "error"):
                        return

            if len(reqs) == 1:
                pumps = None  # single choice: read its queue directly
            else:
                pumps = [
                    threading.Thread(target=pump, args=(i, r), daemon=True)
                    for i, r in enumerate(reqs)
                ]
                for t in pumps:
                    t.start()

            def events():
                """The stream's (choice, event) pairs, one at a time:
                everything that is there at one wake (a chunk's tokens)
                before it blocks again, and the frames made so far are
                written before it does."""
                nonlocal taken
                while True:
                    flush()
                    if pumps is None:
                        source = reqs[0].out
                        try:
                            evs = [(0, ev) for ev in source.get_many(timeout=600)]
                        except queue.Empty:
                            evs, source = [(0, ("error", "generation timed out"))], None
                    else:
                        source = merged
                        evs = merged.get_many()
                    if source is not None and source.taken_at is not None:
                        taken = (source.taken_at, len(evs))
                    yield from evs

            remaining = len(reqs)
            prompt_tokens = 0
            completion_tokens = 0
            # Tokens emitted per still-running choice: the best-effort
            # usage a terminal ERROR path reports (OpenAI semantics —
            # include_usage promises a usage block on EVERY terminal
            # path, and billing/metering consumers need the partial
            # counts of a cancelled/errored/deadline-aborted stream,
            # not silence).
            emitted_live: dict[int, int] = {}

            def usage_chunk() -> str:
                live = sum(emitted_live.values())
                return json.dumps({
                    "id": rid, "object": obj, "created": created,
                    "model": srv.model_name, "choices": [],
                    "usage": {
                        "prompt_tokens": prompt_tokens or prompt_tokens_hint,
                        "completion_tokens": completion_tokens + live,
                        "total_tokens": (prompt_tokens or prompt_tokens_hint)
                        + completion_tokens + live,
                    },
                })
            # Budget-capped streams hold the detokenizer's text-only
            # tail flush (ev token id -1) until the finish reason is
            # known: a handoff finish must NOT emit it — the decode
            # replica re-delivers those held-back bytes inside its own
            # later chunks, so flushing here would duplicate them after
            # the proxy's event-count suppression. A natural stop
            # within budget forwards the held text before its finish
            # chunk, exactly as an uncapped stream would have.
            held_flush: dict[int, str] = {}
            try:
                if chat:
                    # Inside the try: a client that disconnected before
                    # the role chunks flush must cancel all n choices,
                    # not leave them generating for a dead socket.
                    for idx in range(len(reqs)):
                        first = {"id": rid, "object": obj, "created": created, "model": srv.model_name,
                                 "choices": [{"index": idx, "delta": {"role": "assistant"}, "finish_reason": None}]}
                        send_chunk(json.dumps(first))
                elif echo_text:
                    for idx in range(len(reqs)):
                        send_chunk(json.dumps({
                            "id": rid, "object": obj, "created": created,
                            "model": srv.model_name,
                            "choices": [{"index": idx, "text": echo_text,
                                         "finish_reason": None}],
                        }))
                for idx, ev in events():
                    if ev[0] == "token":
                        if ev[1] >= 0:
                            # Counted BEFORE the empty-delta skip: a
                            # held-back token is still an emitted token
                            # for the error-path usage block.
                            emitted_live[idx] = emitted_live.get(idx, 0) + 1
                        has_lp = (
                            want_logprobs and ev[1] >= 0 and len(ev) > 3
                            and ev[3] is not None
                        )
                        if not ev[2] and not has_lp:
                            continue
                        if handoff_cap and ev[1] < 0:
                            held_flush[idx] = held_flush.get(idx, "") + ev[2]
                            continue
                        top = ev[4] if len(ev) > 4 else None
                        if chat:
                            choice = {"index": idx, "delta": {"content": ev[2]}, "finish_reason": None}
                            if has_lp:
                                entry = {"token": self._token_text(ev[1]), "logprob": ev[3]}
                                if top_n:
                                    entry["top_logprobs"] = self._top_entries(top, top_n, chat) or []
                                choice["logprobs"] = {"content": [entry]}
                        else:
                            choice = {"index": idx, "text": ev[2], "finish_reason": None}
                            if has_lp:
                                choice["logprobs"] = {
                                    "tokens": [self._token_text(ev[1])],
                                    "token_logprobs": [ev[3]],
                                    "top_logprobs": (
                                        [self._top_entries(top, top_n, chat) or {}]
                                        if top_n
                                        else None
                                    ),
                                }
                        send_chunk(json.dumps({
                            "id": rid, "object": obj, "created": created,
                            "model": srv.model_name, "choices": [choice],
                        }))
                    elif ev[0] == "done":
                        fin = ev[1]
                        remaining -= 1
                        prompt_tokens = fin.prompt_tokens
                        completion_tokens += fin.completion_tokens
                        emitted_live.pop(idx, None)  # exact count landed
                        # Budget-capped prefill finish: "length" here
                        # means "the handoff budget ran out", not "the
                        # client's max_tokens ran out" — the proxy keys
                        # its cutover on the rewritten reason. A
                        # genuine stop within budget passes through.
                        reason = fin.reason
                        if handoff_cap and reason == "length":
                            reason = "handoff"
                            M_HANDOFF_CAPPED.inc()
                        held = held_flush.pop(idx, None)
                        if held and reason != "handoff":
                            send_chunk(json.dumps({
                                "id": rid, "object": obj, "created": created,
                                "model": srv.model_name,
                                "choices": [
                                    {"index": idx, "delta": {"content": held},
                                     "finish_reason": None}
                                    if chat
                                    else {"index": idx, "text": held,
                                          "finish_reason": None}
                                ],
                            }))
                        choice = (
                            {"index": idx, "delta": {}, "finish_reason": reason}
                            if chat
                            else {"index": idx, "text": "", "finish_reason": reason}
                        )
                        payload = {
                            "id": rid, "object": obj, "created": created,
                            "model": srv.model_name, "choices": [choice],
                        }
                        if fin.kv:
                            # Parked-KV offer riding the marker chunk:
                            # the proxy captures it (and withholds the
                            # marker), then stamps X-KV-* on the resume
                            # so the next replica can import instead of
                            # replaying. Clients that see it ignore an
                            # unknown extension field.
                            payload["kubeai_kv"] = fin.kv
                        send_chunk(json.dumps(payload))
                        if remaining == 0 and include_usage:
                            # OpenAI stream_options semantics: usage
                            # arrives as its own final chunk with EMPTY
                            # choices (SDK consumers key on that shape).
                            send_chunk(usage_chunk())
                        if remaining == 0:
                            send_chunk("[DONE]")
                            frames.append(b"0\r\n\r\n")
                            flush()
                            return
                    else:
                        _cancel_all(reqs)
                        if include_usage:
                            # Terminal-path contract: errored/deadline-
                            # aborted/timed-out streams deliver a best-
                            # effort usage block too — the old code only
                            # emitted it at remaining == 0, so every
                            # non-ok stream ended usage-less and its
                            # tokens were unbillable.
                            send_chunk(usage_chunk())
                        send_chunk(json.dumps({"error": {"message": ev[1]}}))
                        frames.append(b"0\r\n\r\n")
                        flush()
                        return
            except FaultError:
                # Injected mid-stream death: die like a crashed replica —
                # sever the socket with the chunked stream UNterminated,
                # so the downstream proxy sees a dead upstream (and its
                # replay path engages), not a clean short response.
                import socket as _socket

                _cancel_all(reqs)
                try:
                    # The frames of the events BEFORE the one it fired at
                    # had left, a write each: they still leave.
                    flush()
                    self.connection.shutdown(_socket.SHUT_RDWR)
                except OSError:
                    pass
                self.close_connection = True
            except (BrokenPipeError, ConnectionResetError):
                _cancel_all(reqs)
            finally:
                delivered()

    return Handler


# ---------------------------------------------------------------------------
# CLI — the entrypoint engine pods run.


def _stage_remote(url: str, base_dir: str, prefix: str = "") -> str:
    from kubeai_tpu.loader import stage_remote

    return stage_remote(url, base_dir, prefix=prefix)


def _resolve_model_path(model: str) -> str:
    """Stage remote model sources to local disk so the weight loader
    always reads a directory — without this, every hf:// TPUEngine pod
    without a cacheProfile would crashloop at startup
    (load_engine_from_path only reads local checkpoints)."""
    return _stage_remote(
        model, os.environ.get("KUBEAI_MODEL_STAGING_DIR", "/tmp/kubeai-models")
    )


def engine_config_from_args(args):
    """EngineConfig from a parsed engine-server arg namespace. Shared
    with the cold-start warm compiler (loader --warm-compile-cache,
    parked --park-config) so warmed shapes can never drift from what a
    serving pod started with the same args would run."""
    from kubeai_tpu.engine.core import EngineConfig

    return EngineConfig(
        max_slots=args.max_slots,
        max_seq_len=args.max_seq_len,
        page_size=getattr(args, "page_size", 64),
        num_pages=getattr(args, "kv_pages", 0),
        prefix_cache_min=getattr(args, "prefix_cache_min", 16),
        kv_cache_dtype=getattr(args, "kv_cache_dtype", ""),
    )


def build_engine_from_args(args, publisher=None, warmup: bool | None = None) -> tuple[Engine, str]:
    from kubeai_tpu.engine.core import build_test_engine

    ec = engine_config_from_args(args)
    if args.model.startswith("test:"):
        eng = build_test_engine(engine_config=ec)
        return eng, args.served_model_name or args.model
    # Real checkpoint path: HF-format directory with config.json +
    # safetensors weights; remote URLs are staged to local disk first.
    from kubeai_tpu.engine.coldstart import ColdStartTimeline
    from kubeai_tpu.engine.weights import load_engine_from_path

    timeline = ColdStartTimeline().install()
    with timeline.phase("stage"):
        local_path = _resolve_model_path(args.model)
    eng = load_engine_from_path(
        local_path,
        ec,
        tp=args.tensor_parallel_size,
        quantization=args.quantization,
        publisher=publisher,
        timeline=timeline,
        warmup=warmup,
    )
    return eng, args.served_model_name or args.model


def maybe_init_distributed() -> list[str] | None:
    """Multi-host slice bootstrap: the controller stamps gang pods with
    TPU_WORKER_ID + TPU_WORKER_HOSTNAMES + KUBEAI_GANG_SECRET
    (controller.reconcile_pods); rank 0's host serves as the
    jax.distributed coordinator so the gang forms one device mesh across
    hosts. Returns the gang host list (or None for single-host pods)."""
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    hosts = [h.strip() for h in hostnames.split(",") if h.strip()]
    if len(hosts) < 2:
        return None
    if not os.environ.get("KUBEAI_GANG_SECRET"):
        # A TPU VM's own environment lists the slice's workers under the
        # same variable; only the controller's stamp (which always comes
        # with the gang secret) makes this pod a gang rank.
        log.info(
            "TPU_WORKER_HOSTNAMES lists %d hosts but no gang secret is "
            "stamped: serving single-host", len(hosts),
        )
        return None
    import jax

    process_id = int(os.environ.get("TPU_WORKER_ID", "0"))
    coordinator = f"{hosts[0]}:{os.environ.get('TPU_COORDINATOR_PORT', '8476')}"
    log.info("joining slice: coordinator=%s rank=%d/%d", coordinator, process_id, len(hosts))
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=len(hosts),
        process_id=process_id,
    )
    return hosts


def _gang_port() -> int:
    from kubeai_tpu.engine.gang import DEFAULT_GANG_PORT

    return int(os.environ.get("KUBEAI_GANG_PORT", str(DEFAULT_GANG_PORT)))


def _gang_secret() -> str:
    """Controller-provisioned shared secret authenticating gang members
    (stamped per slice gang by the controller / LocalRuntime). Required:
    the dispatch stream carries prompt tokens and adapter paths, so an
    unauthenticated gang port is both a leak and a denial-of-assembly."""
    secret = os.environ.get("KUBEAI_GANG_SECRET", "")
    if not secret:
        raise SystemExit(
            "KUBEAI_GANG_SECRET is required for multi-host gangs "
            "(the controller stamps it on slice pods)"
        )
    return secret


def run_follower(args, hosts: list[str]) -> None:
    """Serve as a gang follower (rank > 0): build the same engine over
    the global mesh, connect to rank 0's dispatch stream, expose ONLY
    health/metrics over HTTP (the LB routes inference to rank 0), and
    replay dispatches until rank 0 stops or the stream drops — then exit
    so the controller recreates the slice gang."""
    from kubeai_tpu.engine.gang import GangFollower

    import jax

    follower = GangFollower(
        hosts[0], _gang_port(), secret=_gang_secret(), rank=jax.process_index()
    )
    engine, name = build_engine_from_args(args)

    class FollowerHandler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *a):
            log.debug("%s " + fmt, self.address_string(), *a)

        def do_GET(self):
            path = self.path.split("?")[0]
            if path in ("/health", "/healthz", "/readyz"):
                body = json.dumps(
                    {"status": "ok", "model": name, "role": "follower"}
                ).encode()
                ctype = "application/json"
            elif path == "/metrics":
                body = default_registry.render().encode()
                ctype = "text/plain; version=0.0.4"
            else:
                body = json.dumps(
                    {"error": {"message": "follower rank serves no inference"}}
                ).encode()
                ctype = "application/json"
                self.send_response(404)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        do_POST = do_GET

    httpd = ThreadingHTTPServer((args.host, args.port), FollowerHandler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    log.info("gang follower rank %d serving health on :%d", int(os.environ.get("TPU_WORKER_ID", "0")), httpd.server_port)
    try:
        engine.run_follower(follower)
    finally:
        httpd.shutdown()
        follower.close()
    log.info("gang follower exiting")


def make_engine_arg_parser(require_model: bool = True) -> argparse.ArgumentParser:
    """The engine pod's CLI parser. Also consumed (require_model=False)
    by the loader's --warm-compile-cache step and the parked replica's
    attach path, which both parse Model.spec.args with it — one parser,
    so engine-shape defaults can never drift between warming and
    serving."""
    parser = argparse.ArgumentParser("kubeai-tpu-engine")
    parser.add_argument(
        "--model", required=require_model, default=None,
        help="checkpoint dir or test:tiny",
    )
    parser.add_argument("--served-model-name", default=None)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--max-slots", type=int, default=8)
    parser.add_argument("--max-seq-len", type=int, default=2048)
    parser.add_argument("--tensor-parallel-size", type=int, default=1)
    parser.add_argument("--quantization", default="", choices=["", "int8"])
    parser.add_argument(
        "--kv-cache-dtype", default="", choices=["", "fp8", "int8"],
        help="paged KV pool storage dtype (fp8 = float8_e4m3fn, scale-"
             "free; halves KV HBM so the slot ceiling roughly doubles)",
    )
    parser.add_argument(
        "--page-size", type=int, default=64, help="KV pool tokens per page"
    )
    parser.add_argument(
        "--kv-pages", type=int, default=0,
        help="total KV pool pages (0 = auto: max_slots * max_seq_len/page_size + 1)",
    )
    parser.add_argument(
        "--prefix-cache-min", type=int, default=16,
        help="min shared-prefix tokens to reuse across slots (0 disables)",
    )
    parser.add_argument(
        "--role", default="", choices=["", "prefill", "decode"],
        help="disaggregated phase role (docs/disaggregation.md): "
             "prefill replicas cap streamed generations at the handoff "
             "budget and mark the capped finish 'handoff'; decode "
             "replicas serve uncapped and accept resumed work; empty = "
             "unified serving",
    )
    parser.add_argument(
        "--handoff-budget", type=int,
        default=int(os.environ.get("KUBEAI_HANDOFF_BUDGET", "8")),
        help="max tokens a prefill-role replica streams before the "
             "capped 'handoff' finish (ignored unless --role prefill)",
    )
    parser.add_argument(
        "--drain-grace", type=float,
        default=float(os.environ.get("KUBEAI_DRAIN_GRACE", "30")),
        help="seconds SIGTERM lets in-flight generations finish before "
             "the hard stop (keep below terminationGracePeriodSeconds)",
    )
    parser.add_argument(
        "--warmup", action="store_true",
        default=os.environ.get("KUBEAI_ENGINE_WARMUP", "0") == "1",
        help="pre-dispatch every step-function shape before serving "
             "(cheap with a warm compile cache; the first real "
             "request then never pays a compile)",
    )
    parser.add_argument(
        "--parked", action="store_true",
        help="start with NO model: hold compiled programs (via "
             "--park-config + the shared compile cache) and wait for a "
             "POST /v1/attach to stream weights in — scale-from-zero "
             "attaches to a parked pod instead of cold-spawning",
    )
    parser.add_argument(
        "--park-config", default=os.environ.get("KUBEAI_PARK_CONFIG", ""),
        help="checkpoint dir (config.json + tokenizer) whose shapes a "
             "parked replica AOT-compiles at park time",
    )
    return parser


def main(argv=None):
    # Persistent XLA compilation cache: replicas of the same model shape
    # skip recompilation (big cold-start cut when the cache dir is a
    # shared mount). Gang followers come through here too.
    from kubeai_tpu.engine.coldstart import setup_compile_cache

    cache_dir = setup_compile_cache()
    gang_hosts = maybe_init_distributed()

    parser = make_engine_arg_parser(require_model=False)
    args = parser.parse_args(argv)
    if not args.parked and not args.model:
        parser.error("--model is required (unless --parked)")
    setup_logging("engine")
    import jax

    # Initializes the backend: with JAX_PLATFORMS=tpu and no chip this
    # raises here, before any model work, instead of serving from CPU.
    dev = jax.devices()[0]
    log.info(
        "engine process on platform=%s device_kind=%s devices=%d compile_cache=%s",
        dev.platform, dev.device_kind, jax.device_count(), cache_dir,
    )

    if args.parked:
        if gang_hosts:
            parser.error("--parked is not supported on multi-host gangs")
        srv = EngineServer(
            None, "(parked)", host=args.host, port=args.port,
            drain_grace=args.drain_grace,
        )
        if args.park_config:
            # Park-time warm: AOT-compile the expected model's step
            # functions so the eventual attach (and every sibling
            # replica sharing the compile cache) pays disk reads, not
            # XLA. Background — the attach endpoint is live meanwhile —
            # but registered BEFORE the server accepts attaches, so an
            # early attach joins it instead of racing the compiles.
            import sys

            from kubeai_tpu.engine.coldstart import BackgroundWarm, warm_from_checkpoint

            raw = argv if argv is not None else sys.argv[1:]
            park_args = [a for a in raw if a != "--parked"]
            srv.park_warm = BackgroundWarm(
                lambda: warm_from_checkpoint(args.park_config, park_args)
            )
        srv.install_signal_handlers()
        srv.start()
        log.info("parked replica on :%d (awaiting /v1/attach)", srv.port)
        try:
            while not srv.stopped_event.is_set():
                srv.stopped_event.wait(3600)
        except KeyboardInterrupt:
            srv.stop()
        return

    publisher = None
    if gang_hosts and args.model.startswith("test:"):
        # build_test_engine has no mesh/publisher plumbing: rank 0 would
        # serve unsharded and never publish, stranding the followers.
        parser.error("test: models cannot serve on a multi-host gang")
    if gang_hosts:
        import jax

        rank = jax.process_index()
        if rank > 0:
            run_follower(args, gang_hosts)
            return
        # Rank 0: every dispatch fans out to the followers (lockstep
        # tensor-parallel serving over the slice; engine/gang.py).
        from kubeai_tpu.engine.gang import GangPublisher

        publisher = GangPublisher(
            len(gang_hosts) - 1, port=_gang_port(), secret=_gang_secret()
        )

    engine, name = build_engine_from_args(
        args, publisher=publisher, warmup=args.warmup
    )
    if publisher is not None:
        # Gang assembly: block until every follower is wired up before
        # serving (a dispatch before that would strand the followers).
        publisher.accept_all()
    srv = EngineServer(
        engine, name, host=args.host, port=args.port,
        drain_grace=args.drain_grace,
        role=args.role, handoff_budget=args.handoff_budget,
    )
    srv.install_signal_handlers()
    srv.start()
    log.info("serving %s%s", name, f" (role={args.role})" if args.role else "")
    try:
        while not srv.stopped_event.is_set():
            srv.stopped_event.wait(3600)
    except KeyboardInterrupt:
        srv.stop()


if __name__ == "__main__":
    main()
