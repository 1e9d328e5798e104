"""Sends a traffic.Plan to the operator's OpenAI proxy and records, for
every request, when it was due, when it was sent and when each streamed
token arrived. One process, one thread per request in flight; no jax.

Open loop: arrivals are paced against absolute times (the schedule never
drifts with the generator's own delays), and a request's latency counts
from when it was DUE, so a stall is charged to every request it delayed.
How late the generator itself ran is a per-layer metric (`loadgen_late_ms`).
"""

from __future__ import annotations

import http.client
import json
import threading
import time

BYTE_EOS = 257  # kubeai_tpu/engine/tokenizer.py ByteTokenizer
# Random weights over a 152k vocabulary almost never pick one of the 256
# byte ids, and the server streams no event for a token without text. A
# bias onto the printable ASCII ids makes every generated token one
# character, as a trained model's tokens are text; the bias rows are a
# static [slots, 300] input of every step either way (core.max_logit_bias).
LOGIT_BIAS = {**{str(b): 100 for b in range(32, 127)}, str(BYTE_EOS): -100}


class Record:
    __slots__ = (
        "tag", "prompt_tokens", "max_tokens", "due", "sent", "token_times",
        "done", "ok", "error", "text", "status", "usage",
    )

    def __init__(self, req, due):
        self.tag = req.tag
        self.prompt_tokens = req.prompt_tokens
        self.max_tokens = req.max_tokens
        self.due = due
        self.sent = None
        self.token_times: list[float] = []
        self.done = None
        self.ok = False
        self.error = None
        self.text = ""
        self.status = None
        self.usage = None


def send(base: str, model: str, req, rec: Record, timeout: float, extra: dict | None = None) -> Record:
    """One streamed /openai/v1/completions request. ok means: 200, exactly
    max_tokens tokens (usage and text agree), the prompt_tokens sent, and
    finish_reason length."""
    host, port = base.split(":")
    body = {
        "model": model, "prompt": req.prompt, "max_tokens": req.max_tokens,
        "temperature": 0, "logit_bias": LOGIT_BIAS, "stream": True,
        "stream_options": {"include_usage": True}, **(extra or {}),
    }
    payload = json.dumps(body)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    finish = None
    pieces = []
    try:
        rec.sent = time.monotonic()
        conn.request("POST", "/openai/v1/completions", payload, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec.status = resp.status
        if resp.status != 200:
            rec.error = f"status {resp.status}: {resp.read(300).decode(errors='replace')}"
            return rec
        for raw in resp:
            if not raw.startswith(b"data:"):
                continue
            now = time.monotonic()
            data = raw[5:].strip()
            if data == b"[DONE]":
                continue
            ev = json.loads(data)
            if ev.get("error"):
                rec.error = f"stream error: {str(ev['error'])[:200]}"
                return rec
            for ch in ev.get("choices", ()):
                piece = ch.get("text") or ""
                if piece:
                    pieces.append((piece, ch.get("logprobs")))
                    # One character is one token (LOGIT_BIAS above).
                    rec.token_times.extend([now] * len(piece))
                finish = ch.get("finish_reason") or finish
            rec.usage = ev.get("usage") or rec.usage
        rec.done = time.monotonic()
        rec.text = "".join(p for p, _ in pieces)
        usage = rec.usage or {}
        problems = []
        if usage.get("completion_tokens") != req.max_tokens:
            problems.append(f"completion_tokens {usage.get('completion_tokens')} != {req.max_tokens}")
        if len(rec.text) != req.max_tokens:
            problems.append(f"{len(rec.text)} characters streamed for {req.max_tokens} tokens")
        if usage.get("prompt_tokens") != req.prompt_tokens:
            problems.append(f"prompt_tokens {usage.get('prompt_tokens')} != {req.prompt_tokens}")
        if finish != "length":
            problems.append(f"finish_reason {finish!r}")
        rec.ok = not problems
        if problems:
            rec.error = "; ".join(problems)
        if extra and extra.get("logprobs"):
            rec.usage = {**usage, "first_top_logprobs": _first_top(pieces)}
        return rec
    except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
        rec.error = f"{type(e).__name__}: {e}"
        return rec
    finally:
        conn.close()


def ttft_tpot_ms(rec: Record) -> tuple[float, float] | None:
    """(time to first token from when the request was due, mean time per
    output token after the first), in ms; None for a request that failed."""
    if not rec.ok or not rec.token_times:
        return None
    tt = rec.token_times
    tpot = 1000.0 * (tt[-1] - tt[0]) / (len(tt) - 1) if len(tt) > 1 else 0.0
    return 1000.0 * (tt[0] - rec.due), tpot


def _first_top(pieces) -> dict:
    for _, lp in pieces:
        if lp and lp.get("top_logprobs"):
            return lp["top_logprobs"][0] or {}
    return {}


class Load:
    """Runs a plan. `start()` begins the load (the ramp), the window is
    [t_open, t_close) on time.monotonic(), `finish()` stops new sends,
    lets in-flight requests drain and returns every record.

    New requests are sent until `t_end`: the window's close, or with
    *hold* (`--trace 2`: the same traffic goes on past the window, under
    the tail's trace) whenever `end_sending()` is called."""

    def __init__(self, base: str, model: str, plan, seconds: float, timeout: float = 300.0, hold: bool = False):
        self.base, self.model, self.plan, self.seconds = base, model, plan, seconds
        self.timeout, self.hold = timeout, hold
        self.records: list[Record] = []
        self._lock = threading.Lock()
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()
        self.exhausted = False  # a closed-loop client ran out of requests
        self.t_start = self.t_open = self.t_close = self.t_end = 0.0

    def start(self) -> None:
        self.t_start = time.monotonic()
        self.t_open = self.t_start + self.plan.ramp_s
        self.t_close = self.t_open + self.seconds
        self.t_end = float("inf") if self.hold else self.t_close
        if self.plan.loop == "open":
            th = threading.Thread(target=self._pace, daemon=True)
            th.start()
            self._threads.append(th)
            return
        it = iter(self.plan.shared)
        it_lock = threading.Lock()

        def shared_next():
            with it_lock:
                return next(it, None)

        for c in range(self.plan.clients):
            if self.plan.per_client:
                script = iter(self.plan.per_client[c])
                nxt = lambda s=script: next(s, None)  # noqa: E731
            else:
                nxt = shared_next
            delay = c * self.plan.stagger_s if self.plan.per_client else 0.0
            th = threading.Thread(target=self._client, args=(nxt, delay), daemon=True)
            th.start()
            self._threads.append(th)

    def _one(self, req, due: float) -> None:
        rec = Record(req, due)
        with self._lock:
            self.records.append(rec)
        send(self.base, self.model, req, rec, self.timeout)

    def _client(self, nxt, delay: float = 0.0) -> None:
        if delay > 0 and self._stop.wait(delay):
            return
        while time.monotonic() < self.t_end and not self._stop.is_set():
            req = nxt()
            if req is None:
                with self._lock:
                    self.exhausted = True
                return
            self._one(req, time.monotonic())

    def _pace(self) -> None:
        workers = []
        for req in self.plan.shared:
            due = self.t_start + req.due_s
            if due >= self.t_end:
                break
            delay = due - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                break
            if self._stop.is_set() or due >= self.t_end:
                break
            th = threading.Thread(target=self._one, args=(req, due), daemon=True)
            th.start()
            workers.append(th)
        else:
            if self.hold and self.t_end == float("inf"):
                with self._lock:
                    self.exhausted = True  # the plan ended before the tail's trace did
                self.end_sending()
        for th in workers:
            th.join(timeout=max(self.t_end + self.plan.drain_s - time.monotonic(), 0.1))

    def snapshot(self) -> list[Record]:
        """The records so far, those still in flight among them."""
        with self._lock:
            return list(self.records)

    def end_sending(self) -> None:
        """With *hold*: no new request from now on (in-flight ones go on)."""
        self.t_end = min(self.t_end, time.monotonic())

    def finish(self) -> list[Record]:
        self.end_sending()  # without *hold* t_end is t_close, which is past
        deadline = self.t_end + self.plan.drain_s
        for th in self._threads:
            th.join(timeout=max(deadline - time.monotonic(), 0.1))
        self._stop.set()
        with self._lock:
            return list(self.records)
