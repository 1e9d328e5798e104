"""A request receives what a fetched chunk holds for it in ONE hand-over
(ISSUE 52): `Engine._emit_chunk` walks slot by slot and hands each request
its K tokens at once, and `_stream_response` writes their frames at once.

What must not change is the result. `Reference` below is the emission the
tree had before (one `put` a token, walked step by step), copied here once
and FROZEN: it shares no code with the engine's, and the two are driven
with the same chunks. The wire's bytes are held the same way, against the
frames rebuilt from a request's events by the rule `send_chunk` had."""

import json
import queue
import random
import socket
import sys
import threading
import time
from dataclasses import dataclass, field

import numpy as np
import pytest

from kubeai_tpu import faults
from kubeai_tpu.engine import core
from kubeai_tpu.engine.core import EngineConfig, EventQueue, FinishInfo, Request, build_test_engine
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.tokenizer import ByteTokenizer, IncrementalDetokenizer
from kubeai_tpu.metrics import default_registry

B, K, TOP = 6, 8, 5
EOS = ByteTokenizer.eos_id


# -- the parent's emission, frozen ---------------------------------------


@dataclass
class RefRequest:
    prompt_ids: list
    params: SamplingParams
    park_kv: str = ""
    out: "queue.Queue" = field(default_factory=queue.Queue)
    cancelled: threading.Event = field(default_factory=threading.Event)


@dataclass
class RefSlot:
    req: RefRequest
    detok: IncrementalDetokenizer
    prompt_len: int
    generated: int = 0
    committed_text: str = ""
    delivered_chars: int = 0
    budget: int = 0
    event_log: list | None = None
    kv_steps: int = 0

    @property
    def holdback(self) -> int:
        return max((len(s) for s in self.req.params.stop), default=1) - 1


class Reference:
    """`_emit_admitted`, `_emit_chunk`, `_emit_token` and the delivery of
    `_free` as commit 79accd6 had them, less what no event depends on
    (pages, parking, the trace, the step record)."""

    def __init__(self, tokenizer):
        self.tokenizer = tokenizer
        self.slots: list[RefSlot | None] = [None] * B
        self.kv_history: list[list[int]] = [[] for _ in range(B)]
        self.kv_pending: list[int | None] = [None] * B
        self.epoch = [0] * B
        self.generated_tokens_total = 0
        self.slot_steps_total = {"active": 0, "idle": 0}

    def register(self, i, req, budget):
        slot = RefSlot(req=req, detok=IncrementalDetokenizer(self.tokenizer),
                       prompt_len=len(req.prompt_ids), budget=budget)
        if req.park_kv:
            slot.event_log = []
        self.slots[i] = slot
        self.kv_history[i] = list(req.prompt_ids)
        self.kv_pending[i] = None
        self.epoch[i] += 1
        return slot

    def emit_admitted(self, i, epoch, tok, lp, top):
        if self.epoch[i] == epoch:
            self.kv_pending[i] = tok
        slot = self.slots[i]
        if slot is not None and self.epoch[i] == epoch:
            self.emit_token(i, tok, lp, top if slot.req.params.logprobs else None)

    def emit_chunk(self, snapshot, corr, lp_c, t_ids, t_lp):
        K_steps = int(corr.shape[0])
        self.slot_steps_total["active"] += K_steps * len(snapshot)
        self.slot_steps_total["idle"] += K_steps * (B - len(snapshot))
        n_emitted = 0
        for k in range(K_steps):
            for i, slot_obj, epoch in snapshot:
                tok = int(corr[k, i])
                if self.epoch[i] == epoch:
                    if self.kv_pending[i] is not None:
                        self.kv_history[i].append(self.kv_pending[i])
                    self.kv_pending[i] = tok
                if self.slots[i] is not slot_obj:
                    continue
                slot_obj.kv_steps += 1
                top = None
                if t_ids is not None and slot_obj.req.params.logprobs:
                    top = list(zip(t_ids[k, i].tolist(), t_lp[k, i].tolist()))
                self.emit_token(i, tok, float(lp_c[k, i]), top)
                n_emitted += 1
        return n_emitted

    def emit_token(self, slot_idx, token_id, logprob=None, top=None):
        slot = self.slots[slot_idx]
        req = slot.req
        if req.cancelled.is_set():
            self.free(slot_idx, "stop", deliver=False)
            return
        slot.generated += 1
        self.generated_tokens_total += 1
        eos = self.tokenizer.eos_id
        if eos is not None and token_id == eos:
            self.free(slot_idx, "stop")
            return
        slot.committed_text += slot.detok.push(token_id)
        text = slot.committed_text
        search_from = max(0, slot.delivered_chars - slot.holdback)
        for s in req.params.stop:
            pos = text.find(s, search_from)
            if pos != -1:
                tail = text[slot.delivered_chars : pos]
                slot.delivered_chars = pos
                ev = ("token", token_id, tail, logprob, top)
                if slot.event_log is not None:
                    slot.event_log.append(ev)
                req.out.put(ev)
                self.free(slot_idx, "stop", flush=False)
                return
        emit_upto = max(len(text) - slot.holdback, slot.delivered_chars)
        delta = text[slot.delivered_chars : emit_upto]
        slot.delivered_chars = emit_upto
        ev = ("token", token_id, delta, logprob, top)
        if slot.event_log is not None:
            slot.event_log.append(ev)
        req.out.put(ev)
        if slot.generated >= slot.budget:
            self.free(slot_idx, "length")

    def free(self, slot_idx, reason, deliver=True, flush=True):
        slot = self.slots[slot_idx]
        self.slots[slot_idx] = None
        if deliver:
            if flush:
                text = slot.detok.text()
                end = len(text)
                search_from = max(0, slot.delivered_chars - slot.holdback)
                for s in slot.req.params.stop:
                    pos = text.find(s, search_from)
                    if pos != -1:
                        end = min(end, pos)
                        reason = "stop"
                tail = text[slot.delivered_chars : end]
                if tail:
                    slot.req.out.put(("token", -1, tail, None, None))
            slot.req.out.put(("done", FinishInfo(reason, slot.prompt_len, slot.generated, kv=None)))


# -- the engine and the reference, driven in step ------------------------


@pytest.fixture(scope="module")
def engine():
    # Never started: the test is the scheduler, and fills the slots by hand.
    return build_test_engine(EngineConfig(max_slots=B, max_seq_len=256, decode_chunk=K))


def _gen_total() -> float:
    return default_registry.counter("kubeai_engine_generated_tokens_total").value()


def _handovers() -> float:
    return default_registry.counter("kubeai_engine_emit_handovers_total").value()


def _slot_steps(state: str) -> float:
    return default_registry.counter("kubeai_engine_slot_steps_total").value({"state": state})


@dataclass
class Spec:
    """One request: the tokens the device chooses for it (`tokens[0]` at
    its admission, then K a chunk; past their end the slot decodes noise,
    as a slot does between its request's end and its chunk's)."""

    tokens: list
    stop: tuple = ()
    budget: int = 10_000
    logprobs: bool = False
    park: bool = False
    cancel_before: int | None = None  # ... the chunk of this number
    expect: tuple | None = None  # (finish reason, completion tokens)
    at: int = 0  # the chunk before which it was admitted (set by Pair)


class Pair:
    def __init__(self, eng, seed):
        self.eng = eng
        self.ref = Reference(eng.tokenizer)
        self.rng = np.random.default_rng(seed)
        self.requests = []  # (spec, engine request, engine slot, ref request, ref slot)
        self.holder: list = [None] * B  # the entry of `requests` a slot decodes for
        self.chunk_no = 0
        for i in range(B):  # what an earlier case left
            if eng._slots[i] is not None:
                eng._free(i, "stop", deliver=False)
            eng._kv_history[i], eng._kv_pending[i] = [], None
        self.base = (_gen_total(), _slot_steps("active"), _slot_steps("idle"))

    def admit(self, i, spec, first=True):
        eng, ref = self.eng, self.ref
        sp = SamplingParams(stop=tuple(spec.stop), logprobs=spec.logprobs)
        prompt = self.rng.integers(0, 256, int(self.rng.integers(1, 9))).tolist()
        park = "handoff" if spec.park else ""
        req = Request(prompt_ids=list(prompt), params=sp, park_kv=park)
        # Engine._register's host bookkeeping, as far as emission reads it.
        slot = core._Slot(req=req, detok=IncrementalDetokenizer(eng.tokenizer),
                          prompt_len=len(prompt), budget=spec.budget)
        if park:
            slot.event_log = []
        with eng._in_system_lock:
            eng._in_system += 1
        eng._slots[i] = slot
        eng._n_active += 1
        eng._kv_history[i], eng._kv_pending[i] = list(prompt), None
        eng._slot_epoch[i] += 1
        eng._h_active[i] = True
        rreq = RefRequest(prompt_ids=list(prompt), params=sp, park_kv=park)
        rslot = ref.register(i, rreq, spec.budget)
        spec.at = self.chunk_no
        self.requests.append((spec, req, slot, rreq, rslot))
        self.holder[i] = self.requests[-1]
        if first:
            tok, lp = int(spec.tokens[0]), float(np.float32(-self.rng.random()))
            tids = self.rng.integers(0, 259, TOP).astype(np.int32)
            tlps = (-self.rng.random(TOP)).astype(np.float32)
            eng._emit_admitted([(i, eng._slot_epoch[i], np.int32(tok), None, np.float32(lp), tids, tlps)])
            ref.emit_admitted(i, ref.epoch[i], tok, lp, list(zip(tids.tolist(), tlps.tolist())))

    def chunk(self, between=None):
        """One decode chunk: dispatch (the snapshots), whatever *between*
        does to the slots while it is in flight, fetch and emission."""
        eng, ref, c = self.eng, self.ref, self.chunk_no
        for spec, req, _, rreq, _ in self.requests:
            if spec.cancel_before == c:
                req.cancelled.set()
                rreq.cancelled.set()
        snap = [(i, s, eng._slot_epoch[i]) for i, s in enumerate(eng._slots) if s is not None]
        rsnap = [(i, s, ref.epoch[i]) for i, s in enumerate(ref.slots) if s is not None]
        assert [i for i, _, _ in snap] == [i for i, _, _ in rsnap]
        corr = self.rng.integers(0, 256, (K, B)).astype(np.int32)  # noise where no request decodes
        for i, _, _ in snap:
            spec = self.holder[i][0]
            lo = 1 + (c - spec.at) * K
            col = spec.tokens[lo : lo + K]
            corr[: len(col), i] = col
        lp_c = (-self.rng.random((K, B))).astype(np.float32)
        t_ids = t_lp = None
        if any(s.req.params.logprobs for _, s, _ in snap):
            t_ids = self.rng.integers(0, 259, (K, B, TOP)).astype(np.int32)
            t_lp = (-self.rng.random((K, B, TOP))).astype(np.float32)
        if between is not None:
            between()
        step = eng._emit_chunk(snap, 0.01, corr, lp_c, t_ids, t_lp)
        n = ref.emit_chunk(rsnap, corr, lp_c, t_ids, t_lp)
        assert step["tokens"] == n and step["steps"] == K
        self.chunk_no += 1
        self.check_slots()

    def check_slots(self):
        eng, ref = self.eng, self.ref
        for i in range(B):
            assert (eng._slots[i] is None) == (ref.slots[i] is None), f"slot {i}"
            assert eng._kv_history[i] == ref.kv_history[i], f"slot {i}"
            assert eng._kv_pending[i] == ref.kv_pending[i], f"slot {i}"

    def check(self):
        """Every request's events, and what emission leaves on its slot."""
        self.check_slots()
        for n, (spec, req, slot, rreq, rslot) in enumerate(self.requests):
            got, want = [], []
            while not req.out.empty():
                got.append(req.out.get_nowait())
            while not rreq.out.empty():
                want.append(rreq.out.get_nowait())
            assert got == want, f"request {n}"
            for name in ("generated", "kv_steps", "committed_text", "delivered_chars", "event_log"):
                assert getattr(slot, name) == getattr(rslot, name), f"request {n}: {name}"
            assert not slot.outbox and not slot.uncounted
            if spec.expect is not None:
                assert want[-1][0] == "done", f"request {n}"
                fin = want[-1][1]
                assert (fin.reason, fin.completion_tokens) == spec.expect, f"request {n}"
        g0, a0, i0 = self.base
        assert _gen_total() - g0 == self.ref.generated_tokens_total
        assert _slot_steps("active") - a0 == self.ref.slot_steps_total["active"]
        assert _slot_steps("idle") - i0 == self.ref.slot_steps_total["idle"]


def text_tokens(rng, n) -> list:
    """*n* tokens of lower-case letters: none ends a request by itself."""
    return rng.integers(ord("a"), ord("z") + 1, n).tolist()


def ending_at(rng, chunk, step, payload: bytes) -> list:
    """Letters, then *payload*, whose LAST byte is the token of *step* of
    *chunk* (the first token is the admission's, then K a chunk)."""
    n = 1 + chunk * K + step + 1
    return text_tokens(rng, n - len(payload)) + list(payload)


def noise_spec(rng) -> Spec:
    """A request as a fleet sends them: text with multi-byte characters,
    a stray byte, now and then an end of sequence; some with stop strings,
    some asking for log-probs, some recorded for a park."""
    toks: list = []
    while len(toks) < 12 * K:
        r = rng.random()
        if r < 0.70:
            toks.append(int(rng.integers(32, 127)))
        elif r < 0.90:
            toks += list(str(rng.choice(["é", "日", "🙂", "ß"])).encode())
        elif r < 0.93:
            toks.append(int(rng.integers(128, 192)))  # a continuation byte with no lead
        elif r < 0.95:
            toks.append(int(rng.integers(259, 272)))  # the vocabulary's padding
        elif r < 0.97:
            toks.append(EOS)
        else:
            toks += list(b"ab")
    stops = [(), (), ("ab",), ("é", "zz"), ("the end", "\n")]
    return Spec(
        tokens=toks, stop=stops[int(rng.integers(len(stops)))], budget=int(rng.integers(2, 70)),
        logprobs=bool(rng.random() < 0.3), park=bool(rng.random() < 0.3),
    )


def _stop_inside(rng):
    return [Spec(ending_at(rng, 1, 3, b"STOP") + text_tokens(rng, 40), stop=("STOP",),
                 expect=("stop", 1 + K + 4))]


def _stop_across(rng):
    # "ST" on the last two steps of chunk 0, "OP" on the first two of chunk 1.
    return [Spec(ending_at(rng, 1, 1, b"STOP") + text_tokens(rng, 40), stop=("STOP",),
                 expect=("stop", 1 + K + 2))]


def _stop_last(rng):
    return [Spec(ending_at(rng, 1, K - 1, b"STOP") + text_tokens(rng, 40), stop=("STOP", "never"),
                 expect=("stop", 1 + 2 * K))]


def _stop_in_flush(rng):
    # The budget ends the request on the lead byte of a character: only
    # `_free`'s flush decodes it (to a replacement char), finds the stop
    # string there and turns "length" into "stop".
    toks = text_tokens(rng, 1 + 4) + [0xE6] + text_tokens(rng, 40)
    return [Spec(toks, stop=("\ufffd",), budget=6, expect=("stop", 6))]


def _eos(step):
    def build(rng):
        toks = text_tokens(rng, 1 + K + step) + [EOS] + text_tokens(rng, 40)
        return [Spec(toks, expect=("stop", 1 + K + step + 1)), Spec(text_tokens(rng, 200), stop=("#",))]

    return build


def _budget_mid(rng):
    return [Spec(text_tokens(rng, 200), budget=1 + K + 3, expect=("length", 1 + K + 3)),
            Spec(text_tokens(rng, 200), stop=("0", "11"), budget=1 + 2 * K, expect=("length", 1 + 2 * K))]


def _cancelled(rng):
    return [Spec(text_tokens(rng, 200), cancel_before=1), Spec(text_tokens(rng, 200), cancel_before=2, park=True)]


def _logprobs(rng):
    return [Spec(ending_at(rng, 1, 5, b"END") + text_tokens(rng, 40), stop=("END",), logprobs=True,
                 expect=("stop", 1 + K + 6)),
            Spec(text_tokens(rng, 200), logprobs=False, budget=1 + 2 * K + 1),
            Spec(text_tokens(rng, 60) + [EOS], logprobs=True)]


def _utf8(rng):
    smile, kanji = "🙂".encode(), "日".encode()
    # 4 bytes over steps 6, 7 of chunk 0 and 0, 1 of chunk 1; 3 bytes inside
    # chunk 1; a lead byte as the LAST token before the budget ends it.
    split = text_tokens(rng, 1 + 6) + list(smile) + text_tokens(rng, 2) + list(kanji) + text_tokens(rng, 40)
    cut = text_tokens(rng, 1 + K + 2) + list(smile[:2])
    stop_mb = ending_at(rng, 1, 0, "日é".encode()) + text_tokens(rng, 40)
    return [Spec(split, budget=1 + 3 * K), Spec(cut, budget=len(cut), expect=("length", len(cut))),
            Spec(stop_mb, stop=("日é",), expect=("stop", 1 + K + 1))]


def _event_log(rng):
    return [Spec(text_tokens(rng, 200), park=True, budget=1 + K + 5, expect=("length", 1 + K + 5)),
            Spec(ending_at(rng, 0, 6, b"ab") + text_tokens(rng, 40), park=True, stop=("ab",),
                 expect=("stop", 1 + 7))]


def _two_end_in_one_chunk(rng):
    # The higher slot ends at an EARLIER step: a walk by step frees it
    # first, a walk by slot last. Neither request can tell.
    return [Spec(text_tokens(rng, 200), budget=1 + K + 6, expect=("length", 1 + K + 6)),
            Spec(text_tokens(rng, 1 + K + 1) + [EOS] + text_tokens(rng, 40), expect=("stop", 1 + K + 2))]


CASES = {
    "no_stop": lambda rng: [Spec(text_tokens(rng, 200)), Spec(text_tokens(rng, 200), stop=("0",))],
    "stop_inside_chunk": _stop_inside,
    "stop_across_two_chunks": _stop_across,
    "stop_on_chunks_last_token": _stop_last,
    "stop_found_by_the_flush": _stop_in_flush,
    "eos_at_step_0": _eos(0),
    "eos_mid_chunk": _eos(4),
    "eos_at_last_step": _eos(K - 1),
    "budget_ends_mid_chunk": _budget_mid,
    "cancelled_between_chunks": _cancelled,
    "logprobs_top_n": _logprobs,
    "utf8_split_across_tokens_and_chunks": _utf8,
    "event_log_recorded": _event_log,
    "two_end_in_one_chunk": _two_end_in_one_chunk,
}


@pytest.mark.parametrize("case", list(CASES) + ["readmitted_in_flight"] + [f"fleet_seed_{n}" for n in range(6)])
def test_same_events_as_the_per_token_walk(engine, case):
    """The engine's delivery against the frozen per-token reference, on
    the same chunks: every request's events, `FinishInfo`, `generated`,
    `kv_steps`, the slots' `_kv_history` / `_kv_pending`, the counters."""
    seed = sum(case.encode()) * 7919
    rng = np.random.default_rng(seed)
    pair = Pair(engine, seed + 1)
    specs = CASES[case](rng) if case in CASES else []
    for i in range(B):
        # The case's requests first, a fleet's noise in the other slots.
        pair.admit(i, specs[i] if i < len(specs) else noise_spec(rng))
    for c in range(10):
        between = None
        if case == "readmitted_in_flight" and c in (1, 3):
            def between():
                # While the chunk is in flight slot 1 is freed AND given a
                # new request (its epoch moves: the chunk's tokens are not
                # the newcomer's), and slot 2 is freed and left empty (the
                # history still takes all K tokens: nobody reset it).
                pair.eng._free(1, "stop", deliver=False)
                pair.ref.free(1, "stop", deliver=False)
                pair.eng._free(2, "preempted", flush=False)
                pair.ref.free(2, "preempted", flush=False)
                pair.admit(1, noise_spec(rng), first=False)
        pair.chunk(between)
        for i in range(B):
            # The next admission round fills what the chunk freed.
            if pair.eng._slots[i] is None:
                pair.admit(i, noise_spec(rng))
    pair.check()


def test_a_chunk_is_one_handover_a_slot(engine):
    """B live slots, K tokens each: B hand-overs and not K x B, and
    /debug/engine reads K tokens a hand-over."""
    rng = np.random.default_rng(5)
    pair = Pair(engine, 6)
    for i in range(B):
        pair.admit(i, Spec(text_tokens(rng, 100)), first=False)
    engine._handed[:] = [0, 0]
    h0, g0 = _handovers(), _gen_total()
    pair.chunk()
    assert _handovers() - h0 == B
    assert _gen_total() - g0 == K * B
    assert engine._perf_debug_section()["tokens_per_handover"] == K
    # An admission's first token is a hand-over of one; a request that ends
    # in its chunk still gets ONE (its tokens with its `done`).
    pair.eng._free(0, "stop", deliver=False)
    pair.ref.free(0, "stop", deliver=False)
    pair.admit(0, Spec(text_tokens(rng, 100), budget=3), first=False)
    h0 = _handovers()
    pair.chunk()
    assert _handovers() - h0 == B
    evs = pair.requests[-1][1].out.get_many(timeout=1)
    assert [e[0] for e in evs] == ["token"] * 3 + ["done"]


def test_a_blocked_reader_is_woken_once_a_chunk(engine):
    rng = np.random.default_rng(7)
    pair = Pair(engine, 8)
    for i in range(B):
        pair.admit(i, Spec(text_tokens(rng, 100)), first=False)
    outs = [r.out for _, r, *_ in pair.requests]
    notifies = [0] * B
    for n, q in enumerate(outs):
        def counting(*a, _n=n, _notify=q.not_empty.notify):
            notifies[_n] += 1
            return _notify(*a)

        q.not_empty.notify = counting
    got: list = [None] * B

    def reader(n):
        got[n] = outs[n].get_many(timeout=30)

    threads = [threading.Thread(target=reader, args=(n,), daemon=True) for n in range(B)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 10
    while any(not q.not_empty._waiters for q in outs) and time.monotonic() < deadline:
        time.sleep(0.005)  # until every reader blocks
    pair.chunk()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert notifies == [1] * B
    assert [len(evs) for evs in got] == [K] * B  # ... and the one wake found the whole chunk


def test_event_queue_keeps_the_queues_surface():
    q = EventQueue()
    q.put(("token", 0, "a", None, None))
    q.put_many([("token", n, "b", None, None) for n in range(1, 8)])
    # Eight `get`s after one wake: seven find their event without blocking.
    assert [q.get(timeout=0)[1] for _ in range(8)] == list(range(8))
    with pytest.raises(queue.Empty):
        q.get(timeout=0.01)
    with pytest.raises(queue.Empty):
        q.get_many(timeout=0.01)
    q.put_many([1, 2])
    q.put(3)
    assert q.get_many() == [1, 2, 3] and q.empty()
    # Two readers of ONE queue, two events in one hand-over: both wake.
    got: list = []
    readers = [threading.Thread(target=lambda: got.append(q.get(timeout=10)), daemon=True) for _ in range(2)]
    for t in readers:
        t.start()
    while len(q.not_empty._waiters) < 2:
        time.sleep(0.005)
    q.put_many(["x", "y"])
    for t in readers:
        t.join(timeout=10)
    assert sorted(got) == ["x", "y"]


def test_event_queue_loses_nothing_under_contention():
    """More threads than cores, a short switch interval: every event put
    (singly or as a list) is taken exactly once and in its writer's order."""
    n_pairs, n_events = 24, 400
    queues = [EventQueue() for _ in range(n_pairs)]
    taken: list = [[] for _ in range(n_pairs)]

    def writer(n):
        rnd, i = random.Random(n), 0
        while i < n_events:
            k = min(rnd.choice((1, 1, 8, 3)), n_events - i)
            if k == 1:
                queues[n].put(i)
            else:
                queues[n].put_many(list(range(i, i + k)))
            i += k

    def reader(n):
        rnd = random.Random(-n)
        while len(taken[n]) < n_events:
            if rnd.random() < 0.5:
                taken[n].append(queues[n].get(timeout=20))
            else:
                taken[n] += queues[n].get_many(timeout=20)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=f, args=(n,), daemon=True)
                   for n in range(n_pairs) for f in (writer, reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert taken == [list(range(n_events))] * n_pairs


# -- the wire --------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """A started engine behind its server, every request and every event
    it is handed kept for the test: [(request, [events])]."""
    from kubeai_tpu.engine.server import EngineServer

    eng = build_test_engine(EngineConfig(max_slots=4, max_seq_len=256, decode_chunk=K))
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
    yield srv, seen
    srv.stop()
    eng.stop()


@pytest.fixture
def recorded(monkeypatch):
    """{id(queue): [events]} of every `put` / `put_many` while a test runs."""
    log: dict = {}
    put, put_many = EventQueue.put, EventQueue.put_many

    def spy_put(self, ev, *a, **kw):
        log.setdefault(id(self), []).append(ev)
        return put(self, ev, *a, **kw)

    def spy_put_many(self, evs, *a):
        log.setdefault(id(self), []).extend(evs)
        return put_many(self, evs, *a)

    monkeypatch.setattr(EventQueue, "put", spy_put)
    monkeypatch.setattr(EventQueue, "put_many", spy_put_many)
    return log


def raw_post(port, path, body) -> bytes:
    """The response's body as it crossed the socket, chunk framing and all."""
    payload = json.dumps(body).encode()
    with socket.create_connection(("127.0.0.1", port), timeout=120) as s:
        s.sendall(
            f"POST {path} HTTP/1.1\r\nHost: t\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n".encode() + payload
        )
        data = b""
        while True:
            try:
                part = s.recv(65536)
            except ConnectionResetError:
                break
            if not part:
                break
            data += part
    head, _, rest = data.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200") or head.startswith(b"HTTP/1.0 200"), head
    return rest


def wire(payloads) -> bytes:
    """`send_chunk`'s framing as the parent had it, a frame a payload."""
    out = b""
    for p in payloads:
        data = f"data: {p}\n\n".encode()
        out += f"{len(data):x}\r\n".encode() + data + b"\r\n"
    return out


def parent_payloads(events, tok, rid, created, chat, want_logprobs, top_n, include_usage, echo=""):
    """The SSE payloads `_stream_response` made of ONE request's events at
    commit 79accd6 (no handoff cap), in order: the frozen framing rule."""
    obj = "chat.completion.chunk" if chat else "text_completion"
    head = {"id": rid, "object": obj, "created": created, "model": "m"}

    def text_of(tid):
        return tok.decode([tid])

    def tops(top):
        if not top_n or not top:
            return None
        pairs = top[:top_n]
        if chat:
            return [{"token": text_of(t), "logprob": lp} for t, lp in pairs]
        out: dict = {}
        for t, lp in pairs:
            out.setdefault(text_of(t), lp)
        return out

    payloads = []
    if chat:
        payloads.append(json.dumps({**head, "choices": [
            {"index": 0, "delta": {"role": "assistant"}, "finish_reason": None}]}))
    elif echo:
        payloads.append(json.dumps({**head, "choices": [{"index": 0, "text": echo, "finish_reason": None}]}))
    for ev in events:
        if ev[0] == "token":
            has_lp = want_logprobs and ev[1] >= 0 and ev[3] is not None
            if not ev[2] and not has_lp:
                continue
            if chat:
                choice = {"index": 0, "delta": {"content": ev[2]}, "finish_reason": None}
                if has_lp:
                    entry = {"token": text_of(ev[1]), "logprob": ev[3]}
                    if top_n:
                        entry["top_logprobs"] = tops(ev[4]) or []
                    choice["logprobs"] = {"content": [entry]}
            else:
                choice = {"index": 0, "text": ev[2], "finish_reason": None}
                if has_lp:
                    choice["logprobs"] = {
                        "tokens": [text_of(ev[1])], "token_logprobs": [ev[3]],
                        "top_logprobs": [tops(ev[4]) or {}] if top_n else None,
                    }
            payloads.append(json.dumps({**head, "choices": [choice]}))
        else:
            assert ev[0] == "done"
            fin = ev[1]
            choice = ({"index": 0, "delta": {}, "finish_reason": fin.reason} if chat
                      else {"index": 0, "text": "", "finish_reason": fin.reason})
            payloads.append(json.dumps({**head, "choices": [choice]}))
            if include_usage:
                payloads.append(json.dumps({**head, "choices": [], "usage": {
                    "prompt_tokens": fin.prompt_tokens, "completion_tokens": fin.completion_tokens,
                    "total_tokens": fin.prompt_tokens + fin.completion_tokens}}))
            payloads.append("[DONE]")
    return payloads


def first_payload(body: bytes) -> dict:
    line = body.split(b"\r\n", 2)[1]
    assert line.startswith(b"data: "), line
    return json.loads(line[len(b"data: "):])


STREAMS = {
    "completions": dict(chat=False),
    "completions_logprobs_top3": dict(chat=False, logprobs=3),
    "completions_logprobs_no_top": dict(chat=False, logprobs=0),
    "completions_usage_stop": dict(chat=False, usage=True, stop=["e", "a"]),
    "completions_echo": dict(chat=False, echo=True),
    "chat": dict(chat=True),
    "chat_logprobs_top2": dict(chat=True, logprobs=2),
    "chat_usage": dict(chat=True, usage=True),
    "chat_logprobs_usage_sampled": dict(chat=True, logprobs=4, usage=True, temperature=0.9),
}


@pytest.mark.parametrize("name", list(STREAMS))
def test_streamed_bytes_are_the_parents(served, recorded, name):
    """A streamed completion's body, byte for byte: the frames the parent's
    rule makes of the request's events, each in the parent's chunk framing."""
    srv, seen = served
    o = STREAMS[name]
    chat, top_n = o["chat"], o.get("logprobs")
    body = {"model": "m", "stream": True, "max_tokens": 21, "seed": 11,
            "temperature": o.get("temperature", 0.0)}
    if chat:
        body["messages"] = [{"role": "user", "content": "say it, " + name}]
        if top_n is not None:
            body.update(logprobs=True, top_logprobs=top_n)
    else:
        body["prompt"] = "say it, " + name
        if top_n is not None:
            body["logprobs"] = top_n
        if o.get("echo"):
            body["echo"] = True
    if o.get("usage"):
        body["stream_options"] = {"include_usage": True}
    if o.get("stop"):
        body["stop"] = o["stop"]
    del seen[:]
    got = raw_post(srv.port, "/v1/chat/completions" if chat else "/v1/completions", body)
    assert len(seen) == 1
    events = recorded[id(seen[0].out)]
    assert events[-1][0] == "done" and sum(e[0] == "token" for e in events) >= 2
    first = first_payload(got)
    want = wire(parent_payloads(
        events, srv.engine.tokenizer, first["id"], first["created"], chat,
        want_logprobs=top_n is not None, top_n=top_n or 0, include_usage=bool(o.get("usage")),
        echo=body["prompt"] if o.get("echo") else "",
    )) + b"0\r\n\r\n"
    assert got == want


def test_two_choices_stream_each_in_order(served, recorded):
    """n = 2: the choices interleave as their pumps run, but each choice's
    frames are the parent's frames of its events, in order."""
    srv, seen = served
    del seen[:]
    got = raw_post(srv.port, "/v1/completions", {
        "model": "m", "prompt": "two of them", "stream": True, "n": 2, "max_tokens": 19,
        "temperature": 0.8, "seed": 5,
    })
    assert len(seen) == 2 and got.endswith(wire(["[DONE]"]) + b"0\r\n\r\n")
    first = first_payload(got)
    by_choice: dict = {0: [], 1: []}
    for frame in got.split(b"\r\n")[1::2]:
        if frame.startswith(b"data: {"):
            p = frame[len(b"data: "):].decode().strip()
            by_choice[json.loads(p)["choices"][0]["index"]].append(p)
    for idx, req in enumerate(seen):
        want = parent_payloads(recorded[id(req.out)], srv.engine.tokenizer, first["id"], first["created"],
                               False, False, 0, False)[:-1]  # one [DONE] for both, checked above
        want = [json.dumps({**json.loads(p), "choices": [{**json.loads(p)["choices"][0], "index": idx}]})
                for p in want]
        assert by_choice[idx] == want


@pytest.mark.parametrize("n_events", [1, 3, 8, 13])
def test_stream_failpoint_severs_after_n_events(served, recorded, n_events):
    """`engine.stream=error:1:skip=N` counts EVENTS, not writes: exactly
    the first N frames of the stream arrive, then the socket dies with the
    chunked stream unterminated."""
    srv, seen = served
    del seen[:]
    faults.arm_spec("engine.stream", f"error:1:skip={n_events}")
    try:
        got = raw_post(srv.port, "/v1/completions", {
            "model": "m", "prompt": "cut me short", "stream": True, "max_tokens": 40, "temperature": 0.0,
        })
    finally:
        faults.clear_fault("engine.stream")
    assert len(seen) == 1
    first = first_payload(got)
    # The request was cancelled at the fault: its events up to then still
    # give the frames that preceded it.
    events = [e for e in recorded[id(seen[0].out)] if e[0] == "token"]
    want = parent_payloads(events, srv.engine.tokenizer, first["id"], first["created"], False, False, 0, False)
    assert len(want) >= n_events
    assert got == wire(want[:n_events])
