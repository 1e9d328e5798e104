"""Continuous-batching inference engine (JetStream-style slots, paged KV).

The TPU-native replacement for the engine containers the reference
orchestrates but never implements (ref: charts/kubeai/values.yaml:39-75
engine image matrix; SURVEY.md §2.9). Architecture:

- A fixed pool of **decode slots** backed by a **paged KV pool**
  [L, Kv, pages, page, h] that lives on device and is donated through
  every jitted step (no per-step copies). Each slot maps its sequence
  onto pool pages through a block table; pages holding full, content-
  addressed prefixes are ref-counted and **shared across slots**
  (engine/paging.py), so a hot system prompt is prefilled once and
  reused by every concurrent request that shares it — the engine-side
  complement to PrefixHash routing. Pages for prompt+budget are
  reserved at admission (requests wait, never die mid-decode), and HBM
  is consumed proportional to actual sequence lengths, not
  max_slots x max_seq_len.
- **Prefill** pads the prompt to a power-of-two bucket and writes
  through the slot's block table (one compilation per bucket); requests
  resuming after a shared-prefix hit take the chunked path from the
  reuse offset.
- **Decode** runs all slots every step in a single jitted call that also
  samples (per-slot temperature/top-k/top-p arrays) and advances per-slot
  PRNG keys device-side; only the sampled token ids [max_slots] cross back
  to the host per step.
- A single scheduler thread owns the device state; HTTP handler threads
  talk to it through queues. Stop handling (max_tokens, EOS, stop strings)
  is host-side on the incrementally detokenized stream.
"""

from __future__ import annotations

import bisect
import math
import queue
import threading
import time
import uuid
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.engine.sampling import (
    EPILOGUE_PARTS,
    SamplingParams,
    apply_logit_bias,
    apply_penalties,
    epilogue_parts,
    sample,
)
from kubeai_tpu import faults
from kubeai_tpu.faults import FaultError, fault
from kubeai_tpu.engine import kvstate
from kubeai_tpu.engine.tokenizer import IncrementalDetokenizer
from kubeai_tpu.metrics import default_registry
from kubeai_tpu.models import family
from kubeai_tpu.models.base import LiveRows, ModelConfig
from kubeai_tpu.obs import default_recorder
from kubeai_tpu.obs import perf as perf_obs
from kubeai_tpu.obs.tenants import default_accountant as tenant_accountant
from kubeai_tpu.obs.recorder import (
    register_engine_debug_section,
    unregister_engine_debug_section,
)
from kubeai_tpu.obs.logs import get_logger, trace_extra
from kubeai_tpu.obs.trace import RequestTrace, TraceContext
from kubeai_tpu.ops import chunk_attention, mla_attention, moe, paged_attention, ssm
from kubeai_tpu.qos import QoSQueue, record_admitted, record_preemption
from kubeai_tpu.qos import install_queue as qos_install_queue
from kubeai_tpu.qos import uninstall_queue as qos_uninstall_queue

# The scheduler loop is ONE thread multiplexing many requests, so the
# contextvar-bound request identity can't apply here — per-request log
# sites stamp explicitly with ``extra=trace_extra(req.trace)``.
log = get_logger("kubeai_tpu.engine")

# The scheduler segments a decode chunk's step record carries, as
# dispatch_ms / host_overlap_ms / fetch_wait_ms / emit_ms.
_CHUNK_SEGMENTS = ("dispatch", "host_overlap", "fetch_wait", "emit")

# What a queued request waits for (Engine._queue_parts), as the labels of
# kubeai_engine_queue_wait_by_cause_seconds_total.
_QUEUE_CAUSES = ({"cause": "turn"}, {"cause": "slots"}, {"cause": "pages"})


def _name_os_thread(name: str) -> None:
    """Name the calling thread for the kernel (PR_SET_NAME, 15 bytes): a
    profiler trace labels a thread's line with that name, and Python 3.12
    leaves it the process's."""
    try:
        import ctypes

        ctypes.CDLL(None, use_errno=True).prctl(15, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):  # not Linux: the line keeps the process name
        pass


class GangLost(ConnectionError):
    """A gang follower's dispatch connection failed — the gang's
    collectives cannot line up until the follower reconnects and the
    gang re-forms (or, failing that within the supervision window,
    the rank exits for the controller to recreate the slice)."""


class GangDesync(RuntimeError):
    """Rank 0 broadcast an op but failed before/while executing it
    locally (advisor r3, core.py): the followers have already entered
    that op's global-mesh collective and are blocked waiting for rank 0
    to join — a "reset" op can never reach them (they aren't reading the
    stream), so per-request swallowing or reset recovery just hangs the
    gang. Fatal for the rank: fail in-flight requests and exit for the
    controller to recreate the slice gang."""


@dataclass
class EngineConfig:
    max_slots: int = 8
    max_seq_len: int = 2048
    prefill_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024)
    max_queue: int = 512
    # Cap on new tokens per request (request max_tokens is clamped to fit
    # the slot: prompt_len + max_tokens <= max_seq_len).
    default_max_tokens: int = 256
    # Decode steps fused into one jitted lax.scan call: host<->device
    # round-trips are amortized K x at the cost of up to K-1 wasted
    # steps per finished sequence and admission latency quantized to
    # one chunk.
    decode_chunk: int = 8
    # Batched multi-LoRA capacity (bank allocated on first adapter load;
    # the first load triggers one recompile of the step functions).
    max_adapters: int = 8
    max_lora_rank: int = 64
    # Non-greedy sampling candidate space (see engine/sampling.py);
    # <= 0 samples the exact full distribution (full-vocab sort).
    max_top_k: int = 128
    # Cold prefill runs at exactly TWO compiled batch sizes per bucket:
    # 1 row and min(this, max_slots) rows. Two sizes bound the compile
    # count and let warmup cover every shape. This names the full-group
    # shape only: a round's same-bucket cold prompts run as calls of
    # this many rows while that many are left, and the rest as one-row
    # calls, so no call computes a row nobody sent; where warm-up has
    # measured what a call costs, one of the rest may instead ride a
    # chunk call of two slots as a row that starts at 0, where that saves
    # its own read of the weights (_plan_admissions, round_calls).
    prefill_group_cap: int = 8
    # Paged KV: tokens per page. 64 keeps TPU tiling happy (page x head
    # dims land on (16,128)+ bf16 tiles) while giving fine-grained HBM
    # accounting; tests use smaller pages for sharper assertions.
    page_size: int = 64
    # Total pool pages (incl. reserved trash page 0). 0 = auto-size to
    # max_slots * ceil(max_seq_len/page_size) + 1, i.e. the same HBM as
    # a dense slot cache — sharing then shows up as headroom. Operators
    # can overcommit (more slots than fully-backed sequences) or shrink.
    num_pages: int = 0
    # Cross-slot prefix caching: a prompt whose resident shared prefix
    # (whole pages, content-addressed — engine/paging.py) is >= this many
    # tokens skips prefilling it (KV for a matching prefix is identical
    # by causality). This is what makes PrefixHash routing pay off inside
    # the engine — the reference relies on vLLM's prefix cache for the
    # same effect. 0 disables.
    prefix_cache_min: int = 16
    # KV pool storage dtype override: "" keeps ModelConfig's choice,
    # "fp8"/"int8" quantize the paged pool (see ModelConfig.kv_cache_dtype
    # — halves KV HBM, doubling the slot ceiling on a 16GB chip).
    kv_cache_dtype: str = ""
    # logit_bias entries honored per request. Default equals the
    # OpenAI/proxy cap (openai_types.LOGIT_BIAS_CAP) so proxy-valid
    # requests can't be rejected downstream; the engine server 400s
    # requests exceeding this cap (it does NOT silently truncate —
    # _bias_rows' first-N drop is only a backstop for direct submit()
    # callers). Static shape — the [B, K] bias arrays ride every decode
    # dispatch regardless of use (~2.4 KB/slot at 300).
    max_logit_bias: int = 300
    # Top-N alternative logprobs per choice point: one lax.top_k over
    # the vocab, in decode only in the chunks where a slot asked for
    # logprobs (a fifth of a step at a 152k vocabulary otherwise).
    # Requests can ask for at most this many (OpenAI caps completions
    # logprobs at 5, chat top_logprobs at 20).
    top_logprobs_k: int = 5


def engine_dims(cfg: EngineConfig) -> tuple[int, int, int]:
    """Derived device-state dimensions shared by the engine's state init
    and the cold-start AOT warm compiler (one source of truth — a drift
    between them silently turns every pre-warmed executable into a
    cache miss): (max_pages_per_slot, total_pool_pages, hist_width)."""
    ps = cfg.page_size
    max_pages = -(-cfg.max_seq_len // ps)
    P = cfg.num_pages or (cfg.max_slots * max_pages + 1)
    hist_width = cfg.max_seq_len + cfg.decode_chunk + 1
    return max_pages, P, hist_width


def wide_chunk(cfg: EngineConfig) -> int:
    """Rows of the widest chunk call a prompt of this engine is cut into
    (prefill_plan): twice the largest prefill bucket, 2048 as configured,
    where a prompt can be that long, and the largest bucket where none
    can (`max_seq_len` does not exceed it: no such program is compiled).
    `max(prefill_buckets)` stays the longest prompt ONE cold call takes."""
    top = max(cfg.prefill_buckets)
    return 2 * top if cfg.max_seq_len > 2 * top else top


# What a prefill call costs, as (seconds to read the held weights once,
# seconds a row): Engine.call_cost, measured at warm-up where the device is
# one obs/perf.py knows. Until then, and on any other device, a read costs
# nothing, so every choice below falls to the fewest rows.
UNKNOWN_DEVICE = (0.0, 1.0)


def call_seconds(cost: tuple[float, float], n: int, rows: int) -> float:
    """A call of *n* slots x *rows*: every weight is read once, whatever
    the rows, and every row pays its own work."""
    return cost[0] + n * rows * cost[1]


def pair_rows(cfg: EngineConfig, model_config: ModelConfig) -> tuple[int, ...]:
    """The row counts compiled for TWO slots a chunk call: the three
    widest (512, 1024 and the wide chunk as configured). None with one
    slot, and none for a family with REUSE_WHOLE_PREFILL_CALLS: it promises
    a prompt the bits of its own cold prefill whatever was cached
    (models/deepseek.py), and a piece padded up to a partner's rows in a
    [2, rows] program is another program and shape than the [1, own rows]
    call the prompt runs alone."""
    if cfg.max_slots < 2 or family(model_config).REUSE_WHOLE_PREFILL_CALLS:
        return ()
    return tuple(sorted({*cfg.prefill_buckets, wide_chunk(cfg)})[-3:])


def prefill_plan(cfg: EngineConfig, left: int, cost: tuple[float, float] = UNKNOWN_DEVICE) -> list[tuple[int, int]]:
    """The chunk calls that prefill the *left* tokens of a prompt behind
    whatever is cached, as (rows of the call, real tokens in it), widest
    first; only the last call is padded. Wide calls while that many
    tokens are left, then the tail in the smallest bucket that holds it,
    behind one call of the largest bucket if more than that is left; or,
    in that last case, ONE padded wide call where that costs less under
    *cost* (call_seconds): the one comparison a prompt. So a read is only
    ever saved for rows, never rows for a read: the cost's line is
    measured between the two widest calls and under-prices a narrow one,
    whose rows do not feed the matrix unit. Where reading the weights
    costs more than the rows of the padding, an expert family on the
    chip, a prompt of 1025-2047 tokens is ONE padded wide call and not
    [1024, tail]; a FLOP-bound deployment keeps the cut of fewest rows
    but for two full calls of the largest bucket, which become one wide
    call; a device of unknown peaks keeps it altogether. Reads nothing but
    the tokens left and the deployment's two costs: the plan of a prompt's
    last `left` tokens is the end of the plan of the whole prompt wherever
    the cut falls on a call's edge (_plan_admission cuts a hit there for
    the families with REUSE_WHOLE_PREFILL_CALLS), and never depends on who
    else is admitted in the round (round_calls)."""
    top, wide = max(cfg.prefill_buckets), wide_chunk(cfg)
    bucket = lambda n: next(b for b in cfg.prefill_buckets if n <= b)  # noqa: E731
    n_wide, rest = divmod(left, wide)
    rows = [wide] * n_wide
    if rest > top:
        cut = [top, bucket(rest - top)]
        padded = call_seconds(cost, 1, wide) < sum(call_seconds(cost, 1, r) for r in cut)
        rows += [wide] if padded else cut
    elif rest:
        rows.append(bucket(rest))
    return [(r, r) for r in rows[:-1]] + [(r, left - sum(rows[:-1])) for r in rows[-1:]]


def round_calls(
    plans: list[list[tuple[int, int]]], cost: tuple[float, float], pairs: tuple[int, ...],
    first: list[int] | None = None, spare: frozenset[int] = frozenset(),
) -> list[tuple[int, list[tuple[int, int]]]]:
    """The chunk calls of one admission round, in dispatch order, as (rows
    of the call, [(prompt i, its piece k)]): *plans[i]* is prompt i's
    prefill_plan, and wave w holds piece `w - first[i]` of every prompt
    (*first*: the wave a prompt starts in, 0 unless it reads pages that a
    prompt of this round writes), so a prompt's pieces keep their order
    and no call holds two pieces of one prompt. Inside a wave, widest
    first, a piece shares ONE call of two slots with the next, the
    narrower padded up to the wider's rows (the next of *pairs*, the row
    counts compiled for two slots), wherever that costs less than the two
    apart: one read of the weights saved for the rows of the padding. (The
    matching of neighbours that saves MOST costs 0.7% less prefill time on
    fleet-sat's lengths than this first fit: PERF.md section 6, PR 54.) A
    prompt in *spare* (a cold prompt with a one-row call of its own
    elsewhere) is laid out only where it shares a call, and left out
    otherwise."""
    first = first or [0] * len(plans)
    waves: dict[int, list[tuple[int, int, int]]] = {}
    for i, plan in enumerate(plans):
        for k, (rows, _) in enumerate(plan):
            waves.setdefault(first[i] + k, []).append((rows, i, k))
    calls = []
    for w in sorted(waves):
        pieces = sorted(waves[w], key=lambda p: -p[0])  # stable: the planned order among equals
        while pieces:
            rows, i, k = pieces.pop(0)
            shared = next((r for r in pairs if r >= rows), None)
            if pieces and shared and call_seconds(cost, 2, shared) < call_seconds(cost, 1, rows) + call_seconds(cost, 1, pieces[0][0]):
                calls.append((shared, [(i, k), pieces.pop(0)[1:]]))
            elif i not in spare:
                calls.append((rows, [(i, k)]))
    return calls


def window_pool_dims(model_config: ModelConfig, cfg: EngineConfig) -> tuple[int, int]:
    """(window in tokens, pages of the window layers' pool) for a family
    whose window layers keep a page pool of their own beside the full
    layers' (models/smallthinker.py); (0, 0) for every other family: one
    page budget a slot. Nothing is configured: the window is the model's,
    the pool holds every slot's cap (engine/paging.py::WindowPages), and
    `num_pages` is then the FULL layers' pool."""
    from kubeai_tpu.engine.paging import WindowPages

    window = family(model_config).window_pool_tokens(model_config)
    if not window:
        return 0, 0
    cap = WindowPages.slot_cap(engine_dims(cfg)[0], window, wide_chunk(cfg), cfg.page_size)
    return window, cfg.max_slots * cap + 1


def table_width(model_config: ModelConfig, cfg: EngineConfig) -> int:
    """Columns of a slot's block-table row: `max_pages`, and as many
    again behind them for the window layers' table."""
    return engine_dims(cfg)[0] * (2 if window_pool_dims(model_config, cfg)[0] else 1)


def serving_configs(
    model_config: ModelConfig, engine_config: EngineConfig | None
) -> tuple[ModelConfig, EngineConfig]:
    """(ModelConfig, EngineConfig) as an Engine serves them: the pool's
    dtype is the engine's where it sets one, and a family that reuses no
    prefix looks none up. The Engine and the list of its step programs
    (engine/step_programs.py) both start from here, so a warm thread's
    programs are the Engine's."""
    import dataclasses as _dc

    cfg = engine_config or EngineConfig()
    if cfg.kv_cache_dtype:
        model_config = _dc.replace(model_config, kv_cache_dtype=cfg.kv_cache_dtype)
    if cfg.prefix_cache_min and not family(model_config).PREFIX_REUSE:
        # The family's rule (models/nemotron_h.py): nothing is looked
        # up and nothing registered; the hit counters stay 0.
        cfg = _dc.replace(cfg, prefix_cache_min=0)
    return model_config, cfg


def init_pools(model_config: ModelConfig, cfg: EngineConfig):
    """The family's page pool(s) at the engine's dimensions (shared with
    the AOT warm compiler, as engine_dims is), and beside them, for a
    family whose sequences keep state that is not pages, that state for
    every slot (models/nemotron_h.py: the slot is its address)."""
    model = family(model_config)
    window_pages = window_pool_dims(model_config, cfg)[1]
    return model.init_paged_cache(
        model_config, engine_dims(cfg)[1], cfg.page_size,
        **({"window_pages": window_pages} if window_pages else {}),
        **({"slots": cfg.max_slots} if model.SLOT_STATE else {}),
    )


@dataclass
class FinishInfo:
    reason: str  # "stop" | "length"
    prompt_tokens: int
    completion_tokens: int
    # KV restore offer ({key, source, tokens, bytes}) attached when the
    # finish parked the request's page state (preemption / handoff cap).
    # The server copies it into the marker chunk as `kubeai_kv` so the
    # proxy can stamp X-KV-* on the resume dispatch. None = no offer;
    # the resume regenerates by deterministic replay as before.
    kv: dict | None = None


class EventQueue(queue.Queue):
    """A request's events: `queue.Queue`'s surface (`put(ev)`, `get(timeout=)`
    of ONE event) and the two calls of a hand-over. The tokens of a decode
    chunk reach the host together, so the scheduler hands a request its
    share of them at once and the reader is woken once for all of them.
    `handed_at` is the stamp (`put_many`'s *stamp*) of the oldest hand-over
    whose events are still here, `taken_at` that stamp for what the last
    `get_many` took (None: its events came with no stamp), for the queue's
    one reader: from hand-over to bytes written is the reader's to measure."""

    handed_at: float | None = None
    taken_at: float | None = None

    def put_many(self, events, stamp: float | None = None) -> None:
        """`put` for each of *events*, under one lock and with one notify."""
        with self.not_empty:
            if self.handed_at is None:
                self.handed_at = stamp
            self.queue.extend(events)
            self.unfinished_tasks += len(events)
            self.not_empty.notify(len(events))

    def get_many(self, timeout: float | None = None) -> list:
        """Everything that is there, in order, waiting as `get` does for
        the first (`queue.Empty` after *timeout*)."""
        first = self.get(timeout=timeout)
        with self.mutex:
            rest = list(self.queue)
            self.queue.clear()
            self.taken_at, self.handed_at = self.handed_at, None
        return [first, *rest]


@dataclass
class Request:
    prompt_ids: list[int]
    params: SamplingParams
    adapter: str | None = None
    out: EventQueue = field(default_factory=EventQueue)
    # events on `out`: ("token", id, text_delta, logprob, top) |
    # ("done", FinishInfo) | ("error", message). id -1 = text-only flush
    # (held-back chars; logprob None).
    cancelled: threading.Event = field(default_factory=threading.Event)
    arrival: float = field(default_factory=time.monotonic)
    # Absolute end-to-end deadline (time.monotonic()); the scheduler
    # aborts queued AND mid-decode requests past it (slot + pages freed,
    # outcome=cancelled) instead of decoding for a caller that gave up.
    deadline: float | None = None
    # Lifecycle trace (obs/): stamped by the scheduler loop, assembled
    # into spans off-thread by the flight recorder.
    trace: RequestTrace | None = None
    # Terminal-accounting claim (set atomically by _finish_request under
    # the engine's _in_system_lock): two threads finishing the same
    # request concurrently — submit()'s shutdown race vs _fail_inflight —
    # must not double-count metrics or double-decrement _in_system.
    finished: bool = False
    # Hashed tenant id (X-KubeAI-Tenant from the proxy): the scheduler
    # attributes this request's slot/page-seconds to it at release.
    # Empty = un-attributed (direct submits, canary probes) — no cost
    # accounting, by design.
    tenant: str = ""
    # QoS class (kubeai_tpu/qos, X-Priority from the proxy): queue lane
    # and shed/preemption behavior. The queue treats unknown values as
    # standard.
    priority: str = "standard"
    # Proxy-stamped (X-Preemptible): this stream's slot may be seized
    # mid-decode for a waiting interactive request — only set for
    # replayable batch streams with no planned handoff, so the proxy's
    # resume cursor can regenerate it with zero dup/zero drop.
    preemptible: bool = False
    # KV parking intent (engine/kvstate.py): "" = never park;
    # "preempt" parks at a "preempted" finish (batch victim),
    # "handoff" parks at the budget-capped "length" finish the server
    # rewrites to "handoff". Set by the server only for streams whose
    # resume the proxy can actually consume.
    park_kv: str = ""
    # Validated restore state (kvstate.RestoreState) attached by the
    # server before submit: the scheduler admits by page import instead
    # of prefill. Cleared on ANY restore failure — the same request then
    # falls through to the normal prefill/replay path.
    restore: Any = None
    # Park-store key the restore state came from (same-replica resume):
    # the scheduler's restore admission reclaims the matching pinned
    # pages (skipping the payload upload) and drops the blob once used.
    restore_key: str = ""


@dataclass
class _Chunked:
    """A prompt on the chunk route in its admission round: where its next
    piece starts, and what its calls have cost so far (its step record is
    written with its last piece)."""

    slot: int
    req: Request
    reuse: int  # tokens found in the prefix cache: the first piece starts behind them
    plan: list[tuple[int, int]]  # prefill_plan of what is left: (rows, real tokens) a piece
    done: int = 0  # pieces dispatched
    seed: Any = None  # drawn with the first piece
    pad: int = 0  # rows of its calls' rows that held no token of its own
    seconds: float = 0.0  # host seconds of the calls it rode


@dataclass
class _Slot:
    req: Request
    detok: IncrementalDetokenizer
    prompt_len: int
    generated: int = 0
    committed_text: str = ""  # decodable text so far (incomplete UTF-8 held back)
    delivered_chars: int = 0  # prefix of committed_text already sent to client
    budget: int = 0  # max new tokens for this request
    # Slot admission instant: the base of the per-tenant cost proxies
    # (slot-seconds held, x pages reserved = KV-page-seconds) recorded
    # once at release (obs/tenants.py).
    admitted_at: float = field(default_factory=time.monotonic)
    # Emitted ("token", ...) events, recorded verbatim for park-eligible
    # requests (req.park_kv set): a restore re-emits exactly these so the
    # proxy's suppress-N cursor needs no new alignment rules and the
    # client stream stays byte-identical. None = not recording.
    event_log: list | None = None
    # PRNG reconstruction state for a park snapshot: the decode step
    # evolves each slot's key once per fused step device-side, but the
    # device array runs 1-2 in-flight chunks AHEAD of the emitted
    # stream — so the park recomputes the key as kv_key0 (admission
    # rebase, or the restored key row) evolved kv_steps times, counted
    # host-side as steps whose tokens were actually emitted.
    kv_seed: int = 0
    kv_steps: int = 0
    kv_key0: Any = None  # np.uint32 raw key data, set on restore
    # Events made for the request and not yet handed to it, and how many
    # generated tokens among them are not yet on the counter: what ONE
    # fetched array held for the slot goes over in one `_hand_over`.
    outbox: list = field(default_factory=list)
    uncounted: int = 0

    @property
    def holdback(self) -> int:
        """Chars withheld from streaming so a stop string spanning chunk
        boundaries can be trimmed before the client sees it."""
        stops = self.req.params.stop
        return max((len(s) for s in stops), default=1) - 1


class Engine:
    """Single-model engine; one instance per process/replica."""

    def __init__(
        self,
        model_config: ModelConfig,
        params,
        tokenizer,
        engine_config: EngineConfig | None = None,
        mesh=None,
        publisher=None,
        step_table=None,
    ):
        model_config, self.cfg = serving_configs(model_config, engine_config)
        family(model_config).refuse_unsupported(model_config)
        self.model_config = model_config
        self.params = params
        self.tokenizer = tokenizer
        # Multi-host lockstep (engine/gang.py): when this process is one
        # rank of a multi-process gang, device state must be GLOBAL mesh
        # arrays (every rank holds its shard) and — on rank 0 — every
        # jitted dispatch is broadcast to follower ranks first, which
        # replay it (same op order, same numpy args, own device carries).
        self._mesh = mesh
        self._publisher = publisher
        self._multiproc = mesh is not None and jax.process_count() > 1
        # Class-aware admission queue (kubeai_tpu/qos): strict priority
        # across classes, deficit-round-robin per tenant within one,
        # batch-first shedding. Same surface/errors as the old FIFO
        # queue.Queue, so put/get call sites are unchanged.
        self._queue: QoSQueue = QoSQueue(maxsize=self.cfg.max_queue)
        # Auxiliary device work (embeddings) routed through the scheduler
        # thread so ALL device dispatch is serialized on one thread —
        # jitted calls from handler threads would contend with decode
        # chunks (and break the lockstep ordering gang followers mirror).
        self._aux: "queue.Queue[tuple]" = queue.Queue()
        self._slots: list[_Slot | None] = [None] * self.cfg.max_slots
        self._n_active = 0
        # Requests inside the engine (submit() accepted, no terminal
        # accounting yet). Unlike queue_depth()+active_slots(), this has
        # no blind window while a request is BETWEEN queue and slot
        # (mid-admission) — drain's idle check must not race that gap.
        self._in_system = 0
        self._in_system_lock = threading.Lock()
        self._running = False
        self._thread: threading.Thread | None = None
        self._wake = threading.Event()
        # Gang supervision: set while a follower is lost and the gang
        # has not re-formed — is_ready() reads False so the balancer
        # stops routing here, and the loop parks in _handle_gang_loss
        # instead of dispatching into a dead stream.
        self._gang_degraded = threading.Event()
        # Adapter SOURCES (name -> original path) loaded on this rank:
        # gang re-form must replay these to the reconnected follower —
        # a restarted follower process has an empty adapter bank, and a
        # LoRA dispatch it can't satisfy would kill it again
        # (crash-loop). Survives _init_device_state like _adapters.
        self._adapter_sources: dict[str, str] = {}
        # KV-page serialization (engine/kvstate.py): the host-RAM park
        # store of serialized request state (preempt-park-restore,
        # handoff page transfer, restore-aware recovery) plus the pinned
        # device pages it mirrors (paging.PagePool park entries). The
        # advertised address rides restore offers so a peer replica can
        # fetch the blob over GET /v1/kv/<key>; EngineServer.start()
        # stamps it.
        self.kv_park = kvstate.ParkStore()
        self.kv_advertise = ""
        self._kv_fp = kvstate.model_fingerprint(
            self.model_config, self.cfg.page_size
        )
        self._kv_park_sweep_at = 0.0
        # Seconds rank 0 waits for a lost follower to reconnect before
        # falling back to rank termination (the pre-recovery blast
        # radius). <= 0 restores the old terminate-immediately behavior.
        from kubeai_tpu.utils import env_float

        self.gang_reform_timeout = env_float("KUBEAI_GANG_REFORM_TIMEOUT", 300.0)

        # Metrics (engine-side gauges the autoscaler can ingest).
        self.m_queue = default_registry.gauge(
            "kubeai_engine_queue_depth", "requests waiting for a slot"
        )
        self.m_active = default_registry.gauge(
            "kubeai_engine_active_slots", "decode slots in use"
        )
        self.m_gen = default_registry.counter(
            "kubeai_engine_generated_tokens_total", "tokens generated"
        )
        self.m_handovers = default_registry.counter(
            "kubeai_engine_emit_handovers_total",
            "hand-overs of events to a request (one lock, one wake of its "
            "reader): what a fetched decode chunk holds for a slot goes over "
            "at once, so generated_tokens_total over this reads about "
            "decode_chunk under load; 1 would mean a wake a token",
        )
        # [tokens, hand-overs] of THIS engine (the registry's counters are
        # the process's): /debug/engine -> perf.tokens_per_handover.
        self._handed = [0, 0]
        self.m_prefill = default_registry.counter(
            "kubeai_engine_prefill_tokens_total", "prompt tokens prefilled"
        )
        self.m_ttft = default_registry.histogram(
            "kubeai_engine_ttft_seconds",
            "submit to first emitted token (true TTFT: queue wait + prefill "
            "+ first-token round-trip)",
        )
        # Per-phase latency histograms derived from request traces, and
        # the outcome-labeled terminal accounting (EVERY request ends in
        # exactly one of ok|error|cancelled — errored/cancelled requests
        # previously hit no latency metric at all).
        self.m_requests = default_registry.counter(
            "kubeai_engine_requests_total",
            "terminal request events by outcome (ok|error|cancelled)",
        )
        self.m_queue_wait = default_registry.histogram(
            "kubeai_engine_queue_wait_seconds",
            "submit to prefill dispatch: the admission turn, then slots, then pages "
            "(kubeai_engine_queue_wait_by_cause_seconds_total divides its sum)",
        )
        self.m_queue_by_cause = default_registry.counter(
            "kubeai_engine_queue_wait_by_cause_seconds_total",
            "kubeai_engine_queue_wait_seconds_sum by what the request waited for: "
            "turn (the scheduler's next admission round, one chunk turnaround apart "
            "under load, and the round's own work before the request's prefill "
            "dispatch) | slots (rounds that ended with every slot busy) | pages "
            "(rounds that left the head of the line deferred on the KV pool)",
        )
        # Admission rounds (_plan_admissions), oldest first: (stamp, seconds
        # since the first of them that a waiting request waited for slots,
        # for pages), and why the newest stopped taking requests.
        self._rounds: list[tuple[float, float, float]] = []
        self._round_stopped = "empty"
        self.m_prefill_s = default_registry.histogram(
            "kubeai_engine_prefill_seconds",
            "per request: its prefill dispatch to its first emitted token (a "
            "latency as the request sees it, not device time: a group of 8 "
            "counts its seconds 8 times)",
        )
        self.m_tpot = default_registry.histogram(
            "kubeai_engine_tpot_seconds",
            "inter-token latency during decode",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5),
        )
        self.m_e2e = default_registry.histogram(
            "kubeai_request_e2e_seconds",
            "request end-to-end latency by terminal outcome",
            # Extends past the default buckets: e2e latencies of long
            # generations land in the tens of seconds, and the SLO
            # monitor can only resolve objectives to a bucket bound
            # (the default 30s e2e objective needs a 30s bucket).
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120),
        )
        # Occupancy metrics are CALLBACK gauges: evaluated when /metrics
        # is scraped, so they can never go stale between the scheduler
        # events that used to .set() them. The fns are captured in
        # locals so stop() can unbind exactly them — the process-global
        # registry must not pin a dead engine's KV pool in memory — and
        # a newer engine's rebinding is never clobbered.
        hbm_used_fn = lambda: float(self._hbm_stats()[0])  # noqa: E731
        hbm_limit_fn = lambda: float(self._hbm_stats()[1])  # noqa: E731
        pages_used_fn = lambda: float(self._pool.used())  # noqa: E731
        pages_cached_fn = lambda: float(self._pool.cached_pages())  # noqa: E731
        pages_total_fn = lambda: float(self._pool.num_pages - 1)  # noqa: E731
        self.m_hbm_used = default_registry.callback_gauge(
            "kubeai_engine_hbm_used_bytes", "accelerator memory in use",
            hbm_used_fn,
        )
        self.m_hbm_limit = default_registry.callback_gauge(
            "kubeai_engine_hbm_limit_bytes", "accelerator memory capacity",
            hbm_limit_fn,
        )
        self.m_prefix_cached = default_registry.counter(
            "kubeai_engine_prefix_cached_tokens_total",
            "prompt tokens skipped via shared-prefix page reuse",
        )
        # The denominator the cached-tokens counter never had: prompt
        # tokens that went THROUGH a prefix-cache lookup at admission.
        # hit ratio = cached_tokens / lookup_tokens — computable per
        # replica and fleet-wide (the fleet collector derives it).
        self.m_prefix_lookup = default_registry.counter(
            "kubeai_engine_prefix_lookup_tokens_total",
            "prompt tokens offered to the shared-prefix cache lookup at "
            "admission (denominator for the prefix hit ratio; 0 growth = "
            "prefix caching disabled)",
        )
        self.m_cached_evictions = default_registry.counter(
            "kubeai_engine_kv_cached_evictions_total",
            "reusable cached KV pages evicted by allocation pressure "
            "(LRU; sustained growth means the prefix cache is thrashing)",
        )
        self._evictions_seen = 0
        self.m_pages_used = default_registry.callback_gauge(
            "kubeai_engine_kv_pages_used",
            "KV pool pages referenced by live slots",
            pages_used_fn,
        )
        self.m_pages_cached = default_registry.callback_gauge(
            "kubeai_engine_kv_pages_cached",
            "free KV pages retaining reusable prefixes",
            pages_cached_fn,
        )
        self.m_pages_total = default_registry.callback_gauge(
            "kubeai_engine_kv_pages_total",
            "allocatable KV pool pages",
            pages_total_fn,
        )
        pages_parked_fn = lambda: float(self._pool.parked_pages())  # noqa: E731
        self.m_pages_parked = default_registry.callback_gauge(
            "kubeai_engine_kv_pages_parked",
            "device pages pinned by parked (preempted/handed-off) request "
            "state awaiting restore — reclaimable, excluded from "
            "kubeai_engine_kv_pages_used so parked state never reads as "
            "live KV pressure",
            pages_parked_fn,
        )
        # The window layers' pool beside the full layers' (a family with
        # two page budgets a slot, models/smallthinker.py; 0 / 0 for every
        # other, whose one pool the series above describe).
        wpages_used_fn = lambda: float(self._wpages.pool.used()) if self._wpages else 0.0  # noqa: E731
        wpages_total_fn = lambda: float(self._wpages.pool.num_pages - 1) if self._wpages else 0.0  # noqa: E731
        self.m_wpages_used = default_registry.callback_gauge(
            "kubeai_engine_kv_window_pages_used",
            "pages of the window layers' pool referenced by live slots "
            "(kubeai_engine_kv_pages_used is then the full layers' pool)",
            wpages_used_fn,
        )
        self.m_wpages_total = default_registry.callback_gauge(
            "kubeai_engine_kv_window_pages_total",
            "allocatable pages of the window layers' pool (0: the model "
            "has one page budget a slot)",
            wpages_total_fn,
        )
        self.m_window_released = default_registry.counter(
            "kubeai_engine_kv_window_pages_released_total",
            "window-pool pages slots handed back because no query of "
            "theirs can see them any more (behind position - window)",
        )
        self.m_attn_pairs = default_registry.counter(
            "kubeai_engine_attn_pairs_total",
            "(query, key) pairs inside the attention mask that the "
            "dispatched calls compute, summed over the layers of a kind "
            "(full | window), by phase (prefill | decode): real tokens "
            "only, from each call's start, tokens and the window; counted "
            "for a family with window layers",
        )
        self.m_attn_pairs_walked = default_registry.counter(
            "kubeai_engine_attn_pairs_walked_total",
            "(query, key) pairs the chunk kernel SCORES (ops/chunk_attention.py: "
            "whole KV blocks for whole query tiles, padded rows too) for the "
            "prefill calls it takes, summed over the layers of a kind (full | "
            "window), phase prefill: from each call's rows, start and the "
            "window; attn_pairs_total of the same calls over it is the share "
            "of the kernel's work inside the mask",
        )
        # State kept by slot beside the pages (a family with recurrent
        # layers, models/nemotron_h.py; 0 / 0 for every other): there is no
        # allocator, so a slot's state is in use while the slot is.
        slot_state = bool(family(self.model_config).SLOT_STATE)
        state_used_fn = lambda: float(sum(s is not None for s in self._slots)) if slot_state else 0.0  # noqa: E731
        state_total_fn = lambda: float(self.cfg.max_slots) if slot_state else 0.0  # noqa: E731
        self.m_state_used = default_registry.callback_gauge(
            "kubeai_engine_state_slots_used",
            "slots whose per-slot state (recurrent state and convolution "
            "tail, beside the slot's pages) belongs to a live request",
            state_used_fn,
        )
        self.m_state_total = default_registry.callback_gauge(
            "kubeai_engine_state_slots_total",
            "slots that own per-slot state beside their pages (0: the "
            "model keeps nothing but pages)",
            state_total_fn,
        )
        self._gauge_callbacks = [
            (self.m_state_used, state_used_fn),
            (self.m_state_total, state_total_fn),
            (self.m_wpages_used, wpages_used_fn),
            (self.m_wpages_total, wpages_total_fn),
            (self.m_hbm_used, hbm_used_fn),
            (self.m_hbm_limit, hbm_limit_fn),
            (self.m_pages_used, pages_used_fn),
            (self.m_pages_cached, pages_cached_fn),
            (self.m_pages_total, pages_total_fn),
            (self.m_pages_parked, pages_parked_fn),
        ]
        # Saturation / goodput instrumentation derived from the scheduler
        # loop (capacity observability: where is this replica's compute
        # going — real tokens, padding, or idle slots).
        self.m_slots_total = default_registry.gauge(
            "kubeai_engine_slots_total", "configured decode slot capacity"
        )
        self.m_slots_total.set(self.cfg.max_slots)
        self.m_step = default_registry.histogram(
            "kubeai_engine_step_seconds",
            "scheduler step wall time by phase. decode_chunk = chunk TURNAROUND "
            "of a pipelined loop (its dispatch call returned to its results on "
            "the host: the next chunk's dispatch and the round's prefills run "
            "inside it, so about two chunks of device time, never a device "
            "time per step; the pure host wait is the step record's "
            "fetch_wait_ms); prefill_* = the host's dispatch call",
        )
        self.m_slot_steps = default_registry.counter(
            "kubeai_engine_slot_steps_total",
            "fused decode slot-steps by state; batch utilization = "
            "active / (active + idle)",
        )
        self.m_decode_rows = default_registry.counter(
            "kubeai_engine_decode_rows_total",
            "rows x steps of the dispatched decode chunks by state: live (rows "
            "active in the dispatched mask) | idle (the rest of the batch)",
        )
        for state in ("live", "idle"):
            self.m_decode_rows.inc(0, labels={"state": state})  # scraped as 0, not absent
        self.m_epilogue = default_registry.counter(
            "kubeai_engine_decode_epilogue_chunks_total",
            "dispatched decode chunks by optional part of the step's epilogue "
            "(top_logprobs | candidates | penalties) and whether an active "
            "slot asked for it (ran=1: the chunk computed it)",
        )
        self.m_pad_prefill = default_registry.counter(
            "kubeai_engine_prefill_padded_tokens_total",
            "prompt positions computed as bucket/batch padding (prefill waste; "
            "compare against kubeai_engine_prefill_tokens_total)",
        )
        self.m_chunk_tokens = default_registry.counter(
            "kubeai_engine_prefill_chunk_tokens_total",
            "real prompt tokens prefilled by chunk calls (a prompt longer "
            "than the largest bucket, or one behind cached tokens), by the "
            "call's rows: the wide chunk, the largest bucket or the tail's "
            "bucket (how often the wide chunk engages)",
        )
        self.m_prefill_calls = default_registry.counter(
            "kubeai_engine_prefill_calls_total",
            "prefill device calls, by kind (group: a cold call of one row or "
            "the group cap | chunk) and the slots a call carried: a chunk call "
            "of slots=2 is two prompts' pieces behind one read of the weights "
            "(kubeai_engine_prefill_tokens_total over this: tokens a call)",
        )
        self.m_prefill_rows = default_registry.counter(
            "kubeai_engine_prefill_rows_total",
            "rows the cold group prefill calls computed, by kind: real (a "
            "prompt that was sent) | duplicate (a copy of one, filling the "
            "compiled row count: 0 while a short group runs as one-row calls)",
        )
        self.m_prefill_rows.inc(0, labels={"kind": "duplicate"})  # scraped as 0, not absent
        self.m_moe_hit = default_registry.counter(
            "kubeai_engine_moe_experts_hit_total",
            "(expert layer, expert) pairs that got at least one row, summed "
            "over the step programs' calls, by phase (decode | prefill): the "
            "expert matrices a call had to read",
        )
        self.m_moe_possible = default_registry.counter(
            "kubeai_engine_moe_expert_reads_possible_total",
            "experts x expert layers x model steps, by phase: what "
            "kubeai_engine_moe_experts_hit_total reads if every call hits "
            "every expert",
        )
        self.m_moe_assign = default_registry.counter(
            "kubeai_engine_moe_assignments_total",
            "(token row, chosen expert) pairs the step programs computed, "
            "padding rows and idle slots included, by phase",
        )
        self.m_moe_absent = default_registry.counter(
            "kubeai_engine_moe_assignments_absent_total",
            "of kubeai_engine_moe_assignments_total, the pairs whose expert "
            "this chip does not hold (a chip's share of the experts: dropped "
            "before the rows are gathered), by phase; 0 for a chip that "
            "holds every expert its router scores",
        )
        self.m_tok_rate = default_registry.gauge(
            "kubeai_engine_tokens_per_second",
            "decode goodput over the most recent chunks (0 when idle)",
        )
        self.m_recompiles = default_registry.counter(
            "kubeai_engine_jit_recompiles_total",
            "step programs this process brought up: the step table's "
            "(loaded from the bundle or compiled ahead of time) and lazy "
            "compiles through the jitted functions (warmup's included; "
            "growth after warmup means shape churn)",
        )
        self._jit_entries_seen = 0
        # Shared sliding-window rate (obs/perf.py): the same
        # implementation the fleet collector's counter-delta tok/s uses,
        # so the two can no longer disagree during idle→busy transitions.
        self._rate_window = perf_obs.TokenRateWindow(span=10.0)
        # Decode's masked (query, key) pairs over the same span, for a
        # family that counts them (_count_attn_pairs): MFU's attention.
        self._pairs_window = perf_obs.TokenRateWindow(span=10.0)
        self.m_gang_reforms = default_registry.counter(
            "kubeai_gang_reforms_total",
            "gang re-formations: a lost follower reconnected and rank 0 "
            "reset + resumed serving (vs the old fatal rank exit)",
        )
        # Weight residency evidence: on a tp gang each rank's local bytes
        # are ~global/ranks (the multi-host e2e asserts this — the model
        # provably spans the gang rather than being replicated).
        self.m_param_global = default_registry.gauge(
            "kubeai_engine_param_bytes_global", "total model parameter bytes"
        )
        self.m_param_local = default_registry.gauge(
            "kubeai_engine_param_bytes_local", "parameter bytes resident on this rank"
        )
        g_bytes = l_bytes = 0
        for leaf in jax.tree_util.tree_leaves(self.params):
            g_bytes += leaf.nbytes
            shards = getattr(leaf, "addressable_shards", None)
            if shards is not None:
                l_bytes += sum(s.data.nbytes for s in shards)
            else:
                l_bytes += leaf.nbytes
        self.m_param_global.set(g_bytes)
        self.m_param_local.set(l_bytes)

        # Live roofline/MFU accounting (obs/perf.py — the deduped
        # docs/benchmarks.md math): FLOPs/token analytic from the
        # config, weight bytes MEASURED off the actual param tree (so
        # int8 trees and their scales are costed as stored), device
        # peak/bandwidth from the shared constant tables. Callback
        # gauges so /metrics always reflects the current rate window.
        self.perf = perf_obs.PerfModel.from_model_config(
            model_config, weight_bytes=g_bytes
        )
        self.perf_env = perf_obs.detect_device()
        # Whole-deployment denominators: on a sharded mesh every device
        # streams its own weight shard concurrently and contributes its
        # own peak FLOPs, so the single-chip constants scale by the
        # mesh's device count (a mesh-less engine runs on one device —
        # extra local devices sit idle and must not inflate the peak).
        self._perf_devices = (
            max(1, self._mesh.devices.size) if self._mesh is not None else 1
        )
        # What a prefill call costs on this deployment (call_seconds):
        # (seconds to read the held weights once, seconds a row). ONE
        # source: warmup() measures it (_measure_call_cost) where the
        # device is one obs/perf.py knows. Until then, on any other device
        # (the CPU) and on an engine that is never warmed up (a gang), a
        # read costs nothing: a prompt's cut is the one of fewest rows and
        # no two prompts share a call (prefill_plan, round_calls). The two
        # peaks do not stand in for the measurement: a family's rows can
        # cost twice what they say and its reads hide behind them
        # (PERF.md section 6, PR 54).
        self.call_cost: tuple[float, float] = UNKNOWN_DEVICE
        self._stall = perf_obs.PipelineStallTracker()
        mfu_fn = lambda: self._mfu()  # noqa: E731
        roofline_fn = lambda: self._roofline_fraction()  # noqa: E731
        self.m_mfu = default_registry.callback_gauge(
            "kubeai_engine_mfu",
            "model FLOPs utilization (fraction of device peak) at the "
            "current decode rate window; 0 on unknown devices/CPU",
            mfu_fn,
        )
        self.m_roofline = default_registry.callback_gauge(
            "kubeai_engine_roofline_fraction",
            "current decode rate as a fraction of the weight-read "
            "roofline at the configured slot count; 0 on unknown devices",
            roofline_fn,
        )
        self._gauge_callbacks += [
            (self.m_mfu, mfu_fn),
            (self.m_roofline, roofline_fn),
        ]
        # ONE bound-method object kept for register/unregister identity
        # (each `self._perf_debug_section` access builds a fresh bound
        # method — `is` checks would never match across accesses).
        self._perf_section_fn = self._perf_debug_section
        register_engine_debug_section("perf", self._perf_section_fn)

        self._init_device_state()
        self._build_step_fns(step_table)

    # -- perf X-ray --------------------------------------------------------

    def _perf_constants(self) -> tuple[float | None, float | None]:
        """(peak_flops, hbm_gbps) aggregated over the serving devices."""
        env = self.perf_env
        n = self._perf_devices
        return (
            env.peak_flops * n if env.peak_flops else None,
            env.hbm_gbps * n if env.hbm_gbps else None,
        )

    def _mfu(self) -> float:
        peak, _ = self._perf_constants()
        return self.perf.mfu(self.m_tok_rate.value(), peak, self._pairs_window.rate())

    def _roofline_fraction(self) -> float:
        _, hbm = self._perf_constants()
        roof = self.perf.roofline_tokens_per_sec(self.cfg.max_slots, hbm)
        return self.m_tok_rate.value() / roof if roof else 0.0

    def _perf_debug_section(self) -> dict:
        """The ``perf`` block of /debug/engine: live rate, MFU, roofline
        context, and the windowed stall summary — one place where an
        on-chip bench's numbers come pre-interpreted."""
        env = self.perf_env
        peak, hbm = self._perf_constants()
        roof = self.perf.roofline_tokens_per_sec(self.cfg.max_slots, hbm)
        kv_bytes_per_token = int(self._cache["kv"].nbytes // (self._pool.num_pages * self.cfg.page_size))
        return {
            "tokens_per_second": self.m_tok_rate.value(),
            "mfu": round(self._mfu(), 5),
            "roofline_fraction": round(self._roofline_fraction(), 5),
            "roofline_toks_per_sec": round(roof, 1) if roof else None,
            "flops_per_token": self.perf.flops_per_token,
            "weight_bytes": self.perf.weight_bytes,
            "platform": env.platform,
            "device": env.kind,
            "devices": self._perf_devices,
            "visible_devices": env.visible_devices,
            "peak_flops": peak,
            "hbm_gbps": hbm,
            # Per local device, as memory_stats() reports it (nothing on
            # the CPU backend): under tp every chip should hold its
            # share of the weights and of the pool.
            "memory": [
                {
                    "id": dev.id,
                    **{
                        k: v for k, v in (dev.memory_stats() or {}).items()
                        if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                    },
                }
                for dev in jax.local_devices()
            ],
            # How this process brought up its step programs
            # (kubeai_engine_step_programs_total; engine/step_programs.py).
            "step_programs": {how: self._table.stats[how] for how in ("loaded", "compiled", "lazy")},
            # (kv pages, queries) a block the ragged paged kernel was
            # given, per call shape this process has traced (none where
            # every program was loaded from the bundle: nothing is traced).
            "paged_kernel_blocks": dict(paged_attention.chosen_blocks),
            # (query rows, keys) a tile and a block of the chunk kernel, per
            # call shape likewise, and by kind of layer the share of the
            # pairs it scored that lay inside the mask (attn_pairs_total over
            # attn_pairs_walked_total of the prefill calls it took; a family
            # with window layers counts them).
            "chunk_kernel_tiles": dict(chunk_attention.chosen_tiles),
            "chunk_kernel_hit_share": {
                kind: round(inside / walked, 4) for kind, (inside, walked) in self._chunk_pairs.items() if walked
            },
            # Generated tokens a hand-over to a request (_hand_over): about
            # decode_chunk under load, less by first tokens and by chunks a
            # request ends in; 1.0 would mean a wake a token.
            "tokens_per_handover": round(self._handed[0] / self._handed[1], 3) if self._handed[1] else None,
            # Likewise: the MLA decode kernel's pages a block, ring and form; (tm, tk, tn) of the grouped
            # matmul; the heads of a slot a program of the state-space step kernel holds (empty: portable).
            "mla_kernel_blocks": dict(mla_attention.chosen_blocks),
            "grouped_matmul_tiles": dict(moe.chosen_tiles), "ssm_kernel_blocks": dict(ssm.chosen_blocks),
            # Bytes of the pool a token occupies, all layers, as stored:
            # the pool's own size over its tokens (a latent page is a
            # page of another width).
            "kv_bytes_per_token": kv_bytes_per_token,
            # ... and what a slot owns outside its pages, whatever its
            # length (models/nemotron_h.py; 0: nothing but pages).
            "state_bytes_per_slot": int(
                sum(self._cache[k].nbytes for k in family(self.model_config).SLOT_STATE) // self.cfg.max_slots
            ),
            # ... and by kind of layer where the window layers keep a
            # pool of their own (the line above is then the full layers').
            **(
                {
                    "kv_bytes_per_token_by_kind": {
                        "full": kv_bytes_per_token,
                        "window": int(
                            self._cache["kv_window"].nbytes
                            // (self._wpages.pool.num_pages * self.cfg.page_size)
                        ),
                    },
                    "window_pool": {
                        "window": self._wpages.window, "slot_cap_pages": self._wpages.cap,
                        "pages_total": self._wpages.pool.num_pages - 1,
                        "pages_used": self._wpages.pool.used(),
                        "released_total": self._wpages.released,
                    },
                }
                if self._wpages is not None else {}
            ),
            # Rows the cold group prefill calls computed (the counter
            # kubeai_engine_prefill_rows_total, since the process began).
            "prefill_rows": {
                kind: int(self.m_prefill_rows.value(labels={"kind": kind}))
                for kind in ("real", "duplicate")
            },
            # Real prompt tokens the chunk calls prefilled, by the call's
            # rows (kubeai_engine_prefill_chunk_tokens_total).
            "prefill_chunk_tokens": {
                str(rows): int(self.m_chunk_tokens.value(labels={"rows": str(rows)}))
                for rows in sorted({*self.cfg.prefill_buckets, wide_chunk(self.cfg)})
            },
            # What a prefill call costs here (call_cost, [ms, us a row]:
            # measured at warm-up, 0 and 1 s a row before it and where the
            # device is not known), the cut of a 1500-token prompt under
            # it, and the device calls so far by kind and slots
            # (kubeai_engine_prefill_calls_total).
            "prefill_call_cost": {
                "read_weights_ms": round(self.call_cost[0] * 1e3, 3), "row_us": round(self.call_cost[1] * 1e6, 3),
                "plan_1500": prefill_plan(self.cfg, 1500, self.call_cost),
                "two_slot_rows": list(pair_rows(self.cfg, self.model_config)),
            },
            "prefill_calls": {
                f"{kind}x{slots}": int(self.m_prefill_calls.value(labels={"kind": kind, "slots": slots}))
                for kind, slots in (("group", "1"), ("group", str(max(1, min(self.cfg.prefill_group_cap, self.cfg.max_slots)))), ("chunk", "1"), ("chunk", "2"))
            },
            # Rows x steps of the decode chunks dispatched so far, by the
            # `active` mask they were given, and the live share of them
            # (kubeai_engine_decode_rows_total).
            "decode_rows": self._decode_rows_report(),
            "stall": self._stall.report(),
        }

    def _decode_rows_report(self) -> dict:
        rows = {
            state: int(self.m_decode_rows.value(labels={"state": state}))
            for state in ("live", "idle")
        }
        total = rows["live"] + rows["idle"]
        return {**rows, "live_share": round(rows["live"] / total, 4) if total else None}

    def pipeline_report(self) -> dict:
        """The GET /debug/pipeline payload: windowed stall attribution
        plus the live MFU/roofline context (the numbers that say whether
        the attributed stalls matter)."""
        report = self._stall.report()
        report["mfu"] = round(self._mfu(), 5)
        report["roofline_fraction"] = round(self._roofline_fraction(), 5)
        report["tokens_per_second"] = self.m_tok_rate.value()
        return report

    def broadcast_profile(self, seconds: float, out_dir: str) -> int:
        """Gang leader: fan a profiler capture out to followers over the
        dispatch control channel (ordered through the scheduler thread —
        the publisher is only safe to use from there). Returns the
        follower count notified; 0 single-host."""
        if self._publisher is None:
            return 0

        def do():
            self._bcast(
                "profile", scalars={"seconds": float(seconds), "dir": out_dir}
            )

        if self._running:
            self._await_aux(self._submit_aux(do), what="profile broadcast")
        else:
            do()
        return int(getattr(self._publisher, "n_followers", 0))

    # -- device state ------------------------------------------------------

    def _init_device_state(self):
        from kubeai_tpu.engine.paging import PagePool, WindowPages

        B = self.cfg.max_slots
        ps = self.cfg.page_size
        self._max_pages, P, hist_width = engine_dims(self.cfg)
        self._pool = PagePool(P, ps)
        # Device-resident token history, read by the penalties (written
        # positions only; padded past max_seq_len so a slot that finishes
        # mid-chunk and keeps stepping never scatter-collides).

        def mk_device_arrays():
            cache = init_pools(self.model_config, self.cfg)
            tok_hist = jnp.zeros((B, hist_width), jnp.int32)
            adm_toks = jnp.zeros((B,), jnp.int32)
            lengths = jnp.zeros((B,), jnp.int32)
            last_tokens = jnp.zeros((B,), jnp.int32)
            # PRNG state rides as RAW uint32 key data (wrapped in-graph
            # by decode_fn): typed key arrays can't take NamedShardings
            # uniformly across versions, and raw data crosses the
            # jit boundary identically on every rank.
            keys = jax.random.key_data(jax.random.split(jax.random.key(0), B))
            return cache, tok_hist, adm_toks, lengths, last_tokens, keys

        if self._mesh is not None:
            # One jitted init with explicit out_shardings: the KV pool is
            # tp-sharded over heads, the small per-slot state fully
            # replicated. Eager jnp.zeros would put the whole pool on the
            # first device (and, on a gang, pin process-local arrays that
            # a global-mesh jit rejects).
            from jax.sharding import NamedSharding, PartitionSpec

            from kubeai_tpu.parallel.sharding import paged_cache_specs

            repl = NamedSharding(self._mesh, PartitionSpec())
            cache_sh = {
                k: NamedSharding(self._mesh, s)
                for k, s in paged_cache_specs().items()
            }
            out = jax.jit(
                mk_device_arrays,
                out_shardings=(cache_sh, repl, repl, repl, repl, repl),
            )()
        else:
            out = mk_device_arrays()
        (
            self._cache, self._tok_hist, self._adm_toks,
            self._lengths, self._last_tokens, self._keys,
        ) = out
        # Host-authoritative block tables, uploaded per dispatch (tiny).
        # A family whose window layers keep a pool of their own has a
        # second table behind the first (table_width); WindowPages is
        # that half's only writer.
        self._page_table = np.zeros((B, table_width(self.model_config, self.cfg)), np.int32)
        window = window_pool_dims(self.model_config, self.cfg)[0]
        self._wpages = (
            WindowPages(self._page_table[:, self._max_pages :], window, wide_chunk(self.cfg), ps)
            if window else None
        )
        # Where each slot's next decode chunk starts (the device's own
        # `lengths`, kept in step on the host: admission sets it, every
        # dispatch adds its steps to the active slots).
        self._w_pos = np.zeros((B,), np.int64)
        # (full layers, window layers): what _count_attn_pairs multiplies by.
        self._kind_layers = family(self.model_config).layer_kinds(self.model_config) if window else (0, 0)
        # By kind, [inside the mask, scored] over the prefill calls the chunk
        # kernel takes: the hit share of /debug/engine's perf section.
        self._chunk_pairs = {"full": [0, 0], "window": [0, 0]}
        self._window_released_seen = 0  # of WindowPages.released, already in the counter
        # Per-slot request state is HOST-authoritative numpy, uploaded
        # with every decode dispatch (the arrays ride the execute RPC —
        # free). Round 2 kept these as device arrays mutated by eager
        # .at[].set per admission: ~9 eager dispatches of host time per
        # admitted request, all spent while the device sat idle. Only state that EVOLVES device-side
        # between host syncs (pool, lengths, last token, PRNG keys,
        # token history) stays as donated device carries.
        self._h_active = np.zeros((B,), bool)
        self._h_temp = np.ones((B,), np.float32)
        self._h_top_p = np.ones((B,), np.float32)
        self._h_top_k = np.zeros((B,), np.int32)
        self._h_presence = np.zeros((B,), np.float32)
        self._h_freq = np.zeros((B,), np.float32)
        # SamplingParams.logprobs per slot: with active/temp/presence/
        # freq it decides which optional parts of the decode epilogue a
        # chunk runs (sampling.epilogue_parts, on both sides).
        self._h_want_top = np.zeros((B,), bool)
        # First generated position per slot (= prompt length): the
        # penalty window over the device token history is
        # [gen_start, lengths) — generated tokens only.
        self._h_gen_start = np.zeros((B,), np.int32)
        # OpenAI logit_bias per slot (pad: token 0 / bias 0.0 — the
        # scatter-add no-op).
        Kb = self.cfg.max_logit_bias
        self._h_bias_ids = np.zeros((B, Kb), np.int32)
        self._h_bias_vals = np.zeros((B, Kb), np.float32)
        self._h_lora_rows = np.zeros((B,), np.int32)
        # Admission merge-in: filled by _register, consumed by the next
        # decode dispatch (the decode step rebases the admitted slots'
        # lengths/last-token/PRNG key in-graph, so admission needs no
        # eager device mutation at all). adm_tok lives DEVICE-side
        # (_adm_toks, a [B] staging vector the prefill call scatters its
        # sampled token into) so dispatching the next chunk never waits
        # on the first-token host sync.
        self._adm_mask = np.zeros((B,), bool)
        self._adm_len = np.zeros((B,), np.int32)
        self._adm_seed = np.zeros((B,), np.uint32)
        # (phase, token rows computed, the program's counters) of prefill
        # calls whose first tokens the host has not fetched yet.
        self._program_counters: list = []
        self._slot_pages: list[list[int]] = [[] for _ in range(B)]
        # Pages content-registered at plan time whose prefill has NOT yet
        # succeeded (cleared by _register): a failed prefill must
        # unregister exactly these so never-written KV can't be reused.
        self._slot_fresh: list[list[int]] = [[] for _ in range(B)]
        # Decode-token budget reserved by _plan_admission, consumed by
        # _register — ONE computation, because the page reservation must
        # exactly cover the slot's decode budget.
        self._slot_budget: list[int] = [0] * B
        # Prefix bookkeeping: per slot, the token ids whose KV has been
        # written to the slot's pages (generated-token pages are content-
        # registered from this at free time), and an epoch guarding
        # against appends from a previous occupant's chunk.
        self._kv_history: list[list[int]] = [[] for _ in range(B)]
        # The token the next decode step will WRITE (KV at a position
        # belongs to that step's input token, not its sampled output).
        self._kv_pending: list[int | None] = [None] * B
        # KV depends on the adapter weights: (row, row-generation), so a
        # recycled or reloaded row can never alias an old sequence.
        self._kv_lora_sig: list[tuple[int, int]] = [(0, 0)] * B
        self._slot_epoch: list[int] = [0] * B
        # Requests that fit a free slot but not the KV pool wait here
        # (strict FIFO: no later request overtakes them).
        self._deferred: list[Request] = []
        if not hasattr(self, "_adapters"):
            self._adapters = None  # AdapterRuntime; survives _recover()

    def _build_step_fns(self, step_table=None):
        """Take the step programs from *step_table* (the warm thread's:
        engine/step_programs.py) where it was filled for exactly this
        engine's configs, else start an empty table of this engine's own
        list. Either way the list states (model_config, cfg, the valid
        vocab) once and the jitted functions are built from it once;
        the table outlives _recover(): executables do not depend on
        device state."""
        from kubeai_tpu.engine.step_programs import StepPrograms, StepTable

        mc = self.model_config
        # The model vocab may be padded past the tokenizer's (tp
        # divisibility, MXU tiling); padded columns carry zero weights and
        # logit 0.0, which is very much sampleable — mask them out.
        n_valid = min(getattr(self.tokenizer, "vocab_size", mc.vocab_size), mc.vocab_size)
        if step_table is not None and not (
            self._mesh is None and step_table.programs.serves(mc, self.cfg, n_valid)
        ):
            log.warning("the warmed step programs are another deployment's; compiling this engine's own")
            step_table = None
        self._table = step_table or StepTable(StepPrograms(mc, self.cfg, n_valid, mesh=self._mesh))
        sf = self._table.programs.step_functions
        self._step_fns = sf
        # What _step falls to on a miss, and what the gang follower's
        # replay calls directly.
        self._prefill_chunk_jit = sf.prefill_chunk_jit
        self._prefill_batch_jit = sf.prefill_batch_jit
        self._decode_jit = sf.decode_jit
        self._lazy_seen = 0

    def _step(self, member: str, shape: tuple, *args, **kw):
        """The one dispatcher of the step programs (the scheduler's three
        dispatch sites and warmup): *member* of StepFunctions at the call
        shape *shape* (() for the decode chunk, the shape of a prefill's
        tokens). A program the table holds runs as the held executable
        (donation and output layout are its own, as compiled from the
        same jax.jit); a miss falls to the engine's jitted function and
        compiles lazily: a shape the list did not foresee, or a call that
        carries an adapter (`lora=`: another signature, another
        program)."""
        held = None if kw else self._table.held.get((member, shape))
        if held is not None:
            return held(*args)
        return getattr(self, "_" + member)(*args, **kw)

    def _attn_kernel(self, kind: str, queries: int) -> str:
        """The attention implementation the step *kind* compiles to at
        *queries* tokens per row, for the step records: "flash",
        "ragged" (the paged kernel), or "xla" (the portable gather
        route: every CPU run, sliding-window models)."""
        model = family(self.model_config)
        route = model.cached_attention_route(
            self.model_config, queries,
            left_aligned=kind == "prefill_group", paged=True,
        )
        return model.PAGED_KERNEL_LABEL if route == "paged_kernel" else route

    # -- public API --------------------------------------------------------

    def start(self):
        # Idempotent: EngineServer.start() starts its engine for the
        # standalone pod path, but callers that pre-start the engine
        # (tests, embedding tools) must not end up with TWO scheduler
        # threads — concurrent loops race on the donated device carries
        # (cache/adm_toks), which surfaces as "Buffer has been deleted
        # or donated" on dispatch and a client-facing 500.
        if self._thread is not None and self._thread.is_alive():
            self._running = True
            return
        self._running = True
        # (Re)bind the occupancy callbacks: a stop() unbinds them, and
        # the most recently started engine should own the gauges (and
        # the /debug/engine perf section).
        for gauge, fn in self._gauge_callbacks:
            gauge.set_callback(fn)
        register_engine_debug_section("perf", self._perf_section_fn)
        qos_install_queue(self._queue)
        self._thread = threading.Thread(target=self._loop, name="engine-loop", daemon=True)
        self._thread.start()

    def _warm_prefill(self, member: str, shape: tuple, tokens=None) -> None:
        """One prefill program run on the trash page (tables all zero),
        as warmup() runs every one: *tokens* [n, rows] or zeros."""
        n, rows = shape
        Kb, cols = self.cfg.max_logit_bias, self._page_table.shape[1]
        # A cold call's `lengths`; a chunk call's `starts` and `last_idx`.
        per_row = (
            (np.full((n,), rows, np.int32),) if member == "prefill_batch_jit"
            else (np.zeros((n,), np.int32), np.full((n,), rows - 1, np.int32))
        )
        *_, self._cache, self._adm_toks, _counters = self._step(
            member, shape,
            self.params,
            np.zeros((n, rows), np.int32) if tokens is None else tokens,
            *per_row,
            np.zeros((n, cols), np.int32),
            np.zeros((n,), np.int32),
            np.zeros((n,), np.uint32),
            np.ones((n,), np.float32),
            np.ones((n,), np.float32),
            np.zeros((n,), np.int32),
            np.zeros((n, Kb), np.int32),
            np.zeros((n, Kb), np.float32),
            self._adm_toks,
            self._cache,
        )

    def _measure_call_cost(self) -> tuple[float, float]:
        """What a chunk call costs HERE (call_seconds), measured once
        every program has run: the two widest one-slot chunk programs run
        four times more each, turn about and alone, on drawn tokens (rows
        of one token would ask an expert family for one set of experts);
        the first run of each is thrown away. A run's time is taken from
        the dispatch's RETURN to `block_until_ready`: the device's own,
        without the host's dispatch, which serving hides behind the call
        before and which would otherwise sit in the read (2-3 ms of 16 on
        lfm2: PERF.md section 5). The line goes through the narrower
        program's FASTEST run and the wider's MEDIAN one, so what the clock
        adds to a run can only make the read smaller: a plan leaves the cut
        of fewest rows only for more than the runs differ by. A read that
        hides behind the rows' work comes out as 0: the plans of fewest
        rows, no shared call. UNKNOWN_DEVICE where the times give no line."""
        widest = sorted({*self.cfg.prefill_buckets, wide_chunk(self.cfg)})[-2:]
        if len(widest) < 2:
            return UNKNOWN_DEVICE
        rng = np.random.default_rng(0)
        tokens = {rows: rng.integers(0, self.model_config.vocab_size, (1, rows)).astype(np.int32) for rows in widest}
        device: dict[int, list[float]] = {rows: [] for rows in widest}
        whole: dict[int, list[float]] = {rows: [] for rows in widest}  # with the dispatch: logged beside, plans nothing
        for _ in range(4):
            for rows in widest:
                called = time.monotonic()
                self._warm_prefill("prefill_chunk_jit", (1, rows), tokens[rows])
                returned = time.monotonic()
                jax.block_until_ready(self._adm_toks)
                ready = time.monotonic()
                device[rows].append(ready - returned)
                whole[rows].append(ready - called)
        narrow, wide = min(device[widest[0]][1:]), sorted(device[widest[1]][1:])[1]
        row = (wide - narrow) / (widest[1] - widest[0])
        cost = (max(narrow - widest[0] * row, 0.0), row) if row > 0 else UNKNOWN_DEVICE
        log.info(
            "prefill call cost: read %.2f ms + %.2f ms a 1024 rows (%s rows, the first run of each not used: "
            "%s ms on the device, %s with the dispatch), so 1500 tokens are %s",
            cost[0] * 1e3, cost[1] * 1024e3, widest,
            [[round(t * 1e3, 2) for t in device[rows]] for rows in widest],
            [[round(t * 1e3, 2) for t in whole[rows]] for rows in widest],
            prefill_plan(self.cfg, 1500, cost),
        )
        return cost

    def warmup(self, include_group: bool = True) -> dict:
        """Execute every step program of the one list once
        (engine/step_programs.py::StepPrograms.calls: the decode chunk,
        batch-1 and group-cap cold prefill for every bucket, a chunk call
        for every bucket and the wide chunk, a chunk call of two slots for
        the three widest), through the dispatcher the
        serving path uses: a program the table holds (loaded from the
        bundle or compiled by the warm thread) only runs, one it does not
        hold compiles through its jitted function. Called BEFORE
        start()/serving so the first real request never pays a compile;
        dispatches write only the KV pool's trash page (tables all zero —
        the designed garbage sink) and touch no slot bookkeeping.
        Single-host only: on a gang every dispatch must be broadcast, and
        followers compile at replay."""
        if self._multiproc or self._publisher is not None:
            log.info("warmup skipped on a multi-host gang")
            return {"shapes": 0, "skipped": "gang"}
        t0 = time.monotonic()
        shapes = 0
        for call in self._table.programs.calls(include_group):
            if call.member == "decode_jit":  # the hot loop
                (
                    *_,
                    self._cache, self._tok_hist, self._lengths,
                    self._last_tokens, self._keys, _counters,
                ) = self._step(
                    call.member, call.shape,
                    self.params, self._cache, self._page_table.copy(), self._tok_hist,
                    self._lengths, self._last_tokens, self._keys,
                    self._h_active.copy(), self._h_temp.copy(), self._h_top_p.copy(),
                    self._h_top_k.copy(), self._h_presence.copy(), self._h_freq.copy(),
                    self._h_want_top.copy(),
                    self._h_gen_start.copy(), self._h_bias_ids.copy(),
                    self._h_bias_vals.copy(), self._adm_mask.copy(),
                    self._adm_len.copy(), self._adm_seed.copy(), self._adm_toks,
                )
            else:
                self._warm_prefill(call.member, call.shape)
            # One program in flight at a time: queued behind each other,
            # held executables would have their outputs and workspaces
            # allocated together, a peak no serving step reaches.
            jax.block_until_ready((self._adm_toks, self._lengths))
            shapes += 1
        if self._kv_enabled():
            # Restore-path jits (park/import/slotset): left lazy, the
            # FIRST preemption compiles them mid-flood — on the critical
            # path of the very interactive request the preemption is
            # freeing a slot for. Import buckets by pow2 page count;
            # warm the small buckets that short parked decodes hit (a
            # longer restore still pays one compile, off the TTFT path
            # of anyone else's request). All writes land on trash pages
            # or slot 0's pre-serving zero state.
            self._ensure_kv_jits()
            self._kv_evolve_jit(
                jax.random.key_data(jax.random.key(np.uint32(0))), np.int32(0)
            )
            shapes += 1
            L = self.model_config.num_layers
            P = self._pool.num_pages
            for n_pad in (1, 2):
                idx = (
                    np.arange(L, dtype=np.int32)[None, :] * P
                    + np.zeros((n_pad, 1), np.int32)
                ).reshape(-1)
                payload = np.zeros(
                    (n_pad * L, *self._cache["kv"].shape[1:]),
                    self._cache["kv"].dtype,
                )
                cache = dict(self._cache)
                cache["kv"] = self._kv_import_jit(self._cache["kv"], idx, payload)
                self._cache = cache
                shapes += 1
            (
                self._tok_hist, self._lengths, self._last_tokens, self._keys,
            ) = self._kv_slotset_jit(
                self._tok_hist, self._lengths, self._last_tokens, self._keys,
                np.int32(0), np.zeros((self._tok_hist.shape[1],), np.int32),
                np.int32(0), np.int32(0),
                np.zeros(self._keys.shape[1:], np.uint32),
            )
            shapes += 1
        jax.block_until_ready(self._adm_toks)
        measured = {}
        if all(self._perf_constants()):  # a device of the tables: its clock is worth planning on
            t = time.monotonic()
            self.call_cost = self._measure_call_cost()
            measured = {
                "call_cost_ms": [round(self.call_cost[0] * 1e3, 3), round(self.call_cost[1] * 1024e3, 3)],  # a read, 1024 rows
                "call_cost_seconds": round(time.monotonic() - t, 3),
            }
        dur = time.monotonic() - t0
        self._update_recompile_counter()
        log.info("engine warmup: %d shapes in %.1fs", shapes, dur)
        return {"shapes": shapes, "seconds": round(dur, 3), **measured}

    def stop(self):
        self._running = False
        self._wake.set()
        if self._thread:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                # Scheduler wedged mid-dispatch (e.g. hung device call):
                # mutating slot state here would race it. Callers time out;
                # the process is going down anyway.
                log.warning("engine loop did not exit; skipping in-flight cleanup")
                if self._publisher is not None:
                    self._publisher.close()
                return
        # Close AFTER the scheduler thread is done: closing first would
        # race its in-flight _bcast onto a dead socket, spuriously
        # triggering the fatal-gang path during a clean shutdown.
        if self._publisher is not None:
            self._publisher.close()  # sends the followers "stop"
        # Fail anything still in flight so callers never hang on shutdown.
        self._fail_inflight("engine shutting down")
        # Unbind this engine's callback gauges and its /debug/engine
        # perf section (only where it is still the current owner): the
        # process-global registries must not pin the stopped engine's
        # KV pool and jit caches for process life.
        for gauge, fn in self._gauge_callbacks:
            gauge.clear_callback(fn)
        unregister_engine_debug_section("perf", self._perf_section_fn)
        qos_uninstall_queue(self._queue)

    def _fail_inflight(self, message: str) -> None:
        """Error out every slotted and queued request and reset counters
        (shared by shutdown and device-error recovery)."""
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._slots[i] = None
                slot.req.out.put(("error", message))
                self._finish_request(
                    slot.req, "error",
                    error=message, completion_tokens=slot.generated,
                )
                self._record_slot_cost(slot, i)
                self._release_slot_pages(i)
        self._n_active = 0
        self._h_active[:] = False
        self._adm_mask[:] = False
        self.m_active.set(0)
        for req in self._deferred:
            req.out.put(("error", message))
            self._finish_request(req, "error", error=message)
        self._deferred.clear()
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            req.out.put(("error", message))
            self._finish_request(req, "error", error=message)
        while True:
            try:
                *_, rq = self._aux.get_nowait()
            except queue.Empty:
                break
            rq.put(("error", message))
        self.m_queue.set(0)

    def _finish_request(self, req: Request, outcome: str, **attrs) -> None:
        """Terminal accounting for EVERY request that entered submit():
        the outcome counter, the per-phase histograms (a handful of
        observes computed from the trace's raw stamps — cheap enough
        for the scheduler thread), and the flight-recorder handoff
        (span assembly happens on the recorder's worker thread)."""
        tr = req.trace
        # Atomic claim: the old `tr.end_mono is not None` check alone was
        # check-then-act — two racing finishers could both pass it before
        # either called tr.finish().
        with self._in_system_lock:
            if req.finished:
                return
            req.finished = True
            self._in_system -= 1
        if tr is not None and tr.end_mono is not None:
            return  # finalized externally (defensive; claim already took it)
        self.m_requests.inc(labels={"outcome": outcome})
        if tr is None:
            return
        tr.finish(outcome, **attrs)
        end = tr.end_mono
        t_prefill = tr.first_mark("prefill")
        # Queue wait ends at prefill dispatch; a request that never made
        # it to a slot waited its whole life, divided by the same rule.
        admitted = t_prefill if t_prefill is not None else end
        self.m_queue_wait.observe(admitted - tr.t0_mono)
        if tr.queue_parts is None:
            tr.queue_parts = self._queue_parts(tr.t0_mono, admitted)
        for labels, seconds in zip(_QUEUE_CAUSES, tr.queue_parts):
            self.m_queue_by_cause.inc(seconds, labels=labels)
        if t_prefill is not None:
            first_tok = tr.tokens[0] if tr.tokens else end
            self.m_prefill_s.observe(first_tok - t_prefill)
        self.m_e2e.observe(
            end - tr.t0_mono, labels={"outcome": outcome},
            exemplar=tr.ctx.trace_id,
        )
        # Per-token TPOT is O(generated tokens) worth of histogram
        # observes — that runs on the recorder's worker thread, not here.
        default_recorder.submit(tr, observe=self._observe_tpot)

    def _observe_tpot(self, tr: RequestTrace) -> None:
        """Recorder-worker-thread hook: derive inter-token latencies
        from the raw token stamps (Histogram.observe is thread-safe)."""
        tid = tr.ctx.trace_id
        for a, b in zip(tr.tokens, tr.tokens[1:]):
            self.m_tpot.observe(b - a, exemplar=tid)

    def submit(
        self,
        prompt_ids: list[int],
        params: SamplingParams,
        adapter: str | None = None,
        trace_ctx: TraceContext | None = None,
        deadline: float | None = None,
        tenant: str = "",
        priority: str = "standard",
        preemptible: bool = False,
        park_kv: str = "",
        restore: Any = None,
        restore_key: str = "",
        received: float | None = None,
    ) -> Request:
        """Enqueue a request; raises queue.Full when saturated (the proxy
        retries another replica, and the server maps it to 429 +
        Retry-After). Prompts beyond the largest prefill bucket are
        chunk-prefilled, up to the slot capacity. *trace_ctx* attaches
        the request to an inbound trace (proxy hop); omitted, a fresh
        trace is generated — every request gets a timeline. *deadline*
        (time.monotonic()-based) lets the scheduler abort the request —
        queued or mid-decode — once the caller's budget is spent.
        *received* (time.monotonic()) is when the server took the request
        in, before it read the body: the timeline's `receive` phase."""
        # Failpoint: chaos tests inject admission errors/delays/hangs.
        fault("engine.submit")
        # The prompt plus at least one generated token must fit both the
        # position space and the page pool (minus the trash page).
        max_prompt = min(
            self.cfg.max_seq_len,
            (self._pool.num_pages - 1) * self.cfg.page_size,
        ) - 1
        if len(prompt_ids) > max_prompt:
            raise ValueError(
                f"prompt too long: {len(prompt_ids)} tokens > {max_prompt}"
            )
        if adapter and (self._adapters is None or self._adapters.row_for(adapter) == 0):
            raise ValueError(f"adapter {adapter!r} is not loaded")
        if not self._running:
            raise RuntimeError("engine is not running")
        if not self._kv_enabled():
            # Gangs and KUBEAI_KV_RESTORE=0 never park or import —
            # resumes take the deterministic-replay path unchanged.
            park_kv, restore, restore_key = "", None, ""
        req = Request(
            prompt_ids=prompt_ids, params=params, adapter=adapter,
            deadline=deadline, tenant=tenant,
            priority=priority, preemptible=preemptible,
            park_kv=park_kv, restore=restore, restore_key=restore_key,
        )
        req.trace = RequestTrace(
            ctx=trace_ctx, component="engine", t0_mono=req.arrival, received=received
        )
        req.trace.attrs["prompt_tokens"] = len(prompt_ids)
        req.trace.attrs["priority"] = priority
        if tenant:
            # Tenant-filterable flight-recorder timelines (the proxy
            # stamps its span the same way).
            req.trace.attrs["tenant"] = tenant
        with self._in_system_lock:
            self._in_system += 1
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            with self._in_system_lock:
                self._in_system -= 1
            raise
        if not self._running:
            # Raced a concurrent stop(): _fail_inflight's queue drain may
            # have run BEFORE our put, which would strand this request
            # with no terminal event ever arriving. Fail it here —
            # harmlessly doubled if the drain did see it (consumers take
            # the first terminal event; _finish_request dedupes).
            req.out.put(("error", "engine shutting down"))
            self._finish_request(req, "error", error="engine shutting down")
            return req
        self.m_queue.set(self.queue_depth())
        self._wake.set()
        return req

    def generate(self, prompt_ids: list[int], params: SamplingParams, timeout: float = 300, adapter: str | None = None):
        """Blocking convenience wrapper: returns (token_ids, text, FinishInfo).
        *timeout* doubles as the scheduler-side deadline, so a timed-out
        generate() frees its slot/pages instead of decoding on."""
        req = self.submit(
            prompt_ids, params, adapter=adapter,
            deadline=time.monotonic() + timeout,
        )
        ids: list[int] = []
        chunks: list[str] = []
        deadline = time.monotonic() + timeout
        while True:
            try:
                ev = req.out.get(timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                # Surface a descriptive timeout instead of a bare
                # queue.Empty (which escaped uncaught and killed the r2
                # bench worker mid-warmup-compile; VERDICT r2 weak #1).
                req.cancelled.set()
                raise TimeoutError(
                    f"generate() produced no event within {timeout}s "
                    f"(got {len(ids)} tokens; first compile of a large "
                    f"model can exceed the default — pass timeout=)"
                ) from None
            if ev[0] == "token":
                if ev[1] >= 0:  # -1 marks a text-only flush of held-back chars
                    ids.append(ev[1])
                chunks.append(ev[2])
            elif ev[0] == "done":
                return ids, "".join(chunks), ev[1]
            else:
                raise RuntimeError(ev[1])

    # -- embeddings --------------------------------------------------------

    def embed(self, prompts: list[list[int]]) -> np.ndarray:
        """Mean-pooled, L2-normalized final hidden states (the
        TextEmbedding feature; the reference delegates this to Infinity
        containers). Each group is dispatched by the SCHEDULER thread
        (queued via _aux) so embeds interleave between decode chunks
        instead of contending with them; only the result fetch happens
        here. Falls back to direct dispatch when the loop isn't running
        (tests, one-shot tools)."""
        self._ensure_embed_jit()
        # Build every group first, then dispatch them all down ONE path —
        # checking _running per group could interleave direct and queued
        # results out of order if the engine stops mid-call.
        groups: list[tuple[int, np.ndarray, np.ndarray]] = []
        max_prompt = max(self.cfg.prefill_buckets)
        B = self.cfg.max_slots
        for start in range(0, len(prompts), B):
            group = prompts[start : start + B]
            longest = max(len(p) for p in group)
            if longest > max_prompt:
                raise ValueError(f"embedding input too long: {longest} > {max_prompt}")
            bucket = self._bucket(longest)
            # Batch dim padded to max_slots so the compile count is bounded
            # by len(prefill_buckets), not by observed batch sizes; padding
            # rows (length 0) pool to zeros and are sliced off.
            tokens = np.zeros((B, bucket), np.int32)
            lengths = np.zeros((B,), np.int32)
            for i, p in enumerate(group):
                tokens[i, : len(p)] = p
                lengths[i] = len(p)
            groups.append((len(group), tokens, lengths))

        out = []
        if self._running:
            pending = []
            for n, tokens, lengths in groups:

                def thunk(tokens=tokens, lengths=lengths):
                    with self._lockstep(
                        "embed", arrays={"tokens": tokens, "lengths": lengths}
                    ):
                        return self._embed_jit(self.params, tokens, lengths)

                pending.append((n, self._submit_aux(thunk)))
            for n, rq in pending:
                val = self._await_aux(rq, what="embedding")
                out.append(np.asarray(jax.device_get(val))[:n])
        else:
            if self._multiproc:
                # Followers only mirror dispatches published by the
                # scheduler; a direct collective here would hang the gang.
                raise RuntimeError("engine is not running")
            for n, tokens, lengths in groups:
                vecs = self._embed_jit(self.params, tokens, lengths)
                out.append(np.asarray(jax.device_get(vecs))[:n])
        return np.concatenate(out, axis=0)

    def _ensure_embed_jit(self) -> None:
        if hasattr(self, "_embed_jit"):
            return
        mc = self.model_config

        def embed_fn(params, tokens, lengths):
            B, S = tokens.shape
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
            hidden, _ = family(mc).apply(params, mc, tokens, pos, return_hidden=True)
            valid = (pos < lengths[:, None]).astype(jnp.float32)[..., None]
            pooled = (hidden * valid).sum(1) / jnp.maximum(valid.sum(1), 1.0)
            return pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12
            )

        kw = {}
        if self._multiproc:
            from jax.sharding import NamedSharding, PartitionSpec

            kw = {"out_shardings": NamedSharding(self._mesh, PartitionSpec())}
        self._embed_jit = jax.jit(embed_fn, **kw)

    def _submit_aux(self, thunk) -> "queue.Queue":
        """Queue device work for the SCHEDULER thread (all device
        dispatch is serialized there — and on a gang, broadcast order
        must equal dispatch order, which only one thread can guarantee)."""
        rq: "queue.Queue" = queue.Queue()
        self._aux.put((thunk, rq))
        self._wake.set()
        return rq

    def _await_aux(self, rq: "queue.Queue", what: str, timeout: float = 600):
        deadline = time.monotonic() + timeout
        while True:
            try:
                kind, val = rq.get(timeout=1.0)
                break
            except queue.Empty:
                # An enqueue that raced stop()'s _aux drain would
                # otherwise wait the full timeout for a reply that can
                # never come.
                if not self._running:
                    raise RuntimeError("engine shutting down") from None
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"{what} produced no result within {timeout}s "
                        "(engine scheduler stalled?)"
                    ) from None
        if kind != "ok":
            raise RuntimeError(f"{what} failed: {val}")
        return val

    def _run_aux(self) -> None:
        """Execute one queued auxiliary thunk (scheduler thread only).
        One item per loop iteration so a large embed batch interleaves
        with decode chunks instead of stalling them. Replies carry the
        (async) result; the caller's thread does any device_get."""
        try:
            thunk, rq = self._aux.get_nowait()
        except queue.Empty:
            return
        try:
            rq.put(("ok", thunk()))
        except GangLost:
            # Lost follower (gang publish failed): this must reach
            # _loop's recovery — which terminates the rank, the gang
            # cannot realign — not be swallowed as a per-request error.
            rq.put(("error", "gang follower lost"))
            raise
        except GangDesync:
            # Broadcast succeeded but the local dispatch failed
            # (advisor r3): the gang cannot realign — escalate to
            # _loop's fatal path instead of per-request swallowing.
            rq.put(("error", "gang dispatch stream desynced"))
            raise
        except Exception as e:  # no donation: decode state is unharmed
            log.exception("aux dispatch failed")
            rq.put(("error", str(e)))

    # -- LoRA adapters -----------------------------------------------------

    def load_adapter(self, name: str, path: str) -> None:
        """Install a PEFT adapter into the bank (first load allocates it
        and costs one step-function recompile). Executed on the
        SCHEDULER thread: the bank swap must be ordered against decode
        dispatches, and on a gang the broadcast position in the dispatch
        stream decides when followers switch banks — an admin-thread
        publish racing the scheduler's would desync the ranks. *path*
        may be a remote source; every rank stages it independently."""

        # Stage on THIS (HTTP admin) thread: a multi-GB download inside
        # the scheduler thunk would freeze every client's token stream
        # for its duration. Only the bank install/broadcast needs
        # dispatch-stream ordering.
        if family(self.model_config).init_lora_bank is None:
            raise ValueError(f"{self.model_config.model_type}: LoRA adapters are not supported")
        staged = self._stage_adapter(name, path)

        def do():
            # Install FIRST: a bad checkpoint then fails as a clean
            # per-request error with no broadcast, leaving every rank
            # untouched — broadcasting first would make the followers
            # fail fatally on content rank 0 itself rejected. On
            # success, the broadcast lands at this thunk's stream
            # position, so followers install before replaying any
            # later dispatch that carries lora state. Followers get the
            # ORIGINAL path — they stage independently (a path staged
            # on this host means nothing on theirs).
            self._install_adapter(name, staged)
            self._bcast("load_adapter", scalars={"name": name, "path": path})
            self._adapter_sources[name] = path

        if self._running:
            self._await_aux(self._submit_aux(do), what="adapter load")
        else:
            do()  # pre-start: no dispatch stream to order against

    @staticmethod
    def _stage_adapter(name: str, path: str) -> str:
        import os as _os

        from kubeai_tpu.loader import stage_remote

        return stage_remote(
            path,
            _os.environ.get("KUBEAI_ADAPTER_STAGING_DIR", "/tmp/kubeai-adapters"),
            prefix=f"{name}-",
        )

    def _load_adapter_local(self, name: str, path: str) -> None:
        """Follower side: stage (blocking the replay loop is inherent —
        later ops may depend on the bank) then install."""
        self._install_adapter(name, self._stage_adapter(name, path))

    def _install_adapter(self, name: str, staged: str) -> None:
        from kubeai_tpu.engine.lora import AdapterRuntime

        if self._adapters is None:
            self._adapters = AdapterRuntime(
                self.model_config,
                max_adapters=self.cfg.max_adapters,
                max_rank=self.cfg.max_lora_rank,
                mesh=self._mesh if self._multiproc else None,
            )
        self._adapters.load(name, staged)

    def unload_adapter(self, name: str) -> bool:
        if self._adapters is None:
            return False

        def do():
            ok = self._adapters.unload(name)
            if ok:  # no-op unloads broadcast nothing (followers agree)
                self._bcast("unload_adapter", scalars={"name": name})
                self._adapter_sources.pop(name, None)
            return ok

        if self._running:
            return self._await_aux(self._submit_aux(do), what="adapter unload")
        return do()

    def loaded_adapters(self) -> list[str]:
        return self._adapters.names() if self._adapters else []

    def _hbm_stats(self) -> tuple[int, int]:
        """(used, limit) bytes over this process's addressable devices
        (an autoscaling signal the reference never had — its metrics stop
        at proxy-side in-flight counts). Remote devices of a multi-host
        slice can't report stats (each worker publishes its own). Feeds
        the HBM callback gauges at /metrics collect time."""
        used = limit = 0
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}  # None on the CPU backend
            used += stats.get("bytes_in_use", 0)
            limit += stats.get("bytes_limit", 0)
        return used, limit

    def _jit_cache_entries(self) -> int:
        """Executables that came up through the jitted functions (jax's
        per-function cache): shapes the table does not hold, calls that
        carry an adapter, embeddings. Growth = a compilation happened."""
        sf = self._step_fns
        fns = [sf.decode_jit, sf.prefill_batch_jit, sf.prefill_chunk_jit]
        if hasattr(self, "_embed_jit"):  # built on first embeddings call
            fns.append(self._embed_jit)
        return sum(fn._cache_size() for fn in fns)

    def _update_recompile_counter(self) -> None:
        """Scheduler-loop poll: surface the programs this process brought
        up (the table's, loaded or compiled ahead of time, AND lazy
        compiles through the jitted functions: warmup's on a start with
        an empty table, shape churn afterwards) as a counter — steady
        growth after warmup is the classic silent TPU latency killer. The
        KV cached-page eviction counter rides the same poll (paging.py
        stays dependency-free; both sources are scheduler-thread-owned)."""
        from kubeai_tpu.engine.step_programs import M_STEP_PROGRAMS

        lazy = self._jit_cache_entries()
        if lazy > self._lazy_seen:
            M_STEP_PROGRAMS.inc(lazy - self._lazy_seen, labels={"how": "lazy"})
        self._lazy_seen = lazy
        self._table.stats["lazy"] = lazy  # /debug/engine: cold_start.warm_compile
        n = len(self._table.held) + lazy
        if n > self._jit_entries_seen:
            self.m_recompiles.inc(n - self._jit_entries_seen)
            self._jit_entries_seen = n
        elif n < self._jit_entries_seen:
            self._jit_entries_seen = n  # caches dropped (recovery rebuild)
        ev = self._pool.evictions
        if ev > self._evictions_seen:
            self.m_cached_evictions.inc(ev - self._evictions_seen)
            self._evictions_seen = ev
        elif ev < self._evictions_seen:
            self._evictions_seen = ev  # pool rebuilt (engine reset)

    def is_ready(self) -> bool:
        """Readiness (k8s probe seam): the scheduler loop is alive and
        accepting submissions, and — on a gang — every follower rank is
        connected (a degraded gang cannot decode; the balancer must
        route to other replicas until the gang re-forms)."""
        gang_complete = True
        if self._publisher is not None:
            # Monitor-detected loss (idle gang, EOF before any publish)
            # must read not-ready immediately — the loop only learns at
            # the next dispatch.
            gang_complete = getattr(self._publisher, "is_complete", lambda: True)()
        return bool(
            self._running
            and self._thread is not None
            and self._thread.is_alive()
            and not self._gang_degraded.is_set()
            and gang_complete
        )

    def queue_depth(self) -> int:
        # Deferred requests (admitted off the queue but waiting for KV
        # pages) are still queued work from the autoscaler's viewpoint.
        return self._queue.qsize() + len(self._deferred)

    def requests_in_system(self) -> int:
        """Requests accepted by submit() with no terminal event yet —
        queued, deferred, mid-admission, or decoding. The drain-idle
        signal (queue_depth()+active_slots() misses the admission gap)."""
        with self._in_system_lock:
            return self._in_system

    def active_slots(self) -> int:
        return self._n_active

    # -- gang follower (ranks > 0 of a multi-host slice) -------------------

    def _follower_lora(self, ar: dict) -> dict:
        """Lora kwargs for a replayed dispatch: keyed off the PAYLOAD
        (rank 0's state at publish time) — load/unload ops are ordered
        in the same stream, so local state must agree."""
        if "lora_rows" not in ar:
            return {}
        if self._adapters is None:
            raise RuntimeError("rank 0 dispatched LoRA state this follower lacks")
        return {"lora": self._adapters.bank, "lora_rows": ar["lora_rows"]}

    def run_follower(self, follower) -> None:
        """Execute rank 0's dispatch stream in lockstep (blocks until the
        publisher sends "stop" or the connection drops). The follower
        holds its own device carries (global-mesh shards); every op's
        numpy arguments arrive on the wire, so the jitted computations
        here are bit-identical to rank 0's and XLA's collectives line up.
        No scheduler, no HTTP inference surface — the LB only routes to
        rank 0 (loadbalancer gang awareness)."""
        self._ensure_embed_jit()
        from kubeai_tpu.utils import env_float

        reconnect_timeout = env_float("KUBEAI_GANG_RECONNECT_TIMEOUT", 60.0)
        while True:
            try:
                op, sc, ar = follower.recv()
            except ConnectionError:
                # Dispatch stream dropped (rank 0 blip, network cut, or
                # an injected follower-drop fault): reconnect with
                # backoff instead of dying — rank 0's supervision holds
                # the gang degraded until we re-prove, then resets every
                # rank. Only a publisher that never comes back (or
                # rejects the handshake) ends this process.
                reconnector = getattr(follower, "reconnect", None)
                if reconnector is None or reconnect_timeout <= 0:
                    log.warning("gang publisher connection closed; follower exiting")
                    return
                log.warning(
                    "gang dispatch stream lost; reconnecting (up to %.0fs)",
                    reconnect_timeout,
                )
                try:
                    reconnector(timeout=reconnect_timeout)
                except Exception as e:
                    log.warning("gang reconnect failed (%s); follower exiting", e)
                    return
                log.info("gang dispatch stream re-established")
                continue
            if op == "stop":
                return
            if op == "reset":
                self._init_device_state()
                continue
            if op == "load_adapter":
                # A follower that cannot install what rank 0 installed
                # cannot stay in lockstep — let the exception end the
                # follower (the pod exits; the controller restarts the
                # slice gang).
                self._load_adapter_local(sc["name"], sc["path"])
                continue
            if op == "unload_adapter":
                if self._adapters is not None:
                    self._adapters.unload(sc["name"])
                continue
            if op == "profile":
                # Rank 0's /debug/profile fan-out: capture the same
                # window on a background thread so the replay loop keeps
                # executing (the replayed dispatches ARE the trace's
                # subject). Best-effort — never kills the follower.
                sc = sc or {}
                perf_obs.start_background_capture(
                    float(sc.get("seconds", 2.0)), sc.get("dir") or None
                )
                continue
            if op == "decode":
                lora_args = self._follower_lora(ar)
                (
                    _, _, _, _,
                    self._cache, self._tok_hist, self._lengths,
                    self._last_tokens, self._keys, _,
                ) = self._decode_jit(
                    self.params, self._cache, ar["tables"], self._tok_hist,
                    self._lengths, self._last_tokens, self._keys,
                    ar["active"], ar["temp"], ar["top_p"], ar["top_k"],
                    ar["presence"], ar["freq"], ar["want_top"], ar["gen_start"],
                    ar["bias_ids"], ar["bias_vals"],
                    ar["adm_mask"], ar["adm_len"], ar["adm_seed"],
                    self._adm_toks, **lora_args,
                )
            elif op == "prefill_batch":
                lora_args = self._follower_lora(ar)
                _, _, _, _, self._cache, self._adm_toks, _ = self._prefill_batch_jit(
                    self.params, ar["tokens"], ar["lengths"], ar["tables"],
                    ar["slots"], ar["seeds"], ar["temps"], ar["top_ps"],
                    ar["top_ks"], ar["bias_ids"], ar["bias_vals"],
                    self._adm_toks, self._cache, **lora_args,
                )
            elif op == "prefill_chunk":
                lora_args = self._follower_lora(ar)
                _, _, _, _, self._cache, self._adm_toks, _ = self._prefill_chunk_jit(
                    self.params, ar["tokens"], ar["starts"], ar["last_idx"], ar["tables"],
                    ar["slots"], ar["seeds"], ar["temps"], ar["top_ps"],
                    ar["top_ks"], ar["bias_ids"], ar["bias_vals"],
                    self._adm_toks, self._cache, **lora_args,
                )
            elif op == "embed":
                self._embed_jit(self.params, ar["tokens"], ar["lengths"])
            else:
                log.error("unknown gang op %r; stopping follower", op)
                return

    # -- scheduler loop ----------------------------------------------------

    def _loop(self):
        """Pipelined scheduler: dispatch decode chunk N+1 before processing
        chunk N's tokens, hiding the host<->device round-trip behind device
        compute. Admissions chain onto the latest dispatched state; a chunk
        dispatched while a slot was still running an earlier request is
        reconciled via the per-dispatch slot snapshot."""
        log.info("engine loop started (slots=%d)", self.cfg.max_slots)
        # Adopt this replica's failpoint scope (stamped by EngineServer
        # before start): engine.step / engine.kv_export / engine.kv_import
        # on this thread then fire @<port> twins so chaos schedules can
        # fault ONE replica of a multi-replica in-process fleet.
        faults.set_thread_scope(getattr(self, "fault_scope", None))
        _name_os_thread("engine-loop")  # the line a profiler trace gives this thread
        perf_obs.gc_watch.install()  # collections on the record while a loop runs
        try:
            self._loop_iterations()
        finally:
            perf_obs.gc_watch.remove()

    def _loop_iterations(self):
        # Every statement of an iteration runs under exactly one segment
        # (obs/perf.py STALL_CAUSES): stamped once, the stamps feed the
        # stall counter, /debug/pipeline, the step records and, while a
        # profiler runs, the sched.* events of its trace.
        segment = self._stall.segment
        pending = None  # (payload_device_refs, [(slot_idx, _Slot, epoch), ...], dispatch Segment)
        while self._running:
            try:
                with segment("sweep"):
                    # Failpoint: chaos tests hang/fail the scheduler here —
                    # an injected error exercises the device-state recovery
                    # path below exactly like a real dispatch failure.
                    fault("engine.step")
                    self._sweep_deadlines()
                    self._sweep_qos_budgets()
                    self._sweep_kv_park()
                admitted = self._admit_waiting()
                dispatched = self._dispatch_chunk() if self._n_active > 0 else None
                # First-token sync AFTER the dispatch: the chunk reads
                # its first tokens from the device staging vector, so
                # this host round-trip overlaps device compute.
                with segment("host_overlap", admitted=len(admitted)):
                    self._emit_admitted(admitted)
                    self._run_aux()
                if pending is not None:
                    self._process_chunk(*pending)
                pending = dispatched
                with segment("sweep"):
                    self._update_recompile_counter()
                    perf_obs.gc_watch.flush()
                if (
                    pending is None and not admitted and self._n_active == 0
                    and self._aux.empty()
                ):
                    with segment("idle"):
                        # Idle: the goodput gauge must read 0, not the last
                        # busy chunk's rate — and the window re-anchors so
                        # the next busy chunk doesn't span the idle gap.
                        if len(self._rate_window):
                            self._rate_window.reset()
                            self._pairs_window.reset()
                            self.m_tok_rate.set(0.0)
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
            except GangDesync as e:
                # The followers executed an op this rank didn't: no reset
                # can realign the gang (they're blocked in its collective).
                # Same blast radius as losing a rank — fail everything and
                # exit for the controller to recreate the slice gang.
                log.critical("%s; terminating rank 0", e)
                self._terminate_rank("gang desynced; slice restarting", code=14)
                return  # tests stub _terminate_rank; production never gets here
            except GangLost as e:
                # A follower's dispatch connection died. Fail in-flight
                # work (its collectives can never complete), go
                # not-ready, and SUPERVISE: wait for the follower to
                # reconnect and re-form the gang instead of wedging or
                # exiting immediately.
                self._handle_gang_loss(str(e))
                pending = None
            except Exception:
                # A failed jitted step may have consumed donated buffers —
                # the device state is unusable. Fail all in-flight requests
                # and rebuild (elastic recovery; the pod stays alive).
                log.exception("engine step failed; resetting device state")
                self._recover()
                pending = None

    def _bcast(self, op: str, scalars: dict | None = None, arrays: dict | None = None) -> None:
        """Rank 0 of a gang: fan the upcoming dispatch out to followers
        BEFORE executing it locally (order on the wire = dispatch order =
        the lockstep contract). No-op single-host."""
        if self._publisher is not None:
            try:
                self._publisher.publish(op, scalars, arrays)
            except OSError as e:
                # Typed so handlers can tell follower loss apart from
                # ordinary OSErrors inside dispatched work (e.g. an
                # adapter download failing is a per-request error, NOT
                # a reason to tear the gang down).
                raise GangLost(str(e)) from e

    @contextmanager
    def _lockstep(self, op: str, scalars: dict | None = None, arrays: dict | None = None):
        """Broadcast *op*, then run the matching local dispatch in the
        with-body. In a gang, a body failure AFTER the broadcast is
        unrecoverable-by-reset: the followers replayed an op rank 0
        never executed, so the ranks' computation streams diverged and
        they are blocked inside the unmatched collective — escalate to
        GangDesync (fatal for the rank). Single-host, the original
        exception propagates unchanged into ordinary reset recovery."""
        self._bcast(op, scalars, arrays)
        try:
            yield
        except (GangLost, GangDesync):
            raise
        except Exception as e:
            if self._publisher is not None:
                raise GangDesync(
                    f"rank 0 failed to execute broadcast op {op!r}: {e}"
                ) from e
            raise

    def _terminate_rank(self, message: str, code: int) -> None:
        """Unrecoverable gang failure: error everything in flight, then
        exit for the controller to recreate the whole slice gang (same
        blast radius as losing a Ray/NCCL rank in the reference's
        delegated engines). Exiting without cleanup would leave clients
        hanging until timeout. Overridable hook so tests can observe the
        fatal path without losing the process."""
        self._fail_inflight(message)
        import os as _os

        _os._exit(code)

    def _recover(self):
        try:
            self._bcast("reset")
        except GangLost as e:
            if self._running:
                # A follower is gone on top of the device error: route
                # into gang supervision (which fails in-flight work and
                # re-forms or, on timeout, terminates the rank).
                self._handle_gang_loss(str(e))
            return
        self._fail_inflight("engine reset after device error")
        self._init_device_state()

    def _handle_gang_loss(self, reason: str) -> None:
        """Rank 0 gang supervision: a follower is gone. Fail everything
        in flight, flip not-ready (the balancer routes elsewhere), then
        wait for the restarted follower to reconnect. On re-form:
        broadcast "reset" so every rank rebuilds device state from the
        same zero, rebuild locally, count kubeai_gang_reforms_total,
        and resume serving. If the gang does not re-form within
        KUBEAI_GANG_REFORM_TIMEOUT, fall back to rank termination (the
        controller recreates the whole slice — the pre-recovery blast
        radius)."""
        self._gang_degraded.set()
        log.warning("gang degraded (%s); waiting for re-form", reason)
        self._fail_inflight(f"gang follower lost; re-forming ({reason})")
        pub = self._publisher
        if pub is None:  # defensive: GangLost only arises with a publisher
            self._gang_degraded.clear()
            return
        if self.gang_reform_timeout <= 0:
            # Supervision disabled: the original terminate-immediately
            # blast radius.
            self._terminate_rank("gang follower lost; slice restarting", code=13)
            return
        deadline = time.monotonic() + self.gang_reform_timeout
        while self._running:
            if pub.wait_complete(0.1):
                try:
                    self._bcast("reset")
                    # Replay adapter loads: a RESTARTED follower has an
                    # empty bank, and the first LoRA dispatch it cannot
                    # satisfy would kill it again (re-form crash-loop).
                    # Survivors re-install idempotently — the ops are
                    # ordered in the same stream, so every rank's bank
                    # converges before any later dispatch.
                    for name, path in self._adapter_sources.items():
                        self._bcast(
                            "load_adapter", scalars={"name": name, "path": path}
                        )
                except GangLost:
                    # Re-formed member died again before the reset
                    # landed; keep supervising until the deadline.
                    if time.monotonic() >= deadline:
                        break
                    continue
                self._init_device_state()
                self._gang_degraded.clear()
                self.m_gang_reforms.inc()
                log.info("gang re-formed; serving resumes")
                return
            if time.monotonic() >= deadline:
                break
        if self._running:
            log.critical(
                "gang did not re-form within %.0fs; terminating rank 0",
                self.gang_reform_timeout,
            )
            self._terminate_rank("gang follower lost; slice restarting", code=13)

    DEADLINE_MSG = "deadline exceeded"

    def _sweep_deadlines(self) -> None:
        """Abort active slots whose end-to-end deadline passed: the
        caller (or the proxy on its behalf) has given up, so decode
        steps spent on them starve live requests. The slot and its KV
        pages free immediately; the terminal outcome is `cancelled`
        (the work was abandoned, not failed). Runs once per scheduler
        iteration — O(max_slots) host time."""
        if all(s is None for s in self._slots):
            return
        now = time.monotonic()
        for i, slot in enumerate(self._slots):
            if slot is None or slot.req.deadline is None:
                continue
            if now > slot.req.deadline:
                slot.req.out.put(("error", self.DEADLINE_MSG))
                log.info(
                    "aborting slot %d past deadline (%d tokens generated)",
                    i, slot.generated,
                )
                self._free(i, "stop", deliver=False)

    QOS_BUDGET_MSG = "queue-wait budget exceeded for priority class"

    def _sweep_qos_budgets(self) -> None:
        """Drop queued requests past their per-class queue-wait budget
        (KUBEAI_QOS_BUDGET_*; the class-aware successor to the single
        global queue-wait deadline). The queue rate-limits the scan
        internally, so this is near-free in the hot loop."""
        dropped = self._queue.sweep_budgets()
        for req in dropped:
            req.out.put(("error", self.QOS_BUDGET_MSG))
            self._finish_request(req, "cancelled", error=self.QOS_BUDGET_MSG)
        if dropped:
            self.m_queue.set(self.queue_depth())

    def _peek_priority(self) -> str | None:
        """Class of the next request admission would serve: the deferred
        head outranks the queue unless the queue holds a strictly
        higher class (the same overtake rule _admit_waiting applies)."""
        if self._deferred:
            head = self._deferred[0].priority
            return (
                self._queue.peek_priority()
                if self._queue.outranks(head)
                else head
            )
        return self._queue.peek_priority()

    def _preempt_one(self, taken: set) -> bool:
        """Seize ONE preemptible batch slot for a waiting interactive
        request. The victim's stream finishes with reason "preempted"
        and NO detokenizer tail flush — flushed text would desync the
        proxy's event-count resume cursor — while its KV pages release
        with content registration, so the deterministic re-run's
        prefill can prefix-reuse them. Victim choice: fewest generated
        tokens (least regeneration wasted). Returns True when a slot
        was freed."""
        victim, best = -1, None
        for i, slot in enumerate(self._slots):
            if slot is None or i in taken:
                continue
            r = slot.req
            if not r.preemptible or r.priority != "batch" or r.finished:
                continue
            if best is None or slot.generated < best:
                victim, best = i, slot.generated
        if victim < 0:
            return False
        slot = self._slots[victim]
        log.info(
            "preempting slot %d (batch, %d tokens generated) for "
            "interactive admission", victim, slot.generated,
            extra=trace_extra(slot.req.trace, qos_class=slot.req.priority),
        )
        record_preemption(slot.generated)
        self._free(victim, "preempted", flush=False, outcome="preempted")
        return True

    def qos_retry_after(self, priority: str) -> int:
        """Retry-After seconds for a shed request of this class, scaled
        by the backlog it would sit behind (classes at or above it)."""
        backlog = self._queue.backlog_at_or_above(priority)
        per_round = max(self.cfg.max_slots, 1)
        return int(min(max(1 + backlog // per_round, 1), 30))

    def _admit_waiting(self) -> list:
        """Admit queued requests into free slots: plan pages, dispatch
        prefill calls (all-numpy args riding the execute RPC), and fill
        the admission merge arrays the next decode dispatch consumes.
        Returns the admitted list for _emit_admitted — the first-token
        host sync happens AFTER the next decode chunk dispatch, so the
        device never idles waiting on it."""
        # admitted entries: (slot_idx, epoch, tok_ref, j, lp_ref) where
        # tok_ref/lp_ref are device arrays ([N] for group members, scalar
        # for chunked singles) and j indexes group outputs (None=scalar).
        admitted: list[tuple] = []
        taken: set[int] = set()
        max_bucket = max(self.cfg.prefill_buckets)
        with self._stall.segment("admit"):
            work = self._plan_admissions(admitted, taken, max_bucket)
            self._run_prefills(work)
        return admitted

    def _plan_admissions(self, admitted: list, taken: set[int], max_bucket: int) -> list:
        """Drain the queue into free slots and reserved pages; returns the
        prefill calls to make, in dispatch order, as (items, thunk). One
        admission ROUND: stamped once, with why it stopped taking requests
        (what those it left behind wait for until the next: _queue_parts)."""
        self._stamp_round()
        stopped = "empty"
        singles: list[tuple[int, "Request", int]] = []  # (slot, req, reuse), in the order planned
        groups: dict[int, list[tuple[int, "Request"]]] = {}  # bucket -> items
        while True:
            if not (self._n_active + len(taken) < self.cfg.max_slots):
                # Every slot is busy. An interactive request at the head
                # of the line may seize a preemptible batch slot instead
                # of waiting behind bulk work (docs/qos.md); otherwise
                # this admission round is done.
                if self._peek_priority() != "interactive" or not self._preempt_one(taken):
                    stopped = "slots"
                    break
            # Pool-blocked requests wait at the head of the line, but a
            # strictly higher class arriving behind them may overtake:
            # the KV wait is the deferred request's problem, not the
            # whole fleet's.
            if self._deferred and not self._queue.outranks(self._deferred[0].priority):
                req = self._deferred.pop(0)
            else:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    break
                self.m_queue.set(self.queue_depth())
            if req.cancelled.is_set():
                self._finish_request(req, "cancelled")
                continue
            if req.deadline is not None and time.monotonic() > req.deadline:
                # Expired while queued/deferred: never takes a slot at all.
                req.out.put(("error", self.DEADLINE_MSG))
                self._finish_request(req, "cancelled", error=self.DEADLINE_MSG)
                continue
            if req.adapter and (
                self._adapters is None or self._adapters.row_for(req.adapter) == 0
            ):
                # Validated at submit(), but the adapter may have been
                # unloaded while the request sat in the queue — running
                # it against the base model would be silently wrong.
                req.out.put(("error", f"adapter {req.adapter!r} is not loaded"))
                self._finish_request(
                    req, "error", error=f"adapter {req.adapter!r} is not loaded"
                )
                continue
            if req.restore is not None:
                res = self._admit_restored(req, taken)
                if isinstance(res, int):
                    # Restored slots join the next decode dispatch
                    # directly — no prefill call, no first-token sync.
                    taken.add(res)
                    continue
                if res == "defer":
                    self._deferred.insert(0, req)
                    self.m_queue.set(self.queue_depth())
                    stopped = "pages"
                    break
                # res is None: restore failed — req.restore was cleared,
                # fall through to the replay (prefill) admission below.
            plan = self._plan_admission(req, taken)
            if plan is None and req.priority == "interactive" and self._preempt_one(taken):
                # Seizing a batch slot released its KV pages too — one
                # replan against the grown pool before deferring.
                plan = self._plan_admission(req, taken)
            if plan is None:
                # KV pool can't back prompt+budget yet; wait for a free.
                self._deferred.insert(0, req)
                self.m_queue.set(self.queue_depth())
                stopped = "pages"
                break
            slot_idx, reuse = plan
            taken.add(slot_idx)
            record_admitted(
                req.priority, max(time.monotonic() - req.arrival, 0.0)
            )
            # Cold, bucket-sized requests batch into one prefill call;
            # reuse/long requests go through the chunked path.
            if reuse == 0 and len(req.prompt_ids) <= max_bucket:
                groups.setdefault(self._bucket(len(req.prompt_ids)), []).append((slot_idx, req))
            else:
                singles.append((slot_idx, req, reuse))
        self._round_stopped = stopped

        work: list[tuple[list, Any]] = []  # (items, thunk)
        # Groups first: shared pages registered by a cold group member
        # must be written before a reuse single reads them (device-stream
        # order follows dispatch order). A bucket's prompts run, in the
        # order they were planned, as calls of the two compiled row
        # counts (EngineConfig.prefill_group_cap): `cap` rows while `cap`
        # prompts are left, then one row each. A call of `cap` rows for
        # fewer prompts computes copies of its last row: on a 7B that
        # costs more device time than the one-row calls at every bucket
        # of 128 or more, and below that wins only for three or more
        # prompts of at most 32 tokens or five of at most 64 in ONE round
        # (the table is in PERF.md section 6, PR 32).
        cost, cap = self.call_cost, max(1, min(self.cfg.prefill_group_cap, self.cfg.max_slots))
        chunked = [
            _Chunked(slot_idx, req, reuse, prefill_plan(self.cfg, len(req.prompt_ids) - reuse, cost))
            for slot_idx, req, reuse in singles
        ]
        parts: list[tuple[list, int]] = []  # the cold calls: (items, bucket)
        for bucket, items in groups.items():
            full = len(items) // cap * cap
            parts += [(items[off : off + cap], bucket) for off in range(0, full, cap)]
            parts += [([item], bucket) for item in items[full:]]
        # A one-row cold prompt may ride a chunk call of two slots as a
        # row that starts at 0, where that saves its own read of the
        # weights (round_calls), and keeps its cold call otherwise.
        spare: dict[int, int] = {}  # its place in `chunked` -> its place in `parts`
        for j, (part, bucket) in enumerate(parts):
            if len(part) == 1:
                (slot_idx, req), = part
                spare[len(chunked)] = j
                chunked.append(_Chunked(slot_idx, req, 0, [(bucket, len(req.prompt_ids))]))
        calls = round_calls(
            [c.plan for c in chunked], cost, pair_rows(self.cfg, self.model_config),
            first=self._first_waves(chunked, set(spare)), spare=frozenset(spare),
        )
        riding = {spare[i] for _, members in calls for i, _ in members if i in spare}
        for j, (part, bucket) in enumerate(parts):
            if j in riding:
                continue

            def batch(items=part, bucket=bucket):
                admitted.extend(self._prefill_group(items, bucket))

            work.append((part, batch))
        for rows, members in calls:
            part = [chunked[i] for i, _ in members]

            def chunk(rows=rows, part=part):
                admitted.extend(self._prefill_chunk_call(rows, part))

            work.append(([(c.slot, c.req) for c in part], chunk))
        return work

    def _first_waves(self, chunked: list["_Chunked"], spare: set[int]) -> list[int]:
        """The wave (round_calls) each of the round's chunk-route prompts
        starts in: 0, or, for a prompt planned on top of pages that a
        prompt of this same round writes (_plan_admission registers a
        prompt's pages as it plans it), the wave behind its writers' last
        pieces; cold calls come before every wave, and a cold prompt that
        may ride a chunk call (*spare*) does so in wave 0."""
        first = [0] * len(chunked)
        if not self.cfg.prefix_cache_min or not any(c.reuse for c in chunked):
            return first
        ends = {c.slot: 1 if i in spare else None for i, c in enumerate(chunked)}  # slot -> the wave behind its last piece
        ps = self.cfg.page_size
        for i, c in enumerate(chunked):
            if c.reuse:
                read = set(self._slot_pages[c.slot][: c.reuse // ps])
                first[i] = max(
                    (end for slot, end in ends.items() if end and not read.isdisjoint(self._slot_fresh[slot])), default=0
                )
            if i not in spare:
                ends[c.slot] = first[i] + len(c.plan)
        return first

    ROUNDS_KEPT = 4096  # admission rounds looked back over: minutes of chunk turnarounds

    def _stamp_round(self) -> None:
        """An admission round begins: one stamp, and the seconds since the
        last round's onto what that round left its requests waiting for."""
        now = time.monotonic()
        rounds = self._rounds
        slots = pages = 0.0
        if rounds:
            last, slots, pages = rounds[-1]
            if self._round_stopped == "slots":
                slots += now - last
            elif self._round_stopped == "pages":
                pages += now - last
        rounds.append((now, slots, pages))
        if len(rounds) > 2 * self.ROUNDS_KEPT:
            # A new list: a finisher on another thread keeps the one it has.
            self._rounds = rounds[-self.ROUNDS_KEPT :]

    def _queue_parts(self, arrival: float, until: float) -> tuple[float, float, float]:
        """The wait from *arrival* to *until* by what it was for, in
        seconds that add up to it: (turn, slots, pages). Over the rounds
        R1 < ... < Rk stamped inside it, each R(i+1) - Ri is for what round
        i stopped at, every slot busy or the head of the line deferred on
        the pool; `turn` is the rest: R1 - arrival, the wait for the
        scheduler to come round to admission (one chunk turnaround apart
        under load), and until - Rk, the admitting round's own work before
        this request's prefill dispatch. Two bisections of the kept rounds;
        what lies before the oldest kept one counts as `turn`."""
        rounds = self._rounds
        first = bisect.bisect_right(rounds, (arrival, math.inf))
        last = bisect.bisect_right(rounds, (until, math.inf)) - 1
        slots = pages = 0.0
        if last > first:
            slots = rounds[last][1] - rounds[first][1]
            pages = rounds[last][2] - rounds[first][2]
        return max(until - arrival - slots - pages, 0.0), slots, pages

    def _admitted(self, req: Request) -> None:
        """*req*'s prefill is the next thing dispatched: its queue wait
        ends with this stamp and is divided here, and a profiler trace
        gets the division as a `req.admit` event on this thread's line."""
        tr = req.trace
        if tr is None:
            return
        tr.mark("prefill")
        now = tr.marks[-1][1]
        turn, slots, pages = tr.queue_parts = self._queue_parts(tr.t0_mono, now)
        perf_obs.trace_mark(
            "req.admit", rid=tr.rid, waited_ms=(now - tr.t0_mono) * 1e3,
            turn_ms=turn * 1e3, slots_ms=slots * 1e3, pages_ms=pages * 1e3,
        )

    def _run_prefills(self, work: list) -> None:
        for w, (items, thunk) in enumerate(work):
            try:
                thunk()
            except Exception as e:
                log.exception("prefill failed")
                poisoned = False
                for slot_idx, req in items:
                    if self._slots[slot_idx] is None and not req.finished:  # (finished: an earlier chunk call of the round failed it)
                        req.out.put(("error", f"prefill failed: {e}"))
                        self._finish_request(req, "error", error=f"prefill failed: {e}")
                        # The prefill never wrote this slot's pages: any
                        # plan-time content registration must be undone
                        # so the never-written KV can't be prefix-reused.
                        fresh = self._slot_fresh[slot_idx]
                        self._slot_fresh[slot_idx] = []
                        if any(self._pool.refcount(p) > 1 for p in fresh):
                            # A same-round request already claimed one of
                            # these pages — its prefill would read
                            # garbage. Escalate to full recovery.
                            poisoned = True
                        self._pool.unregister_pages(fresh)
                        self._release_slot_pages(slot_idx)
                # Escalate to _loop's recovery when the failure can't be
                # contained to this request: a failed jitted prefill may
                # have consumed the donated cache, a same-round claimant
                # of the failed slot's pages would read garbage
                # (poisoned), and a gang failure (lost follower /
                # desync) needs the loop's supervision, not per-request
                # swallowing. Requests drained from the queue but not
                # yet prefilled would otherwise be silently dropped
                # (their callers would hang): error them out first.
                kbuf = self._cache["kv"]
                if (
                    poisoned
                    or isinstance(e, (GangLost, GangDesync))
                    or getattr(kbuf, "is_deleted", lambda: False)()
                ):
                    for later_items, _ in work[w + 1 :]:
                        for slot_idx, req in later_items:
                            if self._slots[slot_idx] is None and not req.finished:
                                req.out.put(("error", f"prefill failed: {e}"))
                                self._finish_request(
                                    req, "error", error=f"prefill failed: {e}"
                                )
                    raise

    def _count_program(self, phase: str, rows: int, counters: dict) -> None:
        """A fetched step program's counters (build_step_functions:
        split_counters) into the engine's series. *rows*: the token rows
        the call computed (decode: steps x slots)."""
        if "moe_hits" not in counters:
            return
        mc = self.model_config
        # Expert layers: a pattern's `E` blocks (models/nemotron_h.py), else
        # every layer behind the leading dense ones. `n_routed_experts` is
        # what THIS chip holds: possible reads are of held experts.
        layers = mc.layer_pattern.count("E") or mc.num_layers - min(mc.first_k_dense_replace, mc.num_layers)
        steps = self.cfg.decode_chunk if phase == "decode" else 1
        labels = {"phase": phase}
        self.m_moe_hit.inc(int(counters["moe_hits"]), labels=labels)
        self.m_moe_possible.inc(mc.n_routed_experts * layers * steps, labels=labels)
        self.m_moe_assign.inc(rows * mc.num_experts_per_tok * layers, labels=labels)
        if "moe_absent" in counters:
            self.m_moe_absent.inc(int(counters["moe_absent"]), labels=labels)

    def _count_attn_pairs(self, phase: str, starts: np.ndarray, n: int, rows: int = 0) -> None:
        """kubeai_engine_attn_pairs_total for calls of *n* real queries
        a row behind *starts* [rows] cached tokens: a query at position
        p sees p + 1 keys in a full layer and min(p + 1, window) in a
        window layer. Exact, on the host, no sync. With *rows*, the
        rows a slot of a prefill call that the chunk kernel takes (every
        one but a cold call on the flash route), also what that kernel
        scores for it: kubeai_engine_attn_pairs_walked_total."""
        W = self._wpages.window
        n_full, n_window = self._kind_layers
        a, b = starts.astype(np.int64) + 1, starts.astype(np.int64) + n  # p + 1 runs a..b
        seen = np.clip(np.minimum(b, W) - a + 1, 0, None)  # queries with at most W keys before and at them
        full = ((a + b) * n // 2).sum()
        window = ((a + np.minimum(b, W)) * seen // 2 + (n - seen) * W).sum()
        self.m_attn_pairs.inc(int(n_full * full), labels={"kind": "full", "phase": phase})
        self.m_attn_pairs.inc(int(n_window * window), labels={"kind": "window", "phase": phase})
        if phase == "decode":
            self._pairs_window.add(float(n_full * full + n_window * window))
        if rows:
            mc, page = self.model_config, self.cfg.page_size
            tiles = chunk_attention.kernel_tiles(rows, mc.num_heads // mc.num_kv_heads, page, self._max_pages)
            for kind, layers, inside, reach in (("full", n_full, full, None), ("window", n_window, window, W)):
                walked = layers * sum(chunk_attention.pairs_walked(rows, int(p), reach, *tiles, page) for p in starts)
                self.m_attn_pairs_walked.inc(walked, labels={"kind": kind, "phase": phase})
                self._chunk_pairs[kind][0] += int(layers * inside)
                self._chunk_pairs[kind][1] += walked

    def _emit_admitted(self, admitted: list) -> None:
        """One host sync for all first tokens of an admission round —
        called AFTER the next decode chunk is dispatched (the chunk takes
        its first tokens from the device staging vector, so this sync is
        for client streaming only and overlaps device compute)."""
        if not admitted:
            return
        calls, self._program_counters = self._program_counters, []
        # Blocks until the round's prefills have run (behind whatever
        # chunk the device is still on): a wait, not host work.
        with self._stall.segment("fetch_wait", of="first_tokens"):
            toks, lps, tids, tlps, counted = jax.device_get((
                [a[2] for a in admitted], [a[4] for a in admitted],
                [a[5] for a in admitted], [a[6] for a in admitted],
                [c[2] for c in calls],
            ))
        for (phase, rows, _), counters in zip(calls, counted):
            self._count_program(phase, rows, counters)
        for (slot_idx, epoch, _, j, *_), tarr, larr, tid, tlp in zip(
            admitted, toks, lps, tids, tlps
        ):
            tok = int(tarr if j is None else tarr[j])
            lp = float(larr if j is None else larr[j])
            if self._slot_epoch[slot_idx] == epoch:
                # This token is what the next decode step writes.
                self._kv_pending[slot_idx] = tok
            slot = self._slots[slot_idx]
            if slot is not None and self._slot_epoch[slot_idx] == epoch:
                top = None
                if slot.req.params.logprobs:
                    row = tid if j is None else tid[j]
                    lrow = tlp if j is None else tlp[j]
                    top = list(zip(row.tolist(), lrow.tolist()))
                if self._emit_token(slot_idx, tok, lp, top):
                    self._hand_over(slot)

    def _lora_sig(self, adapter: str | None) -> tuple[int, int]:
        if self._adapters is None:
            return (0, 0)
        return self._adapters.row_sig(adapter)

    def _plan_admission(self, req: Request, taken: set[int]) -> tuple[int, int] | None:
        """Reserve a slot + KV pages for *req*: claim resident shared-
        prefix pages (cross-slot reuse), allocate private pages covering
        the whole prompt+budget (so decode can never run out mid-flight),
        and write the slot's block-table row. Returns (slot_idx,
        reuse_tokens), or None when the pool can't back it yet."""
        from kubeai_tpu.engine.paging import pages_for

        slot_idx = next(
            i for i, s in enumerate(self._slots) if s is None and i not in taken
        )
        ids = req.prompt_ids
        ps = self.cfg.page_size
        # Clamp the decode budget to BOTH the position space and the
        # whole pool's capacity: without the pool clamp, a request whose
        # prompt+budget exceeds the pool (shrunk --kv-pages) would defer
        # forever and head-of-line-block all admission.
        usable_tokens = (self._pool.num_pages - 1) * ps
        budget = max(
            min(
                req.params.max_tokens or self.cfg.default_max_tokens,
                self.cfg.max_seq_len - len(ids) - 1,
                usable_tokens - len(ids),
            ),
            0,
        )
        n_total = pages_for(len(ids) + budget, ps)
        sig = self._lora_sig(req.adapter)
        claimed: list[int] = []
        wp = self._wpages
        w_digests: list[bytes] = []
        w_claimed: list[int] = []

        def give_back() -> None:
            self._pool.release(claimed)
            if wp is not None:
                wp.pool.release(w_claimed)

        if self.cfg.prefix_cache_min:
            claimed = self._pool.match_prefix(ids, sig)
            # Pages a hit may be cut down to, longest first: any number up
            # to what was found, or ...
            cuts = range(len(claimed), 0, -1)
            if claimed and family(self.model_config).REUSE_WHOLE_PREFILL_CALLS:
                # ... whole leading calls of the prompt's cold prefill and
                # nothing else (models/deepseek.py): the edges between the
                # cold plan's calls that fall on a page's edge.
                edges = np.cumsum([rows for rows, _ in prefill_plan(self.cfg, len(ids), self.call_cost)[:-1]])
                cuts = [int(e) // ps for e in edges[::-1] if e % ps == 0 and e <= len(claimed) * ps]
            keep = next(iter(cuts), 0)
            if wp is not None:
                # ... and only where the window pool still holds the pages
                # the first new query can see (models/smallthinker.py).
                w_digests = wp.pool.chain_digests(ids, sig)
                keep, w_claimed = wp.match(w_digests, cuts)
            self._pool.release(claimed[keep:])
            claimed = claimed[:keep]
            if claimed and len(claimed) * ps < self.cfg.prefix_cache_min:
                give_back()
                claimed, w_claimed = [], []
        if n_total - len(claimed) > self._pool.available():
            give_back()
            return None
        row = claimed + self._pool.allocate(n_total - len(claimed))
        if wp is not None:
            wp.admit(slot_idx, w_digests, len(claimed), w_claimed, n_total)
        if self.cfg.prefix_cache_min:
            # Register the cold prompt pages NOW so a same-round request
            # with the same prefix shares them (its prefill dispatches
            # after ours — see _admit_waiting's ordering).
            self._slot_fresh[slot_idx] = self._pool.register_chain(ids, sig, row)
        self._slot_budget[slot_idx] = budget
        self._slot_pages[slot_idx] = row
        self._page_table[slot_idx, : self._max_pages] = 0
        self._page_table[slot_idx, : len(row)] = row
        reuse = len(claimed) * ps
        if self.cfg.prefix_cache_min:
            # Counted at ADMISSION (not per lookup attempt): a KV-
            # deferred request re-runs the lookup every round, and an
            # attempt-counted denominator would understate the hit
            # ratio exactly when the pool is under pressure.
            self.m_prefix_lookup.inc(len(ids))
        if reuse:
            self.m_prefix_cached.inc(reuse)
        return slot_idx, reuse

    def _release_slot_pages(self, slot_idx: int, register: bool = False) -> None:
        row = self._slot_pages[slot_idx]
        if not row:
            return
        register = register and bool(self.cfg.prefix_cache_min)
        if register:
            # Content-register every full page this slot wrote (prompt
            # AND generated tokens): a follow-up turn extending this
            # conversation hits them from any slot.
            self._pool.register_chain(
                self._kv_history[slot_idx], self._kv_lora_sig[slot_idx], row
            )
        self._pool.release(row)
        self._slot_pages[slot_idx] = []
        if self._wpages is not None:
            self._wpages.free(
                slot_idx,
                self._pool.chain_digests(self._kv_history[slot_idx], self._kv_lora_sig[slot_idx])
                if register else None,
            )
        self._page_table[slot_idx, :] = 0

    def _record_slot_cost(self, slot: "_Slot", slot_idx: int) -> None:
        """Per-tenant cost proxies, recorded ONCE per request at slot
        release (before the page row is cleared): slot-seconds = wall
        time the decode slot was held, KV-page-seconds = that time x
        the pages _plan_admission reserved. These price what the
        request actually occupied on the device — a short prompt that
        sat decoding for a minute costs more than a long prompt that
        finished fast, which token counts alone cannot express.
        Un-attributed requests (no X-KubeAI-Tenant: direct submits,
        canary probes) record nothing. Scheduler-thread cheap: one
        monotonic read + one locked dict update in the accountant."""
        if not slot.req.tenant:
            return
        held = max(time.monotonic() - slot.admitted_at, 0.0)
        pages = len(self._slot_pages[slot_idx])
        tenant_accountant.record_cost(slot.req.tenant, held, held * pages)

    def _bucket(self, n: int) -> int:
        for b in self.cfg.prefill_buckets:
            if n <= b:
                return b
        return self.cfg.prefill_buckets[-1]

    def _prefill_chunk_call(self, rows: int, part: list["_Chunked"]) -> list:
        """ONE chunk call of *rows* for the next piece of each prompt of
        *part* (one, or two that share the read of the weights:
        round_calls), into each slot's block-table pages (reserved by
        _plan_admission) behind what its earlier pieces and the prefix
        cache left; a narrower piece is padded up to *rows*. A prompt
        whose last piece this was is registered, and only that piece's
        sample is kept: returns the round's `admitted` entries of those.
        Every argument is numpy (rides the dispatch)."""
        part = [c for c in part if not c.req.finished]  # an earlier call of the round failed it
        if not part:
            return []
        for c in part:
            if c.seed is None:  # its first piece
                c.seed = self._seed32(c.req.params)
                self._admitted(c.req)
                if c.req.trace is not None:
                    c.req.trace.attrs["reuse_tokens"] = c.reuse
        n = len(part)
        real_tokens = sum(c.plan[c.done][1] for c in part)
        # One segment a DEVICE call (until PR 54: one a prompt, with its
        # plan's length as `calls`); `rid` names every prompt in it.
        with self._stall.segment(
            "prefill", kind="chunk", bucket=rows, batch=n,
            tokens=real_tokens, cached=sum(c.reuse for c in part if not c.done), pad=n * rows - real_tokens,
            calls=1, rid=",".join(c.req.trace.rid for c in part if c.req.trace is not None),
        ) as seg:
            out = self._prefill_chunk_rows(rows, part)
        self.m_step.observe(seg.seconds, labels={"phase": "prefill_chunked"})
        self._stall.end_step("prefill_chunked")
        self.m_prefill_calls.inc(labels={"kind": "chunk", "slots": str(n)})
        if n * rows > real_tokens:
            self.m_pad_prefill.inc(n * rows - real_tokens)
        for c in part:
            c.seconds += seg.seconds
            if c.done == len(c.plan):
                default_recorder.record_step(
                    kind="prefill_chunked", slot=c.slot,
                    kernel=self._attn_kernel("prefill_chunked", c.plan[0][0]),
                    prompt_tokens=len(c.req.prompt_ids), reuse_tokens=c.reuse,
                    pad_tokens=c.pad,
                    dur_ms=round(c.seconds * 1000, 3),
                )
        return out

    def _prefill_chunk_rows(self, rows: int, part: list["_Chunked"]) -> list:
        n = len(part)
        tokens = np.zeros((n, rows), np.int32)
        starts = np.zeros((n,), np.int32)
        last_idx = np.zeros((n,), np.int32)
        slots_arr = np.asarray([c.slot for c in part], np.int32)
        seeds = np.zeros((n,), np.uint32)
        temps = np.ones((n,), np.float32)
        top_ps = np.ones((n,), np.float32)
        top_ks = np.zeros((n,), np.int32)
        bias_ids = np.zeros((n, self.cfg.max_logit_bias), np.int32)
        bias_vals = np.zeros((n, self.cfg.max_logit_bias), np.float32)
        lora_rows_arr = np.zeros((n,), np.int32)
        for j, c in enumerate(part):
            sp = c.req.params
            own_rows, real = c.plan[c.done]
            start = c.reuse + sum(r for _, r in c.plan[: c.done])
            tokens[j, :real] = c.req.prompt_ids[start : start + real]
            starts[j], last_idx[j], seeds[j] = start, real - 1, c.seed
            temps[j], top_ps[j], top_ks[j] = sp.temperature, sp.top_p, sp.top_k
            bias_ids[j], bias_vals[j] = self._bias_rows(sp)
            if self._adapters is not None:
                lora_rows_arr[j] = self._adapters.row_for(c.req.adapter)
            self.m_chunk_tokens.inc(real, labels={"rows": str(rows)})
            if self._wpages is not None:
                # The window table moves with the piece: pages behind its
                # first query's window go back, its own come. (The two
                # families with window pages never share a call: pair_rows.)
                self._wpages.advance(c.slot, start, start + own_rows)
                self._count_attn_pairs("prefill", np.asarray([start]), real, rows=rows)
            c.pad += rows - real
            c.done += 1
        tables = self._page_table[slots_arr]
        lora_args = {}
        if self._adapters is not None:
            lora_args = {"lora": self._adapters.bank, "lora_rows": lora_rows_arr}
        with self._lockstep(
            "prefill_chunk",
            arrays={
                "tokens": tokens, "starts": starts, "last_idx": last_idx, "tables": tables,
                "slots": slots_arr, "seeds": seeds, "temps": temps,
                "top_ps": top_ps, "top_ks": top_ks,
                "bias_ids": bias_ids, "bias_vals": bias_vals,
                # As a cold call's: followers branch on the key's presence.
                **({"lora_rows": lora_rows_arr} if self._adapters is not None else {}),
            },
        ):
            toks, lps, t_ids, t_lp, self._cache, self._adm_toks, counters = self._step(
                "prefill_chunk_jit", tokens.shape,
                self.params, tokens, starts, last_idx, tables, slots_arr, seeds,
                temps, top_ps, top_ks, bias_ids, bias_vals,
                self._adm_toks, self._cache,
                **lora_args,
            )
        self._program_counters.append(("prefill", tokens.size, counters))
        out = []
        for j, c in enumerate(part):
            if c.done == len(c.plan):
                self._register(c.slot, c.req, c.seed, int(lora_rows_arr[j]), c.reuse)
                out.append((c.slot, self._slot_epoch[c.slot], toks, j, lps, t_ids, t_lp))
        return out

    def _bias_rows(self, sp: SamplingParams) -> tuple[np.ndarray, np.ndarray]:
        """A request's logit_bias as fixed-width (ids, vals) rows
        (pad: token 0 / bias 0.0 — the scatter-add no-op). Entries past
        the static cap are dropped (first N win, like the OpenAI cap)."""
        K = self.cfg.max_logit_bias
        ids = np.zeros((K,), np.int32)
        vals = np.zeros((K,), np.float32)
        for j, (t, b) in enumerate(tuple(sp.logit_bias)[:K]):
            ids[j] = int(t)
            vals[j] = float(b)
        return ids, vals

    @staticmethod
    def _seed32(sp: SamplingParams, j: int = 0) -> np.uint32:
        """Request seed as uint32 (PRNG keys derive in-graph from it;
        seeds >= 2^32 alias — acceptable, the API seed contract is
        reproducibility, which masking preserves)."""
        seed = sp.seed if sp.seed is not None else (time.monotonic_ns() & 0xFFFFFFFF) + j
        return np.uint32(seed & 0xFFFFFFFF)

    def _register(self, slot_idx: int, req: Request, seed, lora_row: int, reuse: int):
        """Host bookkeeping for a freshly prefilled slot. Purely numpy —
        the next decode dispatch merges the new slot in-graph from the
        admission arrays (no eager device mutation; round 2 spent ~9
        eager dispatches per admission here)."""
        ids = req.prompt_ids
        sp = req.params
        # The budget was fixed at plan time — the page reservation covers
        # exactly prompt+budget, so it must not be recomputed here.
        budget = self._slot_budget[slot_idx]
        self._slot_fresh[slot_idx] = []  # prefill succeeded; content valid
        if self._wpages is not None:
            self._wpages.settle(slot_idx)
        self._w_pos[slot_idx] = len(ids)
        slot = _Slot(
            req=req,
            detok=IncrementalDetokenizer(self.tokenizer),
            prompt_len=len(ids),
            budget=budget,
        )
        slot.kv_seed = int(seed)
        if req.park_kv:
            slot.event_log = []
        self._slots[slot_idx] = slot
        self._n_active += 1
        self.m_active.set(self._n_active)
        self.m_prefill.inc(len(ids) - reuse)  # actual prefill work done

        # Prefix-cache bookkeeping: the slot now holds exactly the prompt's
        # KV (positions beyond it are stale and unreachable by the mask).
        # The first sampled token becomes the next decode step's WRITE.
        self._kv_history[slot_idx] = list(ids)
        self._kv_pending[slot_idx] = None  # set once the token id is known
        self._kv_lora_sig[slot_idx] = self._lora_sig(req.adapter)
        self._slot_epoch[slot_idx] += 1

        # Host mirrors (uploaded per dispatch) + admission merge-in for
        # the next decode chunk: position of the first generated token is
        # prompt_len; the decode step rebases lengths/last-token/PRNG key
        # in-graph (first token from the device staging vector).
        self._h_active[slot_idx] = True
        self._h_temp[slot_idx] = sp.temperature
        self._h_top_p[slot_idx] = sp.top_p
        self._h_top_k[slot_idx] = sp.top_k
        self._h_presence[slot_idx] = sp.presence_penalty
        self._h_freq[slot_idx] = sp.frequency_penalty
        self._h_want_top[slot_idx] = bool(sp.logprobs)
        self._h_gen_start[slot_idx] = len(ids)
        self._h_bias_ids[slot_idx], self._h_bias_vals[slot_idx] = self._bias_rows(sp)
        self._h_lora_rows[slot_idx] = lora_row
        self._adm_mask[slot_idx] = True
        self._adm_len[slot_idx] = len(ids)
        self._adm_seed[slot_idx] = seed

    def _prefill_group(self, items: list, bucket: int):
        """One prefill call for N same-bucket cold requests, one row a
        request. N is one of the TWO compiled row counts, 1 and the
        group cap (_plan_admissions cuts a round's prompts so): two
        sizes x len(prefill_buckets) bounds the compile count AND lets
        warmup cover every shape serving hits. Every row is a prompt
        that was sent, so the call's padding is its rows' bucket tails."""
        n = len(items)
        for _, req in items:
            self._admitted(req)
        first = items[0][1].trace
        real_tokens = int(sum(len(r.prompt_ids) for _, r in items))
        pad_tokens = n * bucket - real_tokens
        with self._stall.segment(
            "prefill", kind="group", bucket=bucket, batch=n,
            tokens=real_tokens, cached=0, pad=pad_tokens,
            calls=1, rid=first.rid if first is not None else "",
        ) as seg:
            out = self._prefill_group_call(items, bucket)
        self.m_step.observe(seg.seconds, labels={"phase": "prefill_group"})
        self._stall.end_step("prefill_group")
        if pad_tokens > 0:
            self.m_pad_prefill.inc(pad_tokens)
        self.m_prefill_rows.inc(n, labels={"kind": "real"})
        self.m_prefill_calls.inc(labels={"kind": "group", "slots": str(n)})
        default_recorder.record_step(
            kind="prefill_group", bucket=bucket, batch=n,
            kernel=self._attn_kernel("prefill_group", bucket),
            slots=[s for s, _ in items],
            prompt_tokens=real_tokens,
            pad_tokens=pad_tokens,
            dur_ms=round(seg.seconds * 1000, 3),
        )
        return out

    def _prefill_group_call(self, items: list, bucket: int) -> list:
        n = len(items)
        tokens = np.zeros((n, bucket), np.int32)
        lengths = np.zeros((n,), np.int32)
        tables = np.zeros((n, self._page_table.shape[1]), np.int32)
        slots_arr = np.zeros((n,), np.int32)
        seeds = np.zeros((n,), np.uint32)
        temps = np.ones((n,), np.float32)
        top_ps = np.ones((n,), np.float32)
        top_ks = np.zeros((n,), np.int32)
        bias_ids = np.zeros((n, self.cfg.max_logit_bias), np.int32)
        bias_vals = np.zeros((n, self.cfg.max_logit_bias), np.float32)
        lora_rows_arr = np.zeros((n,), np.int32)
        chunk_kernel_rows = 0 if self._attn_kernel("prefill_group", bucket) == "flash" else bucket
        for j, (slot_idx, req) in enumerate(items):
            ids = req.prompt_ids
            sp = req.params
            tokens[j, : len(ids)] = ids
            lengths[j] = len(ids)
            if self._wpages is not None:
                self._wpages.advance(slot_idx, 0, bucket)
                self._count_attn_pairs("prefill", np.zeros((1,), np.int64), len(ids), rows=chunk_kernel_rows)
            tables[j] = self._page_table[slot_idx]
            slots_arr[j] = slot_idx
            seeds[j] = self._seed32(sp, j)
            temps[j] = sp.temperature
            top_ps[j] = sp.top_p
            top_ks[j] = sp.top_k
            bias_ids[j], bias_vals[j] = self._bias_rows(sp)
            if self._adapters is not None:
                lora_rows_arr[j] = self._adapters.row_for(req.adapter)

        lora_args = {}
        if self._adapters is not None:
            lora_args = {"lora": self._adapters.bank, "lora_rows": lora_rows_arr}
        with self._lockstep(
            "prefill_batch",
            arrays={
                "tokens": tokens, "lengths": lengths, "tables": tables,
                "slots": slots_arr, "seeds": seeds, "temps": temps,
                "top_ps": top_ps, "top_ks": top_ks,
                "bias_ids": bias_ids, "bias_vals": bias_vals,
                # Included exactly when this rank passes lora kwargs:
                # followers branch on key presence (their own state must
                # agree — load ops are ordered in the same stream).
                **({"lora_rows": lora_rows_arr} if self._adapters is not None else {}),
            },
        ):
            toks, lps, t_ids, t_lp, self._cache, self._adm_toks, counters = self._step(
                "prefill_batch_jit", tokens.shape,
                self.params,
                tokens,
                lengths,
                tables,
                slots_arr,
                seeds,
                temps,
                top_ps,
                top_ks,
                bias_ids,
                bias_vals,
                self._adm_toks,
                self._cache,
                **lora_args,
            )
        self._program_counters.append(("prefill", tokens.size, counters))
        out = []
        for j, (slot_idx, req) in enumerate(items):
            self._register(slot_idx, req, seeds[j], int(lora_rows_arr[j]), reuse=0)
            out.append((slot_idx, self._slot_epoch[slot_idx], toks, j, lps, t_ids, t_lp))
        return out

    def _dispatch_chunk(self):
        """Dispatch one decode chunk (async) and snapshot which request
        occupied each slot at dispatch time. Host-authoritative arrays
        are passed as numpy COPIES (they ride the execute RPC; copies
        because the host mutates the originals while the transfer may
        still alias them). The admission merge arrays are consumed by
        exactly this dispatch and cleared. Returns (payload, snapshot,
        the dispatch's Segment): a chunk's turnaround counts from the
        segment's end stamp."""
        with self._stall.segment(
            "dispatch", active=self._n_active, steps=self.cfg.decode_chunk
        ) as seg:
            payload, snapshot = self._dispatch_chunk_call()
        return payload, snapshot, seg

    def _dispatch_chunk_call(self):
        if self._wpages is not None:
            K = self.cfg.decode_chunk
            live = np.flatnonzero(self._h_active)
            for i in live:
                self._wpages.advance(int(i), int(self._w_pos[i]), int(self._w_pos[i]) + K)
            self._count_attn_pairs("decode", self._w_pos[live], K)
            self._w_pos[live] += K
            released = self._wpages.released
            self.m_window_released.inc(released - self._window_released_seen)
            self._window_released_seen = released
        lora_args = {}
        if self._adapters is not None:
            lora_args = {"lora": self._adapters.bank, "lora_rows": self._h_lora_rows.copy()}
        with self._lockstep(
            "decode",
            arrays={
                "tables": self._page_table, "active": self._h_active,
                "temp": self._h_temp, "top_p": self._h_top_p,
                "top_k": self._h_top_k, "presence": self._h_presence,
                "freq": self._h_freq, "want_top": self._h_want_top,
                "gen_start": self._h_gen_start,
                "bias_ids": self._h_bias_ids, "bias_vals": self._h_bias_vals,
                "adm_mask": self._adm_mask,
                "adm_len": self._adm_len, "adm_seed": self._adm_seed,
                **({"lora_rows": self._h_lora_rows} if self._adapters is not None else {}),
            },
        ):
            (
                c_seq, lpc_seq, tid_seq, tlp_seq,
                self._cache, self._tok_hist, self._lengths, self._last_tokens, self._keys,
                counters,
            ) = self._step(
                "decode_jit", (),
                self.params,
                self._cache,
                self._page_table.copy(),
                self._tok_hist,
                self._lengths,
                self._last_tokens,
                self._keys,
                self._h_active.copy(),
                self._h_temp.copy(),
                self._h_top_p.copy(),
                self._h_top_k.copy(),
                self._h_presence.copy(),
                self._h_freq.copy(),
                self._h_want_top.copy(),
                self._h_gen_start.copy(),
                self._h_bias_ids.copy(),
                self._h_bias_vals.copy(),
                self._adm_mask.copy(),
                self._adm_len.copy(),
                self._adm_seed.copy(),
                self._adm_toks,
                **lora_args,
            )
        self._adm_mask[:] = False
        n_live = int(self._h_active.sum())
        self.m_decode_rows.inc(self.cfg.decode_chunk * n_live, labels={"state": "live"})
        self.m_decode_rows.inc(
            self.cfg.decode_chunk * (self.cfg.max_slots - n_live), labels={"state": "idle"}
        )
        snapshot = [
            (i, s, self._slot_epoch[i]) for i, s in enumerate(self._slots) if s is not None
        ]
        # The program's own predicates, on the arrays it was just given:
        # which optional parts of the epilogue this chunk ran.
        ran = epilogue_parts(
            self._h_active, self._h_temp, self._h_presence, self._h_freq,
            self._h_want_top,
        )
        for part, r in zip(EPILOGUE_PARTS, ran):
            self.m_epilogue.inc(labels={"part": part, "ran": "1" if r else "0"})
        payload = (c_seq, lpc_seq, counters)
        if ran[0]:
            # Only then do the top-N arrays hold anything (zeros
            # otherwise): a chunk that ran without them never hands
            # them to the host.
            payload += (tid_seq, tlp_seq)
        return payload, snapshot

    def _process_chunk(self, payload, snapshot, dispatched):
        # The top-N alternative arrays are in the payload only when some
        # slot active at the dispatch asked for logprobs: only then did
        # the device compute them (_dispatch_chunk_call).
        with self._stall.segment("fetch_wait", of="chunk") as fetched:  # device_get blocks
            corr, lp_c, counters, *top = jax.device_get(payload)
            t_ids, t_lp = top or (None, None)
        corr = np.asarray(corr)  # [K, B]
        self._count_program("decode", corr.size, counters)
        # The chunk's turnaround: dispatch call returned -> results on the host.
        dur = fetched.t1 - dispatched.t1
        with self._stall.segment("emit", tokens=corr.shape[0] * len(snapshot)):
            step = self._emit_chunk(
                snapshot, dur, corr, np.asarray(lp_c),
                None if t_ids is None else np.asarray(t_ids),
                None if t_lp is None else np.asarray(t_lp),
            )
        # The one set of stamps, once more: ms by cause of this loop
        # iteration's segments (its dispatch and host overlap, and the
        # fetch and emission of the chunk dispatched one iteration ago).
        ms = self._stall.end_step("decode_chunk")
        if "gc_ms" in ms:  # the cyclic collector ran inside this iteration
            step["gc_ms"] = round(ms["gc_ms"], 3)
        default_recorder.record_step(
            **step, **{f"{c}_ms": round(ms.get(c, 0.0), 3) for c in _CHUNK_SEGMENTS}
        )

    def _emit_chunk(self, snapshot, dur, corr, lp_c, t_ids, t_lp) -> dict:
        """Deliver a fetched chunk's tokens ([K, B] device-chosen tokens
        and their log-probs, [K, B, N] top-N alternatives or None): K
        tokens a live slot, in one hand-over to its request; returns its
        step record, less the segment times."""
        # Saturation accounting BEFORE emission: this chunk ran K fused
        # steps over the full [B] batch with only the snapshot's slots
        # doing useful work, and the step's wall time (dispatch ->
        # results fetched) is known the moment the device_get returns.
        # Emission below delivers terminal events — a client unblocked
        # by one must already see these observations.
        K_steps = int(corr.shape[0])
        self.m_step.observe(dur, labels={"phase": "decode_chunk"})
        self.m_slot_steps.inc(K_steps * len(snapshot), labels={"state": "active"})
        idle = K_steps * (self.cfg.max_slots - len(snapshot))
        if idle:
            self.m_slot_steps.inc(idle, labels={"state": "idle"})
        n_emitted = 0
        # Slot by slot, each slot's K tokens in order: what the chunk holds
        # for a request is handed to it ONCE (one lock, one wake of its
        # reader, one socket write), by `_hand_over` here or by `_free` if
        # the request ended in the chunk. Slots share nothing in this walk
        # but the page pool, so only the order in which two requests that
        # end in one chunk return their pages differs from a walk by step.
        toks_by_slot, lps_by_slot = corr.T.tolist(), lp_c.T.tolist()
        for i, slot_obj, epoch in snapshot:
            # A new occupant since the dispatch reset the slot's history:
            # these tokens are not its.
            own = self._slot_epoch[i] == epoch
            # Emit only while the slot still belongs to the request it
            # held at dispatch time (it may finish mid-chunk, or have
            # been freed and re-admitted since dispatch).
            live = self._slots[i] is slot_obj
            want_top = t_ids is not None and slot_obj.req.params.logprobs
            lps = lps_by_slot[i]
            # The device-chosen next tokens (the model's continuation
            # input — greedy argmax OR sampled), each with its logprob
            # under the model.
            for k, tok in enumerate(toks_by_slot[i]):
                # Record KV residency for prefix reuse: each step WROTE
                # its pending (input) token; the emitted token becomes
                # the next write. All K of them, whoever holds the slot
                # now: `_free` reads the history as of the ending token.
                if own:
                    if self._kv_pending[i] is not None:
                        self._kv_history[i].append(self._kv_pending[i])
                    self._kv_pending[i] = tok
                if not live:
                    continue
                # One PRNG key evolution per fused step whose token
                # reaches emission — a park snapshot reconstructs the
                # slot key from this count (see _Slot.kv_steps).
                slot_obj.kv_steps += 1
                top = None
                if want_top:
                    # The model's distribution at this choice point.
                    top = list(zip(t_ids[k, i].tolist(), t_lp[k, i].tolist()))
                live = self._emit_token(i, tok, lps[k], top)
                n_emitted += 1
            if live:
                self._hand_over(slot_obj)
        # Goodput gauge: emitted tokens over a sliding ~10s window
        # (shared TokenRateWindow — counter-delta semantics, so it
        # agrees with the fleet collector's derivation by construction).
        now = time.monotonic()
        self._rate_window.add(n_emitted, now)
        self.m_tok_rate.set(round(self._rate_window.rate(now), 3))
        # Flight-recorder step record: what the scheduler dispatched and
        # what came back (the /debug/engine view — batch composition,
        # token counts, kernel flavor, pages in use).
        step: dict = {
            "kind": "decode_chunk",
            "steps": K_steps,
            "slots": [i for i, _, _ in snapshot],
            "tokens": n_emitted,
            "kernel": self._attn_kernel("decode_chunk", 1),
            "pages_used": self._pool.used(),
            "pages_total": self._pool.num_pages - 1,
            "queue_depth": self.queue_depth(),
            # A chunk TURNAROUND (dispatch returned -> results fetched):
            # the segment times the caller adds (dispatch_ms,
            # host_overlap_ms, fetch_wait_ms, emit_ms) say where in it
            # the host was; dur_ms minus fetch_wait_ms is the loop work
            # the pipelining overlapped.
            "dur_ms": round(dur * 1000, 3),
        }
        return step

    def _emit_token(self, slot_idx: int, token_id: int, logprob: float | None = None, top=None) -> bool:
        """One generated token of the slot's request: apply stop logic and
        put its event in the slot's outbox, for the caller's `_hand_over`.
        Events are ("token", id, text_delta, logprob, top) — the logprob
        is the model's log p(token | prefix) (None for text-only
        flushes); *top* is the model's top-N alternatives at that choice
        point as [(token_id, logprob), ...] when the request asked for
        logprobs, else None. False: the request ended at this token, and
        `_free` has handed over what it was owed."""
        slot = self._slots[slot_idx]
        req = slot.req
        if req.cancelled.is_set():
            self._free(slot_idx, "stop", deliver=False)
            return False

        slot.generated += 1
        slot.uncounted += 1
        if slot.generated == 1:
            # True TTFT: the request's first token reached emission.
            # (Observed here, not at slot admission — admission can be
            # fast while prefill + the first-token sync are not, and
            # the SLO monitor reads this histogram.)
            self.m_ttft.observe(
                time.monotonic() - req.arrival,
                exemplar=req.trace.ctx.trace_id if req.trace is not None else None,
            )
        if req.trace is not None:
            req.trace.tok()  # one monotonic read + list append

        eos = self.tokenizer.eos_id
        if eos is not None and token_id == eos:
            self._free(slot_idx, "stop")
            return False

        # push() returns only newly-completed text (incomplete trailing
        # UTF-8 held back), keeping per-token work O(delta).
        slot.committed_text += slot.detok.push(token_id)
        text = slot.committed_text

        # Stop strings: nothing before delivered_chars can contain one
        # (delivery always holds back max(len(stop))-1 chars), so search
        # only the undelivered tail plus that overlap window.
        search_from = max(0, slot.delivered_chars - slot.holdback)
        for s in req.params.stop:
            pos = text.find(s, search_from)
            if pos != -1:
                tail = text[slot.delivered_chars : pos]
                slot.delivered_chars = pos
                ev = ("token", token_id, tail, logprob, top)
                if slot.event_log is not None:
                    slot.event_log.append(ev)
                slot.outbox.append(ev)
                self._free(slot_idx, "stop", flush=False)
                return False

        emit_upto = max(len(text) - slot.holdback, slot.delivered_chars)
        delta = text[slot.delivered_chars : emit_upto]
        slot.delivered_chars = emit_upto
        ev = ("token", token_id, delta, logprob, top)
        if slot.event_log is not None:
            slot.event_log.append(ev)
        slot.outbox.append(ev)

        if slot.generated >= slot.budget:
            self._free(slot_idx, "length")
            return False
        return True

    def _hand_over(self, slot: "_Slot") -> None:
        """Give the slot's request, in ONE operation on its queue, the
        events made for it since the last hand-over."""
        if slot.uncounted:
            # Before the reader wakes: a client that has its tokens finds
            # them on the counter.
            self.m_gen.inc(slot.uncounted)
            self._handed[0] += slot.uncounted
            slot.uncounted = 0
        if slot.outbox:
            events, slot.outbox = slot.outbox, []
            self.m_handovers.inc()
            self._handed[1] += 1
            # Stamped for the reader: from here to its bytes written is
            # the delivery's lag (engine/server.py, deliver_lag).
            slot.req.out.put_many(events, time.monotonic())

    def _free(self, slot_idx: int, reason: str, deliver: bool = True, flush: bool = True,
              outcome: str | None = None):
        slot = self._slots[slot_idx]
        self._slots[slot_idx] = None
        self._n_active -= 1
        self.m_active.set(self._n_active)
        # Host-side only: the next dispatch uploads active=False; any
        # in-flight chunk's stale writes clamp to the trash page.
        self._h_active[slot_idx] = False
        self._record_slot_cost(slot, slot_idx)
        # Park-eligible finishes (a preempted batch victim, a handoff-
        # capped "length") serialize the slot's KV state BEFORE the page
        # release: the offer rides the finish marker and the resume
        # imports instead of replaying. Any park failure falls through
        # to the plain release + deterministic replay.
        offer = None
        want = {"preempt": "preempted", "handoff": "length"}.get(slot.req.park_kv)
        if deliver and reason == want and self._kv_enabled():
            offer = self._park_slot(slot_idx, slot)
        if offer is None:
            self._release_slot_pages(slot_idx, register=True)
        if deliver:
            if flush:
                # Deliver held-back chars; detok.text() additionally decodes
                # any trailing incomplete UTF-8 to replacement chars
                # (committed_text is always a prefix of it). Those flushed
                # chars were never stop-checked — check them now.
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
                    slot.outbox.append(("token", -1, tail, None, None))
            slot.outbox.append(
                ("done", FinishInfo(reason, slot.prompt_len, slot.generated, kv=offer))
            )
        # With whatever this chunk made for the request before it ended.
        self._hand_over(slot)
        self._finish_request(
            slot.req, outcome or ("ok" if deliver else "cancelled"),
            finish_reason=reason, completion_tokens=slot.generated,
        )

    # -- KV park / restore (engine/kvstate.py) -----------------------------

    def _kv_enabled(self) -> bool:
        """Park/restore is single-host only: a gang's KV pool is sharded
        across processes (no local gather), and its lockstep contract
        admits no out-of-band device mutation. Gated globally by
        KUBEAI_KV_RESTORE."""
        return (
            kvstate.restore_enabled()
            and self._publisher is None
            and not self._multiproc
            and family(self.model_config).KV_PARK
        )

    def _ensure_kv_jits(self) -> None:
        """Lazy jits for the restore path (compiled on first park/import,
        never in the hot decode loop).

        - evolve: replays the decode step's per-step PRNG evolution
          (split -> carry [1]; see decode_fn) from a base key, so the
          park stores the key AS OF the last emitted token — the device
          keys array runs in-flight chunks ahead of the emitted stream.
        - import: scatters blob pages into the donated KV pool; page
          counts bucket to powers of two (pad rows target the trash
          page, logical 0 of each layer) to bound compilations.
        - slotset: one donated update for the restored slot's device
          row (token history, length, last token, PRNG key)."""
        if getattr(self, "_kv_evolve_jit", None) is not None:
            return

        def evolve(key_data, n):
            k = jax.random.wrap_key_data(key_data)
            k = jax.lax.fori_loop(
                0, n, lambda _, kk: jax.random.split(kk, 2)[1], k
            )
            return jax.random.key_data(k)

        def imp(kv, idx, payload):
            return kv.at[idx].set(payload)

        def slotset(tok_hist, lengths, last_tokens, keys, slot, hist_row, n, last, key_row):
            return (
                tok_hist.at[slot].set(hist_row),
                lengths.at[slot].set(n),
                last_tokens.at[slot].set(last),
                keys.at[slot].set(key_row),
            )

        imp_kw = set_kw = {}
        if self._mesh is not None:
            # Single-process tp: the donated pool and carries come back
            # laid out as they went in (see build_step_functions).
            from jax.sharding import NamedSharding, PartitionSpec

            from kubeai_tpu.parallel.sharding import named, paged_cache_specs

            repl = NamedSharding(self._mesh, PartitionSpec())
            imp_kw = {"out_shardings": named(paged_cache_specs(), self._mesh)["kv"]}
            set_kw = {"out_shardings": (repl,) * 4}
        self._kv_evolve_jit = jax.jit(evolve)
        self._kv_import_jit = jax.jit(imp, donate_argnums=(0,), **imp_kw)
        self._kv_slotset_jit = jax.jit(
            slotset, donate_argnums=(0, 1, 2, 3), **set_kw
        )

    def _park_slot(self, slot_idx: int, slot: "_Slot") -> dict | None:
        """Serialize a finishing slot's KV state into the host park store
        (wire format: engine/kvstate.py) and pin its history pages
        device-side for a same-replica fast restore. Returns the offer
        dict the finish marker carries, or None — any failure or
        inconsistency leaves the pages for the caller's normal release,
        and the resume takes deterministic replay."""
        from kubeai_tpu.engine.paging import pages_for

        history = list(self._kv_history[slot_idx])
        pending = self._kv_pending[slot_idx]
        row = self._slot_pages[slot_idx]
        if (
            pending is None
            or not row
            or len(history) - slot.prompt_len != slot.generated - 1
            or slot.event_log is None
            or len(slot.event_log) != slot.generated
        ):
            # Mid-flight state not yet settled (e.g. preempted before the
            # first-token sync): replay is authoritative.
            return None
        n_hist = pages_for(len(history), self.cfg.page_size)
        if n_hist > len(row):
            return None
        hist_pages = row[:n_hist]
        try:
            self._ensure_kv_jits()
            if slot.kv_key0 is None:
                base = jax.random.key_data(
                    jax.random.fold_in(
                        jax.random.key(np.uint32(slot.kv_seed)), 1
                    )
                )
            else:
                base = jnp.asarray(slot.kv_key0)
            key_row = np.asarray(
                self._kv_evolve_jit(base, np.int32(slot.kv_steps))
            )
            P = self._pool.num_pages
            L = self.model_config.num_layers
            idx = (
                np.arange(L, dtype=np.int32)[None, :] * P
                + np.asarray(hist_pages, np.int32)[:, None]
            ).reshape(-1)
            payload = np.asarray(
                jax.device_get(
                    jnp.take(self._cache["kv"], jnp.asarray(idx), axis=0)
                )
            ).reshape(n_hist, L, *self._cache["kv"].shape[1:])
            blob = kvstate.encode_state(
                model_fp=self._kv_fp,
                request_fp=kvstate.request_fingerprint(
                    slot.req.prompt_ids, slot.req.params, slot.req.adapter
                ),
                history=history,
                pending=int(pending),
                prompt_len=slot.prompt_len,
                generated=slot.generated,
                committed_text=slot.committed_text,
                delivered_chars=slot.delivered_chars,
                key_data=key_row,
                events=slot.event_log,
                adapter=slot.req.adapter,
                payload=payload,
            )
            # Failpoint: error aborts the park (resume replays); corrupt
            # stores a mangled blob the import's checksums must reject.
            blob = fault("engine.kv_export", payload=blob)
        except FaultError:
            kvstate.M_KV_EXPORT.inc(labels={"outcome": "error"})
            return None
        except Exception:
            log.exception("KV export failed; resume will replay")
            kvstate.M_KV_EXPORT.inc(labels={"outcome": "error"})
            return None
        if self.cfg.prefix_cache_min:
            # Same content registration the plain release does: follow-up
            # turns still prefix-hit this request's full pages.
            self._pool.register_chain(
                history, self._kv_lora_sig[slot_idx], row
            )
        key = uuid.uuid4().hex
        if all(not self._pool.is_parked(p) for p in hist_pages):
            # Pin the history pages (the slot's reference transfers to
            # the park entry); release only the unwritten remainder.
            self._pool.park(key, hist_pages)
            self._pool.release(row[n_hist:])
        else:
            # Some page already pinned under another key (shared-prefix
            # claim of parked content): blob-only park, restore uploads.
            self._pool.release(row)
        self._slot_pages[slot_idx] = []
        self._page_table[slot_idx, :] = 0
        for evicted in self.kv_park.put(key, blob, len(history)):
            self._pool.drop_park(evicted)
        kvstate.M_KV_EXPORT.inc(labels={"outcome": "ok"})
        log.info(
            "parked KV for slot %d: %d tokens, %d pages, %d bytes (%s)",
            slot_idx, len(history), n_hist, len(blob), slot.req.park_kv,
            extra=trace_extra(slot.req.trace),
        )
        return {
            "key": key,
            "source": self.kv_advertise,
            "tokens": len(history),
            "bytes": len(blob),
        }

    def _admit_restored(self, req: "Request", taken: set[int]) -> int | str | None:
        """Admit a resume that carries validated KV state (Request.
        restore): place pages (unpark fast path, else payload upload),
        rebuild the slot's host mirrors + device row, and re-emit the
        logged pre-park events so the server's resume suppression sees
        exactly the replay-path stream. Returns the slot index, "defer"
        when the pool cannot back prompt+budget yet, or None on any
        failure — the caller then falls through to replay admission
        (clearing req.restore), which keeps state-transfer failures
        invisible to the client and the proxy's breaker."""
        with self._stall.segment("kv_transfer", tokens=len(req.restore.history)) as seg:
            res = self._import_restored(req, taken, seg)
        if isinstance(res, int):
            self._stall.end_step("kv_restore")
        return res

    def _import_restored(self, req: "Request", taken: set[int], seg) -> int | str | None:
        from kubeai_tpu.engine.paging import pages_for

        state = req.restore
        ps = self.cfg.page_size
        ids = req.prompt_ids
        row: list[int] | None = None
        outcome = "error"
        try:
            # Failpoint: chaos tests fail the scheduler-side import even
            # after the serving thread validated the blob.
            fault("engine.kv_import")
            n_hist = pages_for(len(state.history), ps)
            kv_shape = self._cache["kv"].shape
            L = self.model_config.num_layers
            if (
                state.prompt_len != len(ids)
                or state.history[: len(ids)] != list(ids)
                or len(state.history) - len(ids) != state.generated - 1
                or len(state.events) != state.generated
            ):
                raise kvstate.KVFormatError("restore state does not match request")
            if (
                state.payload.shape != (n_hist, L, *kv_shape[1:])
                or state.payload.dtype != self._cache["kv"].dtype
                or tuple(np.asarray(state.key_data).shape)
                != tuple(self._keys.shape[1:])
            ):
                raise kvstate.KVFormatError("restore payload layout mismatch")
            usable_tokens = (self._pool.num_pages - 1) * ps
            budget = max(
                min(
                    req.params.max_tokens or self.cfg.default_max_tokens,
                    self.cfg.max_seq_len - len(ids) - 1,
                    usable_tokens - len(ids),
                ),
                0,
            )
            if state.generated >= budget:
                raise ValueError("parked request has no budget left to resume")
            # Rebuild the detokenizer by replaying the emitted ids; a
            # mismatch against the parked cursor means the stream could
            # not continue byte-identically — reject BEFORE any device
            # mutation.
            detok = IncrementalDetokenizer(self.tokenizer)
            committed = ""
            for t in state.history[len(ids):] + [state.pending]:
                committed += detok.push(t)
            if committed != state.committed_text or not (
                0 <= state.delivered_chars <= len(committed)
            ):
                raise kvstate.KVFormatError("detokenizer replay mismatch")

            n_total = pages_for(len(ids) + budget, ps)
            pinned = self._pool.unpark(req.restore_key) if req.restore_key else None
            if pinned is not None and len(pinned) != n_hist:
                self._pool.release(pinned)
                pinned = None
            if n_total - (len(pinned) if pinned is not None else 0) > self._pool.available():
                if pinned is not None:
                    self._pool.park(req.restore_key, pinned)  # put back untouched
                return "defer"
            slot_idx = next(
                i for i, s in enumerate(self._slots) if s is None and i not in taken
            )
            self._ensure_kv_jits()
            if pinned is not None:
                row = pinned + self._pool.allocate(n_total - len(pinned))
            else:
                row = self._pool.allocate(n_total)
                # Upload the blob payload into the fresh pages: pad the
                # page count to a power of two (bounded compile count);
                # pad rows scatter into each layer's trash page 0.
                P = self._pool.num_pages
                n_pad = 1 << max(0, (n_hist - 1).bit_length())
                idx = (
                    np.arange(L, dtype=np.int32)[None, :] * P
                    + np.asarray(row[:n_hist] + [0] * (n_pad - n_hist), np.int32)[:, None]
                ).reshape(-1)
                payload = state.payload
                if n_pad > n_hist:
                    payload = np.concatenate(
                        [payload, np.zeros((n_pad - n_hist, *payload.shape[1:]), payload.dtype)]
                    )
                payload = np.ascontiguousarray(
                    payload.reshape(n_pad * L, *payload.shape[2:])
                )
                cache = dict(self._cache)
                cache["kv"] = self._kv_import_jit(self._cache["kv"], idx, payload)
                self._cache = cache
            hist_row = np.zeros((self._tok_hist.shape[1],), np.int32)
            hist_row[: len(state.history)] = state.history
            (
                self._tok_hist, self._lengths, self._last_tokens, self._keys,
            ) = self._kv_slotset_jit(
                self._tok_hist, self._lengths, self._last_tokens, self._keys,
                np.int32(slot_idx), hist_row,
                np.int32(len(state.history)), np.int32(state.pending),
                np.asarray(state.key_data, np.uint32),
            )
        except kvstate.KVFormatError as e:
            outcome = "corrupt"
            log.warning("KV restore rejected (%s); falling back to replay", e)
        except FaultError:
            log.warning("KV restore failed (injected); falling back to replay")
        except Exception as e:
            log.warning("KV restore failed (%s); falling back to replay", e)
        else:
            sp = req.params
            sig = self._lora_sig(req.adapter)
            lora_row = (
                self._adapters.row_for(req.adapter) if self._adapters is not None else 0
            )
            slot = _Slot(
                req=req, detok=detok, prompt_len=len(ids), budget=budget,
            )
            slot.committed_text = committed
            slot.delivered_chars = int(state.delivered_chars)
            slot.generated = int(state.generated)
            slot.kv_key0 = np.asarray(state.key_data, np.uint32)
            if req.park_kv:
                slot.event_log = list(state.events)
            self._slots[slot_idx] = slot
            self._n_active += 1
            self.m_active.set(self._n_active)
            self._slot_fresh[slot_idx] = []
            self._slot_budget[slot_idx] = budget
            self._slot_pages[slot_idx] = row
            self._page_table[slot_idx, :] = 0
            self._page_table[slot_idx, : len(row)] = row
            self._kv_history[slot_idx] = list(state.history)
            self._kv_pending[slot_idx] = int(state.pending)
            self._kv_lora_sig[slot_idx] = sig
            self._slot_epoch[slot_idx] += 1
            self._h_active[slot_idx] = True
            self._h_temp[slot_idx] = sp.temperature
            self._h_top_p[slot_idx] = sp.top_p
            self._h_top_k[slot_idx] = sp.top_k
            self._h_presence[slot_idx] = sp.presence_penalty
            self._h_freq[slot_idx] = sp.frequency_penalty
            self._h_want_top[slot_idx] = bool(sp.logprobs)
            self._h_gen_start[slot_idx] = len(ids)
            self._h_bias_ids[slot_idx], self._h_bias_vals[slot_idx] = self._bias_rows(sp)
            self._h_lora_rows[slot_idx] = lora_row
            self._adm_mask[slot_idx] = False
            if self.cfg.prefix_cache_min:
                self._pool.register_chain(state.history, sig, row)
            # Re-emit the pre-park events verbatim: the serving thread's
            # resume suppression consumes them exactly as it would the
            # replay path's regenerated stream.
            for ev in state.events:
                req.out.put(ev)
            self.kv_park.drop(req.restore_key)
            kvstate.M_KV_IMPORT.inc(labels={"outcome": "ok"})
            kvstate.M_KV_RESTORE_SECONDS.observe(
                time.monotonic() - seg.t0, labels={"phase": "import"}
            )
            record_admitted(
                req.priority, max(time.monotonic() - req.arrival, 0.0)
            )
            if req.trace is not None:
                req.trace.mark("kv_restore")
                req.trace.attrs["restored_tokens"] = len(state.history)
            log.info(
                "restored KV into slot %d: %d tokens (%s)",
                slot_idx, len(state.history),
                "unparked" if pinned is not None else "uploaded",
                extra=trace_extra(req.trace),
            )
            req.restore = None
            return slot_idx
        # Shared failure epilogue (the except paths fall through here).
        kvstate.M_KV_IMPORT.inc(labels={"outcome": outcome})
        if row:
            self._pool.release(row)
        req.restore = None
        kbuf = self._cache["kv"]
        if getattr(kbuf, "is_deleted", lambda: False)():
            # The donated pool was consumed by a failed import jit: no
            # per-request containment possible — escalate to _loop's
            # device-state recovery.
            raise RuntimeError("KV import consumed the donated cache")
        return None

    def _sweep_kv_park(self) -> None:
        """Reconcile pinned pages against the blob store (scheduler
        thread — the pool is scheduler-owned, the store expires on its
        own TTL/byte caps): any park entry whose blob is gone releases
        its pages. Throttled; the store is the source of truth."""
        now = time.monotonic()
        if now - self._kv_park_sweep_at < 5.0:
            return
        self._kv_park_sweep_at = now
        self.kv_park.sweep()
        for key in self._pool.parked_keys():
            if self.kv_park.get(key) is None:
                self._pool.drop_park(key)


@dataclass
class StepFunctions:
    """The engine's jitted step functions, built OUTSIDE the Engine so
    the one list of its step programs (engine/step_programs.py) can
    build them ONCE from config alone: the warm thread compiles them
    against abstract args (or loads their executables from the bundle)
    and the Engine runs those executables; a call the table does not
    hold goes through these functions and compiles lazily."""

    prefill_batch_jit: Any
    prefill_chunk_jit: Any
    decode_jit: Any


def build_step_functions(
    model_config: ModelConfig,
    engine_config: EngineConfig,
    n_valid_vocab: int | None = None,
    mesh=None,
) -> StepFunctions:
    """Build the jitted prefill/decode step functions for a config pair.

    Extracted from Engine so the SAME programs can be brought up ahead
    of time (loader warm, parked replicas, the start's warm thread):
    engine/step_programs.py::StepPrograms calls this once a deployment
    and both the warm thread and the Engine use what it returns.
    *n_valid_vocab* is the tokenizer's vocab (logits beyond it are
    masked); defaults to the model vocab (no padding mask)."""
    mc = model_config
    cfg = engine_config
    model = family(mc)  # the one place the step programs name a model module
    n_valid = mc.vocab_size if n_valid_vocab is None else min(n_valid_vocab, mc.vocab_size)

    def split_counters(cache):
        """A family's model call may return, beside the pool (`kv`),
        scalar program counters in the cache dict (models/deepseek.py:
        `moe_hits`). They leave the program as its LAST output and never
        enter one: ({"kv": pool}, {name: scalar}); a second pool
        (models/smallthinker.py: `kv_window`) stays with the first, and
        so does state kept by slot (models/nemotron_h.py: `ssm`, `conv`).
        A family without counters gives {}: no output at all."""
        pools = {k: v for k, v in cache.items() if k.startswith("kv") or k in model.SLOT_STATE}
        return pools, {k: v for k, v in cache.items() if k not in pools}

    def mask_pad(logits):
        if n_valid < mc.vocab_size:
            return logits.at[..., n_valid:].set(-jnp.inf)
        return logits

    mtk = cfg.max_top_k
    topn = max(1, cfg.top_logprobs_k)
    # A family with state by slot is told the slot of every prefill row
    # (a decode step's rows ARE the slots, in `live`'s order).
    slot_state = bool(model.SLOT_STATE)

    def first_tokens(logits, slots, keys, temp, top_p, top_k, bias_ids, bias_vals, adm_toks):
        """A prefill call's epilogue: every row's sampled first token,
        scattered into the device staging vector adm_toks[slots] so the
        NEXT decode dispatch can merge it in-graph without a host
        round-trip (every row is its own request's: the slots are
        distinct)."""
        with jax.named_scope("sampling"):
            masked = mask_pad(logits[:, -1])
            # Bias steers choice; the reported logprob stays the model's
            # raw log p (same contract as decode).
            toks = sample(
                apply_logit_bias(masked, bias_ids, bias_vals),
                keys, temp, top_p, top_k, max_top_k=mtk,
            )
        with jax.named_scope("logprobs"):
            logp = jax.nn.log_softmax(masked, axis=-1)
            lps = jnp.take_along_axis(logp, toks[:, None], axis=1)[:, 0]
            t_lp, t_ids = jax.lax.top_k(logp, topn)
        return toks, lps, t_ids.astype(jnp.int32), t_lp, adm_toks.at[slots].set(toks)

    def prefill_batch_fn(params, tokens, lengths, tables, slots, seeds, temp, top_p, top_k, bias_ids, bias_vals, adm_toks, cache, lora=None, lora_rows=None):
        """Cold prefill for N requests in ONE call (N is one of two
        compiled row counts — 1, and the group cap for a full group):
        tokens [N, S] land in the pages of *tables* [N, max_pages]. PRNG
        keys derive from uint32 *seeds* in-graph, so every argument
        arrives as plain numpy riding the dispatch."""
        keys = jax.vmap(jax.random.key)(seeds)
        logits, cache = model.prefill_paged_cold(
            params, mc, tokens, cache, tables, lengths,
            lora=lora, lora_rows=lora_rows, tp_mesh=mesh,
            **({"slots": slots} if slot_state else {}),
        )
        cache, counters = split_counters(cache)
        *out, adm_toks = first_tokens(logits, slots, keys, temp, top_p, top_k, bias_ids, bias_vals, adm_toks)
        return *out, cache, adm_toks, counters

    def prefill_chunk_fn(params, tokens, starts, last_idx, tables, slots, seeds, temp, top_p, top_k, bias_ids, bias_vals, adm_toks, cache, lora=None, lora_rows=None):
        """One chunk call: a piece [rows] of a long or prefix-resuming
        prompt for each of its N slots (one, or two that share the read
        of the weights: round_calls), row j behind *starts[j]* tokens of
        its own slot (0: a cold prompt as a row). prefill_batch_fn's
        arguments plus *starts*; every row samples, and only the sample
        of a prompt's last piece is kept."""
        keys = jax.vmap(jax.random.key)(seeds)
        logits, cache = model.prefill_paged(
            params, mc, tokens, cache, tables, starts, last_idx,
            lora=lora, lora_rows=lora_rows, tp_mesh=mesh,
            **({"slots": slots} if slot_state else {}),
        )
        cache, counters = split_counters(cache)
        *out, adm_toks = first_tokens(logits, slots, keys, temp, top_p, top_k, bias_ids, bias_vals, adm_toks)
        return *out, cache, adm_toks, counters

    K = cfg.decode_chunk

    def decode_fn(params, cache, tables, hist, lengths, last_tokens, keys, active, temp, top_p, top_k, presence, frequency, want_top, gen_start, bias_ids, bias_vals, adm_mask, adm_len, adm_seed, adm_toks, lora=None, lora_rows=None):
        """K fused decode steps, one token a slot a step. Returns
        (corr [K, B], lp_corr [K, B], t_ids [K, B, N], t_lp [K, B, N])
        then the five carries. corr is THE device-chosen next token
        (greedy: the model's argmax; sampled: the sampled token — never
        substitute argmax, the device decodes from corr so emission
        must match it).

        Slots admitted since the last dispatch are REBASED in-graph
        (adm_mask/adm_len/adm_seed numpy from the host; adm_toks the
        device staging vector the prefill scattered its sample into)
        — admission therefore requires zero eager device mutation
        and the dispatch never waits on a first-token host sync.

        The optional parts of the epilogue (top-N alternatives, sampling
        candidates, penalties) each run under a `lax.cond` on whether an
        active slot of THIS batch asked for them (epilogue_parts): one
        program, both branches, so what nobody reads is not computed
        and what somebody reads is bit for bit what it always was. A
        part that did not run returns what it gives when nobody asks:
        the greedy pick, the unpenalized logits; the top-N arrays are
        zeros then, which the host never fetches."""
        B = lengths.shape[0]
        # Scalars of the batch's request parameters, none of them
        # carried: the same for all K steps, computed once out here.
        run_top, run_cand, run_pen = epilogue_parts(
            active, temp, presence, frequency, want_top
        )
        adm_keys = jax.vmap(
            lambda s: jax.random.fold_in(jax.random.key(s), 1)
        )(adm_seed)
        # *keys* arrives as raw uint32 key data (see mk_device_arrays)
        # and is wrapped here; returned as raw data again below.
        keys = jax.random.wrap_key_data(
            jnp.where(
                adm_mask[:, None],
                jax.random.key_data(adm_keys),
                keys,
            )
        )
        lengths = jnp.where(adm_mask, adm_len, lengths)
        last_tokens = jnp.where(adm_mask, adm_toks, last_tokens)
        # The model's layers run on the live slots' rows first and the
        # paged kernel walks only those. The order is made once a
        # dispatch (`active` is the same for all K steps), and what
        # does not change inside a chunk is taken in it out here; the
        # step's tokens and lengths once a step, below. The model hands
        # its hidden state back in slot order, so everything from the
        # logits on is untouched.
        live = LiveRows.first(active)
        tables_live, lora_rows_live = live.take(tables, lora_rows)

        def body(carry, _):
            cache, hist, lengths, last, keys = carry
            # Record the input this step WRITES into KV at position
            # `lengths` BEFORE the penalty window is read, so the
            # previously emitted token (this step's input) is already
            # in the history when penalties count it (ADVICE r5:
            # computing penalties first lagged them one token — the
            # most recent token's first immediate repeat went
            # unpenalized, off OpenAI/vLLM semantics).
            rows = jnp.arange(B)
            hist = hist.at[rows, lengths].set(
                jnp.where(active, last, hist[rows, lengths])
            )
            last_live, lengths_live = live.take(last, lengths)
            logits, cache = model.decode_step_paged(
                params, mc, last_live[:, None], cache, tables_live, lengths_live,
                lora=lora, lora_rows=lora_rows_live, tp_mesh=mesh, live=live,
            )
            cache, counters = split_counters(cache)
            with jax.named_scope("sampling"):
                logits = mask_pad(logits[:, 0])  # [B, V]

                def penalized():
                    # OpenAI presence/frequency penalties over the
                    # GENERATED window of the device token history —
                    # [gen_start, lengths] INCLUSIVE: position `lengths`
                    # holds this step's input (the token emitted last
                    # step, just scattered above), so the full output so
                    # far counts. The penalized view steers CHOICE only
                    # (argmax / sampling); reported logprobs stay the
                    # model's raw log p(token | prefix), matching how
                    # temperature / top_p shape choice without reshaping
                    # logprobs.
                    w_idx = jnp.arange(hist.shape[1], dtype=jnp.int32)[None, :]
                    pen_valid = (w_idx >= gen_start[:, None]) & (
                        w_idx <= lengths[:, None]
                    )
                    return apply_penalties(
                        logits, hist, pen_valid, presence, frequency
                    )

                # No active slot set a penalty: subtracting zeros gives
                # the logits back, so the scatters are left out.
                pen = jax.lax.cond(run_pen, penalized, lambda: logits)
                pen = apply_logit_bias(pen, bias_ids, bias_vals)
            with jax.named_scope("logprobs"):
                # Chosen-token logprob = raw logit - logsumexp: avoids
                # materializing a normalized [B, V] tensor in the
                # hottest loop just to gather one entry a slot.
                lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [B]
            with jax.named_scope("sampling"):
                yhat_pen = jnp.argmax(pen, axis=-1).astype(jnp.int32)
                # The keys split every step, whatever the batch holds:
                # a seeded sampled request draws the same stream whether
                # or not its neighbours open the gate around it.
                step_keys = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                # Every active slot greedy: `sample` would return the
                # argmax of pen for each, which is yhat_pen.
                sampled = jax.lax.cond(
                    run_cand,
                    lambda: sample(
                        pen, step_keys[:, 0], temp, top_p, top_k, max_top_k=mtk
                    ),
                    lambda: yhat_pen,
                )
                # Greedy picks from the penalized view (identical to raw
                # when penalties are zero).
                corr = jnp.where(temp <= 0.0, yhat_pen, sampled)
                corr = jnp.where(active, corr, last)
            with jax.named_scope("logprobs"):
                lp_corr = (
                    jnp.take_along_axis(logits, corr[:, None], axis=1)[:, 0] - lse
                )

                def top_alternatives():
                    # Top-N alternatives (raw model dist, pre-penalty/
                    # bias — same contract as the chosen logprob). The
                    # operand stays 2-D: on the chip a [B, V] top_k
                    # lowers to the TopK custom call, one with a third
                    # axis to a sort of the whole vocabulary.
                    t_raw, t_ids = jax.lax.top_k(logits, topn)
                    return t_ids.astype(jnp.int32), t_raw - lse[:, None]

                t_ids, t_lp = jax.lax.cond(
                    run_top,
                    top_alternatives,
                    lambda: (
                        jnp.zeros((B, topn), jnp.int32),
                        jnp.zeros((B, topn), jnp.float32),
                    ),
                )
            lengths = jnp.where(active, lengths + 1, lengths)
            return (cache, hist, lengths, corr, step_keys[:, 1]), (
                corr, lp_corr, t_ids, t_lp, counters,
            )

        (cache, hist, lengths, last, keys), (
            c_seq, lpc_seq, tid_seq, tlp_seq, counters_seq,
        ) = jax.lax.scan(
            body, (cache, hist, lengths, last_tokens, keys), None, length=K
        )
        return (
            c_seq, lpc_seq, tid_seq, tlp_seq,
            cache, hist, lengths, last, jax.random.key_data(keys),
            {k: v.sum(0) for k, v in counters_seq.items()},  # the chunk's K steps
        )

    # adm_toks (prefill arg 11 / chunk arg 12) and the cache are
    # donated through prefill calls; decode reads adm_toks without
    # donating it (it survives until the next prefill overwrites it).
    # A mesh pins out_shardings explicitly: the KV pool keeps its tp
    # sharding (left to the compiler, the donated pool could come back
    # laid out differently from how it went in), and everything the
    # host reads back is fully replicated (device_get on a
    # cross-process-sharded array has no local copy to fetch).
    shard_kw = {}
    chunk_kw = {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from kubeai_tpu.parallel.sharding import paged_cache_specs

        repl = NamedSharding(mesh, PartitionSpec())
        cache_sh = {
            k: NamedSharding(mesh, s)
            for k, s in paged_cache_specs().items()
        }
        shard_kw = {
            "out_shardings": (repl, repl, repl, repl, cache_sh, repl, repl, repl, repl, repl)
        }
        chunk_kw = {"out_shardings": (repl, repl, repl, repl, cache_sh, repl, repl)}
    # tables + per-slot request state (active/temp/top_p/top_k and
    # the adm_* merge arrays) are host-authoritative numpy uploaded
    # per dispatch — not donated. cache/hist/lengths/last/keys are
    # the device carries.
    return StepFunctions(
        prefill_batch_jit=jax.jit(
            prefill_batch_fn, donate_argnums=(11, 12), **chunk_kw
        ),
        prefill_chunk_jit=jax.jit(
            prefill_chunk_fn, donate_argnums=(12, 13), **chunk_kw
        ),
        # Jitted through a partial ON PURPOSE: a partial has no
        # __name__, so the program is `jit__unknown` on the device trace,
        # the name perfbench/layer_metrics/ selects the decode program
        # by. jax.jit(decode_fn) would rename it and null every decode
        # per-layer metric; the rename moves with those files (ROADMAP
        # B-III).
        decode_jit=jax.jit(
            partial(decode_fn), donate_argnums=(1, 3, 4, 5, 6), **shard_kw
        ),
    )


def build_test_engine(
    engine_config: EngineConfig | None = None, seed: int = 0, model_config: ModelConfig | None = None
) -> Engine:
    """A tiny randomly-initialized byte-vocab engine for tests/dev — the
    in-process analogue of the reference's mock engine seam."""
    from kubeai_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    mc = model_config or ModelConfig(
        vocab_size=272,  # 259 used; padded up for friendly tiling
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        dtype="float32",
        max_position=2048,
    )
    params = family(mc).init_params(mc, jax.random.key(seed))
    ec = engine_config or EngineConfig(max_slots=4, max_seq_len=256, prefill_buckets=(16, 32, 64, 128))
    return Engine(mc, params, tok, ec)
