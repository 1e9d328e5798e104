"""KV-page serialization: versioned, checksummed export/import of a
request's paged-KV state, plus the host-RAM park store behind
preempt-park-restore, handoff page transfer, and restore-aware
mid-stream recovery (docs/robustness.md "State restore").

Wire format (borrowing the length-prefixed JSON-header + raw-array
framing proven by the gang's device-state channel, engine/gang.py):

    magic "KVPG" | u8 version | u32 header_len | header JSON | payload

The header carries the model/config **fingerprint** (KV layout fields:
layers, kv heads, head dim, page size, pool dtype — anything that
changes the meaning of a page's bytes), the **request fingerprint**
(prompt + sampling params + adapter: a blob may only resume the exact
request that produced it), the page **payload shape/dtype**, a per-page
CRC32 list, and the host-side resume state (token history, pending
token, evolved PRNG key data, emitted-event log, detokenizer cursors).
The payload is the C-order bytes of a [n_pages, L, page, 2*Kv, h]
array gathered from the engine's flat KV pool.

decode_state() rejects on ANY mismatch — magic, version, either
fingerprint, truncated payload, or a failed page checksum — with
KVFormatError. Callers never import silently-wrong state: every
rejection degrades to the deterministic-replay path, which remains the
correctness contract (the client stream is byte-identical either way).

This module is numpy + stdlib only (no jax): the proxy imports the
offer helpers, and blob validation runs on HTTP handler threads.
"""

from __future__ import annotations

import dataclasses
import hashlib
import http.client
import json
import os
import struct
import threading
import time
import zlib
from collections import OrderedDict

import numpy as np

from kubeai_tpu.metrics import default_registry

MAGIC = b"KVPG"
VERSION = 1

# -- metrics (cataloged in docs/observability.md) ---------------------------

M_KV_EXPORT = default_registry.counter(
    "kubeai_kv_export_total",
    "KV page-state exports by outcome (ok|error): serialized park "
    "snapshots taken at preemption or handoff-capped finishes.",
)
M_KV_IMPORT = default_registry.counter(
    "kubeai_kv_import_total",
    "KV page-state import attempts by outcome (ok|corrupt|error|miss): "
    "corrupt = wire-format/checksum/fingerprint rejection, error = "
    "injected or device-side failure, miss = park entry gone before "
    "the resume arrived. Every non-ok outcome degrades to replay.",
)
M_KV_TRANSFER = default_registry.counter(
    "kubeai_kv_transfer_bytes_total",
    "Serialized KV bytes moved over the direct engine-to-engine "
    "transfer socket, by direction (tx = served from the park store, "
    "rx = fetched for a restore).",
)
M_KV_RESTORE_SECONDS = default_registry.histogram(
    "kubeai_kv_restore_seconds",
    "Restore-path latency by phase (acquire = blob fetch + validation "
    "on the serving thread; import = device upload + slot rebuild on "
    "the scheduler thread).",
)


# -- knobs (docs/robustness.md knob table) ----------------------------------


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def restore_enabled() -> bool:
    """KUBEAI_KV_RESTORE=0 turns the whole subsystem off (no parking,
    no offers, no imports) — every resume takes deterministic replay."""
    return os.environ.get("KUBEAI_KV_RESTORE", "1") != "0"


def park_ttl() -> float:
    return max(_env_float("KUBEAI_KV_PARK_TTL", 120.0), 0.0)


def park_cap_bytes() -> int:
    return max(int(_env_float("KUBEAI_KV_PARK_BYTES", float(256 << 20))), 0)


def breakeven_tokens() -> int:
    """Prefix length below which a remote restore is not attempted:
    fetching + importing a short prefix costs more than re-prefilling
    it (docs/robustness.md derives the default; measured per-deployment
    via kubeai_kv_restore_seconds vs prefill throughput). Same-replica
    restores skip the fetch and ignore this floor."""
    return max(int(_env_float("KUBEAI_KV_BREAKEVEN_TOKENS", 256.0)), 0)


def fetch_timeout() -> float:
    return max(_env_float("KUBEAI_KV_FETCH_TIMEOUT", 5.0), 0.1)


def fetch_retries() -> int:
    return max(int(_env_float("KUBEAI_KV_FETCH_RETRIES", 2.0)), 0)


# -- fingerprints -----------------------------------------------------------


class KVFormatError(ValueError):
    """A blob failed wire-format validation (magic/version/fingerprint/
    checksum/shape). Never imported — the caller falls back to replay."""


def model_fingerprint(model_config, page_size: int) -> str:
    """Digest over every field that changes what a page's bytes MEAN.
    Two replicas serving the same checkpoint at the same page size agree;
    anything else (different model, kv dtype, head layout, page size)
    must refuse the import. Pool size (num_pages) is deliberately
    excluded — pages are logical, the blob is layout-independent."""
    mc = model_config
    fields = (
        int(mc.vocab_size),
        int(mc.hidden_size),
        int(mc.num_layers),
        int(mc.num_kv_heads),
        int(mc.head_dim_),
        str(mc.dtype),
        str(getattr(mc, "kv_cache_dtype", "") or ""),
        int(page_size),
        VERSION,
    )
    if getattr(mc, "kv_lora_rank", 0):
        # A latent page ([page, 1, W]): what its bytes mean is decided by
        # the latent's split, not by a head layout. (The blob's header
        # carries the payload's own shape, trailing page shape included,
        # and an import whose layout differs is refused by name.)
        fields += (str(mc.model_type), int(mc.kv_lora_rank), int(mc.qk_rope_head_dim))
    return hashlib.sha256(repr(fields).encode()).hexdigest()[:32]


def request_fingerprint(prompt_ids, params, adapter: str | None) -> str:
    """A blob resumes exactly one request: same prompt, same sampling
    params, same adapter. Keyed lookup already makes collisions
    improbable; this makes a mixed-up key a rejection, not corruption.

    max_tokens is deliberately EXCLUDED: it bounds where the stream
    ends, never what any step generates — and it genuinely differs
    across a handoff (the prefill leg runs with the budget-capped
    value, the decode resume with the client's original)."""
    try:
        params = dataclasses.replace(params, max_tokens=0)
    except TypeError:
        pass
    h = hashlib.sha256()
    h.update(",".join(map(str, prompt_ids)).encode())
    h.update(b"|")
    h.update(repr(params).encode())
    h.update(b"|")
    h.update((adapter or "").encode())
    return h.hexdigest()[:32]


# -- encode / decode --------------------------------------------------------


@dataclasses.dataclass
class RestoreState:
    """A validated blob, ready for the scheduler's restore admission."""

    history: list[int]  # prompt + generated-minus-one token ids (KV written)
    pending: int  # the last emitted token (its KV is the next decode's write)
    prompt_len: int
    generated: int  # emitted events at park time (= len(events))
    committed_text: str
    delivered_chars: int
    key_data: np.ndarray  # evolved raw PRNG key data for the slot row
    events: list  # ("token", id, text, logprob, top) tuples, emitted order
    adapter: str | None
    payload: np.ndarray  # [n_pages, L, page, 2*Kv, h] page contents
    n_bytes: int  # serialized blob size (transfer accounting)


def encode_state(
    *,
    model_fp: str,
    request_fp: str,
    history: list[int],
    pending: int,
    prompt_len: int,
    generated: int,
    committed_text: str,
    delivered_chars: int,
    key_data: np.ndarray,
    events: list,
    adapter: str | None,
    payload: np.ndarray,
) -> bytes:
    """Serialize one request's KV state. *payload* is the gathered
    [n_pages, L, page, 2*Kv, h] page array (C-order)."""
    payload = np.ascontiguousarray(payload)
    page_bytes = payload.nbytes // payload.shape[0] if payload.shape[0] else 0
    raw = payload.tobytes()
    crcs = [
        zlib.crc32(raw[i * page_bytes : (i + 1) * page_bytes])
        for i in range(payload.shape[0])
    ]
    header = {
        "version": VERSION,
        "model_fp": model_fp,
        "request_fp": request_fp,
        "dtype": str(payload.dtype),
        "shape": list(payload.shape),
        "page_crc": crcs,
        "history": list(map(int, history)),
        "pending": int(pending),
        "prompt_len": int(prompt_len),
        "generated": int(generated),
        "committed_text": committed_text,
        "delivered_chars": int(delivered_chars),
        "key_dtype": str(key_data.dtype),
        "key_shape": list(key_data.shape),
        "key_data": [int(x) for x in np.asarray(key_data).reshape(-1)],
        "events": [
            [int(ev[1]), ev[2], ev[3] if len(ev) > 3 else None,
             ev[4] if len(ev) > 4 else None]
            for ev in events
        ],
        "adapter": adapter or "",
    }
    hdr = json.dumps(header, separators=(",", ":")).encode()
    return MAGIC + struct.pack(">BI", VERSION, len(hdr)) + hdr + raw


def peek_header(blob: bytes) -> dict:
    """Parse just the JSON header (cheap pre-checks — event counts,
    prefix length — before paying the payload CRC walk). Raises
    KVFormatError on framing problems."""
    if len(blob) < 9 or blob[:4] != MAGIC:
        raise KVFormatError("bad magic: not a KV state blob")
    version, hlen = struct.unpack(">BI", blob[4:9])
    if version != VERSION:
        raise KVFormatError(f"unsupported KV state version {version}")
    if len(blob) < 9 + hlen:
        raise KVFormatError("truncated header")
    try:
        header = json.loads(blob[9 : 9 + hlen])
    except ValueError as e:
        raise KVFormatError(f"unparseable header: {e}") from None
    if not isinstance(header, dict):
        raise KVFormatError("header must be an object")
    return header


def decode_state(
    blob: bytes, *, expect_model_fp: str, expect_request_fp: str | None = None
) -> RestoreState:
    """Validate and deserialize. Rejects (KVFormatError) on bad magic,
    version skew, fingerprint mismatch, truncated/oversized payload, or
    any failed per-page checksum — never returns silently-wrong state."""
    header = peek_header(blob)
    if header.get("model_fp") != expect_model_fp:
        raise KVFormatError(
            "model/config fingerprint mismatch: blob "
            f"{header.get('model_fp')!r} vs local {expect_model_fp!r}"
        )
    if expect_request_fp is not None and header.get("request_fp") != expect_request_fp:
        raise KVFormatError("request fingerprint mismatch")
    try:
        shape = tuple(int(x) for x in header["shape"])
        dtype = np.dtype(header["dtype"])
        crcs = [int(c) for c in header["page_crc"]]
        history = [int(t) for t in header["history"]]
        key_shape = tuple(int(x) for x in header["key_shape"])
        key_dtype = np.dtype(header["key_dtype"])
        key_flat = [int(x) for x in header["key_data"]]
        events_raw = header["events"]
    except (KeyError, TypeError, ValueError) as e:
        raise KVFormatError(f"malformed header field: {e}") from None
    # [n_pages, L, page, 2*Kv, h], or [n_pages, L, page, W] for latent
    # pages: the header carries the page's own trailing shape, and the
    # importer refuses a layout that is not its pool's, by name.
    if len(shape) not in (4, 5) or any(d < 0 for d in shape):
        raise KVFormatError(f"payload shape must be 4-D or 5-D, got {shape}")
    if len(crcs) != shape[0]:
        raise KVFormatError("page checksum count does not match page count")
    hlen = struct.unpack(">I", blob[5:9])[0]
    raw = blob[9 + hlen :]
    expect_bytes = int(np.prod(shape)) * dtype.itemsize if shape[0] else 0
    if len(raw) != expect_bytes:
        raise KVFormatError(
            f"payload is {len(raw)} bytes, header promises {expect_bytes}"
        )
    page_bytes = expect_bytes // shape[0] if shape[0] else 0
    for i, crc in enumerate(crcs):
        if zlib.crc32(raw[i * page_bytes : (i + 1) * page_bytes]) != crc:
            raise KVFormatError(f"page {i} checksum mismatch")
    payload = np.frombuffer(raw, dtype=dtype).reshape(shape)
    key_data = np.array(key_flat, dtype=key_dtype).reshape(key_shape)
    events = [
        ("token", int(e[0]), e[1], e[2], e[3]) for e in events_raw
    ]
    return RestoreState(
        history=history,
        pending=int(header["pending"]),
        prompt_len=int(header["prompt_len"]),
        generated=int(header["generated"]),
        committed_text=str(header["committed_text"]),
        delivered_chars=int(header["delivered_chars"]),
        key_data=key_data,
        events=events,
        adapter=(header.get("adapter") or None),
        payload=payload,
        n_bytes=len(blob),
    )


# -- the park store ---------------------------------------------------------


@dataclasses.dataclass
class ParkEntry:
    blob: bytes
    tokens: int  # len(history): the prefix a restore saves re-prefilling
    parked_at: float


class ParkStore:
    """Host-RAM store of serialized park blobs, keyed by the random
    offer key that travels proxy-side in the marker chunk. Bounded two
    ways: TTL (KUBEAI_KV_PARK_TTL) and total bytes (KUBEAI_KV_PARK_BYTES,
    LRU eviction). Thread-safe: the scheduler parks, HTTP handler
    threads read (local restore, GET /v1/kv/<key> transfer serving)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, ParkEntry]" = OrderedDict()
        self._bytes = 0

    def put(self, key: str, blob: bytes, tokens: int) -> list[str]:
        """Store a blob; returns the keys evicted to stay under the
        byte cap (the engine drops their pinned pages too)."""
        evicted: list[str] = []
        cap = park_cap_bytes()
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= len(old.blob)
            self._entries[key] = ParkEntry(blob, tokens, time.monotonic())
            self._bytes += len(blob)
            while self._bytes > cap and len(self._entries) > 1:
                k, e = self._entries.popitem(last=False)
                self._bytes -= len(e.blob)
                evicted.append(k)
        return evicted

    def get(self, key: str) -> ParkEntry | None:
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            if time.monotonic() - e.parked_at > park_ttl():
                self._entries.pop(key, None)
                self._bytes -= len(e.blob)
                return None
            return e

    def drop(self, key: str) -> bool:
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                return False
            self._bytes -= len(e.blob)
            return True

    def sweep(self) -> list[str]:
        """Expire TTL-stale entries; returns their keys so the engine
        can drop the matching pinned pages."""
        ttl = park_ttl()
        now = time.monotonic()
        out: list[str] = []
        with self._lock:
            for k in list(self._entries):
                if now - self._entries[k].parked_at > ttl:
                    e = self._entries.pop(k)
                    self._bytes -= len(e.blob)
                    out.append(k)
        return out

    def total_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# -- offers and transfer ----------------------------------------------------

# Proxy->engine headers on a resume/handoff dispatch (the proxy strips
# the inbound versions — clients must not forge a restore source).
KV_KEY_HEADER = "X-KV-Key"
KV_SOURCE_HEADER = "X-KV-Source"
KV_TOKENS_HEADER = "X-KV-Tokens"


def extract_kv_offer(event: bytes) -> dict | None:
    """The `kubeai_kv` offer riding a preempt/handoff marker chunk:
    {"key", "source" ("host:port"), "tokens", "bytes"}. The proxy
    captures it before withholding the marker and stamps the X-KV-*
    headers on the resume dispatch. None for non-offer events."""
    if not event.startswith(b"data:") or b"kubeai_kv" not in event:
        return None
    payload = event[5:].strip()
    if payload == b"[DONE]":
        return None
    try:
        offer = json.loads(payload).get("kubeai_kv")
    except (ValueError, AttributeError):
        return None
    if not isinstance(offer, dict):
        return None
    key, source = offer.get("key"), offer.get("source")
    if not isinstance(key, str) or not key or not isinstance(source, str):
        return None
    try:
        tokens = int(offer.get("tokens", 0))
    except (TypeError, ValueError):
        tokens = 0
    return {"key": key, "source": source, "tokens": tokens,
            "bytes": int(offer.get("bytes", 0) or 0)}


def fetch_blob(source: str, key: str, remaining: float | None = None) -> bytes | None:
    """GET the blob from the parking replica's transfer endpoint
    (http://<source>/v1/kv/<key>) with deadline/retry semantics. None on
    any failure — the caller falls back to replay; a state-transfer
    failure is NEVER surfaced as a request failure."""
    host, _, port = source.partition(":")
    if not host or not port.isdigit():
        return None
    attempts = fetch_retries() + 1
    for attempt in range(attempts):
        timeout = fetch_timeout()
        if remaining is not None:
            if remaining <= 0.05:
                return None
            timeout = min(timeout, remaining)
        t0 = time.monotonic()
        conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
        try:
            conn.request("GET", f"/v1/kv/{key}")
            resp = conn.getresponse()
            if resp.status != 200:
                return None  # a definitive miss/refusal: no point retrying
            blob = resp.read()
            M_KV_TRANSFER.inc(len(blob), labels={"direction": "rx"})
            return blob
        except (OSError, http.client.HTTPException):
            if remaining is not None:
                remaining -= time.monotonic() - t0
            if attempt + 1 >= attempts:
                return None
        finally:
            conn.close()
    return None
