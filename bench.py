"""Headline benchmark — engine serving throughput on one TPU chip.

Prints ONE JSON line on stdout:
  {"metric", "value", "unit", "vs_baseline", "device", ...extras}

One process, one preset: build the engine, warm every shape the measured
phase hits, run the measured load, print the JSON line. Phases
(init/build/warmup/measure) are logged to stderr with timestamps so a
hang is attributable. A chip preset needs a TPU: with none visible, or
when the preset fails, the exit code is non-zero and no result is
printed — no other platform and no smaller preset is substituted.
`--tiny` is the CPU smoke the tests use; its result names the device it
ran on like every other.

The JAX persistent compilation cache is on (engine/coldstart.py places
it), so a second run of a preset re-uses every compiled executable.

Measures steady-state output token throughput of the continuous-batching
engine (random weights — tokens/s does not depend on weight values)
under realistic concurrency. vs_baseline anchors against the only
single-accelerator output-throughput number the reference publishes:
285.25 output tok/s (vLLM, Llama-3.2-11B on 1x L4;
ref: docs/benchmarks/llama-3.2-11b-vision.md:12-30 / BASELINE.md) — an
anchor, not an apples-to-apples comparison.

Usage:
  python bench.py                    # the 8b-int8 preset, needs a TPU
  python bench.py --preset 1.3b      # needs a TPU
  python bench.py --tiny             # CPU smoke
"""

import argparse
import json
import os
import queue
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REFERENCE_SINGLE_ACCEL_TOKS = 285.25

PRESETS = ("8b-int8", "1.3b", "tiny")
# Per-preset worker deadline (s), first compile included.
PRESET_DEADLINE = {"8b-int8": 1200, "1.3b": 480, "tiny": 240}


def log(msg: str) -> None:
    print(f"# [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def _pct(vals: list[float], q: float) -> float | None:
    if not vals:
        return None
    vals = sorted(vals)
    return round(vals[min(len(vals) - 1, int(len(vals) * q))], 2)


def trace_latency_stats(since_wall: float, expected: int = 0) -> dict:
    """p50/p95/p99 TTFT + TPOT from the engine flight recorder's request
    timelines (kubeai_tpu/obs): the recorder keeps per-token offsets per
    request, so the FULL latency distribution is recomputable after the
    fact instead of only client-side means. *since_wall* bounds the
    window to the measured phase (warmup traffic is excluded)."""
    from kubeai_tpu.obs import default_recorder

    since_ms = since_wall * 1000 - 5.0
    ttfts: list[float] = []
    tpots: list[float] = []
    n = 0
    for tl in default_recorder.snapshot():
        if tl.get("component") != "engine" or tl.get("outcome") != "ok":
            continue
        if tl.get("start_ms", 0.0) < since_ms:
            continue
        for ph in tl.get("phases", ()):
            if ph["name"] == "decode":
                offs = ph.get("attrs", {}).get("token_offsets_ms") or []
                if offs:
                    n += 1
                    ttfts.append(offs[0])
                    tpots.extend(b - a for a, b in zip(offs, offs[1:]))
    if not ttfts:
        return {}
    if expected and n < expected:
        # The ring buffer holds the most recent timelines only — say so
        # rather than letting a truncated sample read as full coverage.
        log(f"trace stats cover {n}/{expected} requests (recorder ring bound)")
    out = {
        "ttft_ms": {"p50": _pct(ttfts, 0.5), "p95": _pct(ttfts, 0.95), "p99": _pct(ttfts, 0.99)},
        "latency_source": "flight_recorder",
    }
    if tpots:
        out["tpot_ms"] = {"p50": _pct(tpots, 0.5), "p95": _pct(tpots, 0.95), "p99": _pct(tpots, 0.99)}
    return out


def emit(value: float, extras: dict | None = None) -> None:
    line = {
        "metric": "engine_output_tokens_per_sec_per_chip",
        "value": round(value, 2),
        "unit": "tok/s",
        "vs_baseline": round(value / REFERENCE_SINGLE_ACCEL_TOKS, 3),
    }
    if extras:
        line.update(extras)
    print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# Worker


def synth_int8_params(mc):
    """Host-synthesized int8 weight tree with the exact structure
    quantize_model_params produces for this config: int8 values from a
    fixed RNG, constant per-channel scales matching a normal(0, 1/sqrt(in))
    init's absmax so logits stay in a sane range."""
    import numpy as np

    from kubeai_tpu.ops.quant import QKEY, SKEY

    rng = np.random.default_rng(0)
    D, F, L = mc.hidden_size, mc.intermediate_size, mc.num_layers
    H, Kv, h = mc.num_heads, mc.num_kv_heads, mc.head_dim_
    V = mc.vocab_size
    dt = np.dtype("bfloat16") if mc.dtype == "bfloat16" else np.dtype(mc.dtype)

    def q(*shape, row_scales=False, scale=None):
        fan_in = shape[-1 if row_scales else -2]
        s = (scale or 4.0 / np.sqrt(fan_in)) / 127.0
        sshape = (
            shape[:-1] + (1,) if row_scales else shape[:-2] + (1, shape[-1])
        )
        return {
            QKEY: rng.integers(-127, 128, shape, dtype=np.int8),
            SKEY: np.full(sshape, s, np.float32),
        }

    layers = {
        "ln1": np.ones((L, D), dt),
        "ln2": np.ones((L, D), dt),
        "wq": q(L, D, H * h),
        "wk": q(L, D, Kv * h),
        "wv": q(L, D, Kv * h),
        "wo": q(L, H * h, D),
        "wg": q(L, D, F),
        "wu": q(L, D, F),
        "wd": q(L, F, D),
    }
    return {
        "embed": q(V, D, row_scales=True, scale=0.08),
        "final_norm": np.ones((D,), dt),
        "layers": layers,
        "lm_head": q(D, V, scale=0.08),
    }


def build_engine(preset: str, slots: int = 0, chunk: int = 0, kv_dtype: str = ""):
    import jax

    from kubeai_tpu.engine.core import Engine, EngineConfig
    from kubeai_tpu.engine.tokenizer import ByteTokenizer
    from kubeai_tpu.models import llama
    from kubeai_tpu.models.base import ModelConfig

    if preset == "tiny":
        mc = ModelConfig(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_layers=2, num_heads=4, num_kv_heads=2, dtype="float32",
        )
        ec = EngineConfig(max_slots=4, max_seq_len=256, prefill_buckets=(32, 64, 128))
        params = llama.init_params(mc, jax.random.key(0))
    elif preset == "8b-int8":
        # The BASELINE.json headline config: Llama-3-8B shape on ONE v5e
        # chip via int8 weights. The int8 tree is synthesized directly on
        # host (tokens/s does not depend on weight values; shapes, dtypes
        # and the jitted graph are identical to load_engine_from_path's
        # int8 serving config) — randomly initializing 8B bf16 params and
        # quantizing them burned ~15 host-CPU-minutes per worker attempt
        # in round 2 and is pure setup, not the thing being measured.
        mc = ModelConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
            dtype="bfloat16",
        )
        if jax.default_backend() == "tpu":
            # Match load_engine_from_path's real int8 serving config
            # (engine/weights.py:106-110): flash prefill AND the ragged
            # paged-attention decode kernel. Round 2 measured the portable
            # gather path instead (VERDICT r2 weak #2).
            mc = mc.replace(use_flash_prefill=True, use_paged_kernel=True)
        # Slot scaling measured on v5e: 16 slots = 698 tok/s, 32 = 1031,
        # 48 = 1190 (decode is weight-bandwidth-bound, so batch is nearly
        # free until HBM fills: 8GB int8 weights + ~6.3GB bf16 KV pool at
        # 48 slots was the most the 16GB chip took; 64 bf16 slots OOM'd).
        # fp8 KV (r5) halves pool bytes and the kernel reads fp8 pages
        # faster standalone (3.8ms vs 4.7ms at B=48) — but the measured
        # END-TO-END sweep says more slots do NOT pay at 8B: 96 fp8
        # slots = 498 tok/s (2.5x WORSE than 48 bf16; per-step attention
        # + sampling width outgrow the weight-read amortization). The
        # default stays at the measured best; --kv-dtype/--slots expose
        # the sweep knobs.
        ec = EngineConfig(
            max_slots=48, max_seq_len=1024, prefill_buckets=(128, 256, 512),
            decode_chunk=16,
        )
        t0 = time.monotonic()
        params = synth_int8_params(mc)
        log(f"phase=build int8 tree synthesized on host ({time.monotonic()-t0:.1f}s)")
        t0 = time.monotonic()
        params = jax.device_put(params)
        jax.block_until_ready(params)
        log(f"phase=build weights transferred to device ({time.monotonic()-t0:.1f}s)")
    else:
        # 1.3B-class Llama in bf16.
        mc = ModelConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=16, num_heads=16, num_kv_heads=8, dtype="bfloat16",
        )
        if jax.default_backend() == "tpu":
            mc = mc.replace(use_flash_prefill=True, use_paged_kernel=True)
        # Slot scaling measured on v5e: 32 slots = 2993 tok/s, 64 = 4389
        # (p50 TTFT 2.0s), 96 = 4512 but with worse TTFT (2.5s) — 64 is
        # the throughput/latency knee for this bf16 config.
        ec = EngineConfig(
            max_slots=64, max_seq_len=1024, prefill_buckets=(128, 256, 512),
            decode_chunk=16,
        )
        params = llama.init_params(mc, jax.random.key(0))
    if kv_dtype:
        ec.kv_cache_dtype = "" if kv_dtype == "bf16" else kv_dtype
    if slots:
        ec.max_slots = slots
    if chunk:
        ec.decode_chunk = chunk
    return Engine(mc, params, ByteTokenizer(), ec)


def run_worker(args) -> None:
    import threading

    worker_t0 = time.monotonic()
    timer = None
    if args.watchdog:
        # In-process deadline: hard-exit non-zero, with no result,
        # rather than hanging the caller.

        def bail():
            log(f"watchdog: bench exceeded {args.watchdog}s")
            os._exit(3)

        timer = threading.Timer(args.watchdog, bail)
        timer.daemon = True
        timer.start()

    log(f"phase=init preset={args.preset} importing jax + initializing backend")
    import jax

    t0 = time.monotonic()
    devs = jax.devices()
    backend = devs[0].platform
    dev_kind = devs[0].device_kind
    log(
        f"phase=init done backend={backend} device={dev_kind} "
        f"compile_cache={jax.config.jax_compilation_cache_dir} "
        f"({time.monotonic()-t0:.1f}s)"
    )
    if args.preset != "tiny" and backend != "tpu":
        # A chip preset answers a question about the chip: no other
        # platform and no smaller preset stands in for it.
        log(f"preset {args.preset} needs a TPU; jax came up on {backend!r}")
        sys.exit(2)

    import numpy as np

    from kubeai_tpu.engine.sampling import SamplingParams

    preset = args.preset
    tiny = preset == "tiny"
    # Sized for a >=10s steady-state window at the observed rates (a
    # 2-3s window mostly measures ramp-up/drain edges).
    n_requests = args.requests or (8 if tiny else 256)
    max_tokens = args.max_tokens or (8 if tiny else 128)
    prompt_len = 16 if tiny else 128

    t0 = time.monotonic()
    log(f"phase=build constructing engine (weights on device)")
    eng = build_engine(
        preset, slots=args.slots, chunk=args.chunk, kv_dtype=args.kv_dtype,
    )
    eng.start()
    log(f"phase=build done ({time.monotonic()-t0:.1f}s)")

    rng = np.random.default_rng(0)
    if args.greedy:
        # Greedy runs use REPETITIVE prompts: a repeated phrase pattern,
        # standing in for chat workloads that echo their context.
        phrase = rng.integers(1, 200, 16)
        prompts = [
            np.concatenate(
                [np.roll(phrase, i % 4) for _ in range(prompt_len // 16)]
            )[:prompt_len].tolist()
            for i in range(n_requests)
        ]
        sp = SamplingParams(temperature=0.0, max_tokens=max_tokens)
    else:
        prompts = [rng.integers(1, 200, prompt_len).tolist() for _ in range(n_requests)]
        sp = SamplingParams(temperature=0.7, top_p=0.95, max_tokens=max_tokens, seed=1)

    # Warmup: compile EVERY shape the measure phase hits — the single
    # (pad-1) prefill, the grouped (pad-prefill_group_cap) prefill, and
    # the decode chunk — outside the timed window (round 2 compiled the
    # burst shape mid-measurement, poisoning both tok/s and TTFT). The timeout must
    # cover a cold multi-minute 8B compile — generate()'s default 300s
    # killed the r2 worker mid-compile; the watchdog remains the real
    # deadline.
    t0 = time.monotonic()
    log("phase=warmup compiling prefill (single + burst) + decode")
    warmup_timeout = args.watchdog if args.watchdog else PRESET_DEADLINE[preset]
    wp = SamplingParams(temperature=0.0, max_tokens=4)
    # Warmup prompts draw from a DISJOINT token range so the measure
    # phase runs cold — reusing measure prompts would leave their prefix
    # pages registered and hand the first 2*max_slots measured requests
    # a warm cache.
    wprompts = [
        rng.integers(201, 400, prompt_len).tolist()
        for _ in range(2 * eng.cfg.max_slots)
    ]
    eng.generate(wprompts[0], wp, timeout=warmup_timeout)
    # A full-slot burst guarantees a grouped admission round even if the
    # scheduler races ahead and admits the first request solo. Skips
    # wprompts[0]: the single warmup just content-registered its pages,
    # and resubmitting it would take the chunked prefix-reuse path —
    # cold-compiling a graph the measure phase never runs (multi-minute
    # on the 8B preset).
    burst = [eng.submit(p, wp) for p in wprompts[1:]]
    deadline = time.monotonic() + warmup_timeout
    for r in burst:
        while True:
            if time.monotonic() > deadline:
                raise TimeoutError(f"warmup burst exceeded {warmup_timeout}s")
            try:
                ev = r.out.get(timeout=max(1.0, deadline - time.monotonic()))
            except queue.Empty:
                raise TimeoutError(
                    f"warmup burst produced no event within {warmup_timeout}s"
                ) from None
            if ev[0] == "done":
                break
            if ev[0] == "error":
                raise RuntimeError(ev[1])
    log(f"phase=warmup done ({time.monotonic()-t0:.1f}s)")

    results = [None] * n_requests
    ttfts = [None] * n_requests
    e2es = [None] * n_requests

    def run(i):
        req = eng.submit(prompts[i], sp)
        t_submit = time.monotonic()
        n_toks = 0
        while True:
            ev = req.out.get(timeout=600)
            if ev[0] == "token":
                if n_toks == 0:
                    ttfts[i] = time.monotonic() - t_submit
                if ev[1] >= 0:
                    n_toks += 1
            elif ev[0] == "done":
                results[i] = ev[1]
                e2es[i] = time.monotonic() - t_submit
                return
            else:
                raise RuntimeError(ev[1])

    log(f"phase=measure {n_requests} reqs x {max_tokens} tokens")
    threads = [threading.Thread(target=run, args=(i,)) for i in range(n_requests)]
    measure_wall_t0 = time.time()
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - t0
    if timer is not None:
        timer.cancel()  # measurement complete; teardown must not race bail()

    # None entries = requests whose worker thread errored; the headline
    # and percentiles cover survivors, the slo block below counts the
    # failures against the objectives.
    total_out = sum(r.completion_tokens for r in results if r is not None)
    toks_per_sec = total_out / elapsed
    ok_ttfts = sorted(t for t in ttfts if t is not None)
    p50_ttft = ok_ttfts[len(ok_ttfts) // 2] if ok_ttfts else 0.0

    extras = {
        "preset": preset,
        "device": {"platform": backend, "kind": dev_kind, "count": len(devs)},
        "p50_ttft_ms": round(p50_ttft * 1000, 1),
    }
    # Percentile TTFT/TPOT from trace data (the flight recorder), not
    # just the client-side median above.
    try:
        extras.update(trace_latency_stats(measure_wall_t0, expected=n_requests))
    except Exception as e:  # pragma: no cover - stats are best-effort
        log(f"trace latency stats unavailable: {e}")
    # SLO-attainment block (objective, attainment, burn rate) so stored
    # BENCH_r*.json snapshots track SLOs, not just throughput. NOTE: the
    # saturated phase's TTFT is mostly queueing — the block is honest
    # about that regime, and the rate-controlled phase below carries the
    # within-capacity view. Requests with no sample (errored workers)
    # count AGAINST the latency objectives, matching the server-side
    # SLO monitor's rule.
    try:
        from kubeai_tpu.obs.slo import attainment_block, error_rate_block

        n_failed = sum(1 for r in results if r is None)
        extras["slo"] = {
            "ttft": attainment_block(
                [t for t in ttfts if t is not None], args.slo_ttft, 0.95,
                failures=sum(1 for t in ttfts if t is None),
            ),
            "e2e": attainment_block(
                [t for t in e2es if t is not None], args.slo_e2e, 0.99,
                failures=n_failed,
            ),
            "error_rate": error_rate_block(n_failed, n_requests),
        }
    except Exception as e:  # pragma: no cover - block is best-effort
        log(f"slo block unavailable: {e}")
    if args.greedy:
        extras["sampling"] = "greedy"
    # Roofline/MFU from the SHARED accounting (kubeai_tpu/obs/perf.py —
    # the same math the engine's kubeai_engine_mfu gauge and the sweep
    # JSON use; previously hand-maintained constants here): FLOPs/token
    # analytic from the model config, weight bytes measured off the
    # live param tree, device constants from the shared tables. The
    # roofline block ships in every BENCH JSON so stored numbers carry
    # their own interpretation.
    from kubeai_tpu.obs.perf import device_constants

    env = device_constants(str(dev_kind))
    pm = eng.perf
    roof = pm.roofline_tokens_per_sec(eng.cfg.max_slots, env.hbm_gbps)
    extras["roofline"] = {
        "flops_per_token": pm.flops_per_token,
        "weight_bytes": pm.weight_bytes,
        "slots": eng.cfg.max_slots,
        "device": str(dev_kind),
        "peak_flops": env.peak_flops,
        "hbm_gbps": env.hbm_gbps,
        "roofline_toks_per_sec": round(roof, 1) if roof else None,
        "roofline_fraction": round(toks_per_sec / roof, 4) if roof else None,
    }
    # CPU runs report no MFU.
    if env.peak_flops and backend != "cpu":
        extras["mfu_pct"] = round(pm.mfu(toks_per_sec, env.peak_flops) * 100, 2)
    log(
        f"phase=measure done: {n_requests} reqs x {max_tokens} max_tokens, "
        f"prompt={prompt_len}, elapsed={elapsed:.1f}s, "
        f"p50_ttft={p50_ttft*1000:.0f}ms, total_output_tokens={total_out}"
    )

    # SLO-honest companion number (VERDICT r3 #2a): Poisson arrivals at a
    # controlled rate instead of an all-at-once burst. The saturated
    # number above conflates throughput with unbounded queueing (its
    # p50 TTFT is queue depth, not system latency); this phase reports
    # TTFT with the queue near-empty — the pair (saturated tok/s,
    # rate-controlled TTFT) is BASELINE.json's "req/s/chip + p50 TTFT"
    # north star.
    rate = args.request_rate
    if rate is None and preset != "tiny":
        # Default: ~70% of the just-measured saturated request rate —
        # comfortably inside capacity so TTFT measures the system, not
        # the queue.
        rate = round(0.7 * toks_per_sec / max_tokens, 2)
    if rate and args.watchdog:
        # The headline above MUST survive: the watchdog was cancelled
        # after measure, so nothing bounds this phase but the caller's
        # own time limit — which would forfeit the already-measured
        # number. Skip the companion phase unless its worst case
        # (duration + straggler join + drain) fits what's left of the
        # budget.
        left = args.watchdog - (time.monotonic() - worker_t0)
        if left < args.rate_duration + 220:
            log(f"phase=rate skipped: {left:.0f}s left of worker budget")
            rate = 0
    if rate:
        # Best-effort: the saturated headline above must survive any
        # failure here (a lost companion number is a log line; a lost
        # headline forfeits the whole preset run).
        try:
            extras["rate_controlled"] = _rate_phase(
                eng, prompts, sp, rate, args.rate_duration
            )
            log(f"phase=rate done: {extras['rate_controlled']}")
        except Exception as e:  # pragma: no cover - defensive
            extras["rate_error"] = str(e)[:200]
            log(f"phase=rate FAILED: {e}")
    # Emit the measured headline BEFORE teardown: an exception or hang
    # in eng.stop() must not be able to forfeit an already-measured
    # result (ADVICE r5 — emit() had drifted to after stop()).
    # Teardown failures are a log line, not a lost preset run.
    emit(toks_per_sec, extras)
    try:
        eng.stop()
    except Exception as e:  # pragma: no cover - defensive teardown guard
        log(f"phase=teardown engine stop failed (headline already emitted): {e}")


def _rate_phase(eng, prompts, sp, rate: float, duration: float) -> dict:
    """Open-loop Poisson load at *rate* req/s for *duration* seconds;
    returns achieved tok/s + TTFT percentiles (the SLO-honest view the
    all-at-once saturated phase can't give)."""
    import threading

    import numpy as np

    r_ttfts: list[float] = []
    r_toks = [0]
    r_failed = [0]
    r_lock = threading.Lock()
    threads = []
    rng = np.random.default_rng(7)
    n_prompts = len(prompts)
    t0 = time.monotonic()
    stop_t = t0 + duration
    t_next = t0
    i = 0

    def run_one(idx):
        req = eng.submit(prompts[idx % n_prompts], sp)
        t_submit = time.monotonic()
        first = True
        while True:
            # Short timeout + daemon threads: a wedged engine fails this
            # request's thread, never the bounded join below (the phase
            # must not be able to hang the worker past its budget).
            try:
                ev = req.out.get(timeout=120)
            except queue.Empty:
                # Count it: a silently-vanished wedged request would
                # leave the TTFT percentiles looking clean — the exact
                # failure this phase exists to expose.
                with r_lock:
                    r_failed[0] += 1
                return
            if ev[0] == "token":
                if first:
                    with r_lock:
                        r_ttfts.append(time.monotonic() - t_submit)
                    first = False
            elif ev[0] == "done":
                with r_lock:
                    r_toks[0] += ev[1].completion_tokens
                return
            else:
                with r_lock:
                    r_failed[0] += 1
                return

    while True:
        t_next += rng.exponential(1.0 / rate)
        now = time.monotonic()
        if t_next >= stop_t:
            break
        if t_next > now:
            time.sleep(t_next - now)
        th = threading.Thread(target=run_one, args=(i,), daemon=True)
        th.start()
        threads.append(th)
        i += 1
    join_deadline = time.monotonic() + 180
    for th in threads:
        th.join(timeout=max(0.0, join_deadline - time.monotonic()))
    stragglers = sum(1 for th in threads if th.is_alive())
    r_elapsed = time.monotonic() - t0
    rs = sorted(r_ttfts)
    out = {
        "request_rate_rps": rate,
        "requests": len(threads),
        "tok_s": round(r_toks[0] / r_elapsed, 1),
        "p50_ttft_ms": round(rs[len(rs) // 2] * 1000, 1) if rs else None,
        "p99_ttft_ms": round(rs[min(len(rs) - 1, int(len(rs) * 0.99))] * 1000, 1) if rs else None,
    }
    if stragglers or r_failed[0]:
        out["stragglers"] = stragglers + r_failed[0]
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true", help="CPU smoke mode")
    parser.add_argument(
        "--preset", default=None, choices=list(PRESETS),
        help="preset to run (default 8b-int8; every preset but tiny needs a TPU)",
    )
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--max-tokens", type=int, default=None)
    parser.add_argument(
        "--greedy", action="store_true",
        help="greedy sampling + repetitive prompts",
    )
    parser.add_argument(
        "--slots", type=int, default=0,
        help="override the preset's max decode slots (batch size)",
    )
    parser.add_argument(
        "--chunk", type=int, default=0,
        help="override the preset's fused decode steps per dispatch",
    )
    parser.add_argument(
        "--kv-dtype", default="", choices=["", "bf16", "fp8", "int8"],
        help="override the preset's KV pool dtype (bf16 = unquantized)",
    )
    parser.add_argument(
        "--request-rate", type=float, default=None,
        help="rate-controlled phase: Poisson req/s (default: auto ~70%% "
             "of measured capacity; 0 disables)",
    )
    parser.add_argument(
        "--rate-duration", type=float, default=45.0,
        help="rate-controlled phase duration (s)",
    )
    parser.add_argument(
        "--slo-ttft", type=float, default=2.0,
        help="TTFT SLO objective (s) for the emitted slo block",
    )
    parser.add_argument(
        "--slo-e2e", type=float, default=30.0,
        help="end-to-end latency SLO objective (s) for the emitted slo block",
    )
    parser.add_argument(
        "--watchdog", type=int, default=None,
        help="hard deadline (s); default per preset, 0 disables",
    )
    args = parser.parse_args()
    args.preset = args.preset or ("tiny" if args.tiny else "8b-int8")
    if args.watchdog is None:
        args.watchdog = PRESET_DEADLINE[args.preset]
    from kubeai_tpu.engine.coldstart import setup_compile_cache

    # Persistent compile cache: a second run skips recompilation.
    setup_compile_cache()
    run_worker(args)


if __name__ == "__main__":
    main()
