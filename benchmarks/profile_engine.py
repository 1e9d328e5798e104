"""Per-phase engine profiler: where does a decode chunk's time go?

Times, on the current backend (designed for the real TPU):
  - dispatch RTT: a trivial jitted call, round-tripped (remote-device tax)
  - raw decode chunk device time for the 1.3b preset, gather vs paged
    kernel paths
  - prefill bucket device time (flash vs portable)
  - sampling cost in isolation
  - host-side _process_chunk cost on synthetic payloads

Prints a table plus roofline context (weights bytes / HBM bandwidth),
so the top cost is attributable before touching engine code
(VERDICT r2 "next" #2: close the throughput gap with a profile, not
guesses).

Usage: python benchmarks/profile_engine.py [--preset 1.3b|8b-int8] [--paths gather,paged]

--sweep: kernel-level decode-attention microbench — per-step latency of
the paged attention call ALONE (weights out of the picture, so the
attention term of the 96-slot cliff is measured in isolation), swept
over slot counts x ragged-kernel block shapes (pages:queries, passed to
the wrapper as `blocks=`; "default" is what the wrapper chooses from the
call's shapes), emitted as one JSON document with
grid-utilization diagnosis fields per config. `--smoke` shrinks shapes
so the identical harness runs on CPU in CI (timings are then reference-
implementation numbers — structure and relative trends only, labeled as
such in the output). See docs/benchmarks.md ("the 96-slot cliff").

Usage: python benchmarks/profile_engine.py --sweep [--smoke] [--out f.json]
           [--sweep-slots 16,48,64,96] [--sweep-blocks default,8:32,16:32]
"""

import argparse
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Device constant tables live in the shared perf accounting now
# (kubeai_tpu/obs/perf.py) — one source for bench.py, the engine's live
# MFU/roofline gauges, and this harness.
from kubeai_tpu.obs.perf import HBM_GBPS, PEAK_FLOPS, device_constants  # noqa: E402


def log(msg):
    print(f"# [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def timeit(fn, n=10, warmup=2):
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.monotonic()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / n


def _sweep_shapes(smoke: bool) -> dict:
    """Attention shapes for the kernel microbench. Full mode mirrors the
    8b-int8 flagship preset's attention dims at 1024-token tables (the
    config the 96-slot cliff was measured on); smoke shrinks every axis
    so the identical harness runs on CPU in CI seconds."""
    if smoke:
        return dict(H=4, Kv=2, h=128, page=16, seq=64, iters=3, warmup=1)
    return dict(H=32, Kv=8, h=128, page=64, seq=1024, iters=20, warmup=3)


def run_sweep(
    slots_list=(16, 48, 64, 96),
    blocks=("default", "8:32", "16:32", "32:8", "64:4"),
    smoke=False,
    qlen=1,
    seed=0,
    out_path="",
    resume=False,
):
    """Kernel-level decode-attention microbench: per-step latency of ONE
    paged-attention call (per layer, S=qlen queries per slot) for every
    (kernel, block, slots) combination. Returns the JSON-able document.

    What the numbers attribute (the 96-slot cliff diagnosis):
      - If the RAGGED kernel's latency is flat in `slots` at S=1, its
        grid has collapsed (all B queries fit one query block — grid
        underutilization): more slots add work per program, not more
        programs, and past the VMEM-resident span the serial page walk
        dominates — latency then jumps superlinearly (the cliff shape).
      - `grid_programs` / `q_rows_per_program` per row are the derived
        utilization facts; `kv_mb_walked` is the per-call page traffic
        (identical across blocks at equal slots — any latency delta at
        equal traffic is scheduling, not bandwidth).

    CPU runs (smoke or no accelerator) time the REFERENCE
    implementations — structure and relative trends only, and the
    emitted document says so (`degraded: true`).

    *out_path* + *resume*: per-cell results persist to *out_path*
    (atomic tmp+rename) after EVERY measurement, and a restart with
    resume=True skips cells the existing document already measured — a
    flaky device mid-grid costs one cell, not the whole 30-min run.
    """
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeai_tpu.ops.paged_attention import kernel_blocks, paged_attention_ragged

    sh = _sweep_shapes(smoke)
    H, Kv, h, page, seq = sh["H"], sh["Kv"], sh["h"], sh["page"], sh["seq"]
    iters, warmup = sh["iters"], sh["warmup"]
    backend = jax.default_backend()
    kind = getattr(jax.devices()[0], "device_kind", "unknown")
    degraded = backend == "cpu"
    rng = np.random.default_rng(seed)
    max_pages = seq // page
    dtype = jnp.float32 if degraded else jnp.bfloat16

    # Shared roofline accounting (kubeai_tpu/obs/perf.py): project each
    # measured attention cell onto the FULL-model decode step of the
    # 8b-int8 flagship (the config the 96-slot cliff was measured on) —
    # step = weight-read floor + measured attention x num_layers — so
    # the cliff analysis reads directly off the sweep output as mfu /
    # roofline_fraction columns. The CPU smoke projects onto v5e
    # constants, labeled `assumed_device` — trend-only, like the rest
    # of a degraded run; an accelerator missing from the peak table is
    # an error, never a default.
    from kubeai_tpu.models.base import ModelConfig
    from kubeai_tpu.obs.perf import PerfModel, device_constants

    flagship_layers = 32
    pm = PerfModel.from_model_config(
        ModelConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=flagship_layers, num_heads=32, num_kv_heads=8,
            rope_theta=500000.0, dtype="bfloat16",
        ),
        quantization="int8",
    )
    env = device_constants(str(kind))
    assumed_device = env.hbm_gbps is None or env.peak_flops is None
    if assumed_device and not degraded:
        raise SystemExit(
            f"no peak FLOP/s or HBM bandwidth on record for device kind "
            f"{kind!r} (obs/perf.py PEAK_FLOPS / HBM_GBPS)"
        )
    hbm_gbps = env.hbm_gbps or HBM_GBPS["v5e"]
    peak_flops = env.peak_flops or PEAK_FLOPS["v5e"]
    floor_ms = pm.step_floor_seconds(hbm_gbps) * 1e3

    def make_doc(rows):
        return {
            "metric": "paged_attention_sweep",
            "backend": backend,
            "device": str(kind),
            "degraded": degraded,
            "note": (
                "CPU reference timings — relative trends only, not TPU numbers"
                if degraded else "per-layer kernel call, mid-generation tables"
            ),
            "shapes": {
                "H": H, "Kv": Kv, "head_dim": h, "page": page, "seq": seq,
                "dtype": str(dtype.__name__ if hasattr(dtype, "__name__") else dtype),
            },
            # The constants behind each row's mfu/roofline_fraction —
            # the sweep JSON carries its own interpretation.
            "roofline": {
                "basis": (
                    "8b-int8 flagship; projected full-model step = "
                    "weight-read floor + measured attention x num_layers"
                ),
                "flops_per_token": pm.flops_per_token,
                "weight_bytes": pm.weight_bytes,
                "num_layers": flagship_layers,
                "hbm_gbps": hbm_gbps,
                "peak_flops": peak_flops,
                "step_floor_ms": round(floor_ms, 3),
                "assumed_device": assumed_device,
            },
            "results": rows,
        }

    def cell_key(row):
        return (row.get("kernel"), row.get("block"), row.get("slots"), row.get("qlen"))

    completed: dict[tuple, dict] = {}
    if resume and out_path and os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prior = json.load(f)
        except (OSError, ValueError) as e:
            log(f"resume: cannot read {out_path} ({e}); starting fresh")
            prior = None
        if prior and prior.get("metric") == "paged_attention_sweep":
            for row in prior.get("results", []):
                # A measured latency OR a recorded failure both count as
                # done; a row with neither was interrupted mid-cell.
                if row.get("latency_ms") is not None or row.get("error"):
                    completed[cell_key(row)] = row
            log(f"resume: {len(completed)} completed cells in {out_path}")

    results = []

    def persist():
        if not out_path:
            return
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(make_doc(results), f, indent=2)
            f.write("\n")
        os.replace(tmp, out_path)

    for B in slots_list:
        configs = [("ragged", blk) for blk in blocks]
        if all((kernel, blk, B, qlen) in completed for kernel, blk in configs):
            # Every cell at this slot count is already measured: reuse
            # the rows without allocating the (large) test arrays.
            for kernel, blk in configs:
                results.append(completed[(kernel, blk, B, qlen)])
                log(f"sweep kernel={kernel} block={blk} slots={B}: resumed")
            continue
        P = 1 + B * max_pages
        q = jnp.asarray(rng.standard_normal((B, qlen, H, h)), dtype)
        kv_pages = jnp.asarray(rng.standard_normal((P, page, 2 * Kv, h)), dtype)
        table = np.zeros((B, max_pages), np.int32)
        for b in range(B):
            table[b] = np.arange(1 + b * max_pages, 1 + (b + 1) * max_pages)
        table = jnp.asarray(table)
        # Mid-generation lengths: tables half full (the steady-state
        # decode regime, not the freshly-prefilled best case).
        kv_lens = jnp.full((B,), seq // 2 + qlen, jnp.int32)

        kv_mb = float(B * (seq // 2) * 2 * Kv * h * np.dtype(
            "float32" if degraded else "bfloat16").itemsize) / 1e6

        for kernel, blk in configs:
            if (kernel, blk, B, qlen) in completed:
                results.append(completed[(kernel, blk, B, qlen)])
                log(f"sweep kernel={kernel} block={blk} slots={B}: resumed")
                continue
            # "default": the pair the wrapper chooses from the call's
            # own shapes (ops/paged_attention.py).
            pair = (
                kernel_blocks(qlen, H // Kv, max_pages, page)
                if blk == "default"
                else tuple(int(x) for x in blk.split(":"))
            )
            fn = jax.jit(partial(paged_attention_ragged, blocks=pair))
            # Grid math for the diagnosis columns: one program a query
            # block.
            qb = pair[1]
            programs = -(-B * qlen // qb)
            q_rows = min(B * qlen, qb)
            try:
                for _ in range(warmup):
                    jax.block_until_ready(fn(q, kv_pages, table, kv_lens))
                t0 = time.monotonic()
                for _ in range(iters):
                    out = fn(q, kv_pages, table, kv_lens)
                jax.block_until_ready(out)
                ms = (time.monotonic() - t0) / iters * 1e3
                err = None
            except Exception as e:  # pragma: no cover - TPU-side compile loss
                ms = None
                err = str(e)[:200]
            if ms is not None:
                # Projection onto the flagship's full decode step: the
                # measured per-layer attention call x num_layers added
                # to the weight-read floor (see doc["roofline"]).
                step_ms = floor_ms + ms * flagship_layers
                projected = B * qlen / (step_ms / 1e3)
                mfu = round(pm.mfu(projected, peak_flops), 4)
                roofline_fraction = round(floor_ms / step_ms, 4)
                projected_toks = round(projected, 1)
            else:
                mfu = roofline_fraction = projected_toks = None
            row = {
                "kernel": kernel,
                "block": blk,
                "slots": B,
                "qlen": qlen,
                "latency_ms": None if ms is None else round(ms, 4),
                "toks_per_sec_equiv": (
                    None if not ms else round(B * qlen / (ms / 1e3), 1)
                ),
                "grid_programs": programs,
                "q_rows_per_program": q_rows,
                "kv_mb_walked": round(kv_mb, 2),
                "projected_toks_per_sec": projected_toks,
                "mfu": mfu,
                "roofline_fraction": roofline_fraction,
            }
            if err:
                row["error"] = err
            results.append(row)
            persist()
            log(
                f"sweep kernel={kernel} block={blk} slots={B}: "
                f"{'%.3f ms' % ms if ms else 'FAILED'}"
            )
    persist()
    return make_doc(results)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="1.3b", choices=["1.3b", "8b-int8"])
    p.add_argument("--paths", default="gather,paged")
    p.add_argument("--chunk", type=int, default=16)
    p.add_argument("--slots", type=int, default=32)
    p.add_argument(
        "--sweep", action="store_true",
        help="kernel-level decode-attention sweep (blocks x slots x "
             "kernels) -> one JSON document; see module docstring",
    )
    p.add_argument(
        "--smoke", action="store_true",
        help="tiny shapes for the sweep (CI/CPU; labeled degraded)",
    )
    p.add_argument("--out", default="", help="write the sweep JSON here (default stdout)")
    p.add_argument(
        "--resume", action="store_true",
        help="with --out: skip grid cells the existing JSON already "
             "measured and persist per-cell, so a flaky device mid-grid "
             "costs one cell, not the run",
    )
    p.add_argument(
        "--sweep-slots", default="",
        help="comma list of slot counts (default 16,48,64,96; smoke: 2,4)",
    )
    p.add_argument(
        "--sweep-blocks", default="",
        help="comma list of ragged-kernel blocks as pages:queries or "
             "'default' (default: default,8:32,16:32,32:8,64:4)",
    )
    p.add_argument(
        "--sweep-qlen", type=int, default=1,
        help="queries per slot (1 = decode)",
    )
    args = p.parse_args()

    if args.sweep:
        import json

        slots = (
            tuple(int(x) for x in args.sweep_slots.split(","))
            if args.sweep_slots
            else ((2, 4) if args.smoke else (16, 48, 64, 96))
        )
        blocks = (
            tuple(args.sweep_blocks.split(","))
            if args.sweep_blocks
            else (("default", "2:8") if args.smoke else ("default", "8:32", "16:32", "32:8", "64:4"))
        )
        if args.resume and not args.out:
            p.error("--resume requires --out (the file to resume from)")
        doc = run_sweep(
            slots_list=slots, blocks=blocks, smoke=args.smoke,
            qlen=args.sweep_qlen, out_path=args.out, resume=args.resume,
        )
        payload = json.dumps(doc, indent=2)
        if args.out:
            # run_sweep already persisted per-cell; the file is current.
            log(f"sweep written to {args.out}")
        else:
            print(payload)
        return

    import jax  # noqa: F401  (backend init before shape work)

    from kubeai_tpu.engine.coldstart import setup_compile_cache

    setup_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    kind = getattr(devs[0], "device_kind", "unknown")
    log(f"backend={jax.default_backend()} device={kind}")

    # --- dispatch RTT -------------------------------------------------------
    tinyf = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.float32)
    rtt = timeit(lambda: tinyf(x), n=50, warmup=5)
    print(f"dispatch_rtt_ms {rtt*1e3:.2f}")

    # --- model/config -------------------------------------------------------
    from kubeai_tpu.models import llama
    from kubeai_tpu.models.base import ModelConfig
    from kubeai_tpu.engine.sampling import sample

    if args.preset == "1.3b":
        mc = ModelConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=16, num_heads=16, num_kv_heads=8, dtype="bfloat16",
        )
        params = llama.init_params(mc, jax.random.key(0))
        wbytes = sum(np.prod(v.shape) * v.dtype.itemsize for v in jax.tree_util.tree_leaves(params))
    else:
        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        from bench import synth_int8_params
        mc = ModelConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
            dtype="bfloat16",
        )
        params = jax.device_put(synth_int8_params(mc))
        jax.block_until_ready(params)
        wbytes = sum(np.prod(v.shape) * v.dtype.itemsize for v in jax.tree_util.tree_leaves(params))
    log(f"weights on device: {wbytes/1e9:.2f} GB")

    B, K = args.slots, args.chunk
    S = 1024
    page = 64
    max_pages = S // page
    P = B * max_pages + 1
    bw = device_constants(str(kind)).hbm_gbps
    if bw:
        floor_ms = wbytes / (bw * 1e9) * 1e3
        print(f"roofline_step_ms {floor_ms:.2f}  (weights {wbytes/1e9:.2f} GB / {bw} GB/s)")
        print(f"roofline_toks_per_sec {B / (floor_ms/1e3):.0f}  (batch {B})")

    # --- raw decode step: one forward, no scan, no sampling -----------------
    lengths0 = np.full((B,), 512, np.int32)
    table = np.zeros((B, max_pages), np.int32)
    for b in range(B):
        table[b] = np.arange(1 + b * max_pages, 1 + (b + 1) * max_pages)
    tok0 = np.ones((B, 1), np.int32)

    for path in args.paths.split(","):
        mcp = mc.replace(use_paged_kernel=(path == "paged"))
        cache = llama.init_paged_cache(mcp, P, page)

        # Donate the pool as the engine does — without donation every
        # call pays a full-pool copy the real serving path never pays.
        @partial(jax.jit, donate_argnums=(1,))
        def fwd(params, cache, tokens, tbl, lengths):
            logits, cache = llama.decode_step_paged(params, mcp, tokens, cache, tbl, lengths)
            return logits, cache

        t0 = time.monotonic()
        logits, cache = fwd(params, cache, jnp.asarray(tok0), jnp.asarray(table), jnp.asarray(lengths0))
        jax.block_until_ready(logits)
        log(f"{path}: first decode call (compile) {time.monotonic()-t0:.1f}s")

        def run():
            nonlocal_cache = run.cache
            logits, run.cache = fwd(params, nonlocal_cache, jnp.asarray(tok0), jnp.asarray(table), jnp.asarray(lengths0))
            return logits

        run.cache = cache
        dt = timeit(run, n=20)
        cache = run.cache
        print(f"decode_step_ms[{path}] {dt*1e3:.2f}  -> {B/dt:.0f} tok/s at batch {B}")

        # --- fused chunk of K steps with sampling (the engine's real call) --
        mtk = 128

        def chunk_fn(params, cache, tbl, lengths, last, keys, temp, top_p, top_k):
            def body(carry, _):
                cache, lengths, last, keys = carry
                logits, cache = llama.decode_step_paged(
                    params, mcp, last[:, None], cache, tbl, lengths)
                step_keys = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                tok = sample(logits[:, 0], step_keys[:, 0], temp, top_p, top_k, max_top_k=mtk)
                return (cache, lengths + 1, tok, step_keys[:, 1]), tok
            (cache, lengths, last, keys), toks = jax.lax.scan(
                body, (cache, lengths, last, keys), None, length=K)
            return toks, cache, lengths, last, keys

        cjit = jax.jit(chunk_fn, donate_argnums=(1,))
        cache2 = llama.init_paged_cache(mcp, P, page)
        keys = jax.random.split(jax.random.key(0), B)
        temp = jnp.full((B,), 0.7, jnp.float32)
        top_p = jnp.full((B,), 0.95, jnp.float32)
        top_k = jnp.zeros((B,), jnp.int32)
        lengths = jnp.asarray(lengths0)
        last = jnp.asarray(tok0[:, 0])
        tbl = jnp.asarray(table)

        t0 = time.monotonic()
        toks, cache2, lengths, last, keys = cjit(params, cache2, tbl, lengths, last, keys, temp, top_p, top_k)
        jax.block_until_ready(toks)
        log(f"{path}: chunk compile {time.monotonic()-t0:.1f}s")

        n = 10
        t0 = time.monotonic()
        for _ in range(n):
            toks, cache2, lengths, last, keys = cjit(params, cache2, tbl, lengths, last, keys, temp, top_p, top_k)
        jax.block_until_ready(toks)
        dt = (time.monotonic() - t0) / n
        print(f"decode_chunk_ms[{path}] {dt*1e3:.2f}  ({K} steps) -> {B*K/dt:.0f} tok/s at batch {B}")
        del cache, cache2

    # --- sampling in isolation ---------------------------------------------
    logits_s = jax.random.normal(jax.random.key(1), (B, mc.vocab_size), jnp.float32)
    keys = jax.random.split(jax.random.key(0), B)
    temp = jnp.full((B,), 0.7, jnp.float32)
    top_p = jnp.full((B,), 0.95, jnp.float32)
    top_k = jnp.zeros((B,), jnp.int32)
    sfn = jax.jit(lambda lg, k: sample(lg, k, temp, top_p, top_k, max_top_k=128))
    dt = timeit(lambda: sfn(logits_s, keys), n=20)
    print(f"sample_ms {dt*1e3:.2f}")

    # --- prefill ------------------------------------------------------------
    for flash in (False, True):
        mcp = mc.replace(use_flash_prefill=flash, use_paged_kernel=False)
        cache = llama.init_paged_cache(mcp, P, page)
        ptoks = np.ones((1, 512), np.int32)

        @partial(jax.jit, donate_argnums=(1,))
        def pf(params, cache, tokens, tbl, lengths):
            return llama.prefill_paged_cold(params, mcp, tokens, cache, tbl, lengths)

        t0 = time.monotonic()
        logits, cache = pf(params, cache, jnp.asarray(ptoks), jnp.asarray(table[:1]), jnp.asarray([512]))
        jax.block_until_ready(logits)
        log(f"prefill flash={flash}: compile {time.monotonic()-t0:.1f}s")

        def runp():
            logits, runp.cache = pf(params, runp.cache, jnp.asarray(ptoks), jnp.asarray(table[:1]), jnp.asarray([512]))
            return logits

        runp.cache = cache
        dt = timeit(runp, n=10)
        print(f"prefill_512_ms[flash={flash}] {dt*1e3:.2f}")
        del cache


if __name__ == "__main__":
    main()
