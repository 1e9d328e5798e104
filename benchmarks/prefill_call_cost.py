"""What a prefill chunk call costs on THIS device, by call shape, beside what
the engine takes it to cost (engine/core.py: Engine.call_cost, call_seconds:
measured at warm-up, nothing else sets it; `ms_by_the_line` a shape is by the
line the engine plans with):
one engine start of a configuration's deployment (load_engine_from_path with
warmup, as the engine server makes it), then every chunk program of the one
list (engine/step_programs.py) run alone, `--runs` times each, on the trash
page, a `block_until_ready` a call.

    python3 benchmarks/prefill_call_cost.py <perfbench/configs/x.json> <out.jsonl> [--runs 8] [--rehearse]

The parent never imports jax. One JSON line: per shape the median and least
milliseconds and the line's, the warm-up's seconds, the line fitted through the one-slot
shapes of 1024 rows and the wide chunk (read of the weights + a 1024 rows),
the cut of a 1500-token prompt, and the device's peak bytes after all of it
(every program has then run once: the deployment's `memory_peak_bytes` short
of a window's pages). Times of a CPU run (`--rehearse`) are never a device's.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from engine_starts import deployment  # noqa: E402  (the same checkpoint, arguments and environment as its starts)


def child(ckpt: str, engine_args: list[str], runs: int) -> None:
    import logging

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s: %(message)s")
    t0 = time.monotonic()
    import jax
    import numpy as np

    from kubeai_tpu.engine import core
    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.server import build_engine_from_args, make_engine_arg_parser

    setup_compile_cache()
    args = make_engine_arg_parser(require_model=True).parse_args(["--model", ckpt, *engine_args])
    eng, _ = build_engine_from_args(args, warmup=args.warmup)
    started = time.monotonic() - t0
    cfg, cost = eng.cfg, eng.call_cost  # as warm-up measured it
    Kb, cols = cfg.max_logit_bias, eng._page_table.shape[1]
    shapes = []
    rng = np.random.default_rng(54)
    for call in eng._table.programs.calls():
        if call.member != "prefill_chunk_jit":
            continue
        n, rows = call.shape
        ms = []
        for _ in range(runs + 1):
            t = time.monotonic()
            *_, eng._cache, eng._adm_toks, _counters = eng._step(
                call.member, call.shape, eng.params,
                # Tokens as the benchmark's prompts draw them: a row of ONE token would ask for one set of experts.
                rng.integers(0, 259, (n, rows)).astype(np.int32), np.zeros((n,), np.int32), np.full((n,), rows - 1, np.int32),
                np.zeros((n, cols), np.int32), np.arange(n, dtype=np.int32), np.zeros((n,), np.uint32),
                np.ones((n,), np.float32), np.ones((n,), np.float32), np.zeros((n,), np.int32),
                np.zeros((n, Kb), np.int32), np.zeros((n, Kb), np.float32), eng._adm_toks, eng._cache,
            )
            jax.block_until_ready(eng._adm_toks)
            ms.append((time.monotonic() - t) * 1e3)
        ms = ms[1:]  # the first run of a loaded executable pays its allocation
        shapes.append({
            "slots": n, "rows": rows, "ms_median": round(statistics.median(ms), 3), "ms_min": round(min(ms), 3),
            "ms_by_the_line": round(core.call_seconds(cost, n, rows) * 1e3, 3),
        })
    by = {(s["slots"], s["rows"]): s["ms_median"] for s in shapes}
    top, wide = max(cfg.prefill_buckets), core.wide_chunk(cfg)
    fitted = None
    if wide > top:
        per_row = (by[(1, wide)] - by[(1, top)]) / (wide - top)
        fitted = {"read_weights_ms": round(by[(1, top)] - per_row * top, 3), "ms_per_1024_rows": round(per_row * 1024, 3)}
    mem = jax.local_devices()[0].memory_stats() or {}
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "start_s": round(started, 2),
        "warmup_s": (eng.cold_start_timeline.snapshot()["attrs"].get("warmup") or {}).get("seconds"),
        "measured_at_warm_up": {"read_weights_ms": round(cost[0] * 1e3, 3), "ms_per_1024_rows": round(cost[1] * 1024e3, 3)},
        "fitted": fitted, "shapes": shapes, "plan_1500": core.prefill_plan(cfg, 1500, cost),
        "two_slot_rows": list(core.pair_rows(cfg, eng.model_config)), "peak_bytes": mem.get("peak_bytes_in_use"),
        "bytes_in_use": mem.get("bytes_in_use"), "bytes_limit": mem.get("bytes_limit"),
        "warm_compile": {k: v for k, v in (eng.cold_start_timeline.snapshot()["attrs"].get("warm_compile") or {}).items() if k != "programs"},
    }), flush=True)
    os._exit(0)  # the engine's gauges keep threads; nothing to drain


def main() -> int:
    if sys.argv[1] == "--child":
        child(sys.argv[2], json.loads(sys.argv[3]), int(sys.argv[4]))
        return 0
    cfg_path, out_path = sys.argv[1], sys.argv[2]
    rehearse = "--rehearse" in sys.argv
    runs = int(sys.argv[sys.argv.index("--runs") + 1]) if "--runs" in sys.argv else 8
    name, work, ckpt, engine_args, env = deployment(cfg_path, rehearse, "call_cost", out_path, "5400000001")
    log_path = os.path.join(work, "child.log")
    with open(log_path, "wb") as err:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", ckpt, json.dumps(engine_args), str(runs)],
            env=env, stdout=subprocess.PIPE, stderr=err,
        )
    lines = proc.stdout.decode().strip().splitlines()
    if proc.returncode != 0 or not lines:
        with open(log_path, "rb") as f:
            print(f"{name}: the child exited {proc.returncode}\n{f.read()[-3000:].decode(errors='replace')}", flush=True)
        return 1
    rec = {"config": name, **json.loads(lines[-1])}
    with open(out_path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
