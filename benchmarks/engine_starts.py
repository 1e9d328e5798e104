"""N engine starts of one configuration's deployment,
each its own process (load_engine_from_path with warmup, as the engine server
makes it), with jax's own compile events split by thread and by program.

    python3 benchmarks/engine_starts.py <perfbench/configs/x.json> <n starts> <out.jsonl> [--rehearse]

The parent never imports jax. One JSON line a start: the cold-start timeline,
per-thread sums of trace / lower / backend-compile (= cache key + read +
deserialize when warm) seconds, per-program rows, the device's peak bytes and
what lies in the compile-cache directory beside jax's own entries.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NOT_HF = {"source", "reduced", "assumed", "serving", "rehearsal"}


def child(ckpt: str, engine_args: list[str]) -> None:
    import logging
    import threading

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s: %(message)s")
    t0 = time.monotonic()
    import jax
    from jax import monitoring

    events: list = []

    def on_duration(event, secs, **kw):
        events.append((event, secs, kw.get("fun_name", ""), threading.current_thread().name, time.monotonic() - t0))

    monitoring.register_event_duration_secs_listener(on_duration)

    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.server import build_engine_from_args, make_engine_arg_parser

    cache_dir = setup_compile_cache()
    args = make_engine_arg_parser(require_model=True).parse_args(["--model", ckpt, *engine_args])
    eng, _ = build_engine_from_args(args, warmup=args.warmup)
    total = time.monotonic() - t0
    snap = eng.cold_start_timeline.snapshot()
    short = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend_compile",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
        "/jax/compilation_cache/compile_time_saved_sec": "compile_time_saved",
    }
    by_thread: dict = {}
    rows = []
    for event, secs, fun, thread, at in events:
        name = short.get(event, event)
        t = by_thread.setdefault(thread, {})
        t[name] = round(t.get(name, 0.0) + secs, 4)
        t[name + "_n"] = t.get(name + "_n", 0) + 1
        if secs >= 0.05:
            rows.append([round(at, 2), thread, name, fun, round(secs, 3)])
    extra = []
    for dirpath, _dirs, files in os.walk(cache_dir):
        for f in files:
            if not f.endswith(("-cache", "-atime")) and f != ".lockfile":
                p = os.path.join(dirpath, f)
                extra.append([os.path.relpath(p, cache_dir), os.path.getsize(p)])
    n_cache = sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))
    cache_bytes = sum(os.path.getsize(os.path.join(cache_dir, f)) for f in os.listdir(cache_dir) if f.endswith("-cache"))
    mem = jax.local_devices()[0].memory_stats() or {}
    print(json.dumps({
        "total_s": round(total, 3), "cold_start": snap, "by_thread": by_thread, "rows": rows,
        "jit_recompiles": eng.m_recompiles.value(), "peak_bytes": mem.get("peak_bytes_in_use"),
        "bytes_in_use": mem.get("bytes_in_use"), "cache_dir": cache_dir, "jax_cache_entries": n_cache,
        "jax_cache_bytes": cache_bytes, "beside": extra, "device": jax.devices()[0].device_kind,
    }), flush=True)
    os._exit(0)  # the engine's gauges keep threads; nothing to drain


def deployment(cfg_path: str, rehearse: bool, kind: str, out_path: str, seed: str) -> tuple:
    """A configuration's deployment made ready for child processes (also
    benchmarks/prefill_call_cost.py's): (name, work directory under
    `.perfbench_work/<kind>/`, the seeded checkpoint written there, the
    serving's engine arguments, the children's environment: the compile
    cache the engine server would use, the CPU for a rehearsal)."""
    with open(cfg_path) as f:
        config = json.load(f)
    hf = {k: v for k, v in config.items() if k not in NOT_HF}
    serving = dict(config["serving"])
    if rehearse:
        hf.update(config["rehearsal"]["hf_overrides"])
        serving.update({k: v for k, v in config["rehearsal"].items() if k != "hf_overrides"})
    name = os.path.basename(cfg_path).removesuffix(".json")
    work = os.path.join(ROOT, ".perfbench_work", kind, name)
    os.makedirs(work, exist_ok=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    hf_path = os.path.join(work, "hf_config.json")
    with open(hf_path, "w") as f:
        json.dump(hf, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_compile_cache"))
    ckpt = os.path.join(work, "ckpt")
    subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "children.py"), "checkpoint", ckpt, hf_path, seed],
        env={**env, "JAX_PLATFORMS": "cpu"}, check=True, stdout=subprocess.DEVNULL,
    )
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    return name, work, ckpt, serving["engine_args"], env


def main() -> int:
    if sys.argv[1] == "--child":
        child(sys.argv[2], json.loads(sys.argv[3]))
        return 0
    cfg_path, n, out_path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    rehearse = "--rehearse" in sys.argv
    t = time.monotonic()
    name, work, ckpt, engine_args, env = deployment(cfg_path, rehearse, "starts", out_path, "4800000001")
    print(f"{name}: checkpoint {time.monotonic() - t:.1f}s", flush=True)
    for i in range(n):
        t = time.monotonic()
        with open(os.path.join(work, f"start-{i}.log"), "wb") as err:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child", ckpt, json.dumps(engine_args)],
                env=env, stdout=subprocess.PIPE, stderr=err,
            )
        wall = time.monotonic() - t
        lines = proc.stdout.decode().strip().splitlines()
        if proc.returncode != 0 or not lines:
            with open(os.path.join(work, f"start-{i}.log"), "rb") as f:
                tail = f.read()[-3000:].decode(errors="replace")
            print(f"{name}: start {i} exited {proc.returncode} after {wall:.1f}s\n{tail}", flush=True)
            return 1
        rec = json.loads(lines[-1])
        rec.update(config=name, start=i, wall_s=round(wall, 2))
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        cs = rec["cold_start"]
        print(json.dumps({
            "config": name, "start": i, "wall_s": rec["wall_s"], "total_s": rec["total_s"],
            "phases": {k: [v.get("start_s"), v.get("end_s")] for k, v in cs["phases"].items()},
            "warm_compile": {k: v for k, v in (cs["attrs"].get("warm_compile") or {}).items() if k != "programs"},
            "warmup": cs["attrs"].get("warmup"), "by_thread": rec["by_thread"],
            "peak_bytes": rec["peak_bytes"], "beside": rec["beside"], "jax_cache": [rec["jax_cache_entries"], rec["jax_cache_bytes"]],
        }), flush=True)
    import shutil

    shutil.rmtree(ckpt, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
