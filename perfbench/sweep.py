#!/usr/bin/env python3
"""perfbench/sweep.py — find the knee of an open-loop cell, once.

    python3 perfbench/sweep.py --workload qwen7b-int8-chat-rate --base-rps 9.0 \
        [--fractions 0.4,0.55,0.7,0.85,1.0] [--stage-seconds 30] [--out chiprun_out/sweep.json]

One process, one engine start: the cell's set-up as run.py makes it, then
stages of the cell's own traffic at fixed fractions of --base-rps (the
requests per second the saturated cell completed). Per stage: requests
sent and met (time to first token from when due <= --ttft-ms AND mean time
per output token <= --tpot-ms; a failed request misses), the tails, and
the backlog at the stage's end (requests sent and not finished, and the
engine's own queue depth). Between stages the load drains.

The knee is the highest stage at which at least 90% of requests sent met
both limits with no backlog growing through the stage; the cell then runs
at four fifths of it, written as a number into its traffic file. The
limits only place the knee: no PR is held to them. A benchmark never
searches for a rate inside a run.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import engine_io  # noqa: E402
import loadgen  # noqa: E402
import run as runmod  # noqa: E402
import traffic  # noqa: E402


def stage(run, base: str, engine: str, spec: dict, rate: float, seconds: float, seed: int, limits) -> dict:
    spec = {**spec, "rate_rps": rate, "ramp_s": 0}
    plan = traffic.build(spec, seed, seconds)
    load = loadgen.Load(base, run.model, plan, seconds)
    load.start()
    inflight_mid = None
    while time.monotonic() < load.t_close:
        if inflight_mid is None and time.monotonic() >= load.t_open + seconds / 2:
            inflight_mid = sum(1 for r in list(load.records) if r.done is None and r.error is None)
        time.sleep(0.25)
    at_end = engine_io.scrape(engine)
    inflight_end = sum(1 for r in list(load.records) if r.done is None and r.error is None)
    records = load.finish()
    ttft_ms, tpot_ms = limits
    times = [t for t in map(loadgen.ttft_tpot_ms, records) if t is not None]
    ttfts, tpots = [t[0] for t in times], [t[1] for t in times]
    met = sum(1 for ttft, tpot in times if ttft <= ttft_ms and tpot <= tpot_ms)
    gaps = [1000.0 * (b - c) for r in records for c, b in zip(r.token_times, r.token_times[1:])]
    pct = lambda v, p: runmod.percentile(v, p) if v else None  # noqa: E731
    return {
        "rate_rps": rate, "seconds": seconds, "sent": len(records),
        "failed": sum(1 for r in records if not r.ok), "met": met,
        "met_pct": 100.0 * met / max(len(records), 1),
        "ttft_p50_ms": pct(ttfts, 50), "ttft_p90_ms": pct(ttfts, 90),
        "tpot_p50_ms": pct(tpots, 50), "tpot_p90_ms": pct(tpots, 90), "itl_p99_ms": pct(gaps, 99),
        "inflight_mid": inflight_mid, "inflight_end": inflight_end,
        "engine_queue_depth_end": at_end.value("kubeai_engine_queue_depth"),
        "engine_active_slots_end": at_end.value("kubeai_engine_active_slots"),
        "late_p99_ms": pct([1000.0 * (r.sent - r.due) for r in records if r.sent], 99),
        "drain_s": time.monotonic() - load.t_close,
    }


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--base-rps", type=float, required=True)
    p.add_argument("--fractions", default="0.4,0.55,0.7,0.85,1.0")
    p.add_argument("--stage-seconds", type=float, default=30)
    p.add_argument("--ttft-ms", type=float, default=1000)
    p.add_argument("--tpot-ms", type=float, default=100)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--out", default=os.path.join(runmod.ROOT, "chiprun_out", "sweep.json"))
    a = p.parse_args()
    args = argparse.Namespace(workload=a.workload, seed=a.seed, seconds=int(a.stage_seconds), trace=0,
                              rehearse=a.rehearse, keep=False)
    run = runmod.Run(args)
    shutil.rmtree(run.workdir, ignore_errors=True)
    os.makedirs(run.workdir)
    spec = traffic.load(run.cell["traffic"], a.rehearse)
    if spec["loop"] != "open":
        raise SystemExit("a sweep is of an open-loop cell")
    stages = []
    ckpt = run.phase_checkpoint()
    operator, base, op_log = run.start_operator(ckpt)
    try:
        run.first_request(base, operator, op_log)
        engine = engine_io.engine_address(base, run.model)
        before = engine_io.scrape(engine).value("kubeai_engine_jit_recompiles_total")
        for i, frac in enumerate(float(f) for f in a.fractions.split(",")):
            out = stage(run, base, engine, spec, frac * a.base_rps, a.stage_seconds, a.seed + i,
                        (a.ttft_ms, a.tpot_ms))
            out["fraction"] = frac
            stages.append(out)
            print(json.dumps(out), flush=True)
        after = engine_io.scrape(engine).value("kubeai_engine_jit_recompiles_total")
    finally:
        run.stop(operator)
        run.sweep()
    shutil.rmtree(os.path.join(run.workdir, "ckpt"), ignore_errors=True)
    # No growing backlog: what is in flight at the stage's end is no more
    # than at its middle plus what two seconds of arrivals bring.
    ok = [
        s for s in stages
        if s["met_pct"] >= 90.0 and s["inflight_end"] <= (s["inflight_mid"] or 0) + 2 * s["rate_rps"] + 2
    ]
    knee = max((s["rate_rps"] for s in ok), default=None)
    result = {
        "workload": a.workload, "base_rps": a.base_rps, "limits_ms": {"ttft": a.ttft_ms, "tpot": a.tpot_ms},
        "stages": stages, "knee_rps": knee, "cell_rate_rps": None if knee is None else 0.8 * knee,
        "recompiles": [before, after],
    }
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items() if k != "stages"}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
