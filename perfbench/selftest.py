#!/usr/bin/env python3
"""Checks of the yardstick itself, on the CPU, in seconds:

    python3 perfbench/selftest.py

 * the trace reduction on a hand-made event list (overlapping lines,
   nested and overlapping events, events crossing the window's edge, an
   empty device plane is an error and not 0);
 * the same on one small recorded trace (testdata/cpu-small.xplane.pb, a
   CPU engine's own /debug/profile: the rehearsal's platform);
 * the traffic generator: every seed gives the same multiset of sizes and
   the same arrival span, in another order, and takes seeds past 2**31;
 * resultline.py refuses each way a last line went wrong before, and a
   `--trace 2` line that lacks either kind of metric; every entry that is a
   share of a peak (unit `%`, a reader that divides by one) is refused at
   106, whatever its name ends in, and a `%` that is no such share is not;
 * every idle piece of the device put down to the scheduler segment beside
   it (idle_attribution.py), on hand-made events: a gap under two
   segments, under none, across the window's edge, nested segments, shares
   that add up to the idle share;
 * `--trace 2`: when the tail's trace may begin, on records of each cell's
   kind; the operator's command and environment are those of `--trace 0`;
   a tail's plan has the window's plan as its prefix;
 * BENCHMARK.json: every name resolves to a file, every metric's `moves`
   is reported where the metric is; every file under layer_metrics/ is
   named by exactly one entry; every entry lists its cells; no two entries
   are one measurement (the fold of PR 50 holds), and how many of the 128
   are free; no cell declares fewer per-layer metrics than it was accepted
   with; every name under `tail_view` (trace_in_run.json) is an entry's;
 * the window families' decode rooflines take bytes and time from the same
   seconds: through run.py's own `read_layer_metric`, a context whose
   measured window held caches half as long as its tail's reads the
   tail's, `window_mfu.*` the window's;
 * families/: same seed, same bytes (the shards of both dense
   configurations against the digests the harness wrote before the plan
   moved into families/, testdata/checkpoint-digests.json); every
   configuration has a family file with the three names; the dense
   decoder's shallow cut is the first shards of its full checkpoint; and a
   family is added by files alone (a copy of the benchmark, a toy family
   whose layers depend on the depth, run.py's own checkpoint and logits
   phases, no file of the copy changed).

Not part of tests/ (this PR may add files only under perfbench/).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import children  # noqa: E402
import engine_io  # noqa: E402
import idle_attribution as ia  # noqa: E402
import loadgen  # noqa: E402
import resultline  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
import traffic  # noqa: E402

FAILED: list[str] = []


def check(name: str, ok: bool, detail="") -> None:
    print(("ok   " if ok else "FAIL ") + name + (f": {detail}" if not ok else ""))
    if not ok:
        FAILED.append(name)


def raises(fn, exc) -> bool:
    try:
        fn()
    except exc:
        return True
    return False


def hand_made() -> None:
    # A window of [100, 1100). One device, its ops line:
    ops = [
        ("a", 0, 150),       # crosses the left edge: 50 inside
        ("b", 200, 100),     # [200, 300)
        ("b", 250, 100),     # overlaps the one before: union [200, 350)
        ("k", 260, 20),      # nested
        ("c", 1000, 500),    # crosses the right edge: 100 inside
        ("d", 5000, 10),     # outside
    ]
    modules = [("m1", 190, 170), ("m2", 990, 600)]  # m1 wholly inside, m2 cut
    busy, merged = tr.union_ns(((s, s + d) for _, s, d in ops), 100, 1100)
    check("union clips and merges", busy == 50 + 150 + 100, busy)
    check("merged pieces", merged == [(100, 150), (200, 350), (1000, 1100)], merged)
    gaps = tr.gaps_ns(merged, 100, 1100)
    check("gaps", gaps == [(150, 200), (350, 1000)], gaps)
    out = tr.reduce_events([{"name": "/device:TPU:0", "ops": ops, "modules": modules}], (100, 1100))
    check("busy_s <= window_s", 0 < out["busy_s"] <= out["window_s"], out)
    check("busy is a union, not a sum", abs(out["busy_s"] - 300e-9) < 1e-15, out["busy_s"])
    check("sum of durations would exceed", sum(d for _, _, d in ops) > 300)
    check("whole runs only", out["modules_s"]["m1"] == (170e-9, 1, 170e-9, 1) and out["modules_s"]["m2"][3] == 0, out["modules_s"])
    check("ops inside whole runs", out["ops_in_modules_s"] == {"m1": {"b": (200e-9, 2), "k": (20e-9, 1)}}, out["ops_in_modules_s"])
    check("longest gap first", out["gaps_s"][0] == (250e-9, 650e-9), out["gaps_s"])
    # Two lines that cover the same time (ops and modules): feeding both as
    # "ops" is what gave busy > window once; the reader takes ONE line.
    two = tr.reduce_events(
        [{"name": "d0", "ops": ops, "modules": []}, {"name": "d1", "ops": [("x", 100, 1000)], "modules": []}],
        (100, 1100),
    )
    check("mean over devices", abs(two["busy_s"] - (300e-9 + 1000e-9) / 2) < 1e-15, two["busy_s"])
    check("empty device plane is an error", raises(
        lambda: tr.reduce_events([{"name": "d0", "ops": [], "modules": []}], (100, 1100)), tr.TraceError))
    check("ops outside the window is an error", raises(
        lambda: tr.reduce_events([{"name": "d0", "ops": [("d", 5000, 10)], "modules": []}], (100, 1100)), tr.TraceError))
    check("no plane is an error", raises(lambda: tr.reduce_events([], (0, 1)), tr.TraceError))


def idle_by_host() -> None:
    # The window is [100, 1100). The device is busy in three pieces:
    busy = [(100, 150), (200, 350), (1000, 1100)]  # so idle: [150, 200) and [350, 1000)
    seg = [
        ("admit", 120, 300, {}),                                   # [120, 420)
        ("prefill", 160, 100, {"kind": "group", "tokens": 7}),     # nested in it: [160, 260)
        ("fetch_wait", 500, 400, {"of": "chunk"}),                 # [500, 900)
        ("emit", 950, 500, {"tokens": 5}),                         # crosses the window's edge
        ("idle", 5000, 10, {}),                                    # outside
    ]
    flat = ia.flatten(seg, 100, 1100)
    check("nested segments: the innermost wins, the outer one resumes", flat == [
        (120, 160, "admit", {}), (160, 260, "prefill", {"kind": "group", "tokens": 7}), (260, 420, "admit", {}),
        (500, 900, "fetch_wait", {"of": "chunk"}), (950, 1100, "emit", {"tokens": 5}),
    ], flat)
    gaps = ia.attribute([(150, 200), (350, 1000)], flat)
    check("a gap under two segments", gaps[0]["by"] == {"admit": 10, "prefill": 40} and gaps[0]["most"][2] == "prefill", gaps[0])
    check("a gap under segments and under none", gaps[1]["by"] == {"admit": 70, "fetch_wait": 400, "emit": 50, "other": 130}, gaps[1])
    check("a gap under no segment at all is `other`", ia.attribute([(10, 30)], [])[0]["by"] == {"other": 20})
    t = ia.table(busy, seg, 100, 1100)
    check("idle seconds by cause add up to the idle time", abs(sum(t["idle_by_cause_s"].values()) - t["idle_s"]) < 1e-15
          and abs(t["idle_s"] - 700e-9) < 1e-15, t)
    one = tr.reduce_events([{"name": "d0", "ops": [("x", s, e - s) for s, e in busy], "modules": []}], (100, 1100))
    shares = {c: 100.0 * v / t["window_s"] for c, v in t["idle_by_cause_s"].items()}
    check("the shares add up to the idle share of the same trace",
          abs(sum(shares.values()) - 100.0 * (1 - one["busy_s"] / one["window_s"])) < 1e-9, shares)
    check("split by fetch_wait's `of` and prefill's `kind`",
          t["idle_by_detail_s"] == {"fetch_wait.of=chunk": 400e-9, "prefill.kind=group": 40e-9}, t["idle_by_detail_s"])
    check("gaps counted by the cause that covers most", t["gaps_by_leading_cause"] == {"fetch_wait": 1, "prefill": 1}, t)
    check("a segment across the window's edge is clipped", t["idle_by_cause_s"]["emit"] == 50e-9, t["idle_by_cause_s"])
    check("longest gap first, with its own causes", t["gaps"][0]["seconds"] == 650e-9
          and list(t["gaps"][0]["by_cause_s"]) == ["fetch_wait", "other", "admit", "emit"], t["gaps"][0])
    text = ia.label({"at_s": 1.15331, "seconds": 0.2, "by_cause_s": {"emit": 0.122, "other": 0.054, "dispatch": 0.024},
                     "most": {"cause": "emit", "attrs": {"tokens": 512}}})
    check("a gap's label names its own causes", text == "at +1.1533s, host: emit 61% [tokens=512], other 27%, dispatch 12%", text)
    check("a label is at most 200 characters", len(ia.label({"at_s": 0.0, "seconds": 1.0, "by_cause_s": {f"cause_{i}": 1.0 for i in range(40)},
                                                             "most": None})) <= 200)
    check("a device that never idles has no gap", ia.table([(100, 1100)], seg, 100, 1100)["idle_s"] == 0.0)


def rec(due, first=None, n=0, done=None, error=None):
    r = loadgen.Record(traffic.Request("x", max(n, 1)), due)
    r.sent = due
    r.token_times = [] if first is None else [first + 0.01 * i for i in range(n)]
    r.done, r.error, r.ok = done, error, done is not None
    return r


def in_run(tmp: str) -> None:
    """--trace 2: what the harness decides without a chip."""
    # The window is [10, 60). When may the tail's trace begin?
    ramp = rec(5.0, first=6.0, n=3)                       # due in the ramp, still streaming: nobody reads it
    streaming = rec(50.0, first=51.0, n=4)                # due in the window, first token there, not done
    waiting = rec(59.5)                                   # due in the window, no token yet
    done = rec(20.0, first=20.5, n=8, done=21.0)
    failed = rec(30.0, error="status 500")
    tail = rec(61.0)                                      # sent in the tail: nobody reads it
    records = [ramp, streaming, waiting, done, failed, tail]
    still = lambda metrics, recs=records: run.open_records(metrics, recs, 10.0, 60.0)  # noqa: E731
    check("output_tok_s reads nothing after the close: the trace begins at once (chat-sat, reason-sat, longdoc-sat)",
          still({"output_tok_s", "setup_s"}) == (0, 60.0), still({"output_tok_s", "setup_s"}))
    check("ttft_p50_ms waits for the first token of every request due in the window (docqa)",
          still({"output_tok_s", "ttft_p50_ms", "setup_s"})[0] == 1)
    check("tpot_mean_ms waits for their whole lives (chat-rate)", still({"tpot_mean_ms", "setup_s"})[0] == 2)
    waiting.token_times = [61.5]
    check("a first token closes a record for ttft_p50_ms, and says when", still({"ttft_p50_ms"}) == (0, 61.5), still({"ttft_p50_ms"}))
    check("but not for tpot_mean_ms", still({"tpot_mean_ms"})[0] == 2)
    streaming.done, waiting.done = 62.0, 63.25
    check("the last record closed", still({"tpot_mean_ms"}) == (0, 63.25), still({"tpot_mean_ms"}))
    check("a failed request is closed", still({"tpot_mean_ms"}, [failed]) == (0, 60.0))
    check("an end-to-end metric without a row in the table is an error", raises(lambda: still({"goodput"}), KeyError))
    bench = resultline.load_benchmark()
    check("every declared end-to-end metric has a row", all(m["name"] in run.READS_AFTER_CLOSE for m in bench["end_to_end"]))

    # The same operator, started the same way, in modes 0 and 2.
    import argparse
    import subprocess as sp
    from unittest import mock

    started = {}
    for mode in (0, 1, 2):
        r = run.Run(argparse.Namespace(workload=bench["workloads"][0]["name"], seed=7, rehearse=True, trace=mode, seconds=1, keep=False))
        r.workdir = os.path.join(tmp, "work")
        os.makedirs(r.workdir, exist_ok=True)

        class Started(Exception):
            pass

        def popen(argv, env=None, **kw):
            started[mode] = (argv[:7] + argv[8:], env)  # the port is drawn anew each time
            raise Started

        with mock.patch.object(sp, "Popen", popen):
            raises(lambda: r.start_operator(os.path.join(tmp, "ckpt")), Started)
    check("the operator's command is the same in modes 0 and 2", started[0][0] == started[2][0], (started[0][0], started[2][0]))
    check("and its environment", started[0][1] == started[2][1],
          {k for k in set(started[0][1]) | set(started[2][1]) if started[0][1].get(k) != started[2][1].get(k)})
    check("the gate is set in every mode (it is read when the endpoint is called)",
          all(started[m][1].get("KUBEAI_DEBUG_PROFILE") == "1" for m in (0, 1, 2)))

    # The tail's plan holds the window's as its prefix, whatever the loop.
    key = lambda q: (q.prompt, q.max_tokens, q.tag, q.due_s)  # noqa: E731
    for cell in bench["workloads"]:
        spec = traffic.load(cell["traffic"])
        short, longer = run.build_plan(spec, 2**31 + 3, 50), run.build_plan(spec, 2**31 + 3, 50, 57.0)
        same = [key(q) for q in longer.shared[: len(short.shared)]] == [key(q) for q in short.shared] and all(
            [key(q) for q in lc[: len(sc)]] == [key(q) for q in sc] for sc, lc in zip(short.per_client, longer.per_client))
        more = len(longer.shared) > len(short.shared) or bool(short.per_client)
        check(f"{cell['traffic']}: the tail's plan has the window's as its prefix, and requests beyond it", same and more)

    # A context's tail view: the tail's polls where a reader brackets the
    # trace with them, the run's one context for everything else.
    ctx = run.Context()
    ctx.before, ctx.after, ctx.polls, ctx.window_s, ctx.records = "b", "a", ["p"], 50.0, ["r"]
    view = run.TailView(ctx, before="tb", after="ta", polls=["tp"], window_s=5.0)
    view.kept_by_a_reader = 1
    check("tail view", (view.before, view.after, view.polls, view.window_s, view.records) == ("tb", "ta", ["tp"], 5.0, ["r"])
          and ctx.kept_by_a_reader == 1 and ctx.before == "b")

    # The load goes on past the window only when told to, and stops when told.
    plan = traffic.Plan(loop="closed", ramp_s=0.0, drain_s=1.0, clients=0)
    load = loadgen.Load("127.0.0.1:9", "m", plan, 0.01)
    load.start()
    check("without hold the sends end with the window", load.t_end == load.t_close)
    held = loadgen.Load("127.0.0.1:9", "m", plan, 0.01, hold=True)
    held.start()
    check("with hold they go on", held.t_end == float("inf"))
    held.end_sending()
    check("until told to stop", held.t_close <= held.t_end < float("inf") or held.t_end <= held.t_close)


def _counters(at: float, chunks: int, keys: int, tokens: int, ended: int | None = None) -> engine_io.Scrape:
    """The engine's /metrics after *chunks* decode chunks of 8 steps were
    dispatched (*ended* of them have ended) whose 24 queries each saw
    *keys* keys in a full layer and 2048 in a window layer (one layer of
    each kind counted), half of the experts hit."""
    steps = 8 * chunks
    return engine_io.Scrape("\n".join([
        f'kubeai_engine_decode_rows_total{{state="live"}} {steps * 20}', f'kubeai_engine_decode_rows_total{{state="idle"}} {steps * 4}',
        f'kubeai_engine_attn_pairs_total{{kind="full",phase="decode"}} {steps * 24 * keys}',
        f'kubeai_engine_attn_pairs_total{{kind="window",phase="decode"}} {steps * 24 * 2048}',
        'kubeai_engine_attn_pairs_total{kind="full",phase="prefill"} 0',
        'kubeai_engine_attn_pairs_total{kind="window",phase="prefill"} 0',
        f'kubeai_engine_moe_experts_hit_total{{phase="decode"}} {steps * 32}',
        f'kubeai_engine_moe_expert_reads_possible_total{{phase="decode"}} {steps * 64}',
        f'kubeai_engine_step_seconds_count{{phase="decode_chunk"}} {chunks if ended is None else ended}',
        f"kubeai_engine_prefill_tokens_total {tokens}", f"kubeai_engine_generated_tokens_total {steps * 24}",
    ]), at)


def same_seconds() -> None:
    """A share of a roofline divides bytes by time: both of the traced
    seconds. The measured window [0, 50) decoded over caches of 4000 keys,
    the tail [60, 65) over 8000, and the trace is the tail's: through
    run.py's own `read_layer_metric`, every decode roofline of the two
    window families reads the tail's keys (104.9% was the window's mean
    over the tail's time: ledger, PR 43), `window_mfu.*` the window's."""
    import argparse

    bench = resultline.load_benchmark()
    by = lambda **sec: {"total_s": 1.6, "by_scope_s": {k.replace("_", "."): v for k, v in sec.items()}}  # noqa: E731
    for cell, suffix in (("smallthinker-bf16-longdoc-sat", ""), ("trinitymini-bf16-mixedlen-sat", ".afm")):
        if cell not in [w["name"] for w in bench["workloads"]]:
            continue
        r = run.Run(argparse.Namespace(workload=cell, seed=7, rehearse=False, trace=2, seconds=50, keep=False))
        ctx = run.Context()
        ctx.rehearsal, ctx.hf, ctx.serving, ctx.peaks = False, r.hf, r.serving, {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        ctx.before, ctx.polls, ctx.after, ctx.window_s = _counters(0.0, 0, 0, 0), [], _counters(50.0, 400, 4000, 10**6), 50.0
        ctx.trace_t0, ctx.trace_t1 = 60.5, 64.5
        ctx.trace = {"window_s": 4.0, "modules_s": {"jit__unknown(7)": (1.6, 1.6, 1.6, 10.0)}}
        ctx.swa_scope_shares = {
            "layers": {"jit__unknown(7)": by(attn_full=0.20, attn_window=0.40, moe_experts=0.64)},
            "kernels": {"jit__unknown(7)": by(attn_full=0.08, attn_window=0.16, attn_kernel=0.36, moe_experts=0.64)},
        }
        ctx.tail_view = run.TailView(
            ctx, before=_counters(60.0, 400, 4000, 10**6), polls=[], after=_counters(65.0, 440, 4364, 10**6 + 10**5, ended=439), window_s=5.0,
        )  # 440 x 4364 - 400 x 4000 = 40 x 8004: the tail's 40 chunks saw twice the keys; the last has not ENDED

        # A run traced inside its window (`--trace 1`) has no tail: it reads the context it is given.
        direct = run.Run(argparse.Namespace(workload=cell, seed=7, rehearse=False, trace=1, seconds=50, keep=False)).read_layer_metric
        full, window = "full_attn_decode_roofline" + suffix, "window_attn_decode_roofline" + suffix
        step = "decode_step_roofline" + (suffix or ".swa")
        got = {n: r.read_layer_metric(n, ctx) for n in (full, window, step)}
        check(f"{cell}: the decode rooflines read something", all(v is not None and v > 0 for v in got.values()), got)
        check(f"{cell}: {full} reads the keys of the traced seconds, twice the measured window's",
              abs(got[full] / direct(full, ctx) - 8004 / 4000) < 1e-9, (got[full], direct(full, ctx)))
        # Pairs are counted where a chunk is dispatched: so are the steps they are divided by (40, not the 39 that ended).
        check(f"{cell}: {window} reads the same 2048 either way", abs(got[window] - direct(window, ctx)) < 1e-9 * got[window])
        check(f"{cell}: {step} counts the tail's keys too", got[step] == direct(step, ctx.tail_view) > direct(step, ctx))
        mfu = next(n for n in resultline.declared(bench, cell, 1) if n.startswith("window_mfu"))
        check(f"{cell}: {mfu} keeps the measured window", r.read_layer_metric(mfu, ctx) == direct(mfu, ctx) != direct(mfu, ctx.tail_view))


def recorded() -> None:
    path = os.path.join(HERE, "testdata", "cpu-small.xplane.pb")
    if not os.path.exists(path):
        check("recorded trace present", False, path)
        return
    with open(os.path.join(HERE, "trace.json")) as f:
        spec = json.load(f)
    with open(os.path.join(HERE, "testdata", "cpu-small.expected.json")) as f:
        want = json.load(f)
    planes, window, notes = tr.read_xplane(path, {**spec["cpu"], "profile_seconds": want["profile_seconds"]})
    out = tr.reduce_events(planes, window)
    check("recorded: window from the profiler's sleep", notes["window_from"].startswith("host event"), notes["window_from"])
    check("recorded: window_s", abs(out["window_s"] - want["window_s"]) < 1e-6, out["window_s"])
    check("recorded: busy_s", abs(out["busy_s"] - want["busy_s"]) < 1e-6, out["busy_s"])
    check("recorded: 0 < busy <= window", 0 < out["busy_s"] <= out["window_s"])
    check("recorded: programs found", set(want["modules"]) <= set(out["modules_s"]), sorted(out["modules_s"]))
    check("recorded: wrong plane is an error", raises(
        lambda: tr.read_xplane(path, {**spec["tpu"], "profile_seconds": None}), tr.TraceError))


def generator() -> None:
    for name in ("chat-sat", "chat-rate", "docqa"):
        spec = traffic.load(name)
        plans = [traffic.build(spec, seed, 40) for seed in (1, 2**31 + 11)]

        def sizes(p):
            reqs = p.shared or [r for c in p.per_client for r in c]
            return sorted((r.prompt_tokens, r.max_tokens) for r in reqs)

        a, b = (sizes(p) for p in plans)
        check(f"{name}: same sizes for every seed", a == b)
        first = lambda p: [r.prompt for r in (p.shared or p.per_client[0])[:3]]  # noqa: E731
        check(f"{name}: other bytes for another seed", first(plans[0]) != first(plans[1]))
        check(f"{name}: same seed, same plan", first(plans[0]) == first(traffic.build(spec, 1, 40)))
        cap = spec["max_total_tokens"]
        check(f"{name}: fits the engine", all(p + o <= cap for p, o in a))
        if plans[0].loop == "open":
            ends = [p.shared[-1].due_s for p in plans]
            check(f"{name}: same arrival span", abs(ends[0] - ends[1]) < 1e-6, ends)


def last_line() -> None:
    bench = resultline.load_benchmark()
    cell = bench["workloads"][0]["name"]
    e2e = resultline.declared(bench, cell, False)
    layer = resultline.declared(bench, cell, True)
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1.2e10}
    good0 = {"correct": True, "attempted": 10, "failed": 0, "device": dev,
             "metrics": {n: {"value": 1.5, "unit": u} for n, u in e2e.items()}}
    good1 = {"correct": True, "attempted": 10, "failed": 0, "device": {**dev, "window_s": 4.0, "busy_s": 3.0},
             "metrics": {n: {"value": 1.5, "unit": u} for n, u in layer.items()}}
    # --trace 2: both kinds side by side, and the traced line's device keys.
    good2 = {**good1, "metrics": {**good0["metrics"], **good1["metrics"]},
             "breakdown": {"device_ops": [["fusion in jit__unknown", 0.5]],
                           "idle_gaps": [["at +1.1533s, host: emit 61% [tokens=512], other 27%, dispatch 12%", 0.004]]}}
    bad2 = lambda obj, **kw: bool(resultline.problems(obj, bench, cell, 2, 1, **kw))  # noqa: E731
    check("good line of a run that traced itself", not bad2(good2), resultline.problems(good2, bench, cell, 2, 1))
    check("declared in mode 2 = the end-to-end and the per-layer metrics",
          resultline.declared(bench, cell, 2) == {**e2e, **layer} and resultline.declared(bench, cell, True) == layer
          and resultline.declared(bench, cell, False) == e2e)
    check("mode 2 without the end-to-end metrics refused", bad2({**good2, "metrics": good1["metrics"]}))
    check("mode 2 without the per-layer metrics refused", bad2({**good2, "metrics": good0["metrics"]}))
    check("mode 2: a per-layer metric may be missed", not bad2(
        {**good2, "metrics": {k: v for k, v in good2["metrics"].items() if k != next(iter(layer))}}, may_miss={next(iter(layer))}))
    check("mode 2: an end-to-end metric may not", bad2(
        {**good2, "metrics": {k: v for k, v in good2["metrics"].items() if k != "setup_s"}}, may_miss={"setup_s"}))
    check("mode 2 without window_s refused", bad2({**good2, "device": dev}))
    check("mode 2: a malformed breakdown refused", bad2({**good2, "breakdown": {"device_ops": [], "idle_gaps": [["x"]]}}))
    check("a mode 2 line is no mode 0 line", bool(resultline.problems(good2, bench, cell, 0, 1)))
    bad = lambda obj, trace: bool(resultline.problems(obj, bench, cell, trace, 1))  # noqa: E731
    check("good untraced line", not bad(good0, False), resultline.problems(good0, bench, cell, False, 1))
    check("good traced line", not bad(good1, True), resultline.problems(good1, bench, cell, True, 1))
    name = next(iter(layer))
    check("busy over window refused", bad({**good1, "device": {**good1["device"], "busy_s": 4.5}}, True))
    check("busy 0 refused", bad({**good1, "device": {**good1["device"], "busy_s": 0}}, True))
    check("traced line without window_s refused", bad({**good1, "device": dev}, True))
    check("NaN refused", bad({**good1, "metrics": {**good1["metrics"], name: {"value": float("nan"), "unit": layer[name]}}}, True))
    check("null refused", bad({**good1, "metrics": {**good1["metrics"], name: {"value": None, "unit": layer[name]}}}, True))
    check("wrong unit refused", bad({**good1, "metrics": {**good1["metrics"], name: {"value": 1, "unit": "parsecs"}}}, True))
    check("end-to-end metric in a traced line refused", bad({**good1, "metrics": {**good1["metrics"], **good0["metrics"]}}, True))
    check("missing metric refused", bad({**good0, "metrics": {}}, False))
    check("cpu refused", bad({**good0, "device": {**dev, "platform": "cpu"}}, False))
    check("wrong count refused", bad({**good0, "device": {**dev, "count": 4}}, False))
    check("render refuses NaN", raises(lambda: resultline.render({"x": float("nan")}), ValueError))


def _metric_file(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def over_a_peak() -> None:
    """The 105% rule goes by what the entry is: each share of a peak at 106
    is refused in a cell that declares it, suffix or none (`.moe`, `.swa`,
    `.ssm`, `.afm` behind `_roofline` hid eleven from the name's ending: one
    of them read 104.892% in PR 43's check), and a `%` that divides by no
    peak is not this rule's."""
    bench = resultline.load_benchmark()
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1.2e10, "window_s": 4.0, "busy_s": 3.0}

    def over(cell: str, name: str) -> bool:
        layer = resultline.declared(bench, cell, 1)
        line = {"correct": True, "attempted": 10, "failed": 0, "device": dev,
                "metrics": {n: {"value": 106.0 if n == name else 1.5, "unit": u} for n, u in layer.items()}}
        return any("over 105%" in p for p in resultline.problems(line, bench, cell, 1, 1))

    shares = [m for m in bench["per_layer"] if m["unit"] == "%" and "roofline" in _metric_file(m["name"])["reader"]]
    for m in shares:
        check(f"{m['name']} at 106 is refused in every cell that declares it", all(over(c, m["name"]) for c in m["workloads"]))
    check("the shares of a peak: the 24 there were, or more", len(shares) >= 24, len(shares))
    check("no share of a peak is called otherwise than `_roofline` or `mfu`",
          all("_roofline" in m["name"] or "mfu" in m["name"] for m in shares))
    others = [m for m in bench["per_layer"] if m["unit"] == "%" and m not in shares]
    refused = [m["name"] for m in others if over(m["workloads"][0], m["name"])]
    check("device_idle_pct at 106, and every other `%` that divides by no peak, is not this rule's",
          "device_idle_pct" in [m["name"] for m in others] and not refused, refused)


ACCEPTED_PER_LAYER = {
    "qwen7b-int8-chat-sat": 19, "mistral7b-int8-docqa": 23, "qwen7b-int8-chat-rate": 17, "kanana2-bf16-reason-sat": 20,
    "smallthinker-bf16-longdoc-sat": 25, "nemotron3super-bf16-agent-sat": 23, "trinitymini-bf16-mixedlen-sat": 28,
    "mistral7b-int8-chat-sat": 11,
}


def benchmark_file() -> None:
    bench = resultline.load_benchmark()
    root = resultline.ROOT
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for c in bench["configs"]:
        check(f"config file {c['name']}", os.path.exists(os.path.join(root, c["file"])))
    for w in bench["workloads"]:
        check(f"traffic file {w['traffic']}", os.path.exists(os.path.join(HERE, "traffic", w["traffic"] + ".json")))
        check(f"{w['name']}: an end-to-end metric besides setup_s", len(resultline.declared(bench, w["name"], False)) >= 2)
        check(f"{w['name']}: a per-layer metric", len(resultline.declared(bench, w["name"], True)) >= 1)
    for m in bench["per_layer"]:
        check(f"metric file {m['name']}", os.path.exists(os.path.join(HERE, "layer_metrics", m["name"] + ".json")))
        moved = e2e[m["moves"]]
        where = m.get("workloads", cells)
        check(f"{m['name']} moves {m['moves']} where it is reported",
              all(c in moved.get("workloads", cells) for c in where))
        # No metric without a list: a later cell takes none by default.
        check(f"{m['name']} lists its cells", bool(m.get("workloads")) and set(m["workloads"]) <= set(cells))
    names = [m["name"] for m in bench["per_layer"]]
    files = sorted(n[: -len(".json")] for n in os.listdir(os.path.join(HERE, "layer_metrics")) if n.endswith(".json"))
    check("one file an entry and one entry a file", files == sorted(names) and len(set(names)) == len(names),
          sorted(set(files) ^ set(names)))
    # One entry a measurement (the fold, PR 50): a cell that an accepted entry can be read in joins
    # its `workloads`; the same file under another name would only spend one of the 128 entries.
    seen: dict[tuple, str] = {}
    for m in bench["per_layer"]:
        if m["name"] not in files:
            continue  # reported above
        key = (json.dumps(_metric_file(m["name"]), sort_keys=True), *(m[k] for k in ("moves", "unit", "better", "source", "layer")))
        check(f"{m['name']} is a measurement of its own", key not in seen, f"a second name of {seen.get(key)}")
        seen.setdefault(key, m["name"])
    print(f"     per_layer: {len(names)} entries of 128, {128 - len(names)} free")
    check("per_layer within its cap of 128 entries", len(names) <= 128, len(names))
    # What each accepted cell printed when this list was written (ledger, PR 49; mixedlen-sat's 28 and
    # mistral chat-sat's 11 since they joined the entries PR 42 left out: my chip runs, PR 50):
    # merging entries that are one measurement may rename a metric, never drop one.
    for cell, floor in ACCEPTED_PER_LAYER.items():
        if cell in cells:
            got = len(resultline.declared(bench, cell, 1))
            check(f"{cell}: at least the {floor} per-layer metrics it was accepted with", got >= floor, got)
    with open(os.path.join(HERE, "trace_in_run.json")) as f:
        tail_view = json.load(f)["tail_view"]
    # A name that no entry has would be read over the measured window's polls again, in silence.
    check("every metric under tail_view is declared", set(tail_view) <= set(names), sorted(set(tail_view) - set(names)))
    check("the whole step's share of the peak keeps the measured window", not [n for n in tail_view if "mfu" in n])


def sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def shards(path: str) -> dict[str, str]:
    return {n: sha256(os.path.join(path, n)) for n in sorted(os.listdir(path)) if n.endswith(".safetensors")}


def hf_keys(config: dict, rehearsal: bool) -> dict:
    """The published keys of a configuration file, as run.py reads them."""
    hf = {k: v for k, v in config.items() if k not in run.NOT_HF_KEYS}
    if rehearsal:
        hf.update(config["rehearsal"]["hf_overrides"])
    return hf


def families(tmp: str) -> None:
    bench = resultline.load_benchmark()
    configs = {}
    for c in bench["configs"]:
        with open(os.path.join(resultline.ROOT, c["file"])) as f:
            configs[c["name"]] = json.load(f)
        family = children.family_of(hf_keys(configs[c["name"]], False))
        check(f"{c['name']}: a family file with the three names",
              all(callable(getattr(family, n, None)) for n in ("layer_plan", "outside_plan", "logits")), family)
    with open(os.path.join(HERE, "testdata", "checkpoint-digests.json")) as f:
        digests = json.load(f)
    for key, want in digests.items():
        name, seed = key.split("/")
        hf = hf_keys(configs[name], True)
        hf_path = os.path.join(tmp, "hf.json")
        with open(hf_path, "w") as f:
            json.dump(hf, f)
        full = os.path.join(tmp, f"{name}-{seed}")
        report = children.child_checkpoint(full, hf_path, seed)
        check(f"{key}: same seed, same bytes", shards(full) == want["sha256"] and report == want["report"], report)
        # The shallow cut: what the plan gives at that depth from the same
        # seed, which for the dense decoder is the full checkpoint's first shards.
        depth = configs[name]["rehearsal"]["logits_check_layers"]
        with open(hf_path, "w") as f:
            json.dump({**hf, "num_hidden_layers": depth}, f)
        cut, anew = full + "-cut", full + "-anew"
        report = children.child_checkpoint(cut, hf_path, seed, full)
        names = ["model-outside-layers.safetensors"] + [f"model-layer-{i:03d}.safetensors" for i in range(depth)]
        first = {n: want["sha256"][n] for n in sorted(names)}
        check(f"{key}: the cut links the first shards", report == {"bytes": 0, "shards": depth + 1, "linked": depth + 1}
              and all(os.path.islink(os.path.join(cut, n)) for n in first) and shards(cut) == first, report)
        children.child_checkpoint(anew, hf_path, seed)
        check(f"{key}: and they are what the plan writes at that depth", shards(anew) == first)


TOY_FAMILY = '''"""A family for the selftest: two tensors a layer, and what layer i is
depends on the depth; a logits check that reports what it was given."""
import json, os
from safetensors import safe_open


def layer_plan(hf, i):
    kind = "lower" if i < hf["num_hidden_layers"] // 2 else "upper"
    p = f"model.layers.{i}."
    return [(p + "norm.weight", (hf["hidden_size"],), None), (p + kind + ".weight", (hf["hidden_size"], 3), 0.5)]


def outside_plan(hf):
    return [("model.embed_tokens.weight", (hf["vocab_size"], hf["hidden_size"]), 0.02)]


def logits(path, seed, serving):
    names = {}
    for n in sorted(os.listdir(path)):
        if n.endswith(".safetensors"):
            with safe_open(os.path.join(path, n), "np") as f:
                names[n] = sorted(f.keys())
    with open(os.path.join(path, "config.json")) as f:
        depth = json.load(f)["num_hidden_layers"]
    return {"ok": True, "platform": "cpu", "compared": {"toy": {"max_abs": 0.0}}, "tensors": names,
            "depth": depth, "seed": seed, "model_name": serving["model_name"]}
'''

DRIVE = """
import argparse, json, os, sys
sys.path.insert(0, "perfbench")
import run
r = run.Run(argparse.Namespace(workload=sys.argv[1], seed=2**31 + 9, rehearse=True, trace=0, seconds=1, keep=True))
os.makedirs(r.workdir)
try:
    ckpt = r.phase_checkpoint()
    print(json.dumps({"ckpt": ckpt, "logits": r.phase_logits(ckpt)}))
except run.RunFailure as e:
    print(json.dumps({"failed": str(e)}))
"""


def tree(root: str) -> dict[str, str]:
    return {
        os.path.relpath(os.path.join(d, n), root): sha256(os.path.join(d, n))
        for d, _, names in os.walk(root) if "__pycache__" not in d and ".perfbench_work" not in d for n in names
    }


def by_files_alone(tmp: str) -> None:
    """A configuration of a new family, brought as a later PR may bring it:
    new files under perfbench/ and entries appended to BENCHMARK.json."""
    from safetensors import safe_open

    def tensors(*path: str) -> list[str]:
        with safe_open(os.path.join(*path), "np") as f:
            return sorted(f.keys())

    copy = os.path.join(tmp, "copy")
    shutil.copytree(HERE, os.path.join(copy, "perfbench"), ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(resultline.ROOT, "BENCHMARK.json"), copy)
    before = tree(copy)
    bench = resultline.load_benchmark(copy)
    serving = {"model_name": "toy", "logits_check_layers": 2}
    added = {"perfbench/families/toy.py": TOY_FAMILY}
    for name in ("toy", "orphan"):  # `orphan`: a model_type nobody wrote a family file for
        added[f"perfbench/configs/{name}.json"] = json.dumps({
            "model_type": name, "num_hidden_layers": 6, "hidden_size": 8, "vocab_size": 16, "serving": serving,
            "rehearsal": {"hf_overrides": {}, "logits_check_layers": 2},
        })
        bench["configs"].append({"name": name, "source": "selftest", "file": f"perfbench/configs/{name}.json", "reduced": [], "why": "selftest"})
        bench["workloads"].append({"name": f"{name}-cell", "config": name, "traffic": "chat-sat", "chips": 1, "why": "selftest"})
    added["BENCHMARK.json"] = json.dumps(bench, indent=1)
    for rel, text in added.items():
        with open(os.path.join(copy, rel), "w") as f:
            f.write(text)

    def drive(cell: str) -> dict:
        proc = subprocess.run([sys.executable, "-c", DRIVE, cell], cwd=copy, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=120)
        if proc.returncode != 0:
            return {"failed": proc.stderr.decode()[-1500:]}
        return json.loads(proc.stdout.decode().strip().splitlines()[-1])

    out = drive("toy-cell")
    ok = "logits" in out
    check("toy family: checkpoint, cut and logits through run.py's own phases", ok, out)
    if ok:
        got, ckpt = out["logits"], out["ckpt"]
        shallow = os.path.join(os.path.dirname(ckpt), "ckpt-2-layers")
        check("toy family: its logits report comes back", got["ok"] and got["model_name"] == "toy" and got["depth"] == 2
              and got["seed"] == str(2**31 + 9) and got["compared"] == {"toy": {"max_abs": 0.0}}, got)
        check("toy family: the full checkpoint holds its plan", len(shards(ckpt)) == 7
              and tensors(ckpt, "model-layer-002.safetensors") == ["model.layers.2.lower.weight", "model.layers.2.norm.weight"]
              and tensors(ckpt, "model-layer-003.safetensors") == ["model.layers.3.norm.weight", "model.layers.3.upper.weight"])
        # At depth 2 layer 1 is an upper layer (a lower one at depth 6): written
        # by the plan. Layer 0 and the outside shard are the full checkpoint's.
        check("toy family: a layer that depends on the depth is written, the others are linked",
              got["cut"] == {"bytes": 2 * (8 + 8 * 3), "shards": 3, "linked": 2}
              and got["tensors"]["model-layer-001.safetensors"] == ["model.layers.1.norm.weight", "model.layers.1.upper.weight"]
              and not os.path.islink(os.path.join(shallow, "model-layer-001.safetensors"))
              and os.path.realpath(os.path.join(shallow, "model-layer-000.safetensors")) == os.path.realpath(os.path.join(ckpt, "model-layer-000.safetensors"))
              and os.path.islink(os.path.join(shallow, "model-outside-layers.safetensors")), got)
    out = drive("orphan-cell")
    check("no family file: the checkpoint phase names the file to add",
          "perfbench/families/orphan.py" in out.get("failed", "") and "child checkpoint" in out.get("failed", ""), out)
    after = tree(copy)
    changed = sorted(n for n in before if after.get(n) != before[n])
    check("by files alone: nothing the copy had has changed but BENCHMARK.json", changed == ["BENCHMARK.json"], changed)
    check("by files alone: the new files are the ones added", sorted(set(after) - set(before)) == sorted(set(added) - {"BENCHMARK.json"}),
          sorted(set(after) - set(before)))
    old = resultline.load_benchmark()
    check("by files alone: BENCHMARK.json only grew", set(bench) == set(old) and all(
        bench[k][: len(v)] == v if isinstance(v, list) else bench[k] == v for k, v in old.items()))


if __name__ == "__main__":
    hand_made()
    idle_by_host()
    recorded()
    generator()
    last_line()
    benchmark_file()
    over_a_peak()
    same_seconds()
    with tempfile.TemporaryDirectory() as tmp:
        in_run(tmp)
        families(tmp)
        by_files_alone(tmp)
    print(f"{len(FAILED)} failed" if FAILED else "all passed")
    sys.exit(1 if FAILED else 0)
