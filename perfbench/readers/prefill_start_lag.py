"""Mean milliseconds a prefill call waits on the device's queue: from the
end of the scheduler's dispatch of it (`sched.prefill` on the line
`engine-loop`, attrs `kind` = group | chunk and, since PR 53, `calls`: the
programs the call queued, 1 where the attr is missing) to the start of its
first program's run (`XLA Modules`). The scheduler admits, then dispatches
the next decode chunk, then fetches the admitted requests' first tokens; a
prefill dispatched while a chunk runs starts behind it, and the host
cannot see that wait: only the trace has both ends, on its one clock.

Matching is in order, kind by kind (the device runs what it is handed in
the order it was handed): a call takes the next run of its kind's programs
that starts after the call's event began and is not taken, and with it the
`calls - 1` runs that follow. A call whose run is not wholly inside the
traced interval, or for which the trace has no run left (the last calls
before its end), is an EDGE call and dropped. None where there is no trace,
the program wrote no `sched.prefill` into it, or fewer than 90% of the
interval's other calls found a run (a kind whose programs the trace does not
hold at all found none: the names moved, and the join with them).

Prints one line, `{"phase": "prefill_start_lag", ...}`: calls, found, edge,
the mean and longest lag, and beside them the mean device time of a call's
own runs and the longest single prefill run of the trace; and `stages`, what
the lag is a part of: the engine's own mean `prefill` stage (dispatch to
first emitted token), queue wait and TTFT per request, over the traced
seconds and over the measured window (histogram deltas of the scrapes the
context holds; diagnosis, no metric).

The arithmetic (`match`) works on plain tuples in ns; reading the .xplane.pb
needs jax and runs in a child with JAX_PLATFORMS=cpu:

    python3 perfbench/readers/prefill_start_lag.py <trace.xplane.pb> <platform> <seconds|-> <programs json> [<window event regex>]
"""

import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FOUND_SHARE = 0.9


def match(calls, runs, lo, hi):
    """*calls*: (kind, start, duration, programs queued) of the dispatch
    events; *runs*: kind -> [(start, duration)] of that kind's programs on
    the device; all in ns, [lo, hi] the traced interval. Returns
    (found, edge, lost): found = [(lag, device ns of the call's runs)] of the
    calls inside the interval whose runs lie inside it too, edge = how many
    were dropped at the interval's end, lost = how many found no run though
    the trace went on."""
    found, edge, lost = [], 0, 0
    for kind in sorted({c[0] for c in calls}):
        mine = sorted(runs.get(kind, ()))
        at = 0
        for _, start, dur, queued in sorted(c for c in calls if c[0] == kind):
            if start < lo or start + dur > hi:
                continue
            while at < len(mine) and mine[at][0] < start:
                at += 1  # ran before this call began: an earlier call's
            took = mine[at : at + max(queued, 1)]
            at += len(took)
            if not mine:
                lost += 1
            elif len(took) < max(queued, 1) or took[-1][0] + took[-1][1] > hi:
                edge += 1
            else:
                found.append((max(0, took[0][0] - (start + dur)), sum(d for _, d in took)))
    return found, edge, lost


def summary(calls, runs, lo, hi):
    found, edge, lost = match(calls, runs, lo, hi)
    ms = lambda ns: ns / 1e6  # noqa: E731
    inside = [d for rs in runs.values() for s, d in rs if s >= lo and s + d <= hi]
    return {
        "calls": len(found) + edge + lost, "found": len(found), "edge": edge,
        "lag_ms_mean": ms(sum(f[0] for f in found) / len(found)) if found else None,
        "lag_ms_max": ms(max((f[0] for f in found), default=0)),
        "run_ms_mean": ms(sum(f[1] for f in found) / len(found)) if found else None,
        "longest_run_ms": ms(max(inside, default=0)),
    }


STAGES = {"queue_ms": "kubeai_engine_queue_wait_seconds", "prefill_ms": "kubeai_engine_prefill_seconds", "ttft_ms": "kubeai_engine_ttft_seconds"}


def stage_means(view):
    """Mean ms per request of the engine's stage histograms between a
    view's two scrapes ({} where it has none)."""
    before, after = getattr(view, "before", None), getattr(view, "after", None)
    out = {}
    if before is None or after is None:
        return out
    for key, name in STAGES.items():
        n = after.value(name + "_count") - before.value(name + "_count")
        if n > 0:
            out[key] = 1000.0 * (after.value(name + "_sum") - before.value(name + "_sum")) / n
            out["requests"] = n
    return out


def value(out):
    """The metric of a `summary` (or of {}): the mean lag, or None where
    too few of the calls that are not edge calls found their run."""
    judged = out.get("calls", 0) - out.get("edge", 0)
    if not out.get("found") or out["found"] < FOUND_SHARE * judged:
        return None
    return out["lag_ms_mean"]


def read(ctx, programs):
    trace = getattr(ctx, "trace", None)
    if not trace:
        return None
    from readers import scope_share

    path = getattr(ctx, "trace_path", None) or scope_share.trace_file(ctx)
    if path is None:
        return None
    argv = [path, "cpu" if ctx.rehearsal else "tpu", repr(float(ctx.trace_t1 - ctx.trace_t0)), json.dumps(programs)]
    if getattr(ctx, "window_event_rx", None):
        argv.append(ctx.window_event_rx)
    out, error = {}, None
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *argv],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
        )
        if proc.returncode == 0:
            out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
        else:
            error = proc.stderr.decode(errors="replace")[-800:]
    except (OSError, subprocess.TimeoutExpired, ValueError, IndexError) as e:
        error = f"{type(e).__name__}: {e}"
    stages = {"traced": stage_means(getattr(ctx, "tail_view", None)), "window": stage_means(ctx)}
    print(json.dumps({"phase": "prefill_start_lag", **out, "stages": stages, "error": error}), flush=True)
    return value(out)


def main(argv) -> int:
    """The child: the window as the trace child reads it (trace_reduce.read_xplane,
    perfbench/trace.json; its event replaced where a fifth argument names it),
    the programs' runs from the first device's line of whole programs, the
    dispatch events from the scheduler's line (perfbench/trace_in_run.json)."""
    import idle_attribution
    import trace_reduce

    path, platform, seconds, programs = argv[1], argv[2], argv[3], json.loads(argv[4])
    with open(os.path.join(HERE, "trace.json")) as f:
        part = dict(json.load(f)[platform])
    with open(os.path.join(HERE, "trace_in_run.json")) as f:
        spec = json.load(f)
    part["profile_seconds"] = None if seconds == "-" else float(seconds)
    if len(argv) > 5 and argv[5]:
        part["window_event"] = argv[5]
    planes, window, _ = trace_reduce.read_xplane(path, part)
    if window is None:
        print(json.dumps({}), flush=True)  # no interval to judge an edge by
        return 0
    lo, hi = window
    runs = {
        kind: [(s, d) for name, s, d in planes[0]["modules"] if re.search(rx, name)]
        for kind, rx in programs.items()
    }
    calls = [
        (str(attrs.get("kind")), start, dur, int(attrs.get("calls", 1)))
        for cause, start, dur, attrs in idle_attribution.read_sched(path, spec["sched_line"], spec["sched_event"])
        if cause == "prefill"
    ]
    print(json.dumps(summary(calls, runs, lo, hi)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    sys.exit(main(sys.argv))
