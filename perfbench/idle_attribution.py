"""Every idle piece of the device put down to what the scheduler thread did
beside it, on the trace's one clock.

The device's side is the busy union `trace_reduce.py` computes from ONE
line of operations (containers left out); the host's side is the
`sched.<cause>` events the scheduler loop writes on its own thread's line
(`kubeai_tpu/obs/perf.py::PipelineStallTracker.segment`). Segments nest
(`prefill` and `kv_transfer` inside `admit`): the innermost one is what the
thread was doing. Where the thread is under no segment the cause is
`other`, as in the program's own account.

The arithmetic works on plain tuples in ns, so `selftest.py` checks it on
hand-made events; `read_sched` is the only function that needs jax
(ProfileData) and runs in a child (`python3 perfbench/idle_attribution.py`).
`readers/idle_by_host.py` reads the table for the per-layer metrics, and
`run.py --trace 2` takes the gaps' labels from it.

    python3 perfbench/idle_attribution.py <trace.xplane.pb> <platform> <seconds|-> [<window event regex>]
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OTHER = "other"
# Attrs that split a cause's seconds in the table (diagnosis only).
DETAIL = {"fetch_wait": "of", "prefill": "kind"}


def flatten(segments, lo: int, hi: int) -> list[tuple[int, int, str, dict]]:
    """*segments*: (cause, start, duration, attrs) of ONE thread, nested or
    side by side. Returns disjoint pieces (start, end, cause, attrs) inside
    [lo, hi], in order: every instant under the innermost segment open at it."""
    pieces: list[tuple[int, int, str, dict]] = []
    stack: list[tuple[int, str, dict]] = []  # (end, cause, attrs) of the open segments
    at = lo

    def put(end: int) -> None:
        nonlocal at
        a, b = max(at, lo), min(end, hi)
        if stack and b > a:
            pieces.append((a, b, stack[-1][1], stack[-1][2]))
        at = max(at, end)

    for cause, start, dur, attrs in sorted(segments, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            put(stack[-1][0])
            stack.pop()
        put(start)  # the enclosing segment's own time up to here (nothing if none is open)
        at = max(at, start)
        stack.append((start + dur, cause, attrs or {}))
    while stack:
        put(stack[-1][0])
        stack.pop()
    return pieces


def attribute(idle, pieces) -> list[dict]:
    """For every idle piece (start, end) of the device, in order: its ns by
    cause (what no piece covers is `other`) and the one piece that covers
    most of it. *idle* and *pieces* are sorted and disjoint."""
    out = []
    i = 0
    for s, e in idle:
        by: dict[str, int] = {}
        best = (0, None)
        while i < len(pieces) and pieces[i][1] <= s:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < e:
            ps, pe, cause, attrs = pieces[j]
            ns = min(pe, e) - max(ps, s)
            if ns > 0:
                by[cause] = by.get(cause, 0) + ns
                if ns > best[0]:
                    best = (ns, pieces[j])
            j += 1
        rest = (e - s) - sum(by.values())
        if rest > 0:
            by[OTHER] = by.get(OTHER, 0) + rest
        out.append({"start": s, "end": e, "by": by, "most": best[1]})
    return out


def table(merged_busy, segments, lo: int, hi: int, top: int = 10) -> dict:
    """The whole account of one traced interval [lo, hi] in ns: idle seconds
    by cause (they add up to the device's idle time), by `fetch_wait`'s `of`
    and `prefill`'s `kind`, gaps counted by the cause that covers most of
    each, and the *top* longest gaps with their own causes."""
    import trace_reduce

    idle = trace_reduce.gaps_ns(merged_busy, lo, hi)
    # One walk: a cause with a splitting attr goes in as `cause.attr=value`
    # (a gap's `fetch_wait` may be two fetches, a chunk's and a round's
    # first tokens), and the plain cause is what stands before the dot.
    keyed = [
        (f"{c}.{DETAIL[c]}={attrs.get(DETAIL[c], '?')}" if c in DETAIL else c, start, dur, attrs)
        for c, start, dur, attrs in segments
    ]
    gaps = attribute(idle, flatten(keyed, lo, hi))
    by_cause: dict[str, int] = {}
    by_detail: dict[str, int] = {}
    n_by_cause: dict[str, int] = {}
    for g in gaps:
        plain: dict[str, int] = {}
        for key, ns in g["by"].items():
            cause = key.split(".")[0]
            plain[cause] = plain.get(cause, 0) + ns
            by_cause[cause] = by_cause.get(cause, 0) + ns
            if cause != key:
                by_detail[key] = by_detail.get(key, 0) + ns
        g["by"] = plain
        if g["most"] is not None:
            g["most"] = (*g["most"][:2], g["most"][2].split(".")[0], g["most"][3])
        lead = max(plain, key=lambda c: plain[c])
        n_by_cause[lead] = n_by_cause.get(lead, 0) + 1
    longest = sorted(gaps, key=lambda g: g["start"] - g["end"])[:top]
    sec = lambda d: {k: v / 1e9 for k, v in sorted(d.items(), key=lambda kv: -kv[1])}  # noqa: E731
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(e - s for s, e in idle) / 1e9,
        "n_gaps": len(gaps),
        "n_segments": len(segments),
        "idle_by_cause_s": sec(by_cause),
        "idle_by_detail_s": sec(by_detail),
        "gaps_by_leading_cause": dict(sorted(n_by_cause.items(), key=lambda kv: -kv[1])),
        "gaps": [
            {
                "at_s": (g["start"] - lo) / 1e9, "seconds": (g["end"] - g["start"]) / 1e9,
                "by_cause_s": sec(g["by"]),
                "most": None if g["most"] is None else {"cause": g["most"][2], "attrs": g["most"][3]},
            }
            for g in longest
        ],
    }


def label(gap: dict, limit: int = 200) -> str:
    """One gap of `table()["gaps"]` in a line: where it lay and ITS OWN
    causes, largest first, with the attrs of the segment that covers most
    of it: `at +1.1533s, host: emit 61% [tokens=512], other 27%, dispatch 12%`."""
    total = sum(gap["by_cause_s"].values()) or 1.0
    parts = []
    for cause, s in gap["by_cause_s"].items():  # largest first (table sorts)
        pct = round(100.0 * s / total)
        if pct <= 0 and parts:
            continue
        part = f"{cause} {pct}%"
        most = gap.get("most")
        if most and most["cause"] == cause and most["attrs"] and not any("[" in p for p in parts):
            part += " [" + ", ".join(f"{k}={v}" for k, v in most["attrs"].items()) + "]"
        parts.append(part)
    return (f"at +{gap['at_s']:.4f}s, host: " + ", ".join(parts))[:limit]


def read_sched(path: str, line_rx: str, event_rx: str) -> list[tuple[str, int, int, dict]]:
    """(cause, start, duration, attrs) of the scheduler's segments in the
    host planes of the trace at *path*."""
    from jax.profiler import ProfileData

    line_re, event_re = re.compile(line_rx), re.compile(event_rx)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            if not line_re.search(ln.name):
                continue
            for ev in ln.events:
                m = event_re.search(ev.name)
                if m:
                    attrs = {k: (v if isinstance(v, (int, float, str)) else str(v)) for k, v in ev.stats}
                    out.append((m.group(1), int(ev.start_ns), int(ev.duration_ns), attrs))
    return out


def main(argv) -> int:
    """The child: the same planes and the same window as the trace child
    reads (`trace_reduce.read_xplane`, perfbench/trace.json), with the
    window's event replaced where a fourth argument names it."""
    import trace_reduce

    path, platform, seconds = argv[1], argv[2], argv[3]
    with open(os.path.join(HERE, "trace.json")) as f:
        part = dict(json.load(f)[platform])
    with open(os.path.join(HERE, "trace_in_run.json")) as f:
        spec = json.load(f)
    part["profile_seconds"] = None if seconds == "-" else float(seconds)
    if len(argv) > 4 and argv[4]:
        part["window_event"] = argv[4]
    planes, window, notes = trace_reduce.read_xplane(path, part)
    if window is None:  # as trace_reduce.reduce_events: the span of the device's events
        starts = [s for p in planes for k in ("ops", "modules") for _, s, _ in p[k]]
        ends = [s + d for p in planes for k in ("ops", "modules") for _, s, d in p[k]]
        window = (min(starts), max(ends))
    lo, hi = window
    first = planes[0]  # as trace_reduce: gaps are the first device's
    _, merged = trace_reduce.union_ns(((s, s + d) for _, s, d in first["ops"]), lo, hi)
    segments = read_sched(path, spec["sched_line"], spec["sched_event"])
    out = table(merged, segments, lo, hi)
    out["window_from"] = notes["window_from"]
    out["device"] = first["name"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    sys.exit(main(sys.argv))
