"""From a profiler trace (.xplane.pb) to seconds: device busy time, the
traced window, time per program and per kernel, the longest idle gaps.

The arithmetic works on plain lists of (name, start_ns, duration_ns), so
`selftest.py` checks it on hand-made events; `read_xplane` is the only
function that needs jax (ProfileData), and runs in a child with
JAX_PLATFORMS=cpu after the operator has released the chip.

What bites, and how each is met here:
  * a device plane has several lines that cover the same time (steps,
    modules, ops, their framework names): busy time is the UNION of the
    intervals of ONE line (`ops_line`), never a sum across lines;
  * events overlap or nest inside one line: a union, not a sum of durations;
  * events cross the window's edge: clipped to it;
  * a trace with no event on the device line is an ERROR (the harness
    traced the wrong process, or the device tracer was off), never 0.

Which plane and which lines is data (`perfbench/trace.json`), because the
names differ between the chip and the CPU backend of the rehearsal.
"""

from __future__ import annotations

import bisect
import re
import sys


class TraceError(Exception):
    pass


_HLO = re.compile(r"^(%?[\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def short_name(name: str) -> str:
    """The chip's trace names an operation by its whole HLO text (thousands
    of characters). Keep what is before the `=` and the first result's type
    and shape: `%flash_attention_tpu.6 bf16[8,28,1024,128]`. The shape keeps
    apart the operations that different programs number alike, and is what
    the FLOP count of a kernel call is read from."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    return m.group(1) if not m.group(2) else f"{m.group(1)} {m.group(2)}"


def union_ns(intervals, lo: int, hi: int) -> tuple[int, list[tuple[int, int]]]:
    """Total length and the merged pieces of *intervals* (start, end)
    clipped to [lo, hi]."""
    clipped = sorted(
        (max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi and e > s
    )
    merged: list[tuple[int, int]] = []
    for s, e in clipped:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def gaps_ns(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle pieces of [lo, hi] between *merged* busy pieces."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def by_name(events, lo: int, hi: int) -> dict[str, list[int]]:
    """name -> [ns inside the window, events touching it, ns of the events
    wholly inside it, their count]. Means per run come from the last two:
    a run cut by the window's edge would pull a mean down."""
    out: dict[str, list[int]] = {}
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            slot = out.setdefault(name, [0, 0, 0, 0])
            slot[0] += b - a
            slot[1] += 1
            if s >= lo and s + d <= hi:
                slot[2] += d
                slot[3] += 1
    return out


def ops_inside(modules, ops, lo: int, hi: int) -> dict[str, dict[str, list[int]]]:
    """module name -> op name -> [ns, count] of the operations that ran
    inside a run of that module which lies wholly inside the window.
    A kernel shared by two programs is thereby split between them."""
    runs = sorted((s, s + d, name) for name, s, d in modules if s >= lo and s + d <= hi)
    starts = [r[0] for r in runs]
    out: dict[str, dict[str, list[int]]] = {}
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0:
            continue
        rs, re_, mod = runs[i]
        if s >= rs and s + d <= re_:
            slot = out.setdefault(mod, {}).setdefault(name, [0, 0])
            slot[0] += d
            slot[1] += 1
    return out


def reduce_events(planes: list[dict], window: tuple[int, int] | None) -> dict:
    """*planes*: one dict per device, {"name", "ops": [(name, start, dur)],
    "modules": [...]}; *window*: (lo, hi) in ns, or None to take the span
    of every event given. Returns seconds; busy is the mean over devices."""
    if not planes:
        raise TraceError("no device plane in the trace")
    if window is None:
        starts = [s for p in planes for k in ("ops", "modules") for _, s, _ in p[k]]
        ends = [s + d for p in planes for k in ("ops", "modules") for _, s, d in p[k]]
        if not starts:
            raise TraceError("no event on any device line of the trace")
        window = (min(starts), max(ends))
    lo, hi = window
    if hi <= lo:
        raise TraceError(f"empty traced window {window}")
    per_device = []
    for p in planes:
        busy, merged = union_ns(((s, s + d) for _, s, d in p["ops"]), lo, hi)
        if busy <= 0:
            raise TraceError(
                f"no operation ran on {p['name']} inside the traced window "
                f"({len(p['ops'])} events on its ops line)"
            )
        per_device.append({
            "name": p["name"], "busy_ns": busy, "merged": merged,
            "ops": by_name(p["ops"], lo, hi), "modules": by_name(p["modules"], lo, hi),
            "ops_in": ops_inside(p["modules"], p["ops"], lo, hi),
        })
    first = per_device[0]
    gaps = sorted(gaps_ns(first["merged"], lo, hi), key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_device) / len(per_device) / 1e9,
        "busy_is": f"mean over {len(per_device)} device(s) of the union of one line's operation intervals",
        "devices": [d["name"] for d in per_device],
        # Of the first device (all devices run the same program under tp).
        "ops_s": {k: (v[0] / 1e9, v[1]) for k, v in first["ops"].items()},
        # name -> (s inside the window, runs touching it, s of whole runs, whole runs)
        "modules_s": {k: (v[0] / 1e9, v[1], v[2] / 1e9, v[3]) for k, v in first["modules"].items()},
        # module -> op -> (s, count), whole runs only
        "ops_in_modules_s": {
            m: {k: (v[0] / 1e9, v[1]) for k, v in ops.items()} for m, ops in first["ops_in"].items()
        },
        "gaps_s": [((s - lo) / 1e9, (e - s) / 1e9) for s, e in gaps[:50]],
        "n_gaps": len(gaps),
    }


def read_xplane(path: str, spec: dict) -> tuple[list[dict], tuple[int, int] | None, dict]:
    """The device planes' events, the traced window, and notes on what was
    found. *spec* (perfbench/trace.json, one platform's part):
      plane         regex of the device planes' names
      ops_line      regex of the ONE line per plane that holds operations
                    (several may match only where `ops_lines_many` is true:
                    the CPU backend runs its thunks on a pool of threads)
      container_ops regex of the operations that only contain others (while,
                    conditional, call): left out, or null
      modules_line  regex of the line that holds whole programs, or null
      modules_from_ops_stat  (rehearsal) name of the stat of an operation
                    event that names its program, where there is no such line
      window_event  regex of the host event that spans the traced interval
                    (the profiler's own sleep), or null
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    plane_rx = re.compile(spec["plane"])
    ops_rx = re.compile(spec["ops_line"])
    mod_rx = re.compile(spec["modules_line"]) if spec.get("modules_line") else None
    win_rx = re.compile(spec["window_event"]) if spec.get("window_event") else None
    planes, notes, window = [], {"planes": [], "window_from": "span of device events"}, None
    want_s = spec.get("profile_seconds")
    for plane in data.planes:
        lines = [(ln.name, ln) for ln in plane.lines]
        notes["planes"].append({"name": plane.name, "lines": sorted({n for n, _ in lines})[:40]})
        if win_rx is not None and window is None and plane.name.startswith("/host:"):
            for _, ln in lines:
                for ev in ln.events:
                    if win_rx.search(ev.name) and (
                        want_s is None or abs(ev.duration_ns / 1e9 - want_s) < 0.25 * want_s
                    ):
                        window = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        notes["window_from"] = f"host event {ev.name!r}"
                        break
                if window:
                    break
        if not plane_rx.search(plane.name):
            continue
        ops_lines = [ln for n, ln in lines if ops_rx.search(n)]
        if not ops_lines:
            raise TraceError(f"plane {plane.name}: no line matches {spec['ops_line']!r} (has {[n for n, _ in lines]})")
        if len(ops_lines) > 1 and not spec.get("ops_lines_many"):
            raise TraceError(f"plane {plane.name}: {len(ops_lines)} lines match {spec['ops_line']!r}; busy time is of ONE line")
        # Control flow (a scan's `while` spans all its steps) is not an
        # operation that runs: busy time is of the operations inside it.
        skip = re.compile(spec["container_ops"]) if spec.get("container_ops") else None
        ops = [
            (short_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
            for ln in ops_lines for ev in ln.events
            if skip is None or not skip.search(ev.name)
        ]
        modules = []
        if spec.get("modules_from_ops_stat"):
            # The CPU backend has no line of whole programs: every thunk
            # names its program in a stat. Rehearsal only.
            key = spec["modules_from_ops_stat"]
            for ln in ops_lines:
                for ev in ln.events:
                    mod = dict(ev.stats).get(key)
                    if mod:
                        modules.append((str(mod), int(ev.start_ns), int(ev.duration_ns)))
        elif mod_rx is not None:
            modules = [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for n, ln in lines if mod_rx.search(n) for ev in ln.events
            ]
        planes.append({"name": plane.name, "ops": ops, "modules": modules})
    if not planes:
        raise TraceError(
            f"no plane matches {spec['plane']!r}: the trace was not taken in the "
            f"process that holds the device (planes: {[p['name'] for p in notes['planes']]})"
        )
    return planes, window, notes


def main(argv) -> int:
    """python3 trace_reduce.py <trace.xplane.pb> <platform>: a hand-read."""
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace.json")) as f:
        spec = json.load(f)
    part = {**spec[argv[2] if len(argv) > 2 else "tpu"], "profile_seconds": None}
    planes, window, notes = read_xplane(argv[1], part)
    out = reduce_events(planes, window)
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1][0])[:40]  # noqa: E731
    print(json.dumps({
        "notes": notes, "window": window, "window_s": out["window_s"], "busy_s": out["busy_s"],
        "modules": top(out["modules_s"]), "ops": top(out["ops_s"]), "gaps": out["gaps_s"][:10],
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
