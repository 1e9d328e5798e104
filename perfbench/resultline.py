"""The check every result goes through before it is printed: the object
against BENCHMARK.json and the contract of the last line. `run.py` calls
`check()` in every mode and prints nothing it rejects. A mode is `--trace`'s
value: 0 (the end-to-end metrics), 1 (the per-layer ones, of a traced run
of its own) or 2 (both side by side: the run measured its window and then
traced itself); `False` / `True` still read as 0 / 1.

    python3 perfbench/resultline.py <workload> <0|1|2> < line   # by hand
"""

from __future__ import annotations

import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def declared(bench: dict, workload: str, trace: int) -> dict[str, str]:
    """name -> unit of the metrics this workload carries in this mode: the
    end-to-end ones untraced, the per-layer ones traced, both in mode 2. A
    metric with a `workloads` key belongs to the cells it lists, one
    without to all."""
    if workload not in [w["name"] for w in bench["workloads"]]:
        raise ValueError(f"workload {workload!r} is not in BENCHMARK.json")
    groups = {0: ("end_to_end",), 1: ("per_layer",), 2: ("end_to_end", "per_layer")}[int(trace)]
    return {
        m["name"]: m["unit"] for group in groups for m in bench[group]
        if "workloads" not in m or workload in m["workloads"]
    }


def share_of_a_peak(bench: dict, name: str) -> bool:
    """Whether metric *name* is a share of a peak, which no run may read over
    105%: by what its entry IS, not by how its name ends (eleven of the 24
    carried a suffix behind `_roofline` and escaped the ending until PR 50).
    A per-layer entry is one where its unit is `%` and its file names a reader
    that divides by a peak: today every reader whose module name holds
    `roofline`. A name the contract itself reads so (`*_roofline`, `*mfu*`)
    is held to it whatever reads it."""
    if name.endswith(("_roofline", "_roofline_pct")) or "mfu" in name:
        return True
    entry = next((m for m in bench["per_layer"] if m["name"] == name), None)
    if entry is None or entry["unit"] != "%":
        return False
    with open(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".json")) as f:
        return "roofline" in json.load(f)["reader"]


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def problems(obj, bench: dict, workload: str, trace: int, chips: int,
             rehearsal: bool = False, may_miss: set[str] | None = None) -> list[str]:
    """Everything wrong with *obj* as the last line of a run; [] if nothing.
    *may_miss*: per-layer metrics whose reader found nothing to read (the
    contract lets the harness leave those out of the line; an end-to-end
    metric may never be missed)."""
    out: list[str] = []
    if not isinstance(obj, dict):
        return ["not a JSON object"]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in obj:
            out.append(f"key {key!r} missing")
    if out:
        return out
    if not isinstance(obj["correct"], bool):
        out.append("correct is not a boolean")
    for key in ("attempted", "failed"):
        if not isinstance(obj[key], int) or isinstance(obj[key], bool) or obj[key] < 0:
            out.append(f"{key} is not a whole number >= 0")
    if not out and obj["failed"] > obj["attempted"]:
        out.append("failed > attempted")
    if not out and obj["attempted"] == 0:
        out.append("attempted is 0: the window saw no request")
    want = declared(bench, workload, trace)
    got = obj["metrics"]
    if not isinstance(got, dict):
        return out + ["metrics is not an object"]
    may_miss = set(may_miss or ()) - set(declared(bench, workload, 0))
    for name in sorted(set(want) - set(got) - may_miss):
        out.append(f"metric {name} of this workload and mode is missing")
    for name in sorted(set(got) - set(want)):
        out.append(f"metric {name} is not declared for this workload in this mode")
    for name, m in got.items():
        if name not in want:
            continue
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            out.append(f"metric {name}: not {{value, unit}}")
        elif not _finite(m["value"]):
            out.append(f"metric {name}: value {m['value']!r} is not a finite number")
        elif m["unit"] != want[name]:
            out.append(f"metric {name}: unit {m['unit']!r}, declared {want[name]!r}")
        elif m["value"] > 105 and share_of_a_peak(bench, name):
            out.append(f"metric {name}: {m['value']} is over 105% of a peak")
    dev = obj["device"]
    if not isinstance(dev, dict):
        return out + ["device is not an object"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            out.append(f"device.{key} missing")
    if out:
        return out
    if not rehearsal and dev["platform"] != "tpu":
        out.append(f"device.platform is {dev['platform']!r}, not 'tpu'")
    if rehearsal and dev["platform"] == "tpu":
        out.append("a rehearsal may not report a tpu")
    if not isinstance(dev["kind"], str) or not dev["kind"]:
        out.append("device.kind is not a name")
    if dev["count"] != chips:
        out.append(f"device.count {dev['count']!r}, the cell asks for {chips}")
    if not _finite(dev["memory_peak_bytes"]) or (dev["memory_peak_bytes"] <= 0 and not rehearsal):
        out.append(f"device.memory_peak_bytes {dev['memory_peak_bytes']!r}")
    if trace:
        w, b = dev.get("window_s"), dev.get("busy_s")
        if not _finite(w) or not _finite(b):
            out.append(f"traced run: device.window_s {w!r}, device.busy_s {b!r}")
        elif not 0 < b <= w:
            out.append(f"traced run: need 0 < busy_s <= window_s, got busy_s {b}, window_s {w}")
        bd = obj.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key) if isinstance(bd, dict) else None
                if not isinstance(rows, list) or len(rows) > 10 or not all(
                    isinstance(r, list) and len(r) == 2 and isinstance(r[0], str) and _finite(r[1])
                    for r in rows
                ):
                    out.append(f"breakdown.{key}: not at most 10 [name, seconds] pairs")
    return out


def render(obj) -> str:
    """The line as printed. allow_nan=False: Python would write NaN, which
    is not JSON."""
    return json.dumps(obj, allow_nan=False, separators=(", ", ": "))


def check(obj, workload: str, trace: int, chips: int, rehearsal: bool = False,
          may_miss: set[str] | None = None, bench: dict | None = None) -> str:
    """The printable line, or ValueError listing what is wrong with it."""
    bad = problems(obj, bench or load_benchmark(), workload, trace, chips, rehearsal, may_miss)
    if bad:
        raise ValueError("result line refused: " + "; ".join(bad))
    line = render(obj)
    if "\n" in line or json.loads(line) != json.loads(json.dumps(obj)):
        raise ValueError("result line does not survive a round trip")
    return line


if __name__ == "__main__":
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == sys.argv[1])
    obj = json.loads(sys.stdin.read().strip().splitlines()[-1])
    bad = problems(obj, bench, sys.argv[1], int(sys.argv[2]), cell["chips"],
                   rehearsal=obj.get("device", {}).get("platform") == "cpu")
    print("\n".join(bad) or "ok")
    sys.exit(1 if bad else 0)
