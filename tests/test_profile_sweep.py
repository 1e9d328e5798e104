"""CI smoke for the paged-attention block sweep
(benchmarks/profile_engine.py --sweep): tiny shapes on CPU must produce
the full JSON document — every (kernel, block, slots) row present with
latency + diagnosis fields — so a TPU run of the identical harness is
known-good before it burns accelerator time."""

import json
import os
import pathlib
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_sweep_smoke_emits_full_table():
    from benchmarks.profile_engine import run_sweep

    doc = run_sweep(slots_list=(2, 4), blocks=("default", "2:8"), smoke=True)
    # JSON-serializable end-to-end (the harness writes this to disk).
    doc = json.loads(json.dumps(doc))
    assert doc["metric"] == "paged_attention_sweep"
    assert doc["degraded"] is True  # CPU run must label itself honestly
    assert "not TPU numbers" in doc["note"]
    for key in ("H", "Kv", "head_dim", "page", "seq"):
        assert key in doc["shapes"]

    rows = doc["results"]
    # 2 blocks, per slot count.
    assert len(rows) == 2 * 2
    combos = {(r["kernel"], r["block"], r["slots"]) for r in rows}
    for slots in (2, 4):
        assert ("ragged", "default", slots) in combos
        assert ("ragged", "2:8", slots) in combos
    # The sweep JSON carries the roofline constants its columns used
    # (shared accounting, kubeai_tpu/obs/perf.py) — self-interpreting.
    roof = doc["roofline"]
    assert roof["assumed_device"] is True  # CPU: v5e constants, labeled
    assert roof["flops_per_token"] > 1e10 and roof["weight_bytes"] > 1e9
    assert roof["hbm_gbps"] > 0 and roof["peak_flops"] > 0
    assert roof["step_floor_ms"] > 0

    for r in rows:
        # Every config measured (CPU reference path must never fail).
        assert r.get("error") is None, r
        assert r["latency_ms"] is not None and r["latency_ms"] > 0
        assert r["toks_per_sec_equiv"] > 0
        # The diagnosis columns the 96-slot-cliff analysis reads.
        assert r["grid_programs"] >= 1
        assert r["q_rows_per_program"] >= 1
        assert r["kv_mb_walked"] > 0
        # Per-cell projected MFU / roofline fraction from the shared
        # accounting: floor/(floor + attention) is in (0, 1] and a
        # SLOWER attention cell always projects a smaller fraction.
        assert r["projected_toks_per_sec"] > 0
        assert 0 < r["roofline_fraction"] <= 1
        assert 0 < r["mfu"] <= 1

    # The pair travels as an argument: the environment name the sweep
    # used to set is read nowhere in the package.
    pkg = pathlib.Path(__file__).resolve().parent.parent / "kubeai_tpu"
    for path in pkg.rglob("*.py"):
        assert "KUBEAI_PAGED_KERNEL_BLOCK" not in path.read_text(), path


def test_sweep_resume_skips_completed_cells(tmp_path):
    """--resume (ROADMAP item 1 prep): per-cell results persist
    incrementally, and a restart reuses completed cells verbatim
    instead of re-measuring — a flaky device mid-grid costs one cell,
    not the run."""
    from benchmarks.profile_engine import run_sweep

    out = str(tmp_path / "sweep.json")
    blocks = ("default", "2:8")
    doc1 = run_sweep(slots_list=(2,), blocks=blocks, smoke=True, out_path=out)
    with open(out) as f:
        on_disk = json.load(f)
    assert on_disk["results"] == json.loads(json.dumps(doc1["results"]))
    assert len(doc1["results"]) == 2

    # Simulate a crash mid-grid: drop the second cell from the file.
    on_disk["results"] = [r for r in on_disk["results"] if r["block"] == "default"]
    with open(out, "w") as f:
        json.dump(on_disk, f)

    doc2 = run_sweep(
        slots_list=(2, 4), blocks=blocks, smoke=True, out_path=out, resume=True,
    )
    rows = {(r["block"], r["slots"]): r for r in doc2["results"]}
    assert set(rows) == {("default", 2), ("2:8", 2), ("default", 4), ("2:8", 4)}
    # The completed cell was reused VERBATIM (identical measurement),
    # the dropped + new cells were measured fresh.
    (kept,) = on_disk["results"]
    assert rows[("default", 2)]["latency_ms"] == kept["latency_ms"]
    for key in (("2:8", 2), ("default", 4), ("2:8", 4)):
        assert rows[key]["latency_ms"] is not None and rows[key]["latency_ms"] > 0
    # And the file on disk holds the final full document.
    with open(out) as f:
        final = json.load(f)
    assert len(final["results"]) == 4


def test_sweep_resume_ignores_corrupt_file(tmp_path):
    from benchmarks.profile_engine import run_sweep

    out = str(tmp_path / "sweep.json")
    with open(out, "w") as f:
        f.write("{not json")
    doc = run_sweep(
        slots_list=(2,), blocks=("default",), smoke=True,
        out_path=out, resume=True,
    )
    assert len(doc["results"]) == 1
    with open(out) as f:
        assert len(json.load(f)["results"]) == 1
