"""The benchmark's own machinery (perfbench/), on the CPU: the metrics this
PR declares and where the result line carries them, the plans the one
traffic generator builds, operations filed under their named scope, the
reader that finds a run's trace, and a whole `--rehearse --trace 1` run.
perfbench/selftest.py holds the yardstick's own checks; it runs here too."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import resultline  # noqa: E402
import scope_reduce  # noqa: E402
import traffic  # noqa: E402
from readers import scope_share  # noqa: E402

NEW = {
    "prefill_padding_pct": {"qwen7b-int8-chat-sat", "mistral7b-int8-docqa"},
    "host_work_per_chunk_ms": {"qwen7b-int8-chat-sat", "mistral7b-int8-docqa"},
    "host_work_per_chunk_ms.rate": {"qwen7b-int8-chat-rate"},
    "decode_sampling_share_pct": {"qwen7b-int8-chat-sat", "mistral7b-int8-docqa"},
    "decode_attn_share_pct": {"qwen7b-int8-chat-sat", "mistral7b-int8-docqa"},
    "decode_ffn_share_pct": {"qwen7b-int8-chat-sat", "mistral7b-int8-docqa"},
    "decode_epilogue_ran_pct": {"qwen7b-int8-chat-sat", "mistral7b-int8-docqa"},
    "decode_epilogue_ran_pct.rate": {"qwen7b-int8-chat-rate"},
}


def test_selftest_passes():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "selftest.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=300,
    )
    out = proc.stdout.decode()
    assert proc.returncode == 0 and out.strip().endswith("all passed"), out[-3000:]


# The cells PR 24/25's eight metrics name; a cell of a later PR (another
# family's) carries none of them and has its own case below.
NEW_CELLS = sorted(set().union(*NEW.values()))
# PR 33's cell and what it declares: every per-layer metric that lists it.
MOE_CELL = "kanana2-bf16-reason-sat"
MOE_METRICS = {
    "decode_step_ms.moe", "device_idle_pct.moe", "prefill_share_pct.moe", "batch_occupancy_pct.moe",
    "kv_pages_peak_pct.moe", "host_work_per_chunk_ms.moe", "prefill_padding_pct.moe", "decode_mla_share_pct",
    "decode_moe_share_pct", "decode_sampling_share_pct.moe", "moe_experts_hit_pct", "scale_from_zero_s.moe",
    "engine_load_s.moe", "engine_warmup_s.moe", "moe_experts_roofline", "mla_decode_roofline",
    "decode_step_roofline.moe", "window_mfu.moe",
}


def test_benchmark_declares_the_new_metrics():
    bench = resultline.load_benchmark()
    assert bench["trace_in_run"] is True  # since PR 38 a run measures and then traces itself (--trace 2)
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in NEW]  # each declared, wherever later PRs' entries stand
    assert at == sorted(at) and at == list(range(at[0], at[0] + len(NEW)))  # together, in the issue's order
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert set(m["workloads"]) == NEW[m["name"]]
    for name in NEW:
        with open(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "perfbench", "readers", spec["reader"] + ".py"))


# PR 38's four metrics: one reader (idle_by_host), by the cell's own end-to-end metric.
SAT_CELLS = {"qwen7b-int8-chat-sat", "mistral7b-int8-docqa", "kanana2-bf16-reason-sat", "smallthinker-bf16-longdoc-sat"}
IDLE = {
    "idle_exposed_host_pct": SAT_CELLS, "idle_in_fetch_pct": SAT_CELLS,
    "idle_exposed_host_pct.rate": {"qwen7b-int8-chat-rate"}, "idle_in_fetch_pct.rate": {"qwen7b-int8-chat-rate"},
}
HOST_WORK = {"sweep", "admit", "prefill", "kv_transfer", "dispatch", "host_overlap", "emit", "other"}


@pytest.mark.parametrize("name", sorted(IDLE))
def test_the_idle_gaps_metrics_are_declared_last_and_read_by_one_reader(name):
    bench = resultline.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(IDLE)))  # appended together, in the issue's order, wherever later PRs' entries stand
    assert names[at : at + 4] == list(IDLE)
    assert set(entry["workloads"]) == IDLE[name] and entry["layer"] == "scheduler" and entry["source"] == "device_trace"
    assert entry["moves"] == ("tpot_mean_ms" if name.endswith(".rate") else "output_tok_s") and entry["better"] == "lower"
    with open(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "idle_by_host"
    # Host work that did not hide, or the wait in the fetch: `idle` (no
    # request) is in neither, and the three add up to the idle share.
    causes = set(spec["params"]["causes"])
    assert causes == ({"fetch_wait"} if "in_fetch" in name else HOST_WORK)


@pytest.mark.parametrize("cell", sorted(SAT_CELLS | {"qwen7b-int8-chat-rate"}))
def test_a_line_of_a_run_that_traced_itself_carries_both_kinds(cell):
    bench = resultline.load_benchmark()
    both = resultline.declared(bench, cell, 2)
    e2e, layer = resultline.declared(bench, cell, 0), resultline.declared(bench, cell, 1)
    assert both == {**e2e, **layer} and len(both) == len(e2e) + len(layer)
    assert {n for n, cells in IDLE.items() if cell in cells} <= set(layer)
    line = _traced_line(both)
    assert resultline.problems(line, bench, cell, 2, 1) == []
    for kind in (e2e, layer):  # a line that lacks either kind is refused
        cut = {**line, "metrics": {k: v for k, v in line["metrics"].items() if k not in kind}}
        assert resultline.problems(cut, bench, cell, 2, 1)
    assert resultline.problems({**line, "device": {k: v for k, v in line["device"].items() if k != "busy_s"}}, bench, cell, 2, 1)


def _tail_ctx(table, window_s=4.0):
    ctx = types.SimpleNamespace(trace={"window_s": window_s, "busy_s": 3.0}, idle_by_host=table, rehearsal=True)
    return ctx


def test_the_idle_reader_splits_the_idle_share_of_the_same_trace():
    from readers import device_idle, idle_by_host

    table = {"window_s": 4.0, "n_segments": 12, "idle_by_cause_s": {"emit": 0.5, "other": 0.1, "fetch_wait": 0.3, "idle": 0.1}}
    ctx = _tail_ctx(table)
    exposed = idle_by_host.read(ctx, sorted(HOST_WORK))
    fetch = idle_by_host.read(ctx, ["fetch_wait"])
    under_idle = idle_by_host.read(ctx, ["idle"])
    assert (exposed, fetch, under_idle) == (pytest.approx(15.0), pytest.approx(7.5), pytest.approx(2.5))
    assert exposed + fetch + under_idle == pytest.approx(device_idle.read(ctx))
    # Nothing to read: no trace, a program that wrote no segment, a table of another interval.
    assert idle_by_host.read(_tail_ctx({}), ["fetch_wait"]) is None
    assert idle_by_host.read(_tail_ctx({**table, "n_segments": 0}), ["fetch_wait"]) is None
    assert idle_by_host.read(_tail_ctx(table, window_s=3.5), ["fetch_wait"]) is None
    none = types.SimpleNamespace(trace=None, rehearsal=True)
    assert idle_by_host.read(none, ["fetch_wait"]) is None and none.idle_by_host == {}


def test_the_idle_reader_reads_the_recorded_trace(capsys):
    """The child on the small recorded trace (a CPU engine from before the
    segments were written: PR 23): the same interval as the trace child's,
    no segment, so nothing to read, and it says so on its line."""
    from readers import idle_by_host

    path = os.path.join(ROOT, "perfbench", "testdata", "cpu-small.xplane.pb")
    with open(os.path.join(ROOT, "perfbench", "testdata", "cpu-small.expected.json")) as f:
        want = json.load(f)
    ctx = types.SimpleNamespace(
        trace={"window_s": want["window_s"], "busy_s": want["busy_s"], "bytes": os.path.getsize(path)},
        trace_path=path, trace_t0=10.0, trace_t1=10.0 + want["profile_seconds"], rehearsal=True,
    )
    assert idle_by_host.read(ctx, ["fetch_wait"]) is None
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "idle_by_host" and line["error"] is None and line["n_segments"] == 0
    assert line["window_s"] == pytest.approx(want["window_s"], abs=1e-9)
    assert line["idle_s"] == pytest.approx(want["window_s"] - want["busy_s"], abs=1e-9)
    assert line["idle_by_cause_s"] == {"other": pytest.approx(line["idle_s"])}


def _traced_line(traced):
    return {
        "correct": True, "attempted": 10, "failed": 0,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1.2e10, "window_s": 4.0, "busy_s": 3.9},
        "metrics": {n: {"value": 1.5, "unit": u} for n, u in traced.items()},
    }


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_a_traced_line_carries_the_new_metrics_in_their_cells(cell):
    bench = resultline.load_benchmark()
    assert cell in [w["name"] for w in bench["workloads"]]
    traced, untraced = resultline.declared(bench, cell, True), resultline.declared(bench, cell, False)
    mine = {n for n, cells in NEW.items() if cell in cells}
    assert mine and mine <= set(traced) and not set(NEW) & set(untraced)
    assert not (set(NEW) - mine) & set(traced)
    line = _traced_line(traced)
    assert resultline.problems(line, bench, cell, True, 1) == []
    # A reader that finds nothing (the parent's program) leaves its metric out.
    cut = {**line, "metrics": {k: v for k, v in line["metrics"].items() if k not in mine}}
    assert resultline.problems(cut, bench, cell, True, 1, may_miss=mine) == []
    assert resultline.problems(cut, bench, cell, True, 1)


def test_every_cell_is_one_of_those_with_a_case_here():
    cells = [w["name"] for w in resultline.load_benchmark()["workloads"]]
    assert sorted(cells) == sorted(NEW_CELLS + [MOE_CELL, SWA_CELL, SSM_CELL, AFM_CELL, MCHAT_CELL])


def test_the_expert_models_cell_declares_its_own_metrics_and_none_of_the_dense_cells():
    bench = resultline.load_benchmark()
    traced, untraced = resultline.declared(bench, MOE_CELL, True), resultline.declared(bench, MOE_CELL, False)
    # Its own, and since PR 38 the two idle-gap metrics every saturated cell reads.
    assert set(traced) == MOE_METRICS | {"idle_exposed_host_pct", "idle_in_fetch_pct"} and not set(NEW) & set(traced)
    assert set(untraced) == {"output_tok_s", "setup_s"}
    for m in bench["per_layer"]:  # no metric without a list: a later cell takes none by default
        assert "workloads" in m, m["name"]
        if m["name"] in MOE_METRICS:
            assert m["workloads"] == [MOE_CELL]
            assert m["moves"] == ("setup_s" if m["name"].split(".")[0] in ("scale_from_zero_s", "engine_load_s", "engine_warmup_s") else "output_tok_s")
    for name in MOE_METRICS:
        with open(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "perfbench", "readers", spec["reader"] + ".py"))


@pytest.mark.parametrize("missing", sorted(n for n in MOE_METRICS if n.endswith(".moe")))
def test_a_traced_line_of_the_expert_models_cell(missing):
    bench = resultline.load_benchmark()
    line = _traced_line(resultline.declared(bench, MOE_CELL, True))
    assert resultline.problems(line, bench, MOE_CELL, True, 1) == []
    cut = {**line, "metrics": {k: v for k, v in line["metrics"].items() if k != missing}}
    assert resultline.problems(cut, bench, MOE_CELL, True, 1) == [f"metric {missing} of this workload and mode is missing"]
    assert resultline.problems(cut, bench, MOE_CELL, True, 1, may_miss={missing}) == []
    over = {**line, "metrics": {**line["metrics"], "window_mfu.moe": {"value": 106.0, "unit": "%"}}}
    assert any("over 105%" in p for p in resultline.problems(over, bench, MOE_CELL, True, 1))


@pytest.mark.parametrize("name", ["chat-sat", "chat-rate", "docqa", "reason-sat"])
@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_a_longer_plan_has_the_shorter_one_as_its_prefix(name, seed):
    spec = traffic.load(name)
    key = lambda r: (r.prompt, r.max_tokens, r.tag, r.due_s)  # noqa: E731
    short = traffic.build(spec, seed, 50)
    longer = traffic.build(spec, seed, 50 + spec["ramp_s"] + 5)
    assert [key(r) for r in longer.shared[: len(short.shared)]] == [key(r) for r in short.shared]
    assert len(longer.per_client) == len(short.per_client)
    for sc, lc in zip(short.per_client, longer.per_client):
        assert [key(r) for r in lc[: len(sc)]] == [key(r) for r in sc]
    if short.loop == "open":  # and the open loop has requests left for the tail
        assert longer.shared[-1].due_s >= short.shared[-1].due_s + spec["ramp_s"] + 4


HLO = """HloModule jit__unknown, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/while/body/sampling/sampling/mul" stack_frame_id=3}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(f)/while/body/sampling/add"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %fusion.9 = f32[8]{0} fusion(%p0.1), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2
  %kernel.6 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/attn/attn.kernel/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(%kernel.6), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/ffn/dot_general"}
  %copy.3 = f32[8]{0} copy(%fusion.2), metadata={op_name="jit(f)/while/body/dynamic_slice"}
  ROOT %sort.20 = f32[8]{0} sort(%copy.3), dimensions={0}, metadata={op_name="jit(f)/while/body/logprobs/top_k"}
}
"""


def test_operations_are_filed_under_their_named_scope():
    scopes = ("embed", "attn", "ffn", "lm_head", "sampling", "logprobs")
    got = scope_reduce.instruction_scopes(HLO, scopes)
    assert got["kernel.6"] == "attn"  # attn.kernel files under attn
    assert got["fusion.2"] == "ffn"  # its own metadata wins over what it calls
    assert got["fusion.1"] == "sampling"  # none of its own: what it calls, transitively
    assert got["sort.20"] == "logprobs"
    assert got["copy.3"] is None  # named, under none of the scopes
    assert "a" not in got  # neither metadata nor callees
    ops = {
        "%kernel.6 f32[8]": [3.0, 28], "%fusion.2 f32[8]": [2.0, 28], "%fusion.1 f32[8]": [1.0, 1],
        "%sort.20 f32[8]": [2.5, 1], "%copy.3 f32[8]": [0.25, 1], "%elsewhere.1": [0.25, 1],
    }
    r = scope_reduce.reduce_program(ops, got, scopes)
    assert r["total_s"] == 9.0 and r["unscoped_s"] == 0.25 and r["unmapped_s"] == 0.25
    assert r["by_scope_s"] == {"embed": 0.0, "attn": 3.0, "ffn": 2.0, "lm_head": 0.0, "sampling": 1.0, "logprobs": 2.5}
    assert r["top"][0] == ["%kernel.6 f32[8]", "attn", 3.0]


def _msg(*fields_):
    """A protobuf message from (field, wire type, value) triples."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for f, w, v in fields_:
        out += varint(f << 3 | w) + (varint(v) if w == 0 else varint(len(v)) + v)
    return out


def test_the_programs_are_found_in_the_metadata_plane():
    hlo_proto = b"\x0a\x03abc" * 400  # stands in for an xla.HloProto
    stat = _msg((1, 0, 7), (6, 2, hlo_proto))
    meta = _msg((1, 0, 12345678901234), (2, 2, b"jit__unknown(12345678901234)"), (5, 2, stat))
    plane = _msg((1, 0, 3), (2, 2, b"/host:metadata"), (4, 2, _msg((1, 0, 12345678901234), (2, 2, meta))))
    other = _msg((2, 2, b"/device:TPU:0"), (4, 2, _msg((1, 0, 1), (2, 2, _msg((2, 2, b"%fusion.1"))))))
    xspace = _msg((1, 2, other), (1, 2, plane))
    assert scope_reduce.hlo_protos(xspace) == {"jit__unknown(12345678901234)": hlo_proto}
    assert scope_reduce.hlo_protos(_msg((1, 2, other))) == {}


def _ctx(**trace):
    return types.SimpleNamespace(trace=trace or None)


def test_the_scope_reader_finds_the_runs_own_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_share, "HERE", str(tmp_path / "perfbench"))
    run = tmp_path / ".perfbench_work" / "some-cell" / "profile" / "profile-1" / "plugins" / "profile" / "t"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"x" * 1234)
    other = tmp_path / ".perfbench_work" / "other-cell" / "profile" / "p"
    other.mkdir(parents=True)
    (other / "kept.xplane.pb").write_bytes(b"x" * 99)
    assert scope_share.trace_file(_ctx(bytes=1234)) == str(run / "host.xplane.pb")
    assert scope_share.trace_file(_ctx(bytes=5)) is None


def test_the_scope_reader_reads_nothing_where_there_is_nothing(tmp_path, monkeypatch, capsys):
    """No trace (an untraced run), no file, or a trace reduced by a harness
    without `ops_in_modules_s`: None, never an exception."""
    monkeypatch.setattr(scope_share, "HERE", str(tmp_path / "perfbench"))
    for ctx in (_ctx(), _ctx(bytes=7, ops_in_modules_s={}), _ctx(bytes=7)):
        assert scope_share.read(ctx, "^jit__unknown", "attn") is None
    # A program without the scopes (from before PR 24): its time is there, named under none.
    ctx = _ctx(bytes=7)
    ctx.scope_shares = {"jit__unknown(1)": {"total_s": 2.0, "by_scope_s": {s: 0.0 for s in scope_share.SCOPES}}}
    assert scope_share.read(ctx, "^jit__unknown", "attn") is None
    ctx.scope_shares["jit__unknown(1)"]["by_scope_s"].update(attn=0.5, ffn=1.0)
    assert scope_share.read(ctx, "^jit__unknown", "attn") == 25.0
    assert scope_share.read(ctx, "^jit__unknown", "attn|ffn") == 75.0
    assert scope_share.read(ctx, "^jit_prefill", "attn") is None


def test_rehearsal_of_a_run_that_traces_itself(tmp_path):
    """--rehearse --trace 2, every phase, on the CPU at a tiny size: one
    last line with both kinds of metric; the end-to-end values are what
    `Run.end_to_end` gave over the window's records; the traced interval is
    the `profile.window` event with the Python tracer off; each idle gap
    names its own causes, and the idle shares add up."""
    cell = "qwen7b-int8-chat-rate"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--workload", cell, "--rehearse",
         "--trace", "2", "--seed", str(2**31 + 5)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=600,
    )
    lines = [json.loads(ln) for ln in proc.stdout.decode().splitlines() if ln.startswith("{")]
    assert proc.returncode == 0, (lines[-1], proc.stderr.decode()[-2000:])
    phases = [ln.get("phase") for ln in lines[:-1]]
    for phase in ("checkpoint", "scale_from_zero", "window_open", "stop", "window", "trace", "logits", "end_to_end"):
        assert phase in phases, phases
    assert phases.index("stop") < phases.index("trace")  # read after the operator has gone, beside the logits child
    last, trace, window = lines[-1], lines[phases.index("trace")], lines[phases.index("window")]
    bench = resultline.load_benchmark()
    layer = resultline.declared(bench, cell, 1)
    assert resultline.problems(last, bench, cell, 2, 1, rehearsal=True, may_miss=set(layer)) == []
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    # Both kinds, the end-to-end ones from the one function that computes them.
    e2e = lines[phases.index("end_to_end")]
    for name in resultline.declared(bench, cell, 0):
        assert last["metrics"][name]["value"] == e2e[name]
    assert {"idle_exposed_host_pct.rate", "idle_in_fetch_pct.rate", "host_work_per_chunk_ms.rate"} <= set(last["metrics"])
    # The interval is the capture's own event, with the Python tracer off,
    # and the capture began only after the window's last record had closed.
    assert trace["window_from"] == "host event 'profile.window'" and trace["python_tracer"] is False
    assert 0 <= trace["last_record_closed_s"] <= trace["capture_began_s"]
    assert last["device"]["window_s"] == pytest.approx(4.0, abs=0.1)
    # Every idle piece under the segment beside it: the shares add up to the idle share.
    table = trace["idle_by_host"]
    assert table["n_segments"] > 0 and sum(table["idle_by_cause_s"].values()) == pytest.approx(table["idle_s"])
    idle_pct = 100.0 * (1 - last["device"]["busy_s"] / last["device"]["window_s"])
    under_idle = 100.0 * table["idle_by_cause_s"].get("idle", 0.0) / table["window_s"]
    split = last["metrics"]["idle_exposed_host_pct.rate"]["value"] + last["metrics"]["idle_in_fetch_pct.rate"]["value"]
    assert split + under_idle == pytest.approx(idle_pct, abs=0.1)
    gaps = last["breakdown"]["idle_gaps"]
    assert gaps and all("host: " in g[0] and "dominant stall cause" not in g[0] and len(g[0]) <= 200 for g in gaps)
    # The window's line keeps where the longest silence lay and the engine's slowest steps inside it.
    assert 0 <= window["longest_silence_at_s"] < 8 and all(-0.5 <= s["at_s"] < 9.5 for s in window["slowest_steps"])
    assert window["slowest_steps"] and {"kind", "total_ms", "ms"} <= set(window["slowest_steps"][0])


def test_rehearsal_of_a_traced_run(tmp_path):
    """--rehearse --trace 1, every phase, on the CPU at a tiny size: the
    accepted harness reads this PR's metrics from this PR's program."""
    cell = "qwen7b-int8-chat-sat"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)  # conftest's eight virtual devices: the cell asks for one
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--workload", cell, "--rehearse",
         "--trace", "1", "--seed", str(2**31 + 5)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=600,
    )
    lines = [json.loads(ln) for ln in proc.stdout.decode().splitlines() if ln.startswith("{")]
    assert proc.returncode == 0, (lines[-1], proc.stderr.decode()[-2000:])
    phases = [ln.get("phase") for ln in lines[:-1]]
    for phase in ("checkpoint", "scale_from_zero", "window_open", "stop", "window", "logits", "trace", "scopes"):
        assert phase in phases, phases
    scopes = lines[phases.index("scopes")]
    assert scopes["error"] is None and any(p.startswith("jit__unknown") for p in scopes["programs"])
    last = lines[-1]
    bench = resultline.load_benchmark()
    assert resultline.problems(
        last, bench, cell, True, 1, rehearsal=True, may_miss=set(resultline.declared(bench, cell, True)),
    ) == []
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    mine = {n for n, cells in NEW.items() if cell in cells}
    assert mine <= set(last["metrics"]), sorted(last["metrics"])
    assert 0 <= last["metrics"]["prefill_padding_pct"]["value"] < 100
    shares = [last["metrics"][n]["value"] for n in mine if n.endswith("_share_pct")]
    assert all(0 <= v <= 100 for v in shares) and sum(shares) <= 100.0


@pytest.mark.slow  # about a minute alone, more beside five other workers: not tier-1 (CHANGES.md, PR 33)
def test_rehearsal_of_the_expert_models_cell(tmp_path):
    """--rehearse --trace 1 of kanana2-bf16-reason-sat at the tests' small
    size: every phase, the family's two-part logits check, and every
    per-layer metric the CPU can read."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--workload", MOE_CELL, "--rehearse",
         "--trace", "1", "--seed", str(2**31 + 7)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=600,
    )
    lines = [json.loads(ln) for ln in proc.stdout.decode().splitlines() if ln.startswith("{")]
    assert proc.returncode == 0, (lines[-1], proc.stderr.decode()[-2000:])
    last = lines[-1]
    bench = resultline.load_benchmark()
    # Time a step of one scope means nothing in a CPU trace (readers/moe_rooflines.py).
    may_miss = {"moe_experts_roofline", "mla_decode_roofline"}
    assert resultline.problems(last, bench, MOE_CELL, True, 1, rehearsal=True, may_miss=may_miss) == []
    assert last["correct"] is True and MOE_METRICS - may_miss <= set(last["metrics"])
    logits = next(ln for ln in lines if ln.get("phase") == "logits")
    assert logits["ok"] and set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices"}
    assert 0 < last["metrics"]["moe_experts_hit_pct"]["value"] <= 100


# -- PR 36: the window family's cell and its readers ---------------------------

SWA_CELL = "smallthinker-bf16-longdoc-sat"
SWA_ROOFLINES = {
    "moe_experts_roofline.swa", "full_attn_decode_roofline", "window_attn_decode_roofline", "prefill_attn_roofline.swa",
}


def test_the_window_cells_metrics_are_its_own():
    bench = resultline.load_benchmark()
    mine = resultline.declared(bench, SWA_CELL, True)
    shared = {"idle_exposed_host_pct", "idle_in_fetch_pct"}  # PR 38: one reader in every saturated cell
    assert len(mine) == 23 + len(shared) and SWA_ROOFLINES | shared <= set(mine)
    assert {"kv_pages_peak_pct.full", "kv_pages_peak_pct.window", "window_mfu.swa", "decode_step_roofline.swa"} <= set(mine)
    # No other cell carries them, and this cell none of theirs.
    for m in bench["per_layer"]:
        if m["name"] not in shared:
            assert (SWA_CELL in m["workloads"]) == (m["workloads"] == [SWA_CELL]), m["name"]
    assert set(resultline.declared(bench, SWA_CELL, False)) == {"output_tok_s", "setup_s"}
    for name in mine:
        assert os.path.exists(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".json")), name


@pytest.mark.parametrize("missing", ["decode_step_ms.swa", "kv_pages_peak_pct.window", "window_attn_decode_roofline", "window_mfu.swa"])
def test_a_traced_line_of_the_window_models_cell(missing):
    bench = resultline.load_benchmark()
    line = _traced_line(resultline.declared(bench, SWA_CELL, True))
    assert resultline.problems(line, bench, SWA_CELL, True, 1) == []
    cut = {**line, "metrics": {k: v for k, v in line["metrics"].items() if k != missing}}
    assert resultline.problems(cut, bench, SWA_CELL, True, 1) == [f"metric {missing} of this workload and mode is missing"]
    assert resultline.problems(cut, bench, SWA_CELL, True, 1, may_miss={missing}) == []
    over = {**line, "metrics": {**line["metrics"], "window_attn_decode_roofline": {"value": 106.0, "unit": "%"}}}
    assert any("over 105%" in p for p in resultline.problems(over, bench, SWA_CELL, True, 1))


def _scrape(at, **series):
    return types.SimpleNamespace(
        at=at, has=lambda name: name in series,
        value=lambda name, **labels: sum(
            v for have, v in series.get(name, ()) if all(have.get(k) == w for k, w in labels.items())
        ),
    )


def test_the_window_rooflines_from_counters_and_scopes():
    """readers/swa_rooflines.py on a made-up window: 100 decode chunks of 8
    steps, pairs that are 24 slots x (3 full layers x 8000 + 9 window
    layers x 4096) keys a step, half the experts hit; scope seconds by the
    difference of the two reductions (readers/swa_scopes.py)."""
    from readers import swa_rooflines, swa_scopes

    with open(os.path.join(ROOT, "perfbench", "configs", "smallthinker-21b-a3b-bf16.json")) as f:
        cfg = json.load(f)
    steps = 800
    pairs = lambda kind, phase, n: ({"kind": kind, "phase": phase}, float(n))  # noqa: E731
    after = {
        "kubeai_engine_attn_pairs_total": [
            pairs("full", "decode", steps * 24 * 3 * 8000), pairs("window", "decode", steps * 24 * 9 * 4096),
            pairs("full", "prefill", 3 * 4e9), pairs("window", "prefill", 9 * 2e9),
        ],
        "kubeai_engine_moe_experts_hit_total": [({"phase": "decode"}, steps * 12 * 32.0)],
        "kubeai_engine_moe_expert_reads_possible_total": [({"phase": "decode"}, steps * 12 * 64.0)],
        "kubeai_engine_step_seconds_count": [({"phase": "decode_chunk"}, 100.0)],
        "kubeai_engine_prefill_tokens_total": [({}, 1.0e6)], "kubeai_engine_generated_tokens_total": [({}, 19200.0)],
    }
    zero = {k: [(labels, 0.0) for labels, _ in v] for k, v in after.items()}
    ctx = types.SimpleNamespace(
        hf={k: v for k, v in cfg.items() if k not in ("serving", "rehearsal", "reduced", "assumed", "source")},
        serving=cfg["serving"], rehearsal=False, window_s=50.0, trace_t0=20.0, trace_t1=24.0,
        before=_scrape(0.0, **zero), after=_scrape(50.0, **after), polls=[],
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"window_s": 4.0, "modules_s": {"jit__unknown(7)": (1.6, 1.6, 1.6, 10.0), "jit_prefill_chunk_fn(9)": (2.0, 2.0, 2.0, 80.0)}},
    )
    by = lambda **s: {"total_s": 1.6, "by_scope_s": {k.replace("_", "."): v for k, v in s.items()}}  # noqa: E731
    ctx.swa_scope_shares = {
        "layers": {
            "jit__unknown(7)": by(attn_full=0.20, attn_window=0.40, moe_experts=0.64),
            "jit_prefill_chunk_fn(9)": {"total_s": 2.0, "by_scope_s": {"attn.full": 0.2, "attn.window": 0.4}},
        },
        "kernels": {
            "jit__unknown(7)": by(attn_full=0.08, attn_window=0.16, attn_kernel=0.36, moe_experts=0.64),
            "jit_prefill_chunk_fn(9)": {"total_s": 2.0, "by_scope_s": {"attn.full": 0.05, "attn.window": 0.1, "attn.kernel": 0.45}},
        },
    }
    assert swa_scopes.read(ctx, "^jit__unknown", "full") == pytest.approx(12.5)
    assert swa_scopes.seconds(ctx, "^jit__unknown", "window", kernel=True)[0] == pytest.approx(0.24)
    n_steps = 10 * 8
    full_bytes, window_bytes = 24 * 3 * 8000 * 2048, 24 * 9 * 4096 * 2048
    read = lambda what, **kw: swa_rooflines.read(ctx, what, **kw)  # noqa: E731
    assert read("full_attn") == pytest.approx(100 * (full_bytes / 819e9) / (0.12 / n_steps))
    assert read("window_attn") == pytest.approx(100 * (window_bytes / 819e9) / (0.24 / n_steps))
    expert_bytes = 12 * 32 * 3 * 2560 * 768 * 2
    assert read("experts") == pytest.approx(100 * (expert_bytes / 819e9) / (0.64 / n_steps))
    outside = (12 * (20_976_640 + 2560 * 64) + 151936 * 2560 + 2560) * 2
    assert read("decode_step") == pytest.approx(
        100 * ((outside + expert_bytes + full_bytes + window_bytes) / 819e9) / (1.6 / n_steps)
    )
    # Prefill: the pairs between the polls around the traced seconds (here the window's edges).
    flops = 4 * 3584 * (3 * 4e9 + 9 * 2e9)
    assert read("prefill_attn", module="^jit_prefill") == pytest.approx(100 * (flops / 197e12) / (0.6 * 50.0 / 4.0))
    active = 12 * (20_976_640 + 2560 * 64 + 6 * 3 * 2560 * 768) + 151936 * 2560 + 2560
    all_pairs = steps * 24 * (3 * 8000 + 9 * 4096) + 3 * 4e9 + 9 * 2e9
    assert read("window_mfu") == pytest.approx(100 * (2 * active * 1019200 + 4 * 3584 * all_pairs) / (197e12 * 50))
    assert 0 < read("window_mfu") < 100
    # A program of another family, or the parent's: no such counter, nothing read, nothing raised.
    ctx.after = _scrape(50.0)
    assert all(read(w) is None for w in ("window_mfu", "experts", "full_attn", "window_attn", "decode_step", "prefill_attn"))
    ctx.trace, ctx.swa_scope_shares = None, None
    assert swa_scopes.read(ctx, "^jit__unknown", "full") is None


@pytest.mark.slow  # a minute and a half alone: not tier-1, as the expert model's rehearsal is not
def test_rehearsal_of_the_window_models_cell(tmp_path):
    """--rehearse --trace 1 of smallthinker-bf16-longdoc-sat at the
    configuration's `rehearsal` keys (window 256, 8 layers): every phase,
    the family's logits check through both pools, and every per-layer
    metric the CPU can read."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--workload", SWA_CELL, "--rehearse",
         "--trace", "1", "--seed", str(2**31 + 11)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=900,
    )
    lines = [json.loads(ln) for ln in proc.stdout.decode().splitlines() if ln.startswith("{")]
    assert proc.returncode == 0, (lines[-1], proc.stderr.decode()[-2000:])
    last = lines[-1]
    bench = resultline.load_benchmark()
    # Time a step of one scope means nothing in a CPU trace (readers/swa_rooflines.py).
    assert resultline.problems(last, bench, SWA_CELL, True, 1, rehearsal=True, may_miss=SWA_ROOFLINES) == []
    assert last["correct"] is True
    assert set(resultline.declared(bench, SWA_CELL, True)) - SWA_ROOFLINES <= set(last["metrics"])
    logits = next(ln for ln in lines if ln.get("phase") == "logits")
    assert logits["ok"] and set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices"}
    assert logits["sample"]["window_pages_released"] > 0
    assert 0 < last["metrics"]["kv_pages_peak_pct.window"]["value"] <= 100


# -- PR 40: the state-space family's cell and its readers ------------------------

SSM_CELL = "nemotron3super-bf16-agent-sat"
SSM_ROOFLINES = {"moe_experts_roofline.ssm", "ssm_decode_roofline", "ssm_prefill_roofline"}
SSM_NEW = SSM_ROOFLINES | {"decode_ssm_share_pct", "prefill_ssm_share_pct", "decode_step_roofline.ssm", "window_mfu.ssm"}


def test_the_state_space_cells_metrics_are_its_own():
    bench = resultline.load_benchmark()
    mine = resultline.declared(bench, SSM_CELL, True)
    assert len(mine) == 23 and SSM_NEW <= set(mine)
    assert all(n.endswith(".ssm") for n in set(mine) - SSM_NEW)  # twins of accepted readers, under its suffix
    # No other cell carries them, this cell none of theirs, and they were appended together, wherever later PRs' stand.
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("decode_step_ms.ssm")
    assert set(names[at : at + 23]) == set(mine)
    for m in bench["per_layer"]:
        assert (SSM_CELL in m["workloads"]) == (m["workloads"] == [SSM_CELL]), m["name"]
    assert set(resultline.declared(bench, SSM_CELL, False)) == {"output_tok_s", "setup_s"}
    for name in mine:
        with open(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "perfbench", "readers", spec["reader"] + ".py")), name
    cell = next(w for w in bench["workloads"] if w["name"] == SSM_CELL)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert (cell["traffic"], cell["chips"]) == ("agent-sat", 1)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    with open(os.path.join(ROOT, config["file"])) as f:
        published = json.load(f)
    assert published["source"] == config["source"] and set(config["reduced"]) < set(published["reduced"])
    assert (published["num_hidden_layers"], published["n_routed_experts"], published["router_experts"]) == (11, 128, 512)
    assert published["serving"]["engine_args"] == ["--warmup", "--max-slots", "96", "--max-seq-len", "8192"]
    spec = traffic.load("agent-sat", False)
    assert (spec["loop"], spec["clients"]) == ("closed", 120)
    assert spec["prompt_tokens"] == {"dist": "lognormal", "median": 1500, "sigma": 0.8, "min": 256, "max": 6000}
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.5, "min": 128, "max": 1024}


@pytest.mark.parametrize("missing", ["decode_step_ms.ssm", "ssm_decode_roofline", "prefill_ssm_share_pct", "window_mfu.ssm"])
def test_a_traced_line_of_the_state_space_models_cell(missing):
    bench = resultline.load_benchmark()
    line = _traced_line(resultline.declared(bench, SSM_CELL, True))
    assert resultline.problems(line, bench, SSM_CELL, True, 1) == []
    cut = {**line, "metrics": {k: v for k, v in line["metrics"].items() if k != missing}}
    assert resultline.problems(cut, bench, SSM_CELL, True, 1) == [f"metric {missing} of this workload and mode is missing"]
    assert resultline.problems(cut, bench, SSM_CELL, True, 1, may_miss={missing}) == []
    over = {**line, "metrics": {**line["metrics"], "ssm_decode_roofline": {"value": 106.0, "unit": "%"}}}
    assert any("over 105%" in p for p in resultline.problems(over, bench, SSM_CELL, True, 1))


def test_the_state_space_rooflines_from_counters_and_scopes():
    """readers/ssm_rooflines.py on a made-up window: 100 decode chunks of 8
    steps with 90 of 96 slots live, half the held experts hit, a million
    prompt tokens; scope seconds as readers/ssm_scopes.py keeps them."""
    from families import nemotron_h_counts as counts
    from readers import ssm_rooflines, ssm_scopes

    with open(os.path.join(ROOT, "perfbench", "configs", "nemotron3-super-120b-a12b-bf16.json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in ("serving", "rehearsal", "reduced", "assumed", "source")}
    steps = 800
    after = {
        "kubeai_engine_state_slots_total": [({}, 96.0)],
        "kubeai_engine_slot_steps_total": [({"state": "active"}, steps * 90.0), ({"state": "idle"}, steps * 6.0)],
        "kubeai_engine_moe_experts_hit_total": [({"phase": "decode"}, steps * 5 * 64.0)],
        "kubeai_engine_moe_expert_reads_possible_total": [({"phase": "decode"}, steps * 5 * 128.0)],
        "kubeai_engine_prefill_tokens_total": [({}, 1.0e6)], "kubeai_engine_generated_tokens_total": [({}, 72000.0)],
    }
    zero = {k: [(labels, 0.0) for labels, _ in v] for k, v in after.items()}
    zero["kubeai_engine_state_slots_total"] = after["kubeai_engine_state_slots_total"]  # a gauge
    ctx = types.SimpleNamespace(
        hf=hf, serving=cfg["serving"], rehearsal=False, window_s=50.0, trace_t0=20.0, trace_t1=24.0,
        before=_scrape(0.0, **zero), after=_scrape(50.0, **after), polls=[], all_records=[],
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"window_s": 4.0, "modules_s": {"jit__unknown(7)": (1.6, 1.6, 1.6, 10.0), "jit_prefill_chunk_fn(9)": (2.0, 2.0, 2.0, 80.0)}},
    )
    ctx.ssm_scope_shares = {
        "jit__unknown(7)": {"total_s": 1.6, "by_scope_s": {"ssm.conv": 0.1, "ssm.scan": 0.7, "ssm.in_proj": 0.1, "moe.experts": 0.4, "moe": 0.1}},
        "jit_prefill_chunk_fn(9)": {"total_s": 2.0, "by_scope_s": {"ssm.scan": 0.5, "ssm.in_proj": 0.3, "moe.experts": 0.6}},
    }
    assert ssm_scopes.read(ctx, "^jit__unknown", "ssm|ssm.in_proj|ssm.conv|ssm.scan|ssm.gate_norm|ssm.out_proj") == pytest.approx(56.25)
    assert ssm_scopes.read(ctx, "^jit_prefill", "ssm.scan") == pytest.approx(25.0)
    n_steps = 10 * 8
    read = lambda what, **kw: ssm_rooflines.read(ctx, what, **kw)  # noqa: E731
    state = 90 * 2 * 5 * (4_194_304 + 61_440)
    assert read("ssm_decode") == pytest.approx(100 * (state / 819e9) / (0.8 / n_steps))
    experts = 5 * 64 * 5_505_024 * 2
    assert read("experts") == pytest.approx(100 * (experts / 819e9) / (0.4 / n_steps))
    outside = counts.weights_outside_experts_bytes(hf, 2)
    assert outside == (5 * 109_640_064 + 35_655_680 + 5 * counts.expert_block_outside_params(hf) + 32768 * 4096 + 4096) * 2
    assert read("decode_step") == pytest.approx(100 * ((outside + experts + state) / 819e9) / (1.6 / n_steps))  # no record: no keys read
    flops = 5 * 6_553_600 * 1.0e6
    assert read("ssm_prefill", module="^jit_prefill") == pytest.approx(100 * (flops / 197e12) / (0.5 * 50.0 / 4.0))
    per_token = 2 * counts.active_params(hf) + 5 * 5 * 128 * 64 * 128
    assert read("window_mfu") == pytest.approx(100 * per_token * 1_072_000 / (197e12 * 50))
    assert all(0 < read(w, **kw) < 100 for w, kw in (("ssm_decode", {}), ("experts", {}), ("decode_step", {}), ("window_mfu", {})))
    # A program of another family, or the parent's: no such gauge, nothing read, nothing raised.
    ctx.after = _scrape(50.0)
    assert all(read(w) is None for w in ("window_mfu", "experts", "ssm_decode", "decode_step", "ssm_prefill"))
    ctx.after, ctx.hf = _scrape(50.0, **after), {**hf, "model_type": "smallthinker"}
    assert read("window_mfu") is None
    # ... and a trace that carries no `ssm` scope gives no share.
    ctx.ssm_scope_shares = {"jit__unknown(7)": {"total_s": 1.6, "by_scope_s": {"attn": 0.4, "moe.experts": 0.4}}}
    assert ssm_scopes.read(ctx, "^jit__unknown", "moe.experts") is None
    ctx.trace, ctx.ssm_scope_shares = None, None
    assert ssm_scopes.read(ctx, "^jit__unknown", "ssm.scan") is None


@pytest.mark.slow  # two minutes alone: not tier-1, as the other families' rehearsals are not
def test_rehearsal_of_the_state_space_models_cell(tmp_path):
    """--rehearse --trace 2 of nemotron3super-bf16-agent-sat at the
    configuration's `rehearsal` keys (11 blocks, 4 of 16 experts held):
    every phase, the family's logits check through the slot's state, and
    every per-layer metric the CPU can read."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--workload", SSM_CELL, "--rehearse",
         "--trace", "2", "--seed", str(2**31 + 13)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=900,
    )
    lines = [json.loads(ln) for ln in proc.stdout.decode().splitlines() if ln.startswith("{")]
    assert proc.returncode == 0, (lines[-1], proc.stderr.decode()[-2000:])
    last = lines[-1]
    bench = resultline.load_benchmark()
    # Time a step means nothing in a CPU trace (readers/ssm_rooflines.py).
    may_miss = SSM_ROOFLINES | {"decode_step_roofline.ssm"}
    assert resultline.problems(last, bench, SSM_CELL, 2, 1, rehearsal=True, may_miss=may_miss) == []
    assert set(resultline.declared(bench, SSM_CELL, 2)) - may_miss <= set(last["metrics"])
    logits = next(ln for ln in lines if ln.get("phase") == "logits")
    assert set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices", "state"}
    assert all(logits["compared"][part]["ok"] for part in ("prefill_cold", "prefill_chunked", "decode", "router_choices"))
    assert logits["held_experts"] == [0, 4, 16] and logits["pattern"] == "MEMEMEM*"
    assert 0 < last["metrics"]["moe_experts_hit_pct.ssm"]["value"] <= 100
    assert 0 < last["metrics"]["decode_ssm_share_pct"]["value"] < 100



# -- PR 42: the gated window family's cell, its readers, and docqa's bypass ------

AFM_CELL = "trinitymini-bf16-mixedlen-sat"
MCHAT_CELL = "mistral7b-int8-chat-sat"
AFM_NEW = {"prefill_attn_full_share_pct", "attn_gate_norm_share_pct"}
AFM_ROOFLINES = {
    "moe_experts_roofline.afm", "full_attn_decode_roofline.afm", "window_attn_decode_roofline.afm", "prefill_attn_roofline.afm",
}


def test_the_two_new_cells_metrics_are_their_own():
    bench = resultline.load_benchmark()
    mine = resultline.declared(bench, AFM_CELL, True)
    assert len(mine) == 23 and AFM_NEW | AFM_ROOFLINES | {"decode_step_roofline.afm", "window_mfu.afm"} <= set(mine)
    assert all(n.endswith(".afm") for n in set(mine) - AFM_NEW)  # twins of accepted readers, under its suffix
    bypass = resultline.declared(bench, MCHAT_CELL, True)
    assert set(bypass) == {"decode_step_ms.mchat", "batch_occupancy_pct.mchat", "prefix_hit_pct.mchat"}
    # No other cell carries them, these cells none of theirs; they stand last, appended; the list is at its cap.
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-26:] == list(mine) + list(bypass) and len(names) == 128
    for m in bench["per_layer"]:
        for cell in (AFM_CELL, MCHAT_CELL):
            assert (cell in m["workloads"]) == (m["workloads"] == [cell]), m["name"]
    for cell in (AFM_CELL, MCHAT_CELL):
        assert set(resultline.declared(bench, cell, False)) == {"output_tok_s", "setup_s"}
    # A data-only twin is its accepted metric's file, byte for byte.
    for twin, of in (
        ("decode_step_ms.afm", "decode_step_ms.swa"), ("kv_pages_peak_pct.window.afm", "kv_pages_peak_pct.window"),
        ("idle_exposed_host_pct.afm", "idle_exposed_host_pct"), ("decode_attn_full_share_pct.afm", "decode_attn_full_share_pct"),
        ("decode_step_ms.mchat", "decode_step_ms.tput"), ("prefix_hit_pct.mchat", "prefix_hit_pct"),
        ("batch_occupancy_pct.mchat", "batch_occupancy_pct"),
    ):
        files = [open(os.path.join(ROOT, "perfbench", "layer_metrics", n + ".json")).read() for n in (twin, of)]
        assert files[0] == files[1], twin
    for name in list(mine) + list(bypass):
        with open(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "perfbench", "readers", spec["reader"] + ".py")), name
        if name in AFM_ROOFLINES | {"decode_step_roofline.afm", "window_mfu.afm"}:
            assert spec["reader"] == "afm_rooflines"  # this family's counts
    cell = next(w for w in bench["workloads"] if w["name"] == AFM_CELL)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert (cell["traffic"], cell["chips"], config["reduced"]) == ("mixedlen-sat", 1, ["num_hidden_layers"])
    with open(os.path.join(ROOT, config["file"])) as f:
        published = json.load(f)
    assert published["source"] == config["source"] and set(config["reduced"]) == set(published["reduced"])
    assert (published["num_hidden_layers"], published["num_experts"], published["sliding_window"], len(published["layer_types"])) == (8, 128, 2048, 32)
    assert published["serving"]["engine_args"] == ["--warmup", "--max-slots", "24", "--max-seq-len", "32768", "--kv-pages", "6145"]
    # The row's keys as published: every number of the catalog's config under the same key.
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
        assert row["source_url"] == published["source"]
        assert {k for k, v in row["config"].items() if published.get(k) != v} == {"num_hidden_layers"}
    spec = traffic.load("mixedlen-sat", False)
    assert (spec["loop"], spec["clients"], spec["block"], spec["max_total_tokens"]) == ("closed", 28, 28, 32768)
    assert spec["prompt_tokens"] == {"dist": "lognormal", "median": 4000, "sigma": 0.9, "min": 256, "max": 24576}
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.6, "min": 64, "max": 1280}
    sizes = traffic.quantiles(spec["prompt_tokens"], 28)
    assert (sizes[0], sizes[-1]) == (604, 24576) and 5500 < sum(sizes) / 28 < 6000  # short and long in ONE block
    other = next(w for w in bench["workloads"] if w["name"] == MCHAT_CELL)
    assert (other["config"], other["traffic"], other["chips"]) == ("mistral-7b-v0.3-int8", "chat-sat", 1)


@pytest.mark.parametrize(
    "cell,missing",
    [(AFM_CELL, n) for n in ("decode_step_ms.afm", "kv_pages_peak_pct.window.afm", "attn_gate_norm_share_pct", "window_mfu.afm")]
    + [(MCHAT_CELL, "prefix_hit_pct.mchat")],
)
def test_a_traced_line_of_the_two_new_cells(cell, missing):
    bench = resultline.load_benchmark()
    line = _traced_line(resultline.declared(bench, cell, 2))
    assert resultline.problems(line, bench, cell, 2, 1) == []
    cut = {**line, "metrics": {k: v for k, v in line["metrics"].items() if k != missing}}
    assert resultline.problems(cut, bench, cell, 2, 1) == [f"metric {missing} of this workload and mode is missing"]
    assert resultline.problems(cut, bench, cell, 2, 1, may_miss={missing}) == []
    if cell == AFM_CELL:
        over = {**line, "metrics": {**line["metrics"], "window_mfu.afm": {"value": 106.0, "unit": "%"}}}
        assert any("over 105%" in p for p in resultline.problems(over, bench, cell, 2, 1))


def test_the_gated_window_familys_rooflines_from_counters_and_scopes():
    """readers/afm_rooflines.py on a made-up window: 100 decode chunks of 8
    steps, pairs that are 24 slots x (2 full layers x 10000 + 6 window
    layers x 2048) keys a step, half the experts hit; scope seconds by the
    difference of swa_scopes' two reductions; afm_scopes' own list."""
    from families import afmoe_counts as counts
    from readers import afm_rooflines, afm_scopes

    with open(os.path.join(ROOT, "perfbench", "configs", "trinity-mini-bf16.json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in ("serving", "rehearsal", "reduced", "assumed", "source")}
    steps = 800
    pairs = lambda kind, phase, n: ({"kind": kind, "phase": phase}, float(n))  # noqa: E731
    after = {
        "kubeai_engine_attn_pairs_total": [
            pairs("full", "decode", steps * 24 * 2 * 10000), pairs("window", "decode", steps * 24 * 6 * 2048),
            pairs("full", "prefill", 2 * 4e9), pairs("window", "prefill", 6 * 1e9),
        ],
        "kubeai_engine_moe_experts_hit_total": [({"phase": "decode"}, steps * 6 * 64.0)],
        "kubeai_engine_moe_expert_reads_possible_total": [({"phase": "decode"}, steps * 6 * 128.0)],
        "kubeai_engine_step_seconds_count": [({"phase": "decode_chunk"}, 100.0)],
        "kubeai_engine_prefill_tokens_total": [({}, 6.0e5)], "kubeai_engine_generated_tokens_total": [({}, 19200.0)],
    }
    zero = {k: [(labels, 0.0) for labels, _ in v] for k, v in after.items()}
    ctx = types.SimpleNamespace(
        hf=hf, serving=cfg["serving"], rehearsal=False, window_s=50.0, trace_t0=20.0, trace_t1=24.0,
        before=_scrape(0.0, **zero), after=_scrape(50.0, **after), polls=[],
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"window_s": 4.0, "modules_s": {"jit__unknown(7)": (1.6, 1.6, 1.6, 10.0), "jit_prefill_chunk_fn(9)": (2.0, 2.0, 2.0, 80.0)}},
    )
    by = lambda **s: {"total_s": 1.6, "by_scope_s": {k.replace("_", "."): v for k, v in s.items()}}  # noqa: E731
    ctx.swa_scope_shares = {
        "layers": {
            "jit__unknown(7)": by(attn_full=0.20, attn_window=0.40, moe_experts=0.64),
            "jit_prefill_chunk_fn(9)": {"total_s": 2.0, "by_scope_s": {"attn.full": 0.5, "attn.window": 0.3}},
        },
        "kernels": {
            "jit__unknown(7)": by(attn_full=0.08, attn_window=0.16, attn_kernel=0.36, moe_experts=0.64),
            "jit_prefill_chunk_fn(9)": {"total_s": 2.0, "by_scope_s": {"attn.full": 0.05, "attn.window": 0.1, "attn.kernel": 0.65}},
        },
    }
    n_steps = 10 * 8
    full_bytes, window_bytes = 24 * 2 * 10000 * 2048, 24 * 6 * 2048 * 2048
    read = lambda what, **kw: afm_rooflines.read(ctx, what, **kw)  # noqa: E731
    assert read("full_attn") == pytest.approx(100 * (full_bytes / 819e9) / (0.12 / n_steps))
    assert read("window_attn") == pytest.approx(100 * (window_bytes / 819e9) / (0.24 / n_steps))
    expert_bytes = 6 * 64 * 3 * 2048 * 1024 * 2
    assert read("experts") == pytest.approx(100 * (expert_bytes / 819e9) / (0.64 / n_steps))
    outside = counts.weights_outside_experts_bytes(hf, 2)
    assert outside == (8 * 27_271_424 + 2 * 37_748_736 + 6 * (262_272 + 6_291_456) + 200192 * 2048 + 2048) * 2
    assert read("decode_step") == pytest.approx(100 * ((outside + expert_bytes + full_bytes + window_bytes) / 819e9) / (1.6 / n_steps))
    flops = 4 * 4096 * (2 * 4e9 + 6 * 1e9)
    assert read("prefill_attn", module="^jit_prefill") == pytest.approx(100 * (flops / 197e12) / (0.8 * 50.0 / 4.0))
    all_pairs = steps * 24 * (2 * 10000 + 6 * 2048) + 2 * 4e9 + 6 * 1e9
    assert read("window_mfu") == pytest.approx(100 * (2 * counts.active_params(hf) * 619200 + 4 * 4096 * all_pairs) / (197e12 * 50))
    assert all(0 < read(w, **kw) < 100 for w, kw in (("full_attn", {}), ("window_attn", {}), ("experts", {}), ("decode_step", {}), ("window_mfu", {})))
    # The tail's own polls bracket the traced seconds where the run traced itself after its window.
    ctx.tail_view = types.SimpleNamespace(before=_scrape(60.0, **zero), polls=[], after=_scrape(65.0, **after), trace_t0=61.0, trace_t1=65.0)
    assert read("prefill_attn", module="^jit_prefill") == pytest.approx(100 * (flops / 197e12) / (0.8 * 5.0 / 4.0))
    # afm_scopes: what the family adds to a plain block, told apart from the layer it sits in.
    ctx.afm_scope_shares = {
        "jit__unknown(7)": {"total_s": 1.6, "by_scope_s": {"attn.qk_norm": 0.02, "attn.gate": 0.03, "norm.post": 0.05, "attn.window": 0.4}},
    }
    assert afm_scopes.read(ctx, "^jit__unknown", "attn.qk_norm|attn.gate|norm.post") == pytest.approx(100 * 0.10 / 1.6)
    # A program of another family, or the parent's: no such counter or scope, nothing read, nothing raised.
    ctx.afm_scope_shares = {"jit__unknown(7)": by(attn_window=0.4, moe_experts=0.6)}
    assert afm_scopes.read(ctx, "^jit__unknown", "attn.qk_norm|attn.gate|norm.post") is None
    ctx.hf = {**hf, "model_type": "smallthinker"}
    assert all(read(w) is None for w in ("window_mfu", "experts", "full_attn", "decode_step"))
    ctx.hf, ctx.after = hf, _scrape(50.0)
    assert all(read(w) is None for w in ("window_mfu", "experts", "full_attn", "window_attn", "decode_step", "prefill_attn"))
    ctx.trace, ctx.afm_scope_shares = None, None
    assert afm_scopes.read(ctx, "^jit__unknown", "norm.post") is None


@pytest.mark.slow  # two minutes alone: not tier-1, as the other families' rehearsals are not
def test_rehearsal_of_the_gated_window_models_cell(tmp_path):
    """--rehearse --trace 2 of trinitymini-bf16-mixedlen-sat at the
    configuration's `rehearsal` keys (8 layers, window 256, 8 experts
    top-2): every phase, the family's logits check through both pools past
    the window, and every per-layer metric the CPU can read."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--workload", AFM_CELL, "--rehearse",
         "--trace", "2", "--seed", str(2**31 + 13)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=900,
    )
    lines = [json.loads(ln) for ln in proc.stdout.decode().splitlines() if ln.startswith("{")]
    assert proc.returncode == 0, (lines[-1], proc.stderr.decode()[-2000:])
    last = lines[-1]
    bench = resultline.load_benchmark()
    # Time a step of one scope means nothing in a CPU trace (readers/afm_rooflines.py), and the tail's 4 s
    # of a rehearsal's five clients need not hold a whole run of a prefill program.
    may_miss = AFM_ROOFLINES | {"prefill_attn_full_share_pct", "prefill_moe_share_pct.afm", "prefill_share_pct.afm"}
    assert resultline.problems(last, bench, AFM_CELL, 2, 1, rehearsal=True, may_miss=may_miss) == []
    assert set(resultline.declared(bench, AFM_CELL, 2)) - may_miss <= set(last["metrics"])
    logits = next(ln for ln in lines if ln.get("phase") == "logits")
    assert set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices"}
    assert all(part["ok"] for part in logits["compared"].values())
    assert logits["sample"]["long_prompt"] == 800 and logits["sample"]["window_pages_released"] > 0
    assert 0 < last["metrics"]["moe_experts_hit_pct.afm"]["value"] <= 100
    assert 0 < last["metrics"]["attn_gate_norm_share_pct"]["value"] < 100
    assert last["metrics"]["kv_pages_peak_pct.window.afm"]["value"] > 0
