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


def test_benchmark_declares_the_new_metrics():
    bench = resultline.load_benchmark()
    assert "trace_in_run" not in bench  # the switch was left out (PERF.md section 7)
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)  # appended, in the issue's order
    for name in NEW:
        with open(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "perfbench", "readers", spec["reader"] + ".py"))


@pytest.mark.parametrize("cell", [w["name"] for w in resultline.load_benchmark()["workloads"]])
def test_a_traced_line_carries_the_new_metrics_in_their_cells(cell):
    bench = resultline.load_benchmark()
    traced, untraced = resultline.declared(bench, cell, True), resultline.declared(bench, cell, False)
    mine = {n for n, cells in NEW.items() if cell in cells}
    assert mine and mine <= set(traced) and not set(NEW) & set(untraced)
    assert not (set(NEW) - mine) & set(traced)
    line = {
        "correct": True, "attempted": 10, "failed": 0,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1.2e10, "window_s": 4.0, "busy_s": 3.9},
        "metrics": {n: {"value": 1.5, "unit": u} for n, u in traced.items()},
    }
    assert resultline.problems(line, bench, cell, True, 1) == []
    # A reader that finds nothing (the parent's program) leaves its metric out.
    cut = {**line, "metrics": {k: v for k, v in line["metrics"].items() if k not in mine}}
    assert resultline.problems(cut, bench, cell, True, 1, may_miss=mine) == []
    assert resultline.problems(cut, bench, cell, True, 1)


@pytest.mark.parametrize("name", ["chat-sat", "chat-rate", "docqa"])
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
