"""The benchmark's own machinery (perfbench/), on the CPU. What
BENCHMARK.json and the files beside it must keep is held as properties, over
every cell, configuration and traffic file the benchmark has when the tests
are collected: a cell is covered by being in `workloads`, and a measurement
is found by what its file says (its reader and parameters), never by its
name, its place in `per_layer` or the length of that list, so a `benchmark`
PR may fold, rename, retire or add entries, and a `model_config` PR may
bring a cell, without an edit here (its family's toy configuration is
tests/test_named_scopes.py's to keep). Besides: the plans the one traffic
generator builds, operations filed under their named scope, the reader that
finds a run's trace, each family's roofline arithmetic, and whole
`--rehearse` runs. perfbench/selftest.py holds the list's own invariants
(one file an entry, every entry lists its cells, `moves` is reported where
the metric is, no cell under the count it was accepted with); it runs here."""

import functools
import json
import os
import re
import subprocess
import sys
import types

import pytest

from kubeai_tpu.models import MODULES, ModelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import resultline  # noqa: E402
import scope_reduce  # noqa: E402
import test_named_scopes as toys  # noqa: E402  (one toy configuration a family, lowered with its scopes)
import traffic  # noqa: E402
from readers import scope_share  # noqa: E402

BENCH = resultline.load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
DECODE = "^jit__unknown"  # the decode program, as a metric file names it (ROADMAP B-III: its name)
HOST_WORK = {"sweep", "admit", "prefill", "kv_transfer", "dispatch", "host_overlap", "emit", "other"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_selftest_passes():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "selftest.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=300,
    )
    out = proc.stdout.decode()
    failed = [ln for ln in out.splitlines() if ln.startswith("FAIL ")]  # the checks that failed, by name
    assert proc.returncode == 0 and out.strip().endswith("all passed"), failed or out[-3000:]


# What a measurement IS: its file's reader, and what the file tells the reader to read.
IS = {
    "idle_gaps": lambda m: m["reader"] == "idle_by_host",
    "decode_by_scope": lambda m: m["reader"] == "scope_share" and m["params"]["module"] == DECODE,
    "of_prefill_programs": lambda m: m["params"].get("module", "").startswith("^jit_prefill"),
    "experts_hit": lambda m: m["reader"] == "delta_ratio" and m["params"]["num"][0]["series"] == "kubeai_engine_moe_experts_hit_total",
    "window_pool_peak": lambda m: m["reader"] == "gauge_peak" and m["params"]["gauge"] == "kubeai_engine_kv_window_pages_used",
    "timed_by_a_familys_reader": lambda m: m["reader"].endswith("_rooflines") and "module" in m["params"],
    "share_of_a_peak": lambda m: "roofline" in m["reader"],
}


def _per_layer(cell, what=None):
    """name -> entry of the per-layer metrics *cell* declares, in the names'
    order, each entry with its FILE's `reader` and `params` beside
    BENCHMARK.json's keys; only those that are *what* (a key of IS), if given."""
    names, found = resultline.declared(BENCH, cell, 1), {}
    for m in BENCH["per_layer"]:
        if m["name"] in names:
            with open(os.path.join(ROOT, "perfbench", "layer_metrics", m["name"] + ".json")) as f:
                found[m["name"]] = {**m, **json.load(f)}
    return {n: found[n] for n in sorted(found) if what is None or IS[what](found[n])}


def _cells_that_declare(what):
    """For a parametrize list. A cell whose files cannot be read is kept:
    its own case then fails and says which, not the module's collection."""
    def declares(cell):
        try:
            return bool(_per_layer(cell, what))
        except (OSError, KeyError, ValueError):
            return True
    return [cell for cell in CELLS if declares(cell)]


def _timed_rooflines(cell, but=()):
    """The cell's shares of a peak for which a family's own reader divides by
    a time taken from the trace (of the program or of one scope in it: the
    file names a `module`), but the readings named *but*. Time a step means
    nothing in a CPU trace, so a rehearsal leaves them out
    (readers/moe_, swa_, ssm_, afm_rooflines.py)."""
    return {n for n, m in _per_layer(cell, "timed_by_a_familys_reader").items() if m["params"]["what"] not in but}


def _config(name):
    """BENCHMARK.json's entry of a configuration, and its file."""
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def _engine_arg(published, flag):
    args = published["serving"]["engine_args"]
    return int(args[args.index(flag) + 1])


# -- what a cell declares, and what defines it -----------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_what_a_cell_declares(cell):
    e2e, layer, both = (resultline.declared(BENCH, cell, mode) for mode in (0, 1, 2))
    assert "setup_s" in e2e and len(e2e) >= 2  # and the metric the cell is judged by
    assert layer and both == {**e2e, **layer} and len(both) == len(e2e) + len(layer)
    for name, m in _per_layer(cell).items():  # which opens every name's file
        assert os.path.exists(os.path.join(ROOT, "perfbench", "readers", m["reader"] + ".py")), (name, m["reader"])


@pytest.mark.parametrize("cell", _cells_that_declare("idle_gaps"))
def test_a_cells_idle_gaps_are_read_by_one_reader_in_two_parts(cell):
    """Host work that did not hide, or the wait in the fetch: `idle` (no
    request) is in neither, and the three add up to the idle share. A cell
    that reports one of the pair reports the other."""
    e2e = resultline.declared(BENCH, cell, 0)
    mine = _per_layer(cell, "idle_gaps").values()
    for m in mine:
        assert (m["layer"], m["source"], m["better"]) == ("scheduler", "device_trace", "lower"), m["name"]
        assert m["moves"] in e2e and m["moves"] != "setup_s", m["name"]
    assert sorted((set(m["params"]["causes"]) for m in mine), key=len) == [{"fetch_wait"}, HOST_WORK]


def _counts_taken(reader):
    """The families (`model_type`s: the files of perfbench/families) whose own counts a reader's source takes."""
    with open(os.path.join(ROOT, "perfbench", "readers", reader + ".py")) as f:
        return set(re.findall(r"""["']families\.(\w+)_counts["']""", f.read()))


def _scopes_asked(m):
    """The scopes an entry's file has its reader look under, where it reads by scope (`kind`: swa_scopes' `attn.<kind>`)."""
    params = m["params"]
    return params["scope"].split("|") if "scope" in params else ["attn." + params["kind"]] if "kind" in params else []


@functools.cache
def _toy_programs(model_type):
    """[(program, its text with the operations' names)] of the toy
    configuration tests/test_named_scopes.py keeps for the family that runs
    *model_type* (kubeai_tpu/models: `MODULES`; every other type is the
    dense family's, whose toy is that file's default), lowered, not compiled."""
    mine = [v for v in vars(toys).values() if isinstance(v, ModelConfig) and v.model_type == model_type]
    assert mine or model_type not in MODULES, f"tests/test_named_scopes.py keeps no toy configuration of {model_type}"
    texts = [debug_text for _, debug_text in toys._lowered_programs(True, *mine[:1])]
    return [(re.search(r"^module @(\S+)", text, flags=re.M).group(1), text) for text in texts]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_declares_only_what_its_familys_program_can_be_read_for(cell):
    """As far as tier-1 can see it: a reader that takes one family's counts
    is declared by that family's cells alone, and an entry read by scope
    asks for a scope that names operations in a program of the cell's
    family that its `module` matches. (That every declared metric IS read is
    the rehearsals' to hold, and on the chip `output_malformed`.)"""
    _, published = _config(CELLS[cell]["config"])
    family = published["model_type"]
    assert os.path.exists(os.path.join(ROOT, "perfbench", "families", family + ".py")), family
    for name, m in _per_layer(cell).items():
        taken = _counts_taken(m["reader"])
        assert taken <= {family}, f"{name}: {m['reader']} takes the counts of {sorted(taken)}, {cell} serves a {family}"
        scopes = _scopes_asked(m)
        if scopes:
            matched = [text for program, text in _toy_programs(family) if re.search(m["params"]["module"], program)]
            named = [s for s in scopes if any(re.search(r'["/]' + re.escape(s) + "/", text) for text in matched)]
            assert named, f"{name}: no program of a {family} that {m['params']['module']} matches names an operation under {scopes}"


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_a_configuration_is_its_source_cut_only_where_it_says(name):
    entry, published = _config(name)
    assert published["source"] == entry["source"]
    assert set(entry["reduced"]) <= set(published["reduced"])  # each cut has its reason in the file
    # The row's keys as published: every key of the catalog's config under the same key with the same value.
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [r for r in map(json.loads, f) if r["source_url"] == published["source"]]
    for row in rows:
        departs = {k for k, v in row["config"].items() if k not in published or published[k] != v}
        assert departs <= set(entry["reduced"]), (row["name"], sorted(departs))


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_traffic_fits_its_configuration_and_saturates_it_where_it_says_so(cell):
    """The numbers themselves (slots, clients, medians) are PERF.md section
    4's and the driver's to hold (`benchmark_edited`, `weakened`): a
    `benchmark` PR that steadies a cell changes them."""
    w = CELLS[cell]
    _, published = _config(w["config"])
    assert w["chips"] == published["serving"]["chips"]
    spec = traffic.load(w["traffic"])
    longest = spec.get("max_total_tokens") or spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"]
    seq_len, slots = _engine_arg(published, "--max-seq-len"), _engine_arg(published, "--max-slots")
    assert longest <= seq_len, f"{w['traffic']} sends {longest} tokens, {w['config']} serves --max-seq-len {seq_len}"
    if spec["loop"] == "closed" and w["traffic"].endswith("-sat"):  # above the knee: a freed slot finds a request waiting
        assert spec["clients"] >= slots, f"{w['traffic']} has {spec['clients']} clients for {w['config']}'s {slots} slots"


# -- the last line of a traced run ---------------------------------------------


def _traced_line(cell, mode=2):
    """A well-formed last line of a run of *cell* in *mode*: every metric it declares there."""
    return {
        "correct": True, "attempted": 10, "failed": 0,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": CELLS[cell]["chips"], "memory_peak_bytes": 1.2e10,
                   "window_s": 4.0, "busy_s": 3.9},
        "metrics": {n: {"value": 1.5, "unit": u} for n, u in resultline.declared(BENCH, cell, mode).items()},
    }


def _problems(line, cell, mode=2, **kw):
    return resultline.problems(line, BENCH, cell, mode, CELLS[cell]["chips"], **kw)


def _without(line, names):
    return {**line, "metrics": {k: v for k, v in line["metrics"].items() if k not in names}}


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_with_all_the_cell_declares_has_no_problems(cell):
    assert _problems(_traced_line(cell, 1), cell, 1) == [] and _problems(_traced_line(cell), cell) == []


@pytest.mark.parametrize("cell", CELLS)
def test_a_line_of_a_run_that_traced_itself_is_refused_without_either_kind(cell):
    line = _traced_line(cell)
    for mode in (0, 1):
        assert _problems(_without(line, resultline.declared(BENCH, cell, mode)), cell)
    assert _problems({**line, "device": {k: v for k, v in line["device"].items() if k != "busy_s"}}, cell)


@pytest.mark.parametrize("at", ["first", "median", "last"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_that_lacks_one_metric(cell, at):
    names = list(_per_layer(cell))
    missing = names[{"first": 0, "median": len(names) // 2, "last": -1}[at]]
    cut = _without(_traced_line(cell), {missing})
    assert _problems(cut, cell) == [f"metric {missing} of this workload and mode is missing"]
    # A reader that finds nothing (the parent's program) leaves its metric out.
    assert _problems(cut, cell, may_miss={missing}) == []


@pytest.mark.parametrize("cell", _cells_that_declare("share_of_a_peak"))
def test_a_share_of_a_peak_over_105_is_refused(cell):
    """Which of a cell's shares of a peak the harness holds to it is the
    harness's own rule (PERF.md section 7: by the name's ending, so a
    suffixed one escapes): each in turn at 106, and at least one is refused."""
    line = _traced_line(cell)
    over = lambda name: {**line, "metrics": {**line["metrics"], name: {**line["metrics"][name], "value": 106.0}}}  # noqa: E731
    refused = [n for n in _per_layer(cell, "share_of_a_peak") if any("over 105%" in p for p in _problems(over(n), cell))]
    assert refused


@pytest.mark.parametrize("name", sorted({w["traffic"] for w in BENCH["workloads"]}))
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


# -- the readers of a trace -----------------------------------------------------


def _tail_ctx(table, window_s=4.0):
    return types.SimpleNamespace(trace={"window_s": window_s, "busy_s": 3.0}, idle_by_host=table, rehearsal=True)


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


# -- each family's shares of a peak, from counters and scopes ----------------------


def _scrape(at, **series):
    return types.SimpleNamespace(
        at=at, has=lambda name: name in series,
        value=lambda name, **labels: sum(
            v for have, v in series.get(name, ()) if all(have.get(k) == w for k, w in labels.items())
        ),
    )


def _made_up_window(config, after, gauges=()):
    """A context as run.py gathers it, of a made-up window of 50 s in which
    the counters went from 0 to *after* (the *gauges* stood still), and
    whose traced 4 s held ten whole runs of the decode program in 1.6 s and
    eighty of a prefill program in 2.0 s; `hf` the configuration's published keys."""
    with open(os.path.join(ROOT, "perfbench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in ("serving", "rehearsal", "reduced", "assumed", "source")}
    zero = {k: v if k in gauges else [(labels, 0.0) for labels, _ in v] for k, v in after.items()}
    return types.SimpleNamespace(
        hf=hf, serving=cfg["serving"], rehearsal=False, window_s=50.0, trace_t0=20.0, trace_t1=24.0,
        before=_scrape(0.0, **zero), after=_scrape(50.0, **after), polls=[], all_records=[],
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"window_s": 4.0, "modules_s": {"jit__unknown(7)": (1.6, 1.6, 1.6, 10.0), "jit_prefill_chunk_fn(9)": (2.0, 2.0, 2.0, 80.0)}},
    )


def _pairs(kind, phase, n):
    return {"kind": kind, "phase": phase}, float(n)


def _by_scope(total_s=1.6, **seconds):
    """A program's seconds by scope as scope_reduce keeps them (`ssm_in_proj` stands for the scope `ssm.in_proj`)."""
    return {"total_s": total_s, "by_scope_s": {k.replace("_", ".", 1): v for k, v in seconds.items()}}


def test_the_window_rooflines_from_counters_and_scopes():
    """readers/swa_rooflines.py on a made-up window: 100 decode chunks of 8
    steps, pairs that are 24 slots x (3 full layers x 8000 + 9 window
    layers x 4096) keys a step, half the experts hit; scope seconds by the
    difference of the two reductions (readers/swa_scopes.py)."""
    from readers import swa_rooflines, swa_scopes

    steps = 800
    after = {
        "kubeai_engine_attn_pairs_total": [
            _pairs("full", "decode", steps * 24 * 3 * 8000), _pairs("window", "decode", steps * 24 * 9 * 4096),
            _pairs("full", "prefill", 3 * 4e9), _pairs("window", "prefill", 9 * 2e9),
        ],
        "kubeai_engine_moe_experts_hit_total": [({"phase": "decode"}, steps * 12 * 32.0)],
        "kubeai_engine_moe_expert_reads_possible_total": [({"phase": "decode"}, steps * 12 * 64.0)],
        "kubeai_engine_step_seconds_count": [({"phase": "decode_chunk"}, 100.0)],
        "kubeai_engine_prefill_tokens_total": [({}, 1.0e6)], "kubeai_engine_generated_tokens_total": [({}, 19200.0)],
    }
    ctx = _made_up_window("smallthinker-21b-a3b-bf16", after)
    ctx.swa_scope_shares = {
        "layers": {
            "jit__unknown(7)": _by_scope(attn_full=0.20, attn_window=0.40, moe_experts=0.64),
            "jit_prefill_chunk_fn(9)": _by_scope(2.0, attn_full=0.2, attn_window=0.4),
        },
        "kernels": {
            "jit__unknown(7)": _by_scope(attn_full=0.08, attn_window=0.16, attn_kernel=0.36, moe_experts=0.64),
            "jit_prefill_chunk_fn(9)": _by_scope(2.0, attn_full=0.05, attn_window=0.1, attn_kernel=0.45),
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


def test_the_state_space_rooflines_from_counters_and_scopes():
    """readers/ssm_rooflines.py on a made-up window: 100 decode chunks of 8
    steps with 90 of 96 slots live, half the held experts hit, a million
    prompt tokens; scope seconds as readers/ssm_scopes.py keeps them."""
    from families import nemotron_h_counts as counts
    from readers import ssm_rooflines, ssm_scopes

    steps = 800
    after = {
        "kubeai_engine_state_slots_total": [({}, 96.0)],
        "kubeai_engine_slot_steps_total": [({"state": "active"}, steps * 90.0), ({"state": "idle"}, steps * 6.0)],
        "kubeai_engine_moe_experts_hit_total": [({"phase": "decode"}, steps * 5 * 64.0)],
        "kubeai_engine_moe_expert_reads_possible_total": [({"phase": "decode"}, steps * 5 * 128.0)],
        "kubeai_engine_prefill_tokens_total": [({}, 1.0e6)], "kubeai_engine_generated_tokens_total": [({}, 72000.0)],
    }
    ctx = _made_up_window("nemotron3-super-120b-a12b-bf16", after, gauges=("kubeai_engine_state_slots_total",))
    hf = ctx.hf
    ctx.ssm_scope_shares = {
        "jit__unknown(7)": _by_scope(ssm_conv=0.1, ssm_scan=0.7, ssm_in_proj=0.1, moe_experts=0.4, moe=0.1),
        "jit_prefill_chunk_fn(9)": _by_scope(2.0, ssm_scan=0.5, ssm_in_proj=0.3, moe_experts=0.6),
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
    ctx.ssm_scope_shares = {"jit__unknown(7)": _by_scope(attn=0.4, moe_experts=0.4)}
    assert ssm_scopes.read(ctx, "^jit__unknown", "moe.experts") is None
    ctx.trace, ctx.ssm_scope_shares = None, None
    assert ssm_scopes.read(ctx, "^jit__unknown", "ssm.scan") is None


def test_the_gated_window_familys_rooflines_from_counters_and_scopes():
    """readers/afm_rooflines.py on a made-up window: 100 decode chunks of 8
    steps, pairs that are 24 slots x (2 full layers x 10000 + 6 window
    layers x 2048) keys a step, half the experts hit; scope seconds by the
    difference of swa_scopes' two reductions; afm_scopes' own list."""
    from families import afmoe_counts as counts
    from readers import afm_rooflines, afm_scopes

    steps = 800
    after = {
        "kubeai_engine_attn_pairs_total": [
            _pairs("full", "decode", steps * 24 * 2 * 10000), _pairs("window", "decode", steps * 24 * 6 * 2048),
            _pairs("full", "prefill", 2 * 4e9), _pairs("window", "prefill", 6 * 1e9),
        ],
        "kubeai_engine_moe_experts_hit_total": [({"phase": "decode"}, steps * 6 * 64.0)],
        "kubeai_engine_moe_expert_reads_possible_total": [({"phase": "decode"}, steps * 6 * 128.0)],
        "kubeai_engine_step_seconds_count": [({"phase": "decode_chunk"}, 100.0)],
        "kubeai_engine_prefill_tokens_total": [({}, 6.0e5)], "kubeai_engine_generated_tokens_total": [({}, 19200.0)],
    }
    ctx = _made_up_window("trinity-mini-bf16", after)
    hf = ctx.hf
    ctx.swa_scope_shares = {
        "layers": {
            "jit__unknown(7)": _by_scope(attn_full=0.20, attn_window=0.40, moe_experts=0.64),
            "jit_prefill_chunk_fn(9)": _by_scope(2.0, attn_full=0.5, attn_window=0.3),
        },
        "kernels": {
            "jit__unknown(7)": _by_scope(attn_full=0.08, attn_window=0.16, attn_kernel=0.36, moe_experts=0.64),
            "jit_prefill_chunk_fn(9)": _by_scope(2.0, attn_full=0.05, attn_window=0.1, attn_kernel=0.65),
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
    zero = {k: [(labels, 0.0) for labels, _ in v] for k, v in after.items()}
    ctx.tail_view = types.SimpleNamespace(before=_scrape(60.0, **zero), polls=[], after=_scrape(65.0, **after), trace_t0=61.0, trace_t1=65.0)
    assert read("prefill_attn", module="^jit_prefill") == pytest.approx(100 * (flops / 197e12) / (0.8 * 5.0 / 4.0))
    # afm_scopes: what the family adds to a plain block, told apart from the layer it sits in.
    ctx.afm_scope_shares = {
        "jit__unknown(7)": _by_scope(attn_qk_norm=0.02, attn_gate=0.03, norm_post=0.05, attn_window=0.4),
    }
    assert afm_scopes.read(ctx, "^jit__unknown", "attn.qk_norm|attn.gate|norm.post") == pytest.approx(100 * 0.10 / 1.6)
    # A program of another family, or the parent's: no such counter or scope, nothing read, nothing raised.
    ctx.afm_scope_shares = {"jit__unknown(7)": _by_scope(attn_window=0.4, moe_experts=0.6)}
    assert afm_scopes.read(ctx, "^jit__unknown", "attn.qk_norm|attn.gate|norm.post") is None
    ctx.hf = {**hf, "model_type": "smallthinker"}
    assert all(read(w) is None for w in ("window_mfu", "experts", "full_attn", "decode_step"))
    ctx.hf, ctx.after = hf, _scrape(50.0)
    assert all(read(w) is None for w in ("window_mfu", "experts", "full_attn", "window_attn", "decode_step", "prefill_attn"))
    ctx.trace, ctx.afm_scope_shares = None, None
    assert afm_scopes.read(ctx, "^jit__unknown", "norm.post") is None


# -- whole runs, every phase, on the CPU at a tiny size ----------------------------


def _run_rehearsal(tmp_path, cell, trace, seed, timeout=600):
    """`run.py --workload <cell> --rehearse --trace <trace>`: the JSON lines it printed, the result last."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)  # conftest's eight virtual devices: the cell asks for one
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--workload", cell, "--rehearse",
         "--trace", str(trace), "--seed", str(seed)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=timeout,
    )
    lines = [json.loads(ln) for ln in proc.stdout.decode().splitlines() if ln.startswith("{")]
    assert proc.returncode == 0, (lines[-1], proc.stderr.decode()[-2000:])
    return lines


def test_the_one_family_reader_takes_its_counts_and_scopes_from_the_model_type():
    """readers/family_rooflines.py + family_scopes.py on a made-up window of
    the short-convolution family: the counts and the scope list are
    families/<model_type>_counts.py's, found by the configuration's
    `model_type`. 100 decode chunks of 8 steps with 180 of 192 slots live,
    every expert hit, a million prompt tokens, two requests whose prefill
    ended between the polls, one of them decoding through the traced seconds."""
    from families import lfm2_moe_counts as counts
    from readers import family_rooflines, family_scopes

    steps = 800
    after = {
        "kubeai_engine_state_slots_total": [({}, 192.0)],
        "kubeai_engine_slot_steps_total": [({"state": "active"}, steps * 180.0), ({"state": "idle"}, steps * 12.0)],
        "kubeai_engine_moe_experts_hit_total": [({"phase": "decode"}, steps * 8 * 64.0)],
        "kubeai_engine_moe_expert_reads_possible_total": [({"phase": "decode"}, steps * 8 * 64.0)],
        "kubeai_engine_prefill_tokens_total": [({}, 1.0e6)], "kubeai_engine_generated_tokens_total": [({}, 300000.0)],
    }
    ctx = _made_up_window("lfm2-24b-a2b-bf16", after, gauges=("kubeai_engine_state_slots_total",))
    hf = ctx.hf
    assert family_scopes.counts_of(ctx) is counts and counts.SCOPES.index("conv.taps") < counts.SCOPES.index("conv")
    record = lambda n, first, k: types.SimpleNamespace(prompt_tokens=n, token_times=[first + 0.02 * i for i in range(k)], done=None)  # noqa: E731
    ctx.all_records = [record(3000, 19.0, 400), record(1000, 30.0, 10)]  # the first decodes all through [20, 24)
    ctx.family_scope_shares = {
        "jit__unknown(7)": _by_scope(conv_in_proj=0.06, conv_taps=0.02, conv_gate=0.01, conv_out_proj=0.03, conv=0.0, attn_kernel=0.2, attn=0.05, moe_experts=1.0, moe=0.1),
        "jit_prefill_chunk_fn(9)": _by_scope(2.0, conv_in_proj=0.2, conv_out_proj=0.1, conv_taps=0.1, attn_kernel=0.25, moe_experts=0.9),
    }
    conv = "conv|conv.in_proj|conv.gate|conv.taps|conv.out_proj"
    assert family_scopes.read(ctx, "^jit__unknown", conv) == pytest.approx(7.5)
    assert family_scopes.read(ctx, "^jit_prefill", conv) == pytest.approx(20.0)
    assert family_scopes.seconds(ctx, "^jit__unknown", "attn.kernel") == pytest.approx((0.2, 1.6, 10.0))
    n_steps = 10 * 8
    read = lambda what, **kw: family_rooflines.read(ctx, what, **kw)  # noqa: E731
    tails = 180 * 2 * 8 * 2 * 2048 * 2
    conv_weights = 8 * 16_785_408 * 2
    assert read("conv_decode") == pytest.approx(100 * ((conv_weights + tails) / 819e9) / (0.12 / n_steps))
    live_tokens = sum(3000 + int((20.0 + (i + 0.5) * 0.1 - 19.0) / 0.02) + 1 for i in range(40)) / 40  # the one live request, as trace_common samples it
    kv = live_tokens * 4096
    assert read("attn_decode") == pytest.approx(100 * (kv / 819e9) / (0.2 / n_steps), rel=1e-3)
    experts = 8 * 64 * 9_437_184 * 2
    assert read("experts") == pytest.approx(100 * (experts / 819e9) / (1.0 / n_steps))
    outside = counts.weights_outside_experts_bytes(hf, 2)
    assert outside == (8 * 16_785_408 + 2 * 10_487_936 + 2 * 72_353_792 + 8 * (2048 * 64 + 64 + 2048) + 65536 * 2048 + 2048) * 2
    assert read("decode_step") == pytest.approx(100 * ((outside + experts + tails + kv) / 819e9) / (1.6 / n_steps), rel=1e-3)
    # Prefill: the counters and the records between the polls around the traced seconds (here the window's edges).
    flops = 8 * 33_554_432 * 1.0e6
    assert read("conv_prefill", module="^jit_prefill") == pytest.approx(100 * (flops / 197e12) / (0.4 * 50.0 / 4.0))
    pairs = 3000 * 3001 / 2 + 1000 * 1001 / 2
    assert read("prefill_attn", module="^jit_prefill") == pytest.approx(100 * (2 * 8192 * pairs / 197e12) / (0.25 * 50.0 / 4.0))
    decode_pairs = (3000 + (1 + 399) / 2) * 399 + (1000 + (1 + 9) / 2) * 9  # token i of a request sees prompt + i keys
    want_mfu = 100 * (2 * counts.active_params(hf) * 1_300_000 + 2 * 8192 * (pairs + decode_pairs)) / (197e12 * 50)
    assert read("window_mfu") == pytest.approx(want_mfu, rel=1e-6)
    assert all(0 < read(w, **kw) < 100 for w, kw in (("conv_decode", {}), ("attn_decode", {}), ("experts", {}), ("decode_step", {}), ("window_mfu", {})))
    # `view="tail"`: the tail's own polls bracket the traced seconds where the run traced itself after its window.
    half = {k: v if k == "kubeai_engine_state_slots_total" else [(labels, x / 2) for labels, x in v] for k, v in after.items()}
    ctx.tail_view = types.SimpleNamespace(**{**vars(ctx), "before": _scrape(60.0, **half), "polls": [], "after": _scrape(70.0, **after), "trace_t0": 62.0, "trace_t1": 66.0})
    assert read("conv_prefill", module="^jit_prefill", view="tail") == pytest.approx(100 * (flops / 2 / 197e12) / (0.4 * 10.0 / 4.0))
    assert read("conv_prefill", module="^jit_prefill") == pytest.approx(100 * (flops / 197e12) / (0.4 * 50.0 / 4.0))
    assert read("window_mfu", view="tail") == pytest.approx(want_mfu, rel=1e-6)  # the measured window's, whatever the view
    del ctx.tail_view
    # A trace that carries none of the family's OWN scopes (another family's program, the parent's) gives no share ...
    ctx.family_scope_shares = {"jit__unknown(7)": _by_scope(attn=0.4, moe_experts=0.4)}
    assert family_scopes.read(ctx, "^jit__unknown", "moe.experts") is None and read("experts") is None and read("conv_decode") is None
    # ... a program without the counters, or a family without a counts module, nothing at all; nothing raised.
    ctx.after = _scrape(50.0)
    assert all(read(w) is None for w in ("window_mfu", "experts", "conv_decode", "conv_prefill", "attn_decode", "prefill_attn", "decode_step"))
    ctx.after, ctx.hf = _scrape(50.0, **after), {**hf, "model_type": "qwen2"}
    assert read("window_mfu") is None and family_scopes.read(ctx, "^jit__unknown", conv) is None
    ctx.hf = {**hf, "model_type": "../x"}
    assert family_scopes.counts_of(ctx) is None
    ctx.hf, ctx.trace, ctx.family_scope_shares = hf, None, None
    assert family_scopes.read(ctx, "^jit__unknown", conv) is None and read("decode_step") is None


def test_the_next_familys_operator_comes_to_the_reader_as_a_row_of_its_counts_table(monkeypatch):
    """readers/family_rooflines.py holds no operator's name: a made-up
    family whose counts file states a `scan` operator (its scopes, and its
    shares as units of work times a count) reads through the same reader and
    the same `what=` with no edit of it; a `what` its table lacks is refused
    by name."""
    from readers import family_rooflines

    made_up = types.ModuleType("families.madeup_counts")
    made_up.SCOPES, made_up.OWN_SCOPES = ("scan.state", "scan", "attn.kernel"), ("scan.state", "scan")
    made_up.ROOFLINES = {
        "scan_decode": {
            "phase": "decode", "peak": "hbm_bytes_per_s", "scopes": ("scan", "scan.state"),
            "work": {"step": lambda hf, sv: 1.0e9 * sv["weight_dtype_bytes"], "live_row": lambda hf, sv: 4.0e6},
        },
        "scan_prefill": {
            "phase": "prefill", "peak": "bf16_flops", "scopes": ("scan",),
            "work": {"prompt_token": lambda hf, sv: 2.0 * hf["hidden_size"] ** 2},
        },
    }
    monkeypatch.setitem(sys.modules, "families.madeup_counts", made_up)
    steps = 800
    after = {
        "kubeai_engine_slot_steps_total": [({"state": "active"}, steps * 96.0), ({"state": "idle"}, steps * 96.0)],
        "kubeai_engine_prefill_tokens_total": [({}, 1.0e6)],
    }
    ctx = _made_up_window("lfm2-24b-a2b-bf16", after)
    ctx.hf = {**ctx.hf, "model_type": "madeup"}
    ctx.family_scope_shares = {
        "jit__unknown(7)": _by_scope(scan_state=0.1, scan=0.3, attn_kernel=0.2),
        "jit_prefill_chunk_fn(9)": _by_scope(2.0, scan=0.5),
    }
    n_steps = 10 * 8
    assert family_rooflines.read(ctx, "scan_decode") == pytest.approx(100 * ((2.0e9 + 96 * 4.0e6) / 819e9) / (0.4 / n_steps))
    assert family_rooflines.read(ctx, "scan_prefill", module="^jit_prefill") == pytest.approx(
        100 * (2.0 * 2048**2 * 1.0e6 / 197e12) / (0.5 * 50.0 / 4.0)
    )
    with pytest.raises(ValueError, match="conv_decode.*scan_decode"):
        family_rooflines.read(ctx, "conv_decode")


def test_rehearsal_of_a_run_that_traces_itself(tmp_path):
    """--rehearse --trace 2, every phase: one last line with both kinds of
    metric; the end-to-end values are what `Run.end_to_end` gave over the
    window's records; the traced interval is the `profile.window` event
    with the Python tracer off; each idle gap names its own causes, and the
    idle shares add up."""
    cell = "qwen7b-int8-chat-rate"
    assert BENCH["trace_in_run"] is True  # since PR 38 this is the traced run the driver asks for
    lines = _run_rehearsal(tmp_path, cell, 2, 2**31 + 5)
    phases = [ln.get("phase") for ln in lines[:-1]]
    for phase in ("checkpoint", "scale_from_zero", "window_open", "stop", "window", "trace", "logits", "end_to_end"):
        assert phase in phases, phases
    assert phases.index("stop") < phases.index("trace")  # read after the operator has gone, beside the logits child
    last, trace, window = lines[-1], lines[phases.index("trace")], lines[phases.index("window")]
    assert _problems(last, cell, rehearsal=True, may_miss=set(_per_layer(cell))) == []
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    # Both kinds, the end-to-end ones from the one function that computes them.
    e2e = lines[phases.index("end_to_end")]
    for name in resultline.declared(BENCH, cell, 0):
        assert last["metrics"][name]["value"] == e2e[name]
    idle_parts = _per_layer(cell, "idle_gaps")
    assert len(idle_parts) == 2 and set(idle_parts) <= set(last["metrics"])
    assert last["metrics"]["host_work_per_chunk_ms.rate"]["value"] >= 0
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
    split = sum(last["metrics"][n]["value"] for n in idle_parts)
    assert split + under_idle == pytest.approx(idle_pct, abs=0.1)
    gaps = last["breakdown"]["idle_gaps"]
    assert gaps and all("host: " in g[0] and "dominant stall cause" not in g[0] and len(g[0]) <= 200 for g in gaps)
    # The window's line keeps where the longest silence lay and the engine's slowest steps inside it.
    assert 0 <= window["longest_silence_at_s"] < 8 and all(-0.5 <= s["at_s"] < 9.5 for s in window["slowest_steps"])
    assert window["slowest_steps"] and {"kind", "total_ms", "ms"} <= set(window["slowest_steps"][0])


def test_rehearsal_of_a_traced_run(tmp_path):
    """--rehearse --trace 1, every phase: the accepted harness reads the
    scopes, the padding and the host's work from the program as it is."""
    cell = "qwen7b-int8-chat-sat"
    lines = _run_rehearsal(tmp_path, cell, 1, 2**31 + 5)
    phases = [ln.get("phase") for ln in lines[:-1]]
    for phase in ("checkpoint", "scale_from_zero", "window_open", "stop", "window", "logits", "trace", "scopes"):
        assert phase in phases, phases
    scopes = lines[phases.index("scopes")]
    assert scopes["error"] is None and any(p.startswith("jit__unknown") for p in scopes["programs"])
    last = lines[-1]
    assert _problems(last, cell, 1, rehearsal=True, may_miss=set(_per_layer(cell))) == []
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    assert 0 <= last["metrics"]["prefill_padding_pct"]["value"] < 100
    assert 0 <= last["metrics"]["decode_epilogue_ran_pct"]["value"] <= 100
    assert last["metrics"]["host_work_per_chunk_ms"]["value"] >= 0
    # The decode step by scope: each share read, and together no more than the step.
    by_scope = _per_layer(cell, "decode_by_scope")
    shares = [last["metrics"][n]["value"] for n in by_scope]
    assert len(shares) >= 3 and all(0 <= v <= 100 for v in shares) and sum(shares) <= 100.0


def _family_rehearsal(tmp_path, cell, trace, seed, may_miss, timeout=900):
    """A rehearsal of an expert family's cell at its configuration's
    `rehearsal` keys: the last line carries every metric the cell declares
    but *may_miss* (so a cell declares only what its own program can be read
    for). -> the last line, the logits child's line."""
    lines = _run_rehearsal(tmp_path, cell, trace, seed, timeout)
    last = lines[-1]
    assert _problems(last, cell, trace, rehearsal=True, may_miss=may_miss) == []
    assert set(resultline.declared(BENCH, cell, trace)) - may_miss <= set(last["metrics"])
    return last, next(ln for ln in lines if ln.get("phase") == "logits")


def _value(last, cell, what):
    """What the line carries for the cell's ONE metric that is *what* (a key of IS)."""
    (name,) = _per_layer(cell, what)
    return last["metrics"][name]["value"]


@pytest.mark.slow  # about a minute alone, more beside five other workers: not tier-1 (CHANGES.md, PR 33)
def test_rehearsal_of_the_expert_models_cell(tmp_path):
    """--rehearse --trace 1 of kanana2-bf16-reason-sat at the tests' small
    size: every phase, the family's two-part logits check, and every
    per-layer metric the CPU can read."""
    cell = "kanana2-bf16-reason-sat"
    last, logits = _family_rehearsal(tmp_path, cell, 1, 2**31 + 7, _timed_rooflines(cell, but=("decode_step",)), timeout=600)
    assert last["correct"] is True
    assert logits["ok"] and set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices"}
    assert 0 < _value(last, cell, "experts_hit") <= 100


@pytest.mark.slow  # a minute and a half alone: not tier-1, as the expert model's rehearsal is not
def test_rehearsal_of_the_window_models_cell(tmp_path):
    """--rehearse --trace 1 of smallthinker-bf16-longdoc-sat at the
    configuration's `rehearsal` keys (window 256, 8 layers): every phase,
    the family's logits check through both pools, and every per-layer
    metric the CPU can read."""
    cell = "smallthinker-bf16-longdoc-sat"
    last, logits = _family_rehearsal(tmp_path, cell, 1, 2**31 + 11, _timed_rooflines(cell, but=("decode_step",)))
    assert last["correct"] is True
    assert logits["ok"] and set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices"}
    assert logits["sample"]["window_pages_released"] > 0
    assert 0 < _value(last, cell, "window_pool_peak") <= 100


@pytest.mark.slow  # two minutes alone: not tier-1, as the other families' rehearsals are not
def test_rehearsal_of_the_state_space_models_cell(tmp_path):
    """--rehearse --trace 2 of nemotron3super-bf16-agent-sat at the
    configuration's `rehearsal` keys (11 blocks, 4 of 16 experts held):
    every phase, the family's logits check through the slot's state, and
    every per-layer metric the CPU can read (this family's reader times the
    whole step in the trace too). Fails in the sandbox on every commit since
    the one that wrote it, for want of the prefill programs' share by scope:
    a CPU trace names every warmed prefill shape alike and scope_reduce.py
    then reduces none (PERF.md section 7, "Open from PR 47": a `benchmark`
    PR's to repair, in perfbench/; what may miss here stays as it was)."""
    cell = "nemotron3super-bf16-agent-sat"
    last, logits = _family_rehearsal(tmp_path, cell, 2, 2**31 + 13, _timed_rooflines(cell))
    assert set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices", "state"}
    assert all(logits["compared"][part]["ok"] for part in ("prefill_cold", "prefill_chunked", "decode", "router_choices"))
    assert logits["held_experts"] == [0, 4, 16] and logits["pattern"] == "MEMEMEM*"
    assert 0 < _value(last, cell, "experts_hit") <= 100
    assert 0 < last["metrics"]["decode_ssm_share_pct"]["value"] < 100


@pytest.mark.slow  # two minutes alone: not tier-1, as the other families' rehearsals are not
def test_rehearsal_of_the_gated_window_models_cell(tmp_path):
    """--rehearse --trace 2 of trinitymini-bf16-mixedlen-sat at the
    configuration's `rehearsal` keys (8 layers, window 256, 8 experts
    top-2): every phase, the family's logits check through both pools past
    the window, and every per-layer metric the CPU can read."""
    cell = "trinitymini-bf16-mixedlen-sat"
    # The tail's 4 s of a rehearsal's five clients need not hold a whole run of a prefill program.
    may_miss = _timed_rooflines(cell, but=("decode_step",)) | set(_per_layer(cell, "of_prefill_programs"))
    last, logits = _family_rehearsal(tmp_path, cell, 2, 2**31 + 13, may_miss)
    assert set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices"}
    assert all(part["ok"] for part in logits["compared"].values())
    assert logits["sample"]["long_prompt"] == 800 and logits["sample"]["window_pages_released"] > 0
    assert 0 < _value(last, cell, "experts_hit") <= 100
    assert 0 < last["metrics"]["attn_gate_norm_share_pct"]["value"] < 100
    assert _value(last, cell, "window_pool_peak") > 0


@pytest.mark.slow  # two minutes alone: not tier-1, as the other families' rehearsals are not
def test_rehearsal_of_the_short_convolution_models_cell(tmp_path):
    """--rehearse --trace 2 of lfm2-bf16-fleet-sat at the configuration's
    `rehearsal` keys (hidden 128, 2 heads of 64, 10 layers, 8 experts top-2):
    every phase, the family's three-part logits check in chunks of 64 behind
    a carried tail, and every per-layer metric the CPU can read through the
    one family reader."""
    cell = "lfm2-bf16-fleet-sat"
    # The tail's 4 s of a rehearsal's eight clients need not hold a whole run of a prefill program.
    may_miss = _timed_rooflines(cell) | set(_per_layer(cell, "of_prefill_programs"))
    last, logits = _family_rehearsal(tmp_path, cell, 2, 2**31 + 17, may_miss)
    assert last["correct"] is True
    assert set(logits["compared"]) == {"prefill_cold", "prefill_chunked", "decode", "router_choices", "tails"}
    assert all(part["ok"] for part in logits["compared"].values())
    assert logits["sample"]["chunks"] == [64, 64, 15] and logits["pattern"] == "ccaccc"
    assert 0 < _value(last, cell, "experts_hit") <= 100
    assert 0 < last["metrics"]["decode_conv_share_pct"]["value"] < 100 and last["metrics"]["state_slots_peak_pct"]["value"] == 100
    assert 0 < last["metrics"]["window_mfu.lfm"]["value"] < 100
