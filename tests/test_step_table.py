"""The engine's step programs as held executables
(kubeai_tpu/engine/step_programs.py): a second start of the same tree and
deployment LOADS every program of the one list from the bundle beside the
compile cache and traces none; what it serves is what a compiling start
and a start with an empty table serve; the bundle's key holds everything
that can decide a lowered text and nothing that cannot; a bad bundle is a
miss, never a failed start; a shape outside the list compiles lazily and
is counted; and the list covers every call `warmup()` made before it
walked the list."""

import dataclasses
import os
import shutil
import types

import jax
import pytest

from kubeai_tpu.engine import core, step_programs
from kubeai_tpu.engine.core import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.step_programs import StepPrograms, StepTable, bundle_key, fill_step_table
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import family
from kubeai_tpu.models.base import ModelConfig
from tests.test_named_scopes import AFMOE, DEEPSEEK, NEMOTRON_H, SMALLTHINKER

DENSE = ModelConfig(
    vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    dtype="float32", max_position=256, use_paged_kernel=True, use_flash_prefill=True,
)
FAMILIES = {"dense": DENSE, "deepseek_v3": DEEPSEEK, "smallthinker": SMALLTHINKER, "nemotron_h": NEMOTRON_H, "afmoe": AFMOE}
# decode + 2 buckets x (1, the cap of 2) + a chunk call a bucket = 7 programs, + the same for two slots (the three
# widest row counts, of which this deployment has two) = 9 where the family shares chunk calls (core.pair_rows).
CFG = EngineConfig(max_slots=2, max_seq_len=64, page_size=16, prefill_buckets=(16, 32), decode_chunk=2)
N_VALID = 259  # the byte tokenizer's vocab under a model vocab of 272
# A cold one-row call, and a prompt past the largest bucket: chunk calls of 32 and 16 rows.
PROMPTS = ([5, 6, 7, 8, 9], list(range(3, 43)))
STEP_BODIES = ("decode_step_paged", "prefill_paged", "prefill_paged_cold")


@pytest.fixture
def cache_dir(tmp_path):
    """A compile-cache directory of the test's own, placed the way library
    code reads it (jax.config), and taken away again afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    path = str(tmp_path / "cache")
    os.makedirs(path)
    jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache.reset_cache()
    yield path
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def _programs(mc=DENSE, cfg=CFG, quantization=""):
    return StepPrograms(mc, cfg, N_VALID, quantization)


def _greedy(table, mc) -> list[list[int]]:
    """What an Engine running *table* (None: one of its own, empty)
    generates for PROMPTS, greedily; the weights are the seed's."""
    params = family(mc).init_params(mc, jax.random.key(0))
    eng = Engine(mc, params, ByteTokenizer(), CFG, step_table=table)
    eng.start()
    try:
        sp = SamplingParams(max_tokens=6, temperature=0.0)
        return [eng.generate(p, sp, timeout=300)[0] for p in PROMPTS]
    finally:
        eng.stop()


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def two_starts(request, tmp_path_factory):
    """A compiling start and then a second start of the same tree and
    deployment, each with the calls its step functions' Python bodies got."""
    from jax.experimental.compilation_cache import compilation_cache

    mc = FAMILIES[request.param]
    model = family(mc)
    path = str(tmp_path_factory.mktemp("cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache.reset_cache()
    body_calls = []
    starts = []
    with pytest.MonkeyPatch.context() as m:
        for name in STEP_BODIES:
            inner = getattr(model, name)

            def counted(*a, _inner=inner, _name=name, **k):
                body_calls.append(_name)
                return _inner(*a, **k)

            m.setattr(model, name, counted)
        try:
            for _ in range(2):
                before = len(body_calls)
                table = fill_step_table(_programs(mc))
                starts.append(types.SimpleNamespace(table=table, bodies=len(body_calls) - before))
        finally:
            jax.config.update("jax_compilation_cache_dir", None)
            compilation_cache.reset_cache()
    return types.SimpleNamespace(mc=mc, cache=path, first=starts[0], second=starts[1])


# -- (a) a second start loads, and traces nothing ---------------------------------


def test_a_second_start_loads_every_program_of_the_list(two_starts):
    n = len(_programs(two_starts.mc).calls())
    assert n == (7 if family(two_starts.mc).REUSE_WHOLE_PREFILL_CALLS else 9)
    first, second = two_starts.first.table.stats, two_starts.second.table.stats
    assert (first["loaded"], first["compiled"], first["shapes"]) == (0, n, n), first
    assert (second["loaded"], second["compiled"], second["shapes"]) == (n, 0, n), second
    assert "errors" not in first and "errors" not in second
    assert set(second["programs"]) == {c.label for c in _programs(two_starts.mc).calls()}
    assert all(p["how"] == "loaded" for p in second["programs"].values())
    # One file a deployment and key, and the second start rewrote nothing.
    bundles = [f for f in os.listdir(two_starts.cache) if f.endswith(".bundle")]
    assert bundles == [os.path.basename(first["bundle"]["path"])]
    assert "written_s" in first["bundle"] and "written_s" not in second["bundle"]
    assert second["bundle"]["bytes"] == first["bundle"]["bytes"] > 0


def test_a_second_start_runs_no_step_functions_python_body(two_starts):
    assert two_starts.first.bodies >= len(_programs(two_starts.mc).calls())  # every program traced its step function
    assert two_starts.second.bodies == 0


# -- (b) the three ways up serve the same tokens -----------------------------------


def test_greedy_tokens_are_the_same_compiled_loaded_and_lazy(two_starts):
    mc = two_starts.mc
    lazy = _greedy(None, mc)
    assert all(len(ids) == 6 for ids in lazy)
    assert _greedy(two_starts.first.table, mc) == lazy
    assert _greedy(two_starts.second.table, mc) == lazy


# -- (e) a shape the table does not hold ---------------------------------------------


def test_a_shape_outside_the_table_compiles_lazily_and_is_counted(two_starts):
    mc = two_starts.mc
    table = StepTable(two_starts.second.table.programs)
    table.stats = dict(two_starts.second.table.stats, lazy=0)
    table.held = dict(two_starts.second.table.held)
    del table.held[("prefill_batch_jit", (1, 16))]  # as if the list had not foreseen it
    eng = Engine(mc, family(mc).init_params(mc, jax.random.key(0)), ByteTokenizer(), CFG, step_table=table)
    eng._update_recompile_counter()
    assert eng._jit_entries_seen == len(table.held) and table.stats["lazy"] == 0
    recompiles = eng.m_recompiles.value()
    lazy = step_programs.M_STEP_PROGRAMS.value(labels={"how": "lazy"})
    eng.start()
    try:
        eng.generate(PROMPTS[0], SamplingParams(max_tokens=2, temperature=0.0), timeout=300)
    finally:
        eng.stop()
    eng._update_recompile_counter()
    assert table.stats["lazy"] == 1 and eng._perf_debug_section()["step_programs"]["lazy"] == 1
    assert step_programs.M_STEP_PROGRAMS.value(labels={"how": "lazy"}) == lazy + 1
    assert eng.m_recompiles.value() == recompiles + 1
    # The programs the table holds came up once: none went through a jit too.
    assert eng._jit_cache_entries() == 1


def test_a_table_of_another_deployment_is_not_run(two_starts):
    mc = two_starts.mc
    other = dataclasses.replace(CFG, max_slots=3)
    eng = Engine(
        mc, family(mc).init_params(mc, jax.random.key(0)), ByteTokenizer(), other,
        step_table=two_starts.second.table,
    )
    assert eng._table is not two_starts.second.table and not eng._table.held
    assert eng._table.programs.cfg.max_slots == 3


# -- (c) the key ---------------------------------------------------------------------


@pytest.fixture
def source_tree(tmp_path):
    root = tmp_path / "a" / "pkg"
    (root / "sub").mkdir(parents=True)
    (root / "x.py").write_text("X = 1\n")
    (root / "sub" / "y.py").write_text("Y = 2\n")
    (root / "notes.txt").write_text("not source\n")
    return str(root)


def _one_byte(root):
    with open(os.path.join(root, "sub", "y.py"), "a") as f:
        f.write("#")


def _jax_version(root):
    jax.__version__ = jax.__version__ + ".dev1"


def _other_checkout(root):
    moved = os.path.join(os.path.dirname(os.path.dirname(root)), "elsewhere", "pkg")
    shutil.copytree(root, moved)
    with open(os.path.join(moved, "notes.txt"), "a") as f:
        f.write("and not python")
    return moved


KEY_CASES = {
    # name: (what changes, whether the key must)
    "one byte of one source file": (_one_byte, {}, True),
    "one ModelConfig field": (None, {"mc": dataclasses.replace(DENSE, rope_theta=DENSE.rope_theta + 1)}, True),
    "one engine dimension": (None, {"cfg": dataclasses.replace(CFG, max_slots=3)}, True),
    "quantization": (None, {"quantization": "int8"}, True),
    "the jax version string": (_jax_version, {}, True),
    "the checkout's path": (_other_checkout, {}, False),
}


@pytest.mark.parametrize("case", sorted(KEY_CASES))
def test_the_key_holds_what_decides_a_program_and_nothing_else(case, source_tree, monkeypatch):
    change, other, moves = KEY_CASES[case]
    monkeypatch.setattr(jax, "__version__", jax.__version__)  # restored whatever a case sets
    deployment, key = bundle_key(_programs(), source_tree)
    assert (deployment, key) == bundle_key(_programs(), source_tree)
    root = (change(source_tree) if change else None) or source_tree
    deployment2, key2 = bundle_key(_programs(**other), root)
    assert (key2 != key) is moves, case
    # The file's name says what is served, not which tree or jax serves it.
    assert (deployment2 != deployment) is bool(other)


def test_the_real_key_digests_the_package(monkeypatch):
    seen = []
    real = step_programs.source_digest
    monkeypatch.setattr(step_programs, "source_digest", lambda root=None: seen.append(root) or real(root))
    bundle_key(_programs())
    assert seen == [None]
    assert step_programs.PACKAGE_ROOT == os.path.dirname(os.path.dirname(os.path.abspath(core.__file__)))
    assert len(real()) == 64


# -- (d) a bad bundle is a miss ------------------------------------------------------


def _truncate(path, key):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size * 2 // 3)


def _of_another_key(path, key):
    records = list(step_programs.read_bundle(path, key).values())
    step_programs.write_bundle(path, "another tree's", records)


def _a_directory_in_its_place(path, key):
    os.unlink(path)
    os.mkdir(path)


@pytest.fixture(scope="module")
def lazy_tokens():
    return _greedy(None, DENSE)


@pytest.mark.parametrize("damage", [_truncate, _of_another_key, _a_directory_in_its_place], ids=lambda f: f.__name__.strip("_"))
def test_a_bad_bundle_is_a_miss_that_compiles_and_serves(damage, cache_dir, lazy_tokens):
    first = fill_step_table(_programs()).stats
    path = first["bundle"]["path"]
    _, key = bundle_key(_programs())
    damage(path, key)
    table = fill_step_table(_programs())
    stats = table.stats
    assert stats["shapes"] == 9 and stats["compiled"] >= 1 and "errors" not in stats, stats
    if damage is _truncate:
        assert 1 <= stats["loaded"] < 9  # the records before the cut are good
    else:
        assert stats["loaded"] == 0
    assert _greedy(table, DENSE) == lazy_tokens
    if damage is not _a_directory_in_its_place:
        # ... and the bundle was written again: the next start loads all of it.
        again = fill_step_table(_programs()).stats
        assert (again["loaded"], again["compiled"]) == (9, 0)


def test_a_loaded_program_of_another_signature_is_a_miss(cache_dir):
    fill_step_table(_programs())
    wider = dataclasses.replace(CFG, max_logit_bias=CFG.max_logit_bias + 1)
    # The bundle of CFG read as if it were the wider deployment's: every
    # argument tree matches, a bias row's shape does not.
    with pytest.MonkeyPatch.context() as m:
        m.setattr(step_programs, "bundle_key", lambda programs, source_root=None: bundle_key(_programs()))
        stats = fill_step_table(_programs(cfg=wider)).stats
    assert (stats["loaded"], stats["compiled"]) == (0, 9)


def test_without_a_cache_directory_nothing_is_written_or_read(tmp_path, monkeypatch):
    assert jax.config.jax_compilation_cache_dir is None
    monkeypatch.setattr(step_programs, "write_bundle", lambda *a: pytest.fail("wrote a bundle"))
    stats = fill_step_table(_programs(), include_group=False).stats
    assert (stats["loaded"], stats["compiled"]) == (0, 7) and "bundle" not in stats


def test_a_deployment_keeps_its_two_newest_bundles(tmp_path):
    paths = [step_programs.bundle_path(str(tmp_path), "dep", f"key{i}") for i in range(3)]
    other = step_programs.bundle_path(str(tmp_path), "another", "key0")
    step_programs.write_bundle(other, "key0", [])
    for i, path in enumerate(paths):
        step_programs.write_bundle(path, f"key{i}", [("decode", "zlib", b"x", None, None)])
        os.utime(path, (1000 + i, 1000 + i))
    step_programs.write_bundle(paths[2], "key2", [])  # rewritten: still the newest
    left = sorted(os.listdir(tmp_path))
    assert left == sorted(os.path.basename(p) for p in (other, paths[1], paths[2]))
    assert step_programs.read_bundle(paths[1], "key1") == {"decode": ("decode", "zlib", b"x", None, None)}
    assert step_programs.read_bundle(paths[1], "key2") == {}


# -- (f) the one list against the two enumerations it replaced ----------------------


def _warmup_calls_before_the_list(cfg, include_group=True) -> list[tuple]:
    """What Engine.warmup() dispatched before it walked the list (PR 47's
    core.py, lines 1134-1194), as (step function, shape of the tokens)."""
    calls = [("decode_jit", ())]
    cap = max(1, min(cfg.prefill_group_cap, cfg.max_slots))
    sizes = (1, cap) if include_group and cap > 1 else (1,)
    for bucket in cfg.prefill_buckets:
        for n_pad in sizes:
            calls.append(("prefill_batch_jit", (n_pad, bucket)))
    for bucket in sorted({*cfg.prefill_buckets, core.wide_chunk(cfg)}):
        calls.append(("prefill_chunk_jit", (1, bucket)))
    return calls


# ... and the three programs a deployment has had since a chunk call takes
# two prompts' pieces (core.round_calls): its three widest row counts at two
# slots a call; none where there is one slot.
TWO_SLOT_ROWS = {
    "tiny": [16, 32], "tiny, no group": [16, 32], "defaults": [256, 512, 1024], "a wide chunk": [512, 1024, 2048],
    "no prompt reaches the wide chunk": [256, 512, 1024], "one slot": [],
}


LIST_CASES = {
    "tiny": (CFG, True),
    "tiny, no group": (CFG, False),
    "defaults": (EngineConfig(), True),
    "a wide chunk": (EngineConfig(max_slots=4, max_seq_len=8192, prefill_buckets=(128, 256, 512, 1024)), True),
    "no prompt reaches the wide chunk": (EngineConfig(max_slots=32, max_seq_len=2048), True),
    "one slot": (EngineConfig(max_slots=1, max_seq_len=256, prefill_buckets=(16, 32, 64, 128)), True),
}


@pytest.mark.parametrize("case", sorted(LIST_CASES))
def test_the_list_covers_every_call_warmup_made(case):
    cfg, include_group = LIST_CASES[case]
    calls = StepPrograms(DENSE, cfg, N_VALID).calls(include_group)
    before = _warmup_calls_before_the_list(cfg, include_group)
    assert [c.key for c in calls[: len(before)]] == before
    assert [c.key for c in calls[len(before) :]] == [("prefill_chunk_jit", (2, rows)) for rows in TWO_SLOT_ROWS[case]]
    # A family that reuses whole prefill calls shares none (core.pair_rows): the list as it was.
    assert [c.key for c in StepPrograms(SMALLTHINKER, cfg, N_VALID).calls(include_group)] == before
    assert len({c.label for c in calls}) == len(calls)  # a label names one program in the bundle
    assert calls[0].member == "decode_jit"  # tests/test_named_scopes.py reads the programs in this order


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_the_abstract_arguments_are_what_the_engine_passes(name):
    """Each call's abstract arguments against the arrays an Engine of the
    same configs holds and warmup() builds: a drift would make every held
    executable refuse its arguments."""
    mc = FAMILIES[name]
    programs = _programs(mc)
    eng = Engine(mc, family(mc).init_params(mc, jax.random.key(0)), ByteTokenizer(), CFG)
    assert programs.serves(eng.model_config, eng.cfg, N_VALID)
    shape = lambda tree: jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)  # noqa: E731
    decode = programs.abstract_args(programs.calls()[0])
    assert shape(decode[0]) == shape(eng.params)
    assert shape(decode[1]) == shape(eng._cache)
    assert shape(decode[2:7]) == shape((eng._page_table, eng._tok_hist, eng._lengths, eng._last_tokens, eng._keys))
    batch = programs.abstract_args(programs.calls()[2])  # [2 x 16]
    assert shape(batch[3]) == ((2, eng._page_table.shape[1]), "int32") and shape(batch[12]) == shape(eng._cache)
    assert shape(batch[11]) == shape(eng._adm_toks)
