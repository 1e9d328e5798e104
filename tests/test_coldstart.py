"""Cold-start fast path: phase timeline math, the shared compile-cache
helper, abstract param shapes vs the real loaders, streamed weight
loading equivalence, AOT warm compile, and the tier-1 overlap smoke
(compile must start before load ends)."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from kubeai_tpu.engine.coldstart import (  # noqa: E402
    ColdStartTimeline,
    padded_vocab_size,
    param_shapes,
    setup_compile_cache,
    warm_compile,
)
from kubeai_tpu.engine.core import EngineConfig  # noqa: E402

TINY_EC = EngineConfig(
    max_slots=2, max_seq_len=64, prefill_buckets=(8, 16), decode_chunk=2
)


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    from kubeai_tpu.engine.weights import save_tiny_test_checkpoint

    path = tmp_path_factory.mktemp("ckpt")
    save_tiny_test_checkpoint(str(path))
    return str(path)


# ---------------------------------------------------------------------------
# Timeline


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_timeline_phase_math_and_overlap():
    clk = FakeClock()
    tl = ColdStartTimeline(clock=clk)
    tl.begin("compile")          # t=100
    clk.t = 101.0
    tl.begin("load")             # load inside compile
    clk.t = 103.0
    tl.end("load")               # load: 2s
    clk.t = 105.0
    tl.end("compile")            # compile: 5s
    clk.t = 106.0
    tl.begin("warmup")           # 1s gap, then serial warmup
    clk.t = 108.0
    tl.end("warmup")             # warmup: 2s
    tl.ready()
    snap = tl.snapshot()
    assert snap["phases"]["load"]["duration_s"] == pytest.approx(2.0)
    assert snap["phases"]["compile"]["duration_s"] == pytest.approx(5.0)
    assert snap["phase_sum_s"] == pytest.approx(9.0)
    # Union coverage is [100,105] + [106,108] = 7s; overlap = 9 - 7 = 2
    # — the serial gap between compile and warmup must NOT mask it.
    assert snap["overlap_s"] == pytest.approx(2.0)
    assert snap["ready_s"] == pytest.approx(8.0)
    json.dumps(snap)  # JSON-able end-to-end


def test_timeline_ready_is_idempotent():
    clk = FakeClock()
    tl = ColdStartTimeline(clock=clk)
    clk.t = 101.0
    tl.ready()
    clk.t = 500.0
    tl.ready()
    assert tl.snapshot()["ready_s"] == pytest.approx(1.0)


def test_timeline_installs_into_debug_engine():
    from kubeai_tpu.obs.recorder import handle_debug_request

    tl = ColdStartTimeline().install()
    with tl.phase("load"):
        pass
    code, ctype, body = handle_debug_request("/debug/engine")
    assert code == 200
    payload = json.loads(body)
    assert "cold_start" in payload
    assert "load" in payload["cold_start"]["phases"]


# ---------------------------------------------------------------------------
# Compile-cache helper


@pytest.fixture
def compile_cache_at(monkeypatch):
    """Place the persistent compile cache where the test says, through
    the one writer, and switch it off again afterwards (in-process
    engines of later tests never asked for one)."""
    from jax.experimental.compilation_cache import compilation_cache

    def place(path: str) -> str:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
        return setup_compile_cache()

    yield place
    compilation_cache.set_cache_dir(None)
    compilation_cache.reset_cache()


def test_setup_compile_cache_env_wins_over_checkout_default(tmp_path, monkeypatch, compile_cache_at):
    from kubeai_tpu.engine import coldstart

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert coldstart.default_compile_cache_dir() == os.path.join(
        repo, ".jax_compile_cache"
    )
    # No env: the checkout's fixed, git-ignored directory (steered to a
    # tmp dir here so the test leaves the real one alone).
    default = str(tmp_path / "checkout" / ".jax_compile_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(coldstart, "default_compile_cache_dir", lambda: default)
    assert setup_compile_cache() == default
    assert os.path.isdir(default)
    assert jax.config.jax_compilation_cache_dir == default

    # Placed from outside: the jax variable wins.
    d2 = str(tmp_path / "cache2")
    assert compile_cache_at(d2) == d2
    assert jax.config.jax_compilation_cache_dir == d2


# ---------------------------------------------------------------------------
# Abstract shapes must equal what the real loaders produce.


@pytest.mark.parametrize("quantization", ["", "int8"])
def test_param_shapes_match_loaded_engine(ckpt_dir, quantization):
    from kubeai_tpu.engine.weights import load_engine_from_path
    from kubeai_tpu.models.base import ModelConfig

    eng = load_engine_from_path(
        ckpt_dir, TINY_EC, dtype="float32", quantization=quantization,
        stream=True, overlap=False, warmup=False,
    )
    config = ModelConfig.from_json_file(ckpt_dir).replace(dtype="float32")
    config = config.replace(vocab_size=padded_vocab_size(config.vocab_size, 1))
    abstract = param_shapes(config, quantization)
    real = jax.tree_util.tree_leaves_with_path(eng.params)
    abst = jax.tree_util.tree_leaves_with_path(abstract)
    assert len(real) == len(abst)
    for (rp, ra), (ap, aa) in zip(real, abst):
        assert rp == ap
        assert ra.shape == aa.shape, (rp, ra.shape, aa.shape)
        assert ra.dtype == aa.dtype, (rp, ra.dtype, aa.dtype)


def test_streamed_load_equals_serial_load(ckpt_dir):
    from kubeai_tpu.engine.weights import load_engine_from_path

    a = load_engine_from_path(
        ckpt_dir, TINY_EC, dtype="float32", stream=True, overlap=False
    )
    b = load_engine_from_path(
        ckpt_dir, TINY_EC, dtype="float32", stream=False, overlap=False
    )
    la = jax.tree_util.tree_leaves_with_path(a.params)
    lb = jax.tree_util.tree_leaves_with_path(b.params)
    assert len(la) == len(lb)
    for (pa, xa), (pb, xb) in zip(la, lb):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    assert a.model_config == b.model_config


def test_streamed_load_tp2_shardings(ckpt_dir):
    a = _load_tp2(ckpt_dir, stream=True)
    b = _load_tp2(ckpt_dir, stream=False)
    for (pa, xa), (pb, xb) in zip(
        jax.tree_util.tree_leaves_with_path(a.params),
        jax.tree_util.tree_leaves_with_path(b.params),
    ):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        assert xa.sharding == xb.sharding, (pa, xa.sharding, xb.sharding)


def _load_tp2(ckpt_dir, stream):
    from kubeai_tpu.engine.weights import load_engine_from_path

    return load_engine_from_path(
        ckpt_dir,
        EngineConfig(max_slots=2, max_seq_len=64, prefill_buckets=(8, 16)),
        tp=2, dtype="float32", stream=stream, overlap=False,
    )


# ---------------------------------------------------------------------------
# AOT warm compile + the overlap smoke.


def test_warm_compile_populates_persistent_cache(ckpt_dir, tmp_path, compile_cache_at):
    from kubeai_tpu.engine.coldstart import warm_from_checkpoint

    cache = compile_cache_at(str(tmp_path / "xla-cache"))
    stats = warm_from_checkpoint(
        ckpt_dir,
        ["--max-slots", "2", "--max-seq-len", "64"],
        include_group=False,
    )
    assert stats["shapes"] > 0
    assert not stats.get("errors")
    entries = [f for f in os.listdir(cache) if f.endswith("-cache")]
    # Every warmed shape must have landed on disk (min-compile-secs=0),
    assert len(entries) >= stats["shapes"]
    # and in the deployment's bundle beside jax's entries, from which the
    # loader Job's next run (and the serving pod's start) loads them all.
    assert [os.path.join(cache, f) for f in os.listdir(cache) if f.endswith(".bundle")] == [stats["bundle"]["path"]]
    again = warm_from_checkpoint(
        ckpt_dir,
        ["--max-slots", "2", "--max-seq-len", "64"],
        include_group=False,
    )
    assert (again["loaded"], again["compiled"]) == (stats["shapes"], 0)


def test_warm_compile_reports_failures_not_raises():
    # An unserveable config (heads not divisible by KV heads — the
    # grouped-attention reshape fails at trace time) must come back as
    # collected errors, not an exception — a warm miss can never fail a
    # load.
    from kubeai_tpu.models.base import ModelConfig

    bad = ModelConfig(
        vocab_size=128, hidden_size=24, intermediate_size=8, num_layers=1,
        num_heads=3, num_kv_heads=2, dtype="float32",
    )
    stats = warm_compile(bad, TINY_EC, include_group=False)
    assert stats["shapes"] == 0
    assert stats["errors"]


def test_compile_overlaps_load_smoke(ckpt_dir):
    """Tier-1 cold-start smoke (ISSUE satellite): via the phase stamps,
    compilation must have STARTED before the weight load ended — the
    engine start is pipelined, not serial."""
    from kubeai_tpu.engine.weights import load_engine_from_path

    eng = load_engine_from_path(
        ckpt_dir, TINY_EC, dtype="float32",
        stream=True, overlap=True, warmup=False,
    )
    snap = eng.cold_start_timeline.snapshot()
    load = snap["phases"]["load"]
    compile_ = snap["phases"]["compile"]
    assert compile_["start_s"] < load["end_s"], snap
    assert snap["attrs"]["warm_compile"]["shapes"] > 0


@pytest.mark.parametrize("overlap", [False, True], ids=["an empty table", "the warm thread's table"])
def test_warmup_covers_all_shapes_and_engine_serves(ckpt_dir, overlap):
    from kubeai_tpu.engine.sampling import SamplingParams
    from kubeai_tpu.engine.step_programs import StepPrograms
    from kubeai_tpu.engine.weights import load_engine_from_path

    eng = load_engine_from_path(
        ckpt_dir, TINY_EC, dtype="float32",
        stream=True, overlap=overlap, warmup=True,
    )
    attrs = eng.cold_start_timeline.snapshot()["attrs"]
    # The one list (engine/step_programs.py), which warm_compile fills
    # the table from and warmup() walks: decode + (1, cap) x 2 buckets +
    # chunk x 2 buckets (the final chunk of a chunked prefill pads to the
    # smallest fitting bucket, so every bucket is a live chunk shape) +
    # the wide chunk of 32 rows (a prompt of TINY_EC's 64 positions
    # reaches it: core.prefill_plan) + those three chunk shapes once
    # more at two slots a call (core.round_calls) = 11 shapes for TINY_EC,
    programs = len(StepPrograms(eng.model_config, TINY_EC).calls())
    assert programs == 11
    # + 4 restore-path shapes (KV evolve, import pow2 1 and 2,
    # slotset) on a single-host engine with KV restore enabled.
    assert attrs["warmup"]["shapes"] == programs + 4 == 15
    if overlap:
        # Every program came up ONCE, on the warm thread, and warmup()
        # ran it: none went through the engine's jitted functions too.
        warm = attrs["warm_compile"]
        assert (warm["shapes"], warm["compiled"], warm["loaded"]) == (programs, programs, 0)
        assert len(eng._table.held) == programs and eng._jit_cache_entries() == 0
    else:
        assert "warm_compile" not in attrs
        assert not eng._table.held and eng._jit_cache_entries() == programs
    assert eng._jit_entries_seen == programs  # jit_recompiles_total, either way
    eng.start()
    try:
        ids, _, fin = eng.generate(
            [1, 2, 3], SamplingParams(max_tokens=3, temperature=0.0), timeout=120
        )
        assert len(ids) == 3
        assert fin.reason == "length"
    finally:
        eng.stop()
