"""Guard: bench.py's host-synthesized int8 tree must stay structurally
identical to the real quantizing loader's output (ADVICE r2: a future
llama tree change would otherwise silently make the bench build a
different jitted graph than serving)."""

import json
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import synth_int8_params
from kubeai_tpu.engine.weights import quantize_model_params
from kubeai_tpu.models import llama
from kubeai_tpu.models.base import ModelConfig


def test_worker_emits_headline_before_teardown_failure(monkeypatch, capsys):
    """ADVICE r5 regression: the measured headline must be emitted
    BEFORE engine teardown, so a hung/raising stop() can't forfeit an
    already-measured result."""
    import types

    import bench
    from kubeai_tpu.engine.core import Engine

    order = []
    real_emit = bench.emit
    monkeypatch.setattr(
        bench, "emit", lambda v, e=None: (order.append("emit"), real_emit(v, e))[1]
    )

    def exploding_stop(self):
        order.append("stop")
        # Still wind the scheduler thread down (this test shares the
        # process with the rest of the suite) — the raise is what
        # exercises the worker's teardown guard.
        self._running = False
        self._wake.set()
        raise RuntimeError("simulated teardown hang")

    monkeypatch.setattr(Engine, "stop", exploding_stop)
    args = types.SimpleNamespace(
        preset="tiny", watchdog=0, requests=2, max_tokens=2,
        greedy=False, slots=0, chunk=0, kv_dtype="",
        request_rate=0, rate_duration=45.0,
    )
    bench.run_worker(args)  # must not raise despite the exploding stop
    assert order == ["emit", "stop"]
    line = [
        json.loads(ln) for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("{")
    ][-1]
    assert line["metric"] == "engine_output_tokens_per_sec_per_chip"
    assert line["value"] > 0  # the measurement survived the teardown failure
    # Every result names the device it ran on: the tiny preset is the
    # labelled CPU smoke, never a chip figure.
    assert line["preset"] == "tiny"
    assert line["device"]["platform"] == "cpu"


def test_synth_tree_matches_quantized_loader():
    # Tiny config with the 8b-int8 preset's *structure* (bf16 dense llama,
    # untied lm_head, GQA) so the comparison is cheap but exercises every
    # key the synth builds.
    mc = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, dtype="bfloat16",
    )
    real = quantize_model_params(
        jax.tree.map(np.asarray, llama.init_params(mc, jax.random.key(0))), mc
    )
    synth = synth_int8_params(mc)

    real_s = jax.tree_util.tree_structure(real)
    synth_s = jax.tree_util.tree_structure(synth)
    assert real_s == synth_s, f"tree structure diverged:\n{real_s}\nvs\n{synth_s}"

    real_leaves = jax.tree_util.tree_leaves_with_path(real)
    synth_leaves = jax.tree_util.tree_leaves_with_path(synth)
    for (pr, lr), (ps, ls) in zip(real_leaves, synth_leaves):
        assert pr == ps
        assert lr.shape == ls.shape, f"{pr}: {lr.shape} != {ls.shape}"
        assert lr.dtype == ls.dtype, f"{pr}: {lr.dtype} != {ls.dtype}"
