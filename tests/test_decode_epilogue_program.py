"""The decode program's structure: every `top_k` / `sort` and both penalty
scatters sit inside a `lax.cond` of the scan's body, three in all, each on
a scalar predicate (a `vmap` over a batched one would have left a select
and both branches); the host's predicates, which feed the counter and
decide what is fetched, are the device's; and the programs carry the names
and the outputs that the benchmark's trace readers and the host's fetch
count on."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine.coldstart import warm_compile
from kubeai_tpu.engine.core import EngineConfig
from kubeai_tpu.engine.sampling import EPILOGUE_PARTS, epilogue_parts
from kubeai_tpu.models.base import ModelConfig

B, V = 2, 272


def _warm_programs(chunk: int):
    """(jaxpr, module name) of every program the warm compile lowers, the
    decode chunk first; nothing is compiled."""
    programs = []
    lower = jax.stages.Traced.lower

    def record(self, *a, **k):
        lowered = lower(self, *a, **k)
        name = re.match(r"module @(\S+)", lowered.as_text()).group(1)
        programs.append((self.jaxpr, name))
        return lowered

    with pytest.MonkeyPatch.context() as m:
        m.setattr(jax.stages.Traced, "lower", record)
        m.setattr(jax.stages.Lowered, "compile", lambda self, *a, **k: None)
        mc = ModelConfig(
            vocab_size=V, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, num_kv_heads=2, dtype="float32", max_position=256,
            use_paged_kernel=True, use_flash_prefill=True,
        )
        cfg = EngineConfig(
            max_slots=B, max_seq_len=64, page_size=16, prefill_buckets=(16, 32),
            decode_chunk=chunk,
        )
        out = warm_compile(mc, cfg, n_valid_vocab=259)
    assert "errors" not in out, out
    return programs


def _walk(jaxpr, path=()):
    """(names of the enclosing primitives, equation) of every equation,
    nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, path + (eqn.primitive.name,))


@pytest.mark.parametrize("chunk", [2, 4], ids=["plain", "chunk4"])
def test_the_optional_epilogue_sits_under_three_conds_in_the_scan_body(chunk):
    eqns = list(_walk(_warm_programs(chunk)[0][0].jaxpr))
    shape = lambda eqn: tuple(eqn.outvars[0].aval.shape)  # noqa: E731

    conds = [(path, e) for path, e in eqns if e.primitive.name == "cond"]
    assert [path for path, _ in conds] == [("scan",)] * 3
    for _, e in conds:
        assert e.invars[0].aval.shape == () and len(e.params["branches"]) == 2
    # In the order the body runs them: penalties -> the [B, V] logits the
    # choice is made from; candidates -> [B] tokens; alternatives -> ids
    # and log-probs [B, 5].
    assert [shape(e) for _, e in conds] == [(B, V), (B,), (B, 5)]

    sorts = [(path, e) for path, e in eqns if e.primitive.name in ("top_k", "sort")]
    assert [path for path, _ in sorts] == [("scan", "cond")] * 2
    # top-128 candidates; the top-5 alternatives.
    assert [shape(e) for _, e in sorts] == [(B, 128), (B, 5)]

    # Scatters that build a [B, V] array: the penalties' two, inside their
    # cond; outside, the logit bias (it stays: it is cheap and always read)
    # and the mask over the vocabulary's padding (259 of 272 are tokens).
    vocab_scatters = [
        (path, e.primitive.name) for path, e in eqns
        if e.primitive.name.startswith("scatter") and shape(e) == (B, V)
    ]
    assert sorted(vocab_scatters) == [
        (("scan",), "scatter"),
        (("scan",), "scatter-add"),
        (("scan", "cond"), "scatter-add"),
        (("scan", "cond"), "scatter-max"),
    ]


def test_the_programs_carry_the_names_the_trace_readers_select_by():
    # perfbench/layer_metrics/ picks the decode program by ^jit__unknown
    # (it is a jitted partial) and the prefill ones by their functions'.
    names = [name for _, name in _warm_programs(2)]
    assert names[0] == "jit__unknown"
    # ... in the one list's order (engine/step_programs.py): two buckets x
    # (one row, the group cap), then a chunk call a bucket and the same
    # for two slots.
    assert names[1:5] == ["jit_prefill_batch_fn"] * 4
    assert names[5:] == ["jit_prefill_chunk_fn"] * 4


def test_the_decode_chunk_returns_four_fetched_arrays_then_five_carries():
    K = 4
    outs = [tuple(v.aval.shape) for v in _warm_programs(K)[0][0].jaxpr.outvars]
    # corr, lp_corr, t_ids, t_lp; then cache, hist, lengths, last, keys.
    assert outs[:4] == [(K, B), (K, B), (K, B, 5), (K, B, 5)]
    assert len(outs) == 9
    assert all(np.prod(shape) > 0 for shape in outs)


def _random_batch(seed: int, n: int = 16):
    """active, temperature, presence, frequency, want_top of *n* slots:
    sparse enough that every part comes out both ways over a few seeds."""
    rng = np.random.default_rng(seed)
    sparse = lambda p, values: np.where(rng.random(n) < p, values, 0.0).astype(np.float32)  # noqa: E731
    return (
        rng.random(n) < 0.3, sparse(0.15, rng.random(n)), sparse(0.1, rng.normal(size=n)),
        sparse(0.1, rng.normal(size=n)), rng.random(n) < 0.15,
    )


SEEDS = range(8)


@pytest.mark.parametrize("seed", SEEDS)
def test_host_and_device_state_the_same_predicates(seed):
    batch = _random_batch(seed)
    host = [bool(r) for r in epilogue_parts(*batch)]
    device = [bool(r) for r in jax.jit(epilogue_parts)(*(jnp.asarray(a) for a in batch))]
    assert host == device and len(host) == len(EPILOGUE_PARTS)
    # An idle slot's stale parameters decide nothing.
    active, temp, presence, frequency, want_top = batch
    assert host == [
        bool(want_top[active].any()), bool((temp[active] > 0).any()),
        bool(((presence[active] != 0) | (frequency[active] != 0)).any()),
    ]


def test_the_seeds_open_and_shut_every_gate():
    seen = {(i, bool(r)) for seed in SEEDS for i, r in enumerate(epilogue_parts(*_random_batch(seed)))}
    assert len(seen) == 2 * len(EPILOGUE_PARTS)
