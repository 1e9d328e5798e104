"""Chunked prefill: long prompts (beyond the largest bucket) must produce
identical results to a hypothetical single-shot prefill."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeai_tpu.engine.core import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import llama
from kubeai_tpu.models.base import ModelConfig

CFG = ModelConfig(
    vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, dtype="float32", max_position=1024,
)


def test_chunked_matches_single_shot_model_level():
    """prefill_chunk_into over 3 chunks == one prefill_into."""
    params = llama.init_params(CFG, jax.random.key(0))
    prompt = np.random.default_rng(0).integers(1, 256, 48)

    single = llama.init_cache(CFG, 2, 64)
    logits_1, single = llama.prefill_into(
        params, CFG, jnp.asarray(prompt[None, :]), single, jnp.int32(1), jnp.int32(48)
    )

    chunked = llama.init_cache(CFG, 2, 64)
    for start in range(0, 48, 16):
        chunk = prompt[start : start + 16]
        logits_n, chunked = llama.prefill_chunk_into(
            params, CFG, jnp.asarray(chunk[None, :]), chunked,
            jnp.int32(1), jnp.int32(start), jnp.int32(len(chunk) - 1),
        )
    np.testing.assert_allclose(
        np.asarray(logits_n), np.asarray(logits_1), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(chunked["k"][:, 1, :48]), np.asarray(single["k"][:, 1, :48]),
        rtol=1e-5, atol=1e-5,
    )


@pytest.fixture(scope="module")
def engines():
    """Two engines, same weights: small buckets (forces chunking) and big
    buckets (single-shot); greedy outputs must agree."""
    params = llama.init_params(CFG, jax.random.key(7))
    small = Engine(
        CFG, params, ByteTokenizer(),
        EngineConfig(max_slots=2, max_seq_len=256, prefill_buckets=(16, 32)),
    )
    big = Engine(
        CFG, params, ByteTokenizer(),
        EngineConfig(max_slots=2, max_seq_len=256, prefill_buckets=(128,)),
    )
    small.start()
    big.start()
    yield small, big
    small.stop()
    big.stop()


def test_engine_long_prompt_greedy_matches(engines):
    small, big = engines
    prompt = list(np.random.default_rng(1).integers(1, 200, 100))
    p = SamplingParams(temperature=0.0, max_tokens=6)
    ids_chunked, _, fin = small.generate(prompt, p)
    ids_single, _, _ = big.generate(prompt, p)
    assert fin.prompt_tokens == 100
    assert ids_chunked == ids_single


def test_prompt_capacity_limit(engines):
    small, _ = engines
    with pytest.raises(ValueError, match="too long"):
        small.submit([1] * 256, SamplingParams())
    # At the boundary it is accepted.
    req = small.submit([1] * 255, SamplingParams(max_tokens=1))
    ev = req.out.get(timeout=60)
    assert ev[0] == "token"


# -- the plan of a prompt's chunk calls (engine/core.py::prefill_plan) ---------
#
# A prompt that goes the chunked route is cut into WIDE calls (twice the
# largest bucket: 2048 rows as configured) while that many tokens are left,
# and what is left after them is prefilled as it always was: a call of the
# largest bucket if more than that is left, then the tail in its bucket.

import os  # noqa: E402
import sys  # noqa: E402

from kubeai_tpu.engine import core  # noqa: E402
from kubeai_tpu.engine.core import prefill_plan, wide_chunk  # noqa: E402
from kubeai_tpu.models import family  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_named_scopes as toys  # noqa: E402  (one toy configuration a family)

SERVING = EngineConfig(max_slots=24, max_seq_len=32768)  # the published buckets: six, ending at 1024
LENGTHS = [1, 1024, 1025, 2047, 2048, 2049, 3072, 3073, 5632, 24576]


def narrow_plan(cfg: EngineConfig, left: int) -> list[tuple[int, int]]:
    """The plan as it was before the wide chunk: calls of the largest
    bucket, the last in the smallest bucket that holds it."""
    top = max(cfg.prefill_buckets)
    plan = [(top, top)] * (left // top)
    if left % top:
        plan.append((next(b for b in cfg.prefill_buckets if left % top <= b), left % top))
    return plan


@pytest.mark.parametrize("reuse", [0, 192], ids=["cold", "behind_three_cached_pages"])
@pytest.mark.parametrize("n", LENGTHS)
def test_the_plan_holds_every_token_once_and_pads_only_its_last_call(n, reuse):
    left = n + (0 if reuse == 0 else 1)  # a hit leaves at least one token
    plan = prefill_plan(SERVING, left)
    assert wide_chunk(SERVING) == 2048
    assert sum(real for _, real in plan) == left
    assert all(rows == real for rows, real in plan[:-1]) and plan[-1][0] >= plan[-1][1]
    assert all(rows in (*SERVING.prefill_buckets, 2048) for rows, _ in plan)
    # Wide calls while 2048 are left, and behind them what a prompt of the rest always ran as.
    assert [rows for rows, _ in plan].count(2048) == left // 2048
    assert plan[left // 2048 :] == narrow_plan(SERVING, left % 2048)
    # Nothing about the plan depends on where the prompt started.
    assert [rows for rows, _ in plan] == sorted((rows for rows, _ in plan), reverse=True)


@pytest.mark.parametrize("n", LENGTHS)
def test_a_prompt_of_2047_tokens_or_fewer_never_sees_the_wide_call(n):
    plan = prefill_plan(SERVING, n)
    assert (plan == narrow_plan(SERVING, n)) == (n < 2048)
    # An engine no prompt of which reaches the wide width plans none at any length.
    short = EngineConfig(max_slots=32, max_seq_len=2048)
    assert wide_chunk(short) == 1024 and prefill_plan(short, min(n, 2047)) == narrow_plan(short, min(n, 2047))


@pytest.mark.parametrize("n", LENGTHS)
def test_the_calls_behind_a_whole_call_hit_are_the_cold_plans_tail(n):
    """REUSE_WHOLE_PREFILL_CALLS: a hit cut to an edge between two calls of
    the cold plan leaves exactly the calls the cold plan ends with."""
    cold = prefill_plan(SERVING, n)
    edge = 0
    for j, (rows, _) in enumerate(cold[:-1]):
        edge += rows
        assert prefill_plan(SERVING, n - edge) == cold[j + 1 :]
    # j x 2048 for every j up to the cold plan's wide calls is such an edge.
    assert [rows for rows, _ in cold[: n // 2048]] == [2048] * (n // 2048)


# -- every family through a wide call ------------------------------------------

PAGE = 8
EC = EngineConfig(max_slots=2, max_seq_len=256, page_size=PAGE, prefill_buckets=(8, 16, 32), decode_chunk=4, prefix_cache_min=16)
FAMILIES = {
    "dense": ModelConfig(
        vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2, dtype="float32",
        max_position=256,
    ),
    "mla_experts": toys.DEEPSEEK, "window_experts": toys.SMALLTHINKER, "state_space": toys.NEMOTRON_H, "gated_window": toys.AFMOE,
}


def greedy(eng, prompt, n):
    """(tokens, their log-probs, each one's top-5 alternatives) of a greedy request."""
    req = eng.submit(prompt, SamplingParams(max_tokens=n, temperature=0.0, logprobs=5))
    toks, lps, tops = [], [], []
    while True:
        ev = req.out.get(timeout=300)
        if ev[0] == "token" and ev[1] >= 0:
            toks.append(ev[1])
            lps.append(ev[3])
            tops.append(ev[4])
        elif ev[0] == "done":
            return toks, lps, tops
        elif ev[0] == "error":
            raise RuntimeError(ev[1])


def served(mc, params, prompt, n, monkeypatch=None):
    """A greedy request through an engine of EC; with *monkeypatch* the
    engine is built and run with the wide width taken away (the test's
    steer: the program has no option for it). Returns what `greedy`
    does, the (rows, real tokens) of the chunk calls, the most window
    pages the slot held against its cap, and the slot's state."""
    if monkeypatch is not None:
        monkeypatch.setattr(core, "wide_chunk", lambda cfg: max(cfg.prefill_buckets))
    eng = Engine(mc, params, ByteTokenizer(), EC)
    calls, held = [], [0]
    chunk_jit = eng._prefill_chunk_jit

    def spy(params, tokens, start, last_idx, *rest, **kw):
        calls.append((tokens.shape[1], int(last_idx) + 1))
        if eng._wpages is not None:
            held[0] = max(held[0], eng._wpages.held(0))
        return chunk_jit(params, tokens, start, last_idx, *rest, **kw)

    eng._prefill_chunk_jit = spy
    eng.start()
    try:
        out = greedy(eng, prompt, n)
    finally:
        eng.stop()
    cap = eng._wpages.cap if eng._wpages is not None else None
    state = {k: np.asarray(eng._cache[k]) for k in family(mc).SLOT_STATE}
    return out, calls, (held[0], cap), state


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_wide_call_gives_what_the_calls_of_the_largest_bucket_gave(name, monkeypatch):
    """150 tokens: two calls of 64 rows and the tail in 32, against four
    calls of 32 and the tail. The same tokens; log-probs of the first
    token and its alternatives within float32's summation order (the CPU
    runs at the highest precision); the window pool never over its cap;
    the state-space family's state carried across the wide calls."""
    mc = FAMILIES[name]
    params = family(mc).init_params(mc, jax.random.key(43))
    prompt = [1] + np.random.default_rng(43).integers(32, 127, 149).tolist()
    (toks, lps, tops), calls, (held, cap), state = served(mc, params, prompt, 6)
    assert calls == [(64, 64), (64, 64), (32, 22)]
    (toks_n, lps_n, tops_n), calls_n, (held_n, cap_n), state_n = served(mc, params, prompt, 6, monkeypatch)
    assert calls_n == [(32, 32)] * 4 + [(32, 22)]
    assert toks == toks_n
    np.testing.assert_allclose(lps, lps_n, atol=2e-5)
    assert [t for t, _ in tops[0]] == [t for t, _ in tops_n[0]]
    np.testing.assert_allclose([lp for _, lp in tops[0]], [lp for _, lp in tops_n[0]], atol=2e-5)
    if cap is not None:
        window = family(mc).window_pool_tokens(mc)
        assert cap == (window + 64) // PAGE + 1 and cap_n == (window + 32) // PAGE + 1
        assert window // PAGE < held <= cap and held_n <= cap_n
    assert sorted(state) == sorted(state_n) == (["conv", "ssm"] if name == "state_space" else [])
    for key in state:  # slot 0's recurrent state and convolution tail after 150 + 6 tokens
        np.testing.assert_allclose(state[key][:, 0], state_n[key][:, 0], atol=2e-5)


@pytest.mark.parametrize("max_seq_len,wide", [(256, 64), (64, 32), (65, 64)], ids=["past_the_wide_width", "at_the_wide_width", "one_past"])
def test_warm_up_compiles_the_wide_chunk_exactly_where_a_prompt_reaches_it(max_seq_len, wide):
    """One more step program where `max_seq_len` exceeds twice the largest
    bucket and none where it does not; serving a prompt that plans every
    width then compiles nothing."""
    mc = FAMILIES["dense"]
    cfg = EngineConfig(max_slots=2, max_seq_len=max_seq_len, page_size=PAGE, prefill_buckets=(8, 16, 32), decode_chunk=4)
    assert wide_chunk(cfg) == wide
    eng = Engine(mc, llama.init_params(mc, jax.random.key(1)), ByteTokenizer(), cfg)
    eng.warmup()
    # A chunk program a bucket, and the wide one; the three widest once more for two slots a call.
    assert eng._prefill_chunk_jit._cache_size() == 3 + (wide == 64) + 3
    assert eng._prefill_batch_jit._cache_size() == 3 * 2  # no cold shape is added: buckets x (1, group) rows
    entries = eng._jit_cache_entries()
    eng.start()
    try:
        for n in (max_seq_len - 1, 40, 9):  # the longest prompt the engine takes: wide calls where they exist, a call of 32 and every tail's bucket
            eng.generate([1] + list(range(40, 40 + n - 1)), SamplingParams(max_tokens=1, temperature=0.0))
        eng._update_recompile_counter()
    finally:
        eng.stop()
    assert eng._jit_cache_entries() == entries
    planned = {rows for rows, _ in prefill_plan(cfg, max_seq_len - 1)}
    assert (64 in planned) == (wide == 64)


def test_the_counter_says_how_many_tokens_each_width_carried():
    mc = FAMILIES["dense"]
    eng = Engine(mc, llama.init_params(mc, jax.random.key(1)), ByteTokenizer(), EC)
    before = {rows: eng.m_chunk_tokens.value(labels={"rows": str(rows)}) for rows in (64, 32, 8)}
    eng.start()
    try:
        eng.generate([1] + list(range(40, 40 + 165)), SamplingParams(max_tokens=1, temperature=0.0))  # 64 + 64 + 32 + 6
    finally:
        eng.stop()
    after = {rows: eng.m_chunk_tokens.value(labels={"rows": str(rows)}) for rows in (64, 32, 8)}
    assert {rows: after[rows] - before[rows] for rows in after} == {64: 128, 32: 32, 8: 6}
    assert eng._perf_debug_section()["prefill_chunk_tokens"].keys() == {"8", "16", "32", "64"}
