"""`model_type: smallthinker` (models/smallthinker.py: periods of one full
layer without rope and three window layers with rope, each kind with a page
pool of its own, softmax-routed ReGLU experts chosen from the layer's
input) against the plain reference (perfbench/families/
smallthinker_reference.py, which imports nothing of the program), on the
CPU at a small size: hidden 64, 8 layers (two periods), 4 query / 2 KV
heads of 16, window 32, page 8, 8 experts top-2, vocab 384; seeded random
weights from the family's own plan. Contexts reach 130 tokens and more:
four windows.

Bounds, each with its reason. The program runs in float32 here (conftest:
"highest" matmul precision), the reference too, so what separates them is
summation order: measured 1e-6 on logits whose standard deviation is 0.16.
LOGITS_ABS = 2e-5 leaves an order of magnitude; the controls (a fault in
the reference's mathematics) move the logits by 1e-3 and more and must
FAIL it. Router choices: in float32 both sides' router logits agree to
1e-7, so two experts change places only where the reference's own logits
are within CHOICE_EPS = 1e-5 of each other; the logits comparison routes
the reference by the program's choices so that it does not hang on a tie.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import children  # noqa: E402  (perfbench: the harness's checkpoint writer)
from families import smallthinker_counts as counts  # noqa: E402
from families import smallthinker_reference as reference  # noqa: E402
from kubeai_tpu.engine.core import EngineConfig, table_width, window_pool_dims  # noqa: E402
from kubeai_tpu.engine.paging import WindowPages  # noqa: E402
from kubeai_tpu.engine.sampling import SamplingParams  # noqa: E402
from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path  # noqa: E402
from kubeai_tpu.models import family, llama, smallthinker  # noqa: E402
from kubeai_tpu.models.base import ModelConfig  # noqa: E402
from kubeai_tpu.obs.perf import param_counts  # noqa: E402
from kubeai_tpu.ops import moe  # noqa: E402

LOGITS_ABS = 2e-5
CHOICE_EPS = 1e-5
PAGE, WINDOW, CHUNK = 8, 32, 32
WIDE = 2 * CHUNK  # the engine's widest chunk call (core.wide_chunk): max_seq_len is past it
CAP = (WINDOW + WIDE) // PAGE + 1

HF = {
    "model_type": "smallthinker", "vocab_size": 384, "hidden_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 32,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    # Longer than the depth, as a checkpoint cut in depth keeps them.
    "rope_layout": [0, 1, 1, 1] * 3, "sliding_window_layout": [0, 1, 1, 1] * 3, "sliding_window_size": WINDOW,
    "rope_theta": 1500000, "rope_scaling": None, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "max_position_embeddings": 512,
}
EC = EngineConfig(
    max_slots=3, max_seq_len=256, page_size=PAGE, prefill_buckets=(8, 16, CHUNK), decode_chunk=4, prefix_cache_min=16,
)
MAX_PAGES = 256 // PAGE


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    d = tmp_path_factory.mktemp("smallthinker")
    hf_path = os.path.join(d, "hf.json")
    with open(hf_path, "w") as f:
        json.dump(HF, f)
    path = os.path.join(d, "ckpt")
    children.child_checkpoint(path, hf_path, "7")
    return path


@pytest.fixture(scope="module")
def eng(ckpt):
    return load_engine_from_path(ckpt, EC, dtype="float32", overlap=False, warmup=False)


@pytest.fixture(scope="module")
def source(ckpt):
    return SafetensorsSource(ckpt)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(0, 259, (1, 140))


def through_the_pools(eng, tokens, prefilled, kernel=False):
    """One row through both pools as the engine drives them: chunks of
    CHUNK behind cached tokens up to *prefilled* (the last padded to its
    bucket), then a decode step a token, the window table moved by the
    host's own manager, whose pool holds ONE slot's cap. Returns the
    logits at every position from the last prompt position on, the
    choices of every position, the most window pages held, the manager."""
    mc = eng.model_config.replace(use_paged_kernel=kernel)
    S = tokens.shape[1]
    table = np.zeros((1, 2 * MAX_PAGES), np.int32)
    table[0, :MAX_PAGES] = 1 + np.arange(MAX_PAGES)
    wp = WindowPages(table[:, MAX_PAGES:], WINDOW, CHUNK, PAGE)
    wp.admit(0, [], 0, [], MAX_PAGES)
    pools = smallthinker.init_paged_cache(mc, MAX_PAGES + 1, PAGE, window_pages=wp.pool.num_pages)
    prefill = jax.jit(
        lambda p, tk, c, tb, start, last: smallthinker.prefill_paged(p, mc, tk, c, tb, start, last, return_choices=True)
    )
    decode = jax.jit(lambda p, tk, c, tb, at: smallthinker.decode_step_paged(p, mc, tk, c, tb, at, return_choices=True))
    got, choices, held = [], [], 0
    for start in range(0, prefilled, CHUNK):
        n = min(CHUNK, prefilled - start)
        bucket = next(b for b in EC.prefill_buckets if b >= n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens[0, start : start + n]
        wp.advance(0, start, start + bucket)
        held = max(held, wp.held(0))
        lg, cache, ch = prefill(eng.params, padded, pools, table.copy(), np.asarray([start]), np.asarray([n - 1]))
        pools = {k: v for k, v in cache.items() if k.startswith("kv")}
        choices.append(np.asarray(ch)[:, :n])
    got.append(np.asarray(lg[0, 0]))
    for pos in range(prefilled, S):
        wp.advance(0, pos, pos + 1)
        held = max(held, wp.held(0))
        lg, cache, ch = decode(eng.params, tokens[:, pos : pos + 1], pools, table.copy(), np.asarray([pos]))
        pools = {k: v for k, v in cache.items() if k.startswith("kv")}
        got.append(np.asarray(lg[0, 0]))
        choices.append(np.asarray(ch))
    return np.stack(got), np.concatenate(choices, axis=1), held, wp


@pytest.fixture(scope="module")
def program_run(eng, tokens):
    """The portable route's run, once for the comparison and its controls."""
    return through_the_pools(eng, tokens, prefilled=100)


def test_the_family_is_chosen_by_model_type_alone(eng):
    mc = eng.model_config
    assert mc.model_type == "smallthinker" and family(mc) is smallthinker
    assert family(ModelConfig()) is llama
    assert mc.sliding_window_layout == (0, 1, 1, 1, 0, 1, 1, 1) and mc.rope_layout == mc.sliding_window_layout
    assert (mc.n_routed_experts, mc.num_experts_per_tok, mc.moe_intermediate_size) == (8, 2, 32)
    assert smallthinker.period(mc) == 4 and smallthinker.layer_kinds(mc) == (2, 6)
    assert mc.sliding_window == 0  # Gemma2's key, llama.py's: not this family's

    # The same keys on another family's config.json stay ignored.
    class Cfg:
        pass

    other = Cfg()
    other.__dict__.update({**HF, "model_type": "llama", "intermediate_size": 128})
    assert ModelConfig.from_hf(other).sliding_window_size == 0
    assert llama.window_pool_tokens(ModelConfig()) == 0 and smallthinker.window_pool_tokens(mc) == WINDOW


# -- (a) the step programs' calls against the reference, past three windows ---


@pytest.mark.parametrize("kernel", [False, True], ids=["portable", "kernel_twin"])
def test_chunked_prefill_and_decode_through_both_pools_agree_with_the_reference(eng, source, tokens, program_run, kernel):
    """100 tokens in chunks of 32 behind cached tokens (the fourth chunk's
    first query is three windows in), then 40 decode steps to position
    139, with pages handed back behind the window all the way."""
    got, choices, held, wp = through_the_pools(eng, tokens, prefilled=100, kernel=True) if kernel else program_run
    want = reference.forward(source.get, HF, tokens, forced=choices)
    assert np.abs(got - want["logits"][0, 99:]).max() <= LOGITS_ABS
    d = reference.choice_disagreements(choices, want["choices"], want["select"])
    assert d["compared"] == 8 * tokens.size and d["worst_gap"] <= CHOICE_EPS
    # The window budget: never more than the cap, pages handed back as
    # the row advanced, and at the end no more than a window's worth.
    assert held <= wp.cap == 9
    assert wp.released == (139 - WINDOW + 1) // PAGE and wp.held(0) == WINDOW // PAGE + 1
    assert wp.pool.used() == wp.held(0)


def test_cold_prefill_agrees_with_the_reference_in_logits(eng, source):
    """Two rows of one cold call (left-aligned, as long as the window)."""
    mc = eng.model_config
    toks = np.random.default_rng(5).integers(0, 259, (2, CHUNK))
    table = np.zeros((2, 2 * MAX_PAGES), np.int32)
    table[:, :4] = table[:, MAX_PAGES : MAX_PAGES + 4] = 1 + np.arange(8).reshape(2, 4)
    pools = smallthinker.init_paged_cache(mc, 9, PAGE, window_pages=9)
    pos = jnp.broadcast_to(jnp.arange(CHUNK)[None], (2, CHUNK))
    lg, cache, choices = smallthinker.apply(
        eng.params, mc, jnp.asarray(toks, jnp.int32), pos, pools, jnp.asarray(table), left_aligned=True, return_choices=True,
    )
    want = reference.forward(source.get, HF, toks, forced=np.asarray(choices))
    assert np.abs(np.asarray(lg) - want["logits"]).max() <= LOGITS_ABS
    assert 0 < int(cache["moe_hits"]) <= 8 * HF["moe_num_primary_experts"]


def generate(eng, prompt, n):
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


def reference_logprobs(source, sequence, first):
    """log-softmax over the ids the byte tokenizer can emit, at every
    position from *first* on."""
    lg = reference.forward(source.get, HF, np.asarray(sequence)[None])["logits"][0, first:, :259].astype(np.float64)
    return lg - np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1, keepdims=True)) - lg.max(-1, keepdims=True)


@pytest.mark.parametrize("n_prompt,n_new", [(20, 110), (110, 24)], ids=["cold_group_then_decode", "chunked_then_decode"])
def test_the_served_path_reports_the_references_log_probs(eng, source, n_prompt, n_new):
    """Through the scheduler, the host's two page managers and the three
    step programs: a prompt prefilled cold in one bucket (then 110 decode
    steps, to four windows) and one prefilled in chunks (three windows and
    a half), each token's log-prob and its top-5 against the reference on
    the sequence the engine produced; the window budget a slot stays
    under its cap while the full budget grows with the length; every page
    of both pools comes back."""
    prompt = [1] + np.random.default_rng(n_prompt).integers(32, 127, n_prompt - 1).tolist()
    eng.start()
    try:
        full0, window0 = eng._pool.available(), eng._wpages.pool.available()
        released0 = eng.m_window_released.value()
        held = []
        # The gauges a scrape reads: polled while the request runs.
        req_tokens, lps, tops = generate(eng, prompt, n_new)
        held.append(eng._wpages.pool.num_pages - 1 - eng._wpages.pool.available())
    finally:
        eng.stop()
    assert len(req_tokens) == n_new
    want = reference_logprobs(source, prompt + req_tokens, n_prompt - 1)
    for i, (tok, lp, top) in enumerate(zip(req_tokens, lps, tops)):
        assert abs(lp - want[i, tok]) <= 5 * LOGITS_ABS, i
        assert tok == int(want[i].argmax())
        assert max(abs(l - want[i, t]) for t, l in top) <= 5 * LOGITS_ABS
    total = n_prompt + n_new
    # Handed back as the slot advanced (the engine runs whole chunks of 4
    # steps, so it may be a page further than the emitted stream).
    assert eng.m_window_released.value() - released0 >= (total - WINDOW) // PAGE
    assert eng._pool.available() == full0 and eng._wpages.pool.available() == window0
    assert held == [0]


def test_a_slots_window_pages_stay_under_the_cap_while_its_full_pages_grow(eng):
    """Polled at every dispatch of a long request: the window table never
    holds more than (window + chunk) / page + 1 pages, the full table
    holds the whole prompt and budget."""
    prompt = [1] + np.random.default_rng(3).integers(32, 127, 149).tolist()
    seen = []
    dispatch = eng._dispatch_chunk_call

    def spy():
        out = dispatch()
        seen.append((eng._wpages.held(0), int((eng._page_table[0, :MAX_PAGES] > 0).sum()), eng._wpages.pool.used()))
        return out

    eng._dispatch_chunk_call = spy
    eng.start()
    try:
        generate(eng, prompt, 60)
    finally:
        eng.stop()
        eng._dispatch_chunk_call = dispatch
    busy = [s for s in seen if s[1]]
    assert busy and max(w for w, _, _ in busy) <= CAP
    assert {f for _, f, _ in busy} == {-(-(150 + 60) // PAGE)}  # prompt + budget, reserved at admission
    assert all(w == used for w, _, used in busy)
    assert min(w for w, _, _ in busy) >= WINDOW // PAGE


# -- (b) a prefix hit across the two pools ------------------------------------


@pytest.mark.parametrize(
    "n,reused", [(120, 96), (40, 32), (30, 0), (200, 192)],
    ids=["a_wide_call_and_one_of_the_largest_bucket", "one_call", "shorter_than_a_call", "three_wide_calls"],
)
def test_a_prefix_hit_across_both_pools_gives_the_cold_runs_bits(eng, source, n, reused):
    """A hit is used in whole prefill calls (the wide chunk, 64 rows, the
    largest bucket, 32) and only where the window pool still holds the
    pages the first new query sees: 120 tokens run cold as chunks at 0 (64
    rows), 64 and 96, and behind their cached pages as the chunk at 96
    alone, on 12 claimed full pages and the 4 window pages of positions
    64-95; 200 tokens as three wide calls and a tail, and behind 24 cached
    pages as the tail alone. The same tokens and
    log-probs to the bit, and the reference's."""
    assert smallthinker.REUSE_WHOLE_PREFILL_CALLS and not smallthinker.KV_PARK and llama.KV_PARK
    prompt = [1] + np.random.default_rng(100 + n).integers(32, 127, n - 1).tolist()
    eng.start()
    try:
        cold = generate(eng, prompt, 12)
        cached0 = eng.m_prefix_cached.value()
        full0, window0 = eng._pool.available(), eng._wpages.pool.available()
        warm = generate(eng, prompt, 12)
        assert eng.m_prefix_cached.value() - cached0 == reused
        assert cold == warm
        assert eng._pool.available() == full0 and eng._wpages.pool.available() == window0
    finally:
        eng.stop()
    want = reference_logprobs(source, prompt + warm[0], n - 1)
    assert max(abs(lp - want[i, t]) for i, (t, lp) in enumerate(zip(warm[0], warm[1]))) <= 5 * LOGITS_ABS


def test_a_hit_whose_window_pages_are_gone_is_recomputed(eng):
    """The full pool still holds a prompt's pages, the window pool's were
    taken by other slots since: no hit (a window layer's keys behind the
    first new query would be missing), the prompt runs cold and right."""
    prompt = [1] + np.random.default_rng(77).integers(32, 127, 99).tolist()
    eng.start()
    try:
        cold = generate(eng, prompt, 6)
        wp = eng._wpages
        taken = wp.pool.allocate(wp.pool.available())  # evicts every cached window page
        wp.pool.release(taken)
        cached0 = eng.m_prefix_cached.value()
        again = generate(eng, prompt, 6)
        assert eng.m_prefix_cached.value() == cached0
        assert again == cold
    finally:
        eng.stop()


# -- (c) the host's window manager alone --------------------------------------


def test_window_pages_moves_one_contiguous_run_and_registers_what_it_hands_back():
    table = np.zeros((2, 16), np.int32)
    wp = WindowPages(table, window=32, chunk=16, page_size=8)
    assert wp.cap == 7 and wp.pool.num_pages == 2 * 7 + 1
    digests = wp.pool.chain_digests(list(range(50)), (0, 0))  # 6 whole pages
    wp.admit(0, digests, 0, [], limit=10)
    for start in range(0, 48, 16):
        wp.advance(0, start, start + 16)
        assert wp.held(0) <= wp.cap
    assert (table[0] > 0).tolist() == [True] * 6 + [False] * 10  # the query at 32 still sees key 1
    wp.advance(0, 48, 56)  # the query at 48 sees keys 17..: pages 0 and 1 go
    assert wp.released == 2 and (table[0] > 0).tolist() == [False] * 2 + [True] * 5 + [False] * 9
    wp.settle(0)
    # What was handed back is still findable; a second slot claims it.
    n, pages = wp.match(digests, range(6, 0, -2))
    assert n == 6 and len(pages) == 6 - wp.first_page(48)
    wp.admit(1, digests, n, pages, limit=10)
    assert wp.held(1) == len(pages) and table[1, wp.first_page(48) : 6].tolist() == pages
    wp.free(1)
    # Nothing is allocated past the slot's limit: writes there go to the trash page.
    wp.advance(0, 90, 98)
    assert table[0, 10:].sum() == 0
    wp.free(0, digests)
    assert wp.pool.used() == 0 and not table.any()


def test_the_two_budgets_follow_from_the_engine_config_and_the_models_layout(eng):
    mc = eng.model_config
    assert window_pool_dims(mc, EC) == (WINDOW, 3 * CAP + 1) and table_width(mc, EC) == 2 * MAX_PAGES
    assert window_pool_dims(ModelConfig(), EC) == (0, 0) and table_width(ModelConfig(), EC) == MAX_PAGES
    P = 3 * MAX_PAGES + 1  # the full pool: --kv-pages, or its automatic value
    assert eng._cache["kv"].shape == (2 * P, PAGE, 4, 16)
    assert eng._cache["kv_window"].shape == (6 * (3 * CAP + 1), PAGE, 4, 16)
    perf = eng._perf_debug_section()
    assert perf["kv_bytes_per_token_by_kind"] == {"full": 2 * 2 * 2 * 16 * 4, "window": 6 * 2 * 2 * 16 * 4}
    assert perf["window_pool"]["slot_cap_pages"] == CAP


# -- (d) controls: each must FAIL the comparison the program passes -----------


@pytest.mark.parametrize("variant", ["window_ignored", "rope_on_global", "router_post_norm", "silu_gate"])
def test_a_faulty_reference_fails_the_bound(source, tokens, program_run, variant):
    got, choices, _, _ = program_run
    forced = None if variant == "router_post_norm" else choices  # that fault IS the choice
    bad = reference.forward(source.get, HF, tokens, forced=forced, variant=variant)["logits"][0, 99:]
    assert np.abs(got - bad).max() > LOGITS_ABS * 10


# -- (e) experts, loader, refusals, counts ------------------------------------


def test_the_router_is_softmax_over_the_chosen_logits():
    r = jnp.asarray(np.random.default_rng(0).normal(size=(5, 8)), jnp.float32)
    idx, w = moe.route_softmax_topk(r, 3)
    full = np.asarray(jax.nn.softmax(r, axis=1))
    chosen = np.take_along_axis(full, np.asarray(idx), axis=1)
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(np.argsort(-np.asarray(r), 1)[:, :3], 1))
    assert np.allclose(np.asarray(w), chosen / chosen.sum(1, keepdims=True), atol=1e-6)
    forced = jnp.asarray([[0, 1, 2]] * 5, jnp.int32)
    assert np.array_equal(np.asarray(moe.route_softmax_topk(r, 3, forced=forced)[0]), np.asarray(forced))


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_routed_experts_take_the_gates_activation(act):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 4, (6, 2)), jnp.int32)
    w = jnp.asarray(rng.random((6, 2)), jnp.float32)
    fn = getattr(jax.nn, act)
    y, _ = moe.routed_experts(x, idx, w, wg, wu, wd, act=fn)
    want = sum(
        w[:, i, None] * jnp.einsum("tf,tfd->td", fn(jnp.einsum("td,tdf->tf", x, wg[idx[:, i]])) * jnp.einsum("td,tdf->tf", x, wu[idx[:, i]]), wd[idx[:, i]])
        for i in range(2)
    )
    assert np.allclose(np.asarray(y), np.asarray(want), atol=1e-4)
    if act == "silu":  # the default, as models/deepseek.py calls it
        assert np.array_equal(np.asarray(y), np.asarray(moe.routed_experts(x, idx, w, wg, wu, wd)[0]))


def test_the_loaders_tree_is_init_params_tree(eng, source):
    from kubeai_tpu.engine.coldstart import padded_vocab_size, param_shapes

    mc = eng.model_config
    assert mc.vocab_size == padded_vocab_size(HF["vocab_size"])
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), param_shapes(mc))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), eng.params)
    assert got == want


@pytest.mark.parametrize(
    "change,match",
    [
        ({"moe_primary_router_apply_softmax": False}, "moe_primary_router_apply_softmax"),
        ({"moe_enable_secondary_experts": True}, "secondary experts"),
        ({"rope_scaling": {"rope_type": "default"}}, "rope_scaling"),
        ({"num_hidden_layers": 6}, "whole periods"),
        ({"sliding_window_layout": [0, 1, 1]}, "sliding_window_layout"),
        ({"sliding_window_size": 0}, "sliding_window_size"),
    ],
    ids=lambda v: v if isinstance(v, str) else "",
)
def test_what_the_config_asks_and_the_family_does_not_run_is_refused_by_name(change, match):
    class Cfg:
        pass

    cfg = Cfg()
    cfg.__dict__.update({**HF, **change})
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf(cfg)


def test_what_the_family_does_not_serve_is_refused_at_load(ckpt, eng):
    with pytest.raises(ValueError, match="quantization"):
        load_engine_from_path(ckpt, EC, dtype="float32", quantization="int8", overlap=False)
    with pytest.raises(ValueError, match="tensor-parallel"):
        load_engine_from_path(ckpt, EC, dtype="float32", tp=2, overlap=False)
    mc = ModelConfig.from_json_file(ckpt)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        smallthinker.refuse_unsupported(mc.replace(kv_cache_dtype="fp8"))
    with pytest.raises(ValueError, match="tied embeddings"):
        smallthinker.refuse_unsupported(mc.replace(tie_word_embeddings=True))
    with pytest.raises(ValueError, match="both full and window"):
        smallthinker.refuse_unsupported(mc.replace(sliding_window_layout=(0,) * 8))
    with pytest.raises(ValueError, match="LoRA"):
        smallthinker.decode_step_paged(None, mc, None, None, None, None, lora={})
    with pytest.raises(ValueError, match="without the paged pool"):
        smallthinker.apply(eng.params, mc, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="LoRA adapters are not supported"):
        eng.load_adapter("a", "/nonexistent")
    assert not eng._kv_enabled()  # KV_PARK: nothing is parked, restored or handed off


def test_param_counts_are_the_benchmarks_counts(ckpt):
    """obs/perf.py (kubeai_engine_mfu) against perfbench/families/
    smallthinker_counts.py, at the small size and at the published widths
    cut to 12 layers: 4.78G in layers + 0.78G outside held; 0.68G + 0.39G
    a token."""
    with open(os.path.join(ROOT, "perfbench", "configs", "smallthinker-21b-a3b-bf16.json")) as f:
        published = json.load(f)

    class Cfg:
        pass

    for hf in (HF, published):
        cfg = Cfg()
        cfg.__dict__.update(hf)
        total, active = param_counts(ModelConfig.from_hf(cfg))
        assert (total, active) == (counts.params_held(hf), counts.active_params(hf))
    assert round(counts.params_held(published) / 1e9, 2) == 5.56
    assert round(counts.active_params(published) / 1e9, 2) == 1.07
    assert counts.kv_bytes_per_token(published, 2) == {"full": 3 * 2048, "window": 9 * 2048}


@pytest.mark.parametrize("start,real,rows", [(8192, 32, 32), (8192, 20, 32), (0, 8, 8), (100, 16, 16)])
def test_walked_pairs_are_counted_beside_the_pairs_of_a_prefill_call(eng, start, real, rows):
    """kubeai_engine_attn_pairs_walked_total: what the chunk kernel scores
    for a prefill call it takes, from the call's rows, start and the
    window (`ops/chunk_attention.py::pairs_walked`): whole blocks for
    every row, padded ones too, so never under the pairs inside the mask;
    and behind 8192 keys a window layer's call walks far fewer than
    rows x keys, where the library's kernel walked every one."""
    from kubeai_tpu.ops import chunk_attention

    pairs = lambda kind: eng.m_attn_pairs.value(labels={"kind": kind, "phase": "prefill"})  # noqa: E731
    walked = lambda kind: eng.m_attn_pairs_walked.value(labels={"kind": kind, "phase": "prefill"})  # noqa: E731
    before = {kind: (pairs(kind), walked(kind), *eng._chunk_pairs[kind]) for kind in ("full", "window")}
    eng._count_attn_pairs("prefill", np.asarray([start]), real, rows=rows)
    tiles = chunk_attention.kernel_tiles(rows, 2, PAGE, MAX_PAGES)
    for kind, layers, reach in (("full", 2, None), ("window", 6, WINDOW)):
        inside, scored = pairs(kind) - before[kind][0], walked(kind) - before[kind][1]
        assert scored == layers * chunk_attention.pairs_walked(rows, start, reach, *tiles, PAGE) >= inside > 0
        assert (eng._chunk_pairs[kind][0] - before[kind][2], eng._chunk_pairs[kind][1] - before[kind][3]) == (inside, scored)
    if start == 8192:
        assert walked("window") - before["window"][1] < 6 * rows * (start + rows) // 16
        assert walked("full") - before["full"][1] <= 2 * rows * (start + rows + tiles[1])
    share = eng._perf_debug_section()["chunk_kernel_hit_share"]
    assert set(share) == {"full", "window"} and all(0 < v <= 1 for v in share.values())
    # A call the flash kernel takes (rows=0) moves the pairs alone.
    scored = walked("full")
    eng._count_attn_pairs("prefill", np.asarray([0]), 8)
    assert walked("full") == scored


def test_attention_pairs_are_counted_from_start_tokens_and_window(eng):
    """kubeai_engine_attn_pairs_total against a loop over queries."""
    def brute(starts, n):
        full = sum(p + 1 for s in starts for p in range(s, s + n))
        win = sum(min(p + 1, WINDOW) for s in starts for p in range(s, s + n))
        return 2 * full, 6 * win

    value = lambda kind, phase: eng.m_attn_pairs.value(labels={"kind": kind, "phase": phase})  # noqa: E731
    for phase, starts, n in (("prefill", [0], 20), ("prefill", [64], 32), ("decode", [5, 31, 32, 200], 4), ("decode", [28], 8)):
        before = value("full", phase), value("window", phase)
        eng._count_attn_pairs(phase, np.asarray(starts), n)
        assert (value("full", phase) - before[0], value("window", phase) - before[1]) == brute(starts, n)
