"""`model_type: afmoe` (models/afmoe.py: periods of three window layers
with rope and one full layer without, each kind with a page pool of its
own; gated attention with query/key norms; a norm after each sub-block; two
leading dense layers inside the first period, then sigmoid-routed experts
beside a shared one) against the plain reference (perfbench/families/
afmoe_reference.py, which imports nothing of the program), on the CPU at a
small size: hidden 64, 8 layers (two periods), 4 query / 2 KV heads of 16,
window 32, page 8, 8 experts top-2 + 1 shared, vocab 384; seeded random
weights from the family's own plan. Contexts reach 140 tokens: four windows.

Bounds, each with its reason. The program runs in float32 here (conftest:
"highest" matmul precision), the reference too, so what separates them is
summation order: measured 2e-6 on logits whose standard deviation is 0.5.
LOGITS_ABS = 2e-5 leaves an order of magnitude; the controls (ONE of the
family's additions left out of the reference) move the logits by 1e-3 and
more and must FAIL it. Router choices: in float32 both sides' scores agree
to 1e-7, so two experts change places only where what the reference chose
from is within CHOICE_EPS = 1e-5; the logits comparison routes the reference
by the program's choices so that it does not hang on a tie.
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
from families import afmoe_counts as counts  # noqa: E402
from families import afmoe_reference as reference  # noqa: E402
from kubeai_tpu.engine.core import EngineConfig, table_width, window_pool_dims  # noqa: E402
from kubeai_tpu.engine.paging import WindowPages  # noqa: E402
from kubeai_tpu.engine.sampling import SamplingParams  # noqa: E402
from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path  # noqa: E402
from kubeai_tpu.models import afmoe, family  # noqa: E402
from kubeai_tpu.models.base import ModelConfig  # noqa: E402
from kubeai_tpu.obs.perf import param_counts  # noqa: E402

LOGITS_ABS = 2e-5
CHOICE_EPS = 1e-5
PAGE, WINDOW, CHUNK = 8, 32, 32
WIDE = 2 * CHUNK  # the engine's widest chunk call (core.wide_chunk): max_seq_len is past it
CAP = (WINDOW + WIDE) // PAGE + 1
TYPES = ["sliding_attention"] * 3 + ["full_attention"]

HF = {
    "model_type": "afmoe", "vocab_size": 384, "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "hidden_act": "silu",
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32, "num_shared_experts": 1, "num_dense_layers": 2,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826, "mup_enabled": True,
    "n_group": 1, "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
    # Longer than the depth, as a checkpoint cut in depth keeps it.
    "layer_types": TYPES * 3, "global_attn_every_n_layers": 4, "sliding_window": WINDOW,
    "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "max_position_embeddings": 512,
}
EC = EngineConfig(
    max_slots=3, max_seq_len=256, page_size=PAGE, prefill_buckets=(8, 16, CHUNK), decode_chunk=4, prefix_cache_min=16,
)
MAX_PAGES = 256 // PAGE


def checkpoint(tmp, hf, name="ckpt", seed="7"):
    hf_path = os.path.join(tmp, name + ".json")
    with open(hf_path, "w") as f:
        json.dump(hf, f)
    path = os.path.join(tmp, name)
    children.child_checkpoint(path, hf_path, seed)
    return path


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return checkpoint(str(tmp_path_factory.mktemp("afmoe")), HF)


@pytest.fixture(scope="module")
def eng(ckpt):
    return load_engine_from_path(ckpt, EC, dtype="float32", overlap=False, warmup=False)


@pytest.fixture(scope="module")
def source(ckpt):
    return SafetensorsSource(ckpt)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(0, 259, (1, 140))


def through_the_pools(eng, tokens, prefilled, chunk=CHUNK, kernel=False):
    """One row through both pools as the engine drives them: chunks of
    *chunk* behind cached tokens up to *prefilled* (the last padded to its
    bucket), then a decode step a token, the window table moved by the
    host's own manager, whose pool holds ONE slot's cap. Returns the
    logits at every position from the last prompt position on, the
    choices of every position, the most window pages held, the manager."""
    mc = eng.model_config.replace(use_paged_kernel=kernel)
    S = tokens.shape[1]
    max_pages = eng._max_pages
    table = np.zeros((1, 2 * max_pages), np.int32)
    table[0, :max_pages] = 1 + np.arange(max_pages)
    wp = WindowPages(table[:, max_pages:], WINDOW, chunk, PAGE)
    wp.admit(0, [], 0, [], max_pages)
    pools = afmoe.init_paged_cache(mc, max_pages + 1, PAGE, window_pages=wp.pool.num_pages)
    prefill = jax.jit(lambda p, tk, c, tb, start, last: afmoe.prefill_paged(p, mc, tk, c, tb, start, last, return_choices=True))
    decode = jax.jit(lambda p, tk, c, tb, at: afmoe.decode_step_paged(p, mc, tk, c, tb, at, return_choices=True))
    got, choices, held = [], [], 0
    for start in range(0, prefilled, chunk):
        n = min(chunk, prefilled - start)
        bucket = next(b for b in (8, 16, 32, 64) if b >= n)
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
    assert mc.model_type == "afmoe" and family(mc) is afmoe
    assert mc.sliding_window_layout == (1, 1, 1, 0, 1, 1, 1, 0) and mc.rope_layout == mc.sliding_window_layout
    assert (mc.n_routed_experts, mc.num_experts_per_tok, mc.moe_intermediate_size, mc.n_shared_experts) == (8, 2, 32, 1)
    assert (mc.first_k_dense_replace, mc.intermediate_size, mc.embed_scale) == (2, 128, True)
    assert (mc.routed_scaling_factor, mc.norm_topk_prob, mc.sliding_window_size) == (2.826, True, WINDOW)
    assert afmoe.period(mc) == 4 and afmoe.layer_kinds(mc) == (2, 6) and afmoe.layer_counts(mc) == (2, 6)
    assert mc.sliding_window == 0 and mc.num_experts == 0  # Gemma2's and Mixtral's keys, llama.py's: not this family's
    assert afmoe.window_pool_tokens(mc) == WINDOW and afmoe.REUSE_WHOLE_PREFILL_CALLS and not afmoe.KV_PARK

    # The same keys on another family's config.json stay ignored.
    class Cfg:
        pass

    other = Cfg()
    other.__dict__.update({**HF, "model_type": "llama"})
    assert ModelConfig.from_hf(other).sliding_window_size == 0 and not ModelConfig.from_hf(other).embed_scale


# -- (a) the step programs' calls against the reference, past three windows ---


@pytest.mark.parametrize(
    "chunk,kernel", [(CHUNK, False), (CHUNK, True), (64, False)], ids=["three_chunks_portable", "three_chunks_kernel_twin", "two_chunks_portable"],
)
def test_chunked_prefill_and_decode_through_both_pools_agree_with_the_reference(eng, source, tokens, program_run, chunk, kernel):
    """100 tokens, longer than three windows, in chunks behind cached
    tokens (of 32: four calls, three whole; of 64: two calls), then 40
    decode steps to position 139, with pages handed back behind the window
    all the way."""
    run = program_run if (chunk, kernel) == (CHUNK, False) else through_the_pools(eng, tokens, 100, chunk=chunk, kernel=kernel)
    got, choices, held, wp = run
    want = reference.forward(source.get, HF, tokens, forced=choices)
    assert np.abs(got - want["logits"][0, 99:]).max() <= LOGITS_ABS
    d = reference.choice_disagreements(choices, want["choices"], want["select"])
    assert d["compared"] == 6 * tokens.size and d["worst_gap"] <= CHOICE_EPS
    # The window budget: never more than the cap, pages handed back as
    # the row advanced, and at the end no more than a window's worth.
    assert held <= (WINDOW + chunk) // PAGE + 1
    assert wp.released == (139 - WINDOW + 1) // PAGE and wp.held(0) == WINDOW // PAGE + 1
    assert wp.pool.used() == wp.held(0)


def test_cold_group_prefill_and_chunked_prefill_agree(eng, source):
    """Two rows of one cold call (left-aligned, as long as the window)
    against the reference, and the first against the same tokens prefilled
    in two chunk calls."""
    mc = eng.model_config
    toks = np.random.default_rng(5).integers(0, 259, (2, CHUNK))
    table = np.zeros((2, 2 * MAX_PAGES), np.int32)
    table[:, :4] = table[:, MAX_PAGES : MAX_PAGES + 4] = 1 + np.arange(8).reshape(2, 4)
    pools = afmoe.init_paged_cache(mc, 9, PAGE, window_pages=9)
    pos = jnp.broadcast_to(jnp.arange(CHUNK)[None], (2, CHUNK))
    cold = jax.jit(lambda p, tk, c: afmoe.apply(p, mc, tk, pos, c, jnp.asarray(table), left_aligned=True, return_choices=True))
    lg, cache, choices = cold(eng.params, jnp.asarray(toks, jnp.int32), pools)
    want = reference.forward(source.get, HF, toks, forced=np.asarray(choices))
    assert np.abs(np.asarray(lg) - want["logits"]).max() <= LOGITS_ABS
    assert 0 < int(cache["moe_hits"]) <= 6 * HF["num_experts"]
    chunk = jax.jit(lambda p, tk, c, start: afmoe.prefill_paged(p, mc, tk, c, jnp.asarray(table[:1]), start, jnp.asarray([15])))
    chunked = pools
    for start in (0, 16):
        last, chunked = chunk(eng.params, jnp.asarray(toks[:1, start : start + 16], jnp.int32), chunked, jnp.asarray([start]))
        chunked = {k: v for k, v in chunked.items() if k.startswith("kv")}
    assert np.abs(np.asarray(last[0, 0]) - np.asarray(lg[0, -1])).max() <= LOGITS_ABS


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
    the sequence the engine produced; a window layer's pages are handed
    back past `position - window` while the full pool's grow with the
    length; every page of both pools comes back; the pairs inside the
    masks are counted by kind."""
    prompt = [1] + np.random.default_rng(n_prompt).integers(32, 127, n_prompt - 1).tolist()
    pairs = lambda kind: sum(eng.m_attn_pairs.value(labels={"kind": kind, "phase": p}) for p in ("prefill", "decode"))  # noqa: E731
    eng.start()
    try:
        full0, window0 = eng._pool.available(), eng._wpages.pool.available()
        released0, pairs0 = eng.m_window_released.value(), (pairs("full"), pairs("window"))
        req_tokens, lps, tops = generate(eng, prompt, n_new)
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
    # 2 full layers see every key before a query, 6 window layers at most 32.
    full, window = pairs("full") - pairs0[0], pairs("window") - pairs0[1]
    assert full >= 2 * total * (total - 1) // 2 and 6 * WINDOW * (total - WINDOW - 4) <= window < 6 * WINDOW * (total + 4)


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


def test_a_prefix_hit_is_used_in_whole_prefill_calls_and_gives_the_cold_runs_bits(eng):
    """120 tokens run cold as chunks at 0, 32, 64 and 96, and behind their
    cached pages as the chunk at 96 alone: the same tokens and log-probs
    to the bit."""
    prompt = [1] + np.random.default_rng(220).integers(32, 127, 119).tolist()
    eng.start()
    try:
        cold = generate(eng, prompt, 12)
        cached0 = eng.m_prefix_cached.value()
        warm = generate(eng, prompt, 12)
        assert eng.m_prefix_cached.value() - cached0 == 96
        assert cold == warm
    finally:
        eng.stop()


def test_the_two_budgets_follow_from_the_engine_config_and_the_models_layout(eng):
    mc = eng.model_config
    assert window_pool_dims(mc, EC) == (WINDOW, 3 * CAP + 1) and table_width(mc, EC) == 2 * MAX_PAGES
    P = 3 * MAX_PAGES + 1  # the full pool: --kv-pages, or its automatic value
    assert eng._cache["kv"].shape == (2 * P, PAGE, 4, 16)
    assert eng._cache["kv_window"].shape == (6 * (3 * CAP + 1), PAGE, 4, 16)
    perf = eng._perf_debug_section()
    assert perf["kv_bytes_per_token_by_kind"] == {"full": 2 * 2 * 2 * 16 * 4, "window": 6 * 2 * 2 * 16 * 4}
    assert perf["window_pool"]["slot_cap_pages"] == CAP and perf["window_pool"]["window"] == WINDOW


# -- (b) each of the family's additions is SEEN ---------------------------------


@pytest.mark.parametrize("variant", [v for v in reference.VARIANTS if v])
def test_a_reference_without_one_of_the_familys_additions_fails_the_bound(source, tokens, program_run, variant):
    got, choices, _, _ = program_run
    forced = None if variant == "no_selection_bias" else choices  # that fault IS the choice
    bad = reference.forward(source.get, HF, tokens, forced=forced, variant=variant)
    assert np.abs(got - bad["logits"][0, 99:]).max() > LOGITS_ABS * 10
    if variant == "no_selection_bias":
        d = reference.choice_disagreements(choices, bad["choices"], bad["select"])
        assert d["disagree"] > 0.05 * d["compared"] and d["worst_gap"] > 100 * CHOICE_EPS


# -- (c) one, two and three periods --------------------------------------------


@pytest.mark.parametrize("layers", [4, 12])
def test_a_stack_of_one_and_of_three_periods_builds_and_agrees(tmp_path, layers):
    """(Two periods: every test above.) One period is the unrolled body
    alone, no scan; three scan two."""
    hf = {**HF, "num_hidden_layers": layers}
    path = checkpoint(str(tmp_path), hf)
    eng = load_engine_from_path(path, EC, dtype="float32", overlap=False, warmup=False)
    try:
        toks = np.random.default_rng(layers).integers(0, 259, (1, 80))
        got, choices, _, _ = through_the_pools(eng, toks, prefilled=70)
        assert choices.shape == (layers - 2, 80, 2)
        want = reference.forward(SafetensorsSource(path).get, hf, toks, forced=choices)
        assert np.abs(got - want["logits"][0, 69:]).max() <= LOGITS_ABS
    finally:
        eng.stop()


# -- (d) loader, refusals, counts ------------------------------------------------


def test_the_loaders_tree_is_init_params_tree(eng):
    from kubeai_tpu.engine.coldstart import padded_vocab_size, param_shapes

    mc = eng.model_config
    assert mc.vocab_size == padded_vocab_size(HF["vocab_size"])
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), param_shapes(mc))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), eng.params)
    assert got == want
    assert got["moe"]["br"] == ((6, 8), "float32") and got["experts"]["we_d"] == ((6, 8, 32, 64), "float32")


@pytest.mark.parametrize(
    "change,match",
    [
        ({"n_group": 2}, "n_group"),
        ({"num_limited_groups": 2}, "num_limited_groups"),
        ({"score_func": "softmax"}, "score_func"),
        ({"rope_scaling": {"rope_type": "llama3", "factor": 8.0}}, "rope_scaling"),
        ({"num_hidden_layers": 6}, "whole periods"),
        ({"layer_types": TYPES[:3]}, "layer_types"),
        ({"global_attn_every_n_layers": 2}, "disagree"),
        ({"num_dense_layers": 5}, "num_dense_layers"),
        ({"sliding_window": 0}, "sliding_window"),
        ({"hidden_act": "gelu"}, "hidden_act"),
    ],
    ids=lambda v: v if isinstance(v, str) else "",
)
def test_what_the_config_asks_and_the_family_does_not_run_is_refused_by_name(change, match):
    class Cfg:
        pass

    cfg = Cfg()
    cfg.__dict__.update({**HF, **change})
    with pytest.raises(ValueError, match="afmoe: .*" + match):
        ModelConfig.from_hf(cfg)


def test_what_the_family_does_not_serve_is_refused_at_load(ckpt, eng):
    with pytest.raises(ValueError, match="quantization"):
        load_engine_from_path(ckpt, EC, dtype="float32", quantization="int8", overlap=False)
    with pytest.raises(ValueError, match="tensor-parallel"):
        load_engine_from_path(ckpt, EC, dtype="float32", tp=2, overlap=False)
    mc = ModelConfig.from_json_file(ckpt)
    for change, match in (
        ({"kv_cache_dtype": "fp8"}, "kv_cache_dtype"), ({"tie_word_embeddings": True}, "tied embeddings"),
        ({"sliding_window_layout": (0,) * 8}, "both full and window"), ({"first_k_dense_replace": 8}, "num_dense_layers"),
        ({"first_k_dense_replace": 4, "num_layers": 4}, "expert layer"),
    ):
        with pytest.raises(ValueError, match=match):
            afmoe.refuse_unsupported(mc.replace(**change))
    with pytest.raises(ValueError, match="LoRA"):
        afmoe.decode_step_paged(None, mc, None, None, None, None, lora={})
    with pytest.raises(ValueError, match="without the paged pool"):
        afmoe.apply(eng.params, mc, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="LoRA adapters are not supported"):
        eng.load_adapter("a", "/nonexistent")
    assert not eng._kv_enabled()  # KV_PARK: nothing is parked, restored or handed off


def test_param_counts_are_the_benchmarks_counts_and_the_published_model_is_26b():
    """obs/perf.py (kubeai_engine_mfu) against perfbench/families/
    afmoe_counts.py, at the small size, at the published widths cut to 8
    layers (11.97 GB in bf16) and at the published 32 layers (26.1B, of
    which 3.06B act on a token)."""
    with open(os.path.join(ROOT, "perfbench", "configs", "trinity-mini-bf16.json")) as f:
        cut = json.load(f)

    class Cfg:
        pass

    for hf in (HF, cut, {**cut, "num_hidden_layers": 32}):
        cfg = Cfg()
        cfg.__dict__.update(hf)
        assert param_counts(ModelConfig.from_hf(cfg)) == (counts.params_held(hf), counts.active_params(hf))
    assert counts.params_held(cut) == 5_984_817_920 and round(counts.active_params(cut) / 1e9, 2) == 1.04
    assert counts.attention_params(cut) == 27_271_424
    assert counts.attention_params(cut) + counts.expert_layer_outside_params(cut) + 128 * counts.expert_params(cut) == 839_131_520
    published = {**cut, "num_hidden_layers": 32}
    assert round(counts.params_held(published) / 1e9, 1) == 26.1 and round(counts.active_params(published) / 1e9, 2) == 3.06
    assert counts.kv_bytes_per_token(cut, 2) == {"full": 2 * 2048, "window": 6 * 2048}
    assert counts.layer_kinds(cut) == (2, 6) and counts.layer_counts(cut) == (2, 6)
