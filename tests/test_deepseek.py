"""`model_type: deepseek_v3` (models/deepseek.py: latent attention over
latent pages, sigmoid-routed experts behind a leading dense layer) against
the plain reference (perfbench/families/deepseek_v3_reference.py, which
imports nothing of the program), on the CPU at a small size: hidden 64, 4
heads, nope/rope/v 16/8/16, latent 32, 8 experts top-2 + 1 shared, 3
layers with `first_k_dense_replace` 1, vocab 384; seeded random weights
from the family's own plan, the router's bias not zero.

Bounds, each with its reason. The program runs in float32 here (conftest:
"highest" matmul precision), the reference too, so what separates them is
summation order: measured 8e-7 on logits whose standard deviation is 0.16.
LOGITS_ABS = 2e-5 leaves 25 times that; the controls (a fault in the
reference's mathematics, or the reference in bfloat16) move the logits by
2e-3 to 1e-1 and must FAIL it. Router choices: in float32 both sides'
selection scores agree to 1e-6, so two experts can change places only
where the reference's own scores are within CHOICE_EPS = 1e-4 of each
other; the logits comparison routes the reference by the program's choices
so that it does not hang on such a tie.
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
from families import deepseek_v3_reference as reference  # noqa: E402
from kubeai_tpu.engine import kvstate  # noqa: E402
from kubeai_tpu.engine.core import EngineConfig  # noqa: E402
from kubeai_tpu.engine.sampling import SamplingParams  # noqa: E402
from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path  # noqa: E402
from kubeai_tpu.models import deepseek, family, llama  # noqa: E402
from kubeai_tpu.models.base import ModelConfig  # noqa: E402
from kubeai_tpu.ops import moe  # noqa: E402

LOGITS_ABS = 2e-5
CHOICE_EPS = 1e-4
PAGE = 16

HF = {
    "model_type": "deepseek_v3", "vocab_size": 384, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": None,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "rope_interleave": True, "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "max_position_embeddings": 4096, "hidden_act": "silu",
}
EC = EngineConfig(
    max_slots=4, max_seq_len=256, page_size=PAGE, prefill_buckets=(16, 32, 64), decode_chunk=2,
    prefix_cache_min=16,
)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    d = tmp_path_factory.mktemp("deepseek")
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
    return np.random.default_rng(11).integers(0, 259, (2, 48))


def tables(B, pages=8):
    return jnp.asarray(1 + np.arange(B * pages).reshape(B, pages), jnp.int32)


def program_logits(eng, tokens):
    """Every position's logits and the router's choices, through the
    paged pool (cold prefill of the whole sequence)."""
    mc = eng.model_config
    B, S = tokens.shape
    pool = deepseek.init_paged_cache(mc, B * 8 + 1, PAGE)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    lg, cache, choices = deepseek.apply(
        eng.params, mc, jnp.asarray(tokens, jnp.int32), pos, pool, tables(B), left_aligned=True, return_choices=True,
    )
    return np.asarray(lg), np.asarray(choices), cache


def test_the_family_is_chosen_by_model_type_alone(eng):
    assert eng.model_config.model_type == "deepseek_v3"
    assert family(eng.model_config) is deepseek
    assert family(ModelConfig()) is llama
    # The same keys on another family's config.json stay ignored.
    class Cfg:
        pass

    other = Cfg()
    other.__dict__.update({**HF, "model_type": "llama"})
    assert ModelConfig.from_hf(other).kv_lora_rank == 0


# -- (a) the three step programs against the reference ------------------------


def test_cold_prefill_agrees_with_the_reference_in_logits(eng, source, tokens):
    got, choices, cache = program_logits(eng, tokens)
    want = reference.forward(source.get, HF, tokens, forced=choices)
    assert np.abs(got - want["logits"]).max() <= LOGITS_ABS
    # (the second part) the program's choices against the reference's FREE
    # choices: any disagreement is a near-tie in the reference's own scores.
    d = reference.choice_disagreements(choices, want["choices"], want["select"])
    assert d["compared"] == 2 * tokens.size and d["worst_gap"] <= CHOICE_EPS
    assert 0 < int(cache["moe_hits"]) <= 2 * HF["n_routed_experts"]


def test_chunked_prefill_and_decode_through_the_pool_agree_with_the_reference(eng, source, tokens):
    mc = eng.model_config
    B, S = tokens.shape
    want = reference.forward(source.get, HF, tokens)["logits"]
    t = jnp.asarray(tokens, jnp.int32)
    pool = deepseek.init_paged_cache(mc, B * 8 + 1, PAGE)
    half = S // 2
    zeros, last = jnp.zeros((B,), jnp.int32), jnp.full((B,), half - 1, jnp.int32)
    _, pool = deepseek.prefill_paged(eng.params, mc, t[:, :half], pool, tables(B), zeros, last)
    # The second chunk stops one short, so that a decode step follows it.
    lg, pool = deepseek.prefill_paged(
        eng.params, mc, t[:, half : S - 1], pool, tables(B), zeros + half, jnp.full((B,), S - half - 2, jnp.int32),
    )
    assert np.abs(np.asarray(lg[:, 0]) - want[:, S - 2]).max() <= LOGITS_ABS
    step, pool = deepseek.decode_step_paged(
        eng.params, mc, t[:, S - 1 :], {"kv": pool["kv"]}, tables(B), jnp.full((B,), S - 1, jnp.int32),
    )
    assert np.abs(np.asarray(step[:, 0]) - want[:, S - 1]).max() <= LOGITS_ABS


def test_the_engines_step_functions_report_the_references_log_probs(eng, source, tokens):
    """Through `build_step_functions`' programs: the cold prefill's top-5
    log-probs at the last position, then a decode chunk's chosen tokens."""
    sf = eng._step_fns
    B, S = 1, 48
    row = tokens[:1]
    # The engine masks what the byte tokenizer cannot emit (ids past 258).
    want = reference.forward(source.get, HF, row)["logits"][0, S - 1, :259]
    want_lp = want - np.log(np.exp(want - want.max()).sum()) - want.max()
    padded = np.zeros((1, 64), np.int32)
    padded[0, :S] = row[0]
    Kb = eng.cfg.max_logit_bias
    table = np.zeros((1, eng._max_pages), np.int32)
    table[0, :8] = 1 + np.arange(8)
    cache = {"kv": jnp.zeros_like(eng._cache["kv"])}
    toks, lps, t_ids, t_lp, cache, _adm, counters = sf.prefill_batch_jit(
        eng.params, padded, np.array([S], np.int32), table, np.zeros((1,), np.int32), np.zeros((1,), np.uint32),
        np.zeros((1,), np.float32), np.ones((1,), np.float32), np.zeros((1,), np.int32),
        np.zeros((1, Kb), np.int32), np.zeros((1, Kb), np.float32), jnp.zeros((4,), jnp.int32), cache,
    )
    assert set(cache) == {"kv"} and set(counters) == {"moe_hits"}
    assert int(toks[0]) == int(want.argmax())
    ids = np.asarray(t_ids[0])
    assert np.abs(np.asarray(t_lp[0]) - want_lp[ids]).max() <= LOGITS_ABS * 5


# -- (b) absorbed against expanded attention ----------------------------------


def test_absorbed_attention_is_the_expanded_one(eng, source):
    """One layer's attention: the program's absorbed form over latents
    against keys and values expanded through W_kvb."""
    rng = np.random.default_rng(3)
    H, dn, dr, dv, r = 4, 16, 8, 16, 32
    B, S, W = 2, 24, 128
    q_nope, q_rope = rng.normal(size=(B, S, H, dn)), rng.normal(size=(B, S, H, dr))
    c, k_rope = rng.normal(size=(B, S, r)), rng.normal(size=(B, S, dr))
    wuk, wuv = rng.normal(size=(H, dn, r)), rng.normal(size=(H, r, dv))
    scale = (dn + dr) ** -0.5
    k = np.concatenate([np.einsum("bsr,hnr->bshn", c, wuk), np.broadcast_to(k_rope[:, :, None], (B, S, H, dr))], -1)
    v = np.einsum("bsr,hrv->bshv", c, wuv)
    s = np.einsum("bqhd,bkhd->bhqk", np.concatenate([q_nope, q_rope], -1), k) * scale
    s = np.where(np.tril(np.ones((S, S), bool))[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)
    from kubeai_tpu.ops.mla_attention import latent_attention_paged

    pad = lambda a: np.concatenate([a, np.zeros((*a.shape[:-1], W - r - dr))], -1)  # noqa: E731
    q_lat = pad(np.concatenate([np.einsum("bshn,hnr->bshr", q_nope, wuk), q_rope], -1))
    lat = pad(np.concatenate([c, k_rope], -1))
    # The latents as pages of 8 tokens, scattered over a pool (row 0 unused).
    page, max_pages = 8, 4  # a table wider than the 3 pages a row fills
    table = 1 + rng.permutation(B * max_pages).reshape(B, max_pages)
    pool = np.zeros((1 + B * max_pages, page, W))
    for b in range(B):
        for j in range(S // page):
            pool[table[b, j]] = lat[b, j * page : (j + 1) * page]
    positions = np.broadcast_to(np.arange(S), (B, S))
    o_lat = latent_attention_paged(
        jnp.asarray(q_lat, jnp.float32), jnp.asarray(pool, jnp.float32), jnp.asarray(table, jnp.int32),
        jnp.asarray(positions, jnp.int32), scale=scale, rank=r,
    )
    got = np.einsum("bshr,hrv->bshv", np.asarray(o_lat, np.float64), wuv)
    assert np.abs(got - want).max() <= 1e-4  # float32 sums of 24 terms of size ~10


# -- (c) the expert layer against the loop over experts -----------------------


def loop_over_experts(x, idx, w, wg, wu, wd):
    y = np.zeros_like(x)
    for e in range(wg.shape[0]):
        gate = (w * (idx == e)).sum(-1)
        h = x @ wg[e]
        y += gate[:, None] * (((h / (1 + np.exp(-h))) * (x @ wu[e])) @ wd[e])
    return y


@pytest.mark.parametrize("load", ["uneven", "one_expert_empty", "all_on_one_expert"])
def test_routed_experts_against_the_loop_over_experts(load):
    rng = np.random.default_rng(5)
    T, D, F, E, k = 24, 16, 8, 6, 2
    x = rng.normal(size=(T, D)).astype(np.float32)
    wg, wu = rng.normal(size=(2, E, D, F)).astype(np.float32) * 0.3
    wd = rng.normal(size=(E, F, D)).astype(np.float32) * 0.3
    w = rng.uniform(0.1, 1.0, size=(T, k)).astype(np.float32)
    if load == "uneven":
        idx = np.stack([rng.choice(E, k, replace=False, p=[0.5, 0.2, 0.1, 0.1, 0.05, 0.05]) for _ in range(T)])
    elif load == "one_expert_empty":
        idx = np.stack([rng.choice(E - 1, k, replace=False) for _ in range(T)])  # nobody takes the last
    else:
        idx = np.stack([np.array([2, int(rng.integers(3, 6))]) for _ in range(T)])  # every token on expert 2
    y, hit = moe.routed_experts(jnp.asarray(x), jnp.asarray(idx, jnp.int32), jnp.asarray(w), jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd))
    assert np.abs(np.asarray(y) - loop_over_experts(x, idx, w, wg, wu, wd)).max() <= 1e-5
    assert int(hit) == len(set(idx.reshape(-1).tolist()))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_a_layer_of_the_experts_stack_is_read_in_place(layer):
    """The scan over expert layers hands the grouped matmul the whole
    stack [L, E, ...] and the layer's index: the result is the layer's
    own, whatever the other layers hold."""
    rng = np.random.default_rng(7)
    T, D, F, E, k, L = 10, 16, 8, 4, 2, 3
    x = rng.normal(size=(T, D)).astype(np.float32)
    wg, wu = rng.normal(size=(2, L, E, D, F)).astype(np.float32) * 0.3
    wd = rng.normal(size=(L, E, F, D)).astype(np.float32) * 0.3
    w = rng.uniform(0.1, 1.0, size=(T, k)).astype(np.float32)
    idx = np.stack([rng.choice(E - 1, k, replace=False) for _ in range(T)])
    y, hit = jax.jit(moe.routed_experts)(
        jnp.asarray(x), jnp.asarray(idx, jnp.int32), jnp.asarray(w), jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd),
        layer=jnp.int32(layer),
    )
    assert np.abs(np.asarray(y) - loop_over_experts(x, idx, w, wg[layer], wu[layer], wd[layer])).max() <= 1e-5
    assert int(hit) == len(set(idx.reshape(-1).tolist()))


# The grouped matmul's call shapes at kanana-2's widths (hidden 2048, expert
# width 768, top-6) as the engine compiles them for the benchmark's cell
# (64 slots; `prefill_buckets` x (1, `prefill_group_cap`) rows) and for the
# 128-slot run of PERF.md: rows = 6 x tokens in the call.
GMM_CALLS = [("decode_64_slots", 6 * 64), ("decode_128_slots", 6 * 128)] + [
    (f"prefill_{rows}x{bucket}", 6 * rows * bucket) for rows in (1, 8) for bucket in (32, 64, 128, 256, 512, 1024)
]
SCOPED_VMEM = 16 << 20  # what Mosaic gives a kernel on a v5e unless it is asked for more


@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)], ids=["gate_up", "down"])
@pytest.mark.parametrize("call,m", GMM_CALLS, ids=[c for c, _ in GMM_CALLS])
def test_the_grouped_matmuls_row_tile_comes_from_the_calls_rows(call, m, k, n):
    """The tile divides the rows (the kernel refuses another), is whole
    bf16 sublane pairs, keeps the expert's matrix in one tile (one DMA an
    expert) and fits the scoped VMEM: two buffers each of the lhs, rhs and
    out blocks in bf16 and the float32 accumulator. The decode shapes get
    what the chip read fastest in the step's own form (PERF.md section 6,
    PR 35)."""
    tm, tk, tn = moe.gmm_tiles(m, k, n)
    assert (tk, tn) == (k, n)
    assert m % tm == 0 and tm % 16 == 0
    assert 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn <= SCOPED_VMEM
    if call.startswith("decode"):
        assert tm == 192


@pytest.mark.parametrize("m,tm", [(6 * 10, 60), (6 * 3, 18), (6 * 100, 120), (6 * 48, 144)])
def test_any_number_of_slots_gets_a_row_tile_mosaic_takes(m, tm):
    """A block of whole 8-row sublanes that divides the rows (100 slots:
    120, not a multiple of 16; 48 slots: 144), and where no such block
    divides them (10 slots, 3 slots) the whole array, always a legal one."""
    assert moe.gmm_tiles(m, 2048, 768)[0] == tm


def test_the_kernel_at_the_chosen_tile_is_ragged_dot():
    """megablox in interpret mode at the tile `gmm_tiles` gives 576 rows
    (three tiles of 192), against `jax.lax.ragged_dot`: the stack of three
    layers with only the middle one's groups filled (empty groups on both
    sides), a group that ends inside a tile, one that straddles a tile's
    edge, an empty one, one that ends on an edge and one that starts there."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rng = np.random.default_rng(11)
    m, k, n, E, L = 576, 128, 128, 5, 3
    tiles = moe.gmm_tiles(m, k, n)
    assert tiles == (192, k, n)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(L * E, k, n)) * k**-0.5, jnp.float32)
    sizes = np.zeros((L * E,), np.int32)
    sizes[E : 2 * E] = [100, 156, 0, 128, 192]
    got = gmm(lhs, rhs, jnp.asarray(sizes), preferred_element_type=jnp.float32, tiling=tiles, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5)


def test_the_router_is_sigmoid_with_the_bias_in_the_choice_only():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(5, 8)), jnp.float32)
    wr = jnp.asarray(np.random.default_rng(2).normal(size=(8, 6)), jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.0, 10.0])
    idx, w = moe.route_sigmoid(x, wr, bias, 2, True, 2.448)
    assert (np.asarray(idx) == 5).any(axis=1).all()  # the bias decides the choice
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(wr))))
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    assert np.allclose(np.asarray(w), chosen / chosen.sum(-1, keepdims=True) * 2.448, atol=1e-6)  # and not the weight


# -- (d), (e): the prefix cache and park/restore on latent pages --------------


def generate(eng, prompt, n=6):
    """(tokens, their log-probs) of a greedy request."""
    req = eng.submit(prompt, SamplingParams(max_tokens=n, temperature=0.0, logprobs=True))
    toks, lps = [], []
    while True:
        ev = req.out.get(timeout=120)
        if ev[0] == "token" and ev[1] >= 0:
            toks.append(ev[1])
            lps.append(ev[3])
        elif ev[0] == "done":
            return toks, lps
        elif ev[0] == "error":
            raise RuntimeError(ev[1])


@pytest.mark.parametrize(
    "n,reused", [(150, 128), (51, 0), (220, 192), (100, 64)],
    ids=["one_wide_call", "shorter_than_a_call", "a_wide_call_and_one_of_the_largest_bucket", "under_the_wide_width"],
)
def test_a_prefix_hit_on_latent_pages_gives_the_cold_runs_bits(eng, n, reused):
    """A hit is used in whole prefill calls (the wide chunk is 128 rows
    here, the largest bucket 64): 150 tokens run cold as a wide call and
    the chunk at 128, and behind their 9 cached pages as the chunk at 128
    alone, the same call on the same pages; 220 tokens run cold as calls
    at 0 (128 rows), 128 (64) and 192, and behind 13 cached pages as the
    last alone; 100 tokens never see the wide call and reuse their first
    64 as they always did; 51 tokens' 3 cached pages are less than a call
    and the prompt runs as it did cold. Either way the same tokens and the same
    log-probs to the bit, which is what a router needs
    (models/deepseek.py)."""
    assert deepseek.REUSE_WHOLE_PREFILL_CALLS and not llama.REUSE_WHOLE_PREFILL_CALLS
    rng = np.random.default_rng(n)
    prompt = [1] + rng.integers(32, 127, n - 1).tolist()
    eng.start()
    try:
        cold = generate(eng, prompt)
        cached0, pages0 = eng.m_prefix_cached.value(), eng._pool.available()
        warm = generate(eng, prompt)
        assert eng.m_prefix_cached.value() - cached0 == reused
        assert cold == warm
        assert eng._pool.available() == pages0  # the pages of the hit that were not used were given back
    finally:
        eng.stop()


def test_a_latent_page_round_trips_through_the_wire_format(eng):
    mc = eng.model_config
    L, W = mc.num_layers, deepseek.page_width(mc)
    assert eng._cache["kv"].shape[1:] == (PAGE, W)
    payload = np.random.default_rng(2).normal(size=(3, L, PAGE, W)).astype(np.float32)
    fp = kvstate.model_fingerprint(mc, PAGE)
    assert fp != kvstate.model_fingerprint(mc.replace(kv_lora_rank=16), PAGE)
    blob = kvstate.encode_state(
        model_fp=fp, request_fp="r", history=list(range(40)), pending=7, prompt_len=30, generated=10,
        committed_text="x", delivered_chars=1, key_data=np.zeros((2,), np.uint32), events=[], adapter=None,
        payload=payload,
    )
    state = kvstate.decode_state(blob, expect_model_fp=fp)
    assert state.payload.shape == payload.shape and np.array_equal(state.payload, payload)
    # Into the pool and out again, as park/restore moves pages.
    idx = jnp.asarray([5, 6, 7], jnp.int32)
    pool = eng._cache["kv"].at[idx].set(jnp.asarray(payload[:, 0]))
    assert np.array_equal(np.asarray(pool[idx]), payload[:, 0])


# -- (f) the loader's tree ----------------------------------------------------


def test_the_loaders_tree_is_init_params_tree(eng, source):
    from kubeai_tpu.engine.coldstart import padded_vocab_size, param_shapes

    mc = eng.model_config
    assert mc.vocab_size == padded_vocab_size(HF["vocab_size"])
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), param_shapes(mc))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), eng.params)
    assert got == want
    # The whole-checkpoint path builds the same arrays as the streamed one.
    sd = {name: source.get(name) for name in source.names()}
    whole = deepseek.params_from_hf(sd, mc.replace(vocab_size=HF["vocab_size"]))
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(whole), jax.tree_util.tree_leaves_with_path(eng.params)
    ):
        assert pa == pb
        if "embed" not in str(pa) and "lm_head" not in str(pa):
            assert np.array_equal(np.asarray(a), np.asarray(b)), pa


def test_what_the_family_does_not_run_is_refused_at_load(ckpt):
    with pytest.raises(ValueError, match="quantization"):
        load_engine_from_path(ckpt, EC, dtype="float32", quantization="int8", overlap=False)
    with pytest.raises(ValueError, match="tensor-parallel"):
        load_engine_from_path(ckpt, EC, dtype="float32", tp=2, overlap=False)
    mc = ModelConfig.from_json_file(ckpt)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        deepseek.refuse_unsupported(mc.replace(kv_cache_dtype="fp8"))
    with pytest.raises(ValueError, match="LoRA"):
        deepseek.decode_step_paged(None, mc, None, None, None, None, lora={})


def test_lora_is_refused_by_the_engine(eng):
    with pytest.raises(ValueError, match="LoRA adapters are not supported"):
        eng.load_adapter("a", "/nonexistent")


# -- (g) controls: each must FAIL the bound the program passes ----------------


@pytest.mark.parametrize(
    "control",
    [{"dtype": "bfloat16"}, {"variant": "softmax_scoring"}, {"variant": "bias_in_weights"},
     {"variant": "no_scaling"}, {"variant": "rope_halves"}],
    ids=lambda c: next(iter(c.values())),
)
def test_a_faulty_reference_fails_the_bound(eng, source, tokens, control):
    got, choices, _ = program_logits(eng, tokens)
    bad = reference.forward(source.get, HF, tokens, forced=choices, **control)["logits"]
    assert np.abs(got - bad).max() > LOGITS_ABS * 10
