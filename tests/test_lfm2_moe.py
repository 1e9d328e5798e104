"""`model_type: lfm2_moe` (models/lfm2_moe.py: gated short convolutions whose
tail lives by slot beside the paged pool, QK-normed attention with heads of
64 in every fourth layer, two KV heads to a 128-lane row of the pool, dense
feed-forwards first and sigmoid-routed experts behind them, a tied head)
against the plain reference (perfbench/families/lfm2_moe_reference.py, which
imports nothing of the program), on the CPU at a small size: hidden 256, 10
layers `c c | a c c c | a c c c` (the published pattern's first ten), 3 taps,
4 query / 2 KV heads of 64, page 8, 8 experts top-3, vocab 384; seeded random
weights from the family's own plan, through the loader.

Bounds, each with its reason. The program runs in float32 here (conftest:
"highest" matmul precision), the reference too, so what separates them is
summation order (a grouped matmul against a loop over experts, a score over
128 lanes of which 64 are zeros against one over 64): read 4e-6 on
log-probabilities of size 6. LOGPROB_ABS = 5e-5 leaves an order of
magnitude; a fault in the mathematics (the controls) moves them by 1e-3 and
more. The comparisons go through `build_step_functions`, whose programs hand
back log-probabilities of the top N tokens: N is the whole vocabulary here,
so every logit is compared, less its row's log-sum-exp on both sides.
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
from families import lfm2_moe as family_file  # noqa: E402  (the benchmark's `logits`, which decides `correct`)
from families import lfm2_moe_counts as counts  # noqa: E402
from families import lfm2_moe_reference as reference  # noqa: E402
from kubeai_tpu.engine.core import EngineConfig, build_step_functions, init_pools, table_width  # noqa: E402
from kubeai_tpu.engine.sampling import SamplingParams  # noqa: E402
from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path  # noqa: E402
from kubeai_tpu.models import SEAM, family, lfm2_moe, llama  # noqa: E402
from kubeai_tpu.models.base import LiveRows, ModelConfig  # noqa: E402
from kubeai_tpu.obs.perf import param_counts  # noqa: E402
from kubeai_tpu.ops import paged_attention, shortconv  # noqa: E402
from kubeai_tpu.ops.attention import attention  # noqa: E402

LOGPROB_ABS = 5e-5
PAGE, CHUNK, V = 8, 32, 384
TYPES = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 10)[:40]  # the published 40 entries

HF = {
    "model_type": "lfm2_moe", "vocab_size": V, "hidden_size": 256, "intermediate_size": 96, "num_hidden_layers": 10,
    # Longer than the depth, as a checkpoint cut in depth keeps it.
    "layer_types": TYPES, "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 3, "moe_intermediate_size": 32, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "tie_word_embeddings": True,
    "max_position_embeddings": 512,
}
EC = EngineConfig(
    max_slots=4, max_seq_len=160, page_size=PAGE, prefill_buckets=(8, 16, CHUNK), decode_chunk=4,
    prefill_group_cap=2, top_logprobs_k=V, prefix_cache_min=16,
)
MAX_PAGES = 160 // PAGE


def _checkpoint(d, hf):
    hf_path = os.path.join(d, "hf.json")
    with open(hf_path, "w") as f:
        json.dump(hf, f)
    path = os.path.join(d, "ckpt")
    children.child_checkpoint(path, hf_path, "7")
    return path


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return _checkpoint(tmp_path_factory.mktemp("lfm2_moe"), HF)


@pytest.fixture(scope="module")
def eng(ckpt):
    return load_engine_from_path(ckpt, EC, dtype="float32", overlap=False, warmup=False)


@pytest.fixture(scope="module")
def source(ckpt):
    return SafetensorsSource(ckpt)


@pytest.fixture(scope="module")
def steps(eng):
    return build_step_functions(eng.model_config, EC, n_valid_vocab=V)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(0, 259, (1, 100))


def logprobs_by_id(t_ids, t_lp):
    """[..., V] log-probabilities in the vocabulary's order from a step
    program's top-N output with N = V."""
    out = np.empty(t_lp.shape, np.float32)
    np.put_along_axis(out, np.asarray(t_ids), np.asarray(t_lp), axis=-1)
    return out


def reference_logprobs(source, toks, hf=HF, **kw):
    want = reference.forward(source.get, hf, toks, **kw)
    return np.asarray(jax.nn.log_softmax(jnp.asarray(want["logits"]), axis=-1)), want


class Driver:
    """The step programs as the engine calls them, on pools of their own:
    one table row a slot, pages handed out in order."""

    def __init__(self, eng, steps):
        self.eng, self.steps, self.mc = eng, steps, eng.model_config
        self.cache = init_pools(self.mc, EC)
        B = EC.max_slots
        self.table = np.zeros((B, table_width(self.mc, EC)), np.int32)
        self.table[:] = 1 + np.arange(B * MAX_PAGES).reshape(B, MAX_PAGES)
        self.adm_toks = jnp.zeros((B,), jnp.int32)
        Kb = EC.max_logit_bias
        self.one = (np.float32(0.0), np.float32(1.0), np.int32(0), np.zeros((Kb,), np.int32), np.zeros((Kb,), np.float32))

    def chunk(self, slot, toks, start, n):
        """One chunk call: *n* real tokens padded to their bucket. Returns
        ([1, V] log-probabilities at the last real token, the greedy token)."""
        lps, toks = self.chunk_rows([slot], [toks[:n]], [start], next(b for b in EC.prefill_buckets if b >= n))
        return lps, int(toks[0])

    def chunk_rows(self, slots, pieces, starts, rows):
        """One chunk call of len(slots) slots x *rows*, slot j's piece behind
        *starts[j]* tokens of its own (engine/core.py::round_calls shares a
        call so): ([n, V] log-probabilities at each piece's last token, the
        greedy tokens)."""
        n = len(slots)
        padded = np.zeros((n, rows), np.int32)
        for j, piece in enumerate(pieces):
            padded[j, : len(piece)] = piece
        toks, _, t_ids, t_lp, self.cache, self.adm_toks, _ = self.steps.prefill_chunk_jit(
            self.eng.params, padded, np.asarray(starts, np.int32), np.asarray([len(p) - 1 for p in pieces], np.int32),
            self.table[list(slots)].copy(), np.asarray(slots, np.int32), np.zeros((n,), np.uint32),
            *(np.repeat(a[None], n, axis=0) for a in self.one), self.adm_toks, self.cache,
        )
        return logprobs_by_id(t_ids, t_lp), np.asarray(toks)

    def chunks(self, slot, toks, sizes):
        start = 0
        for n in sizes:
            out = self.chunk(slot, toks[start : start + n], start, n)
            start += n
        return out

    def cold(self, slots, rows, bucket):
        """One cold group call of len(slots) rows: ([rows, V]
        log-probabilities, the greedy tokens, the program's counters)."""
        n = len(slots)
        padded = np.zeros((n, bucket), np.int32)
        for i, r in enumerate(rows):
            padded[i, : len(r)] = r
        Kb = EC.max_logit_bias
        toks, _, t_ids, t_lp, self.cache, self.adm_toks, counters = self.steps.prefill_batch_jit(
            self.eng.params, padded, np.asarray([len(r) for r in rows], np.int32), self.table[list(slots)].copy(),
            np.asarray(slots, np.int32), np.zeros((n,), np.uint32), np.zeros((n,), np.float32), np.ones((n,), np.float32),
            np.zeros((n,), np.int32), np.zeros((n, Kb), np.int32), np.zeros((n, Kb), np.float32), self.adm_toks, self.cache,
        )
        return logprobs_by_id(t_ids, t_lp), np.asarray(toks), counters

    def decode(self, lengths, last, active):
        """One decode chunk (EC.decode_chunk greedy steps on the program's
        own choices) with the given slots live: ([K, B, V]
        log-probabilities, the tokens chosen [K, B], the lengths and last
        tokens it leaves, its counters)."""
        B, Kb = EC.max_slots, EC.max_logit_bias
        hist = jnp.zeros((B, EC.max_seq_len + EC.decode_chunk + 1), jnp.int32)
        keys = jax.random.key_data(jax.random.split(jax.random.key(0), B))
        z = lambda dt: np.zeros((B,), dt)  # noqa: E731
        corr, _, t_ids, t_lp, self.cache, _, lengths, last, _, counters = self.steps.decode_jit(
            self.eng.params, self.cache, self.table.copy(), hist, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(last, jnp.int32), keys, np.asarray(active, bool), z(np.float32), np.ones((B,), np.float32),
            z(np.int32), z(np.float32), z(np.float32), np.asarray(active, bool), z(np.int32),
            np.zeros((B, Kb), np.int32), np.zeros((B, Kb), np.float32), z(bool), z(np.int32), z(np.uint32), self.adm_toks,
        )
        return logprobs_by_id(t_ids, t_lp), np.asarray(corr), np.asarray(lengths), np.asarray(last), counters

    def tails(self):
        return np.asarray(self.cache["conv"])


def test_the_family_is_chosen_by_model_type_alone_and_states_the_seam(eng):
    mc = eng.model_config
    assert mc.model_type == "lfm2_moe" and family(mc) is lfm2_moe
    assert family(ModelConfig()) is llama
    assert all(isinstance(vars(lfm2_moe)[name], kind) for name, (kind, _) in SEAM.items())
    assert mc.layer_pattern == "ccacccaccc" and lfm2_moe.layout(mc) == (2, 4, 2)
    assert lfm2_moe.rows_of(mc) == {"c": [0, 1, 3, 4, 5, 7, 8, 9], "a": [2, 6]}
    assert (mc.conv_kernel, mc.first_k_dense_replace, mc.n_routed_experts, mc.num_experts_per_tok) == (3, 2, 8, 3)
    assert (mc.head_dim_, mc.rope_theta, mc.rms_norm_eps, mc.tie_word_embeddings) == (64, 1e6, 1e-5, True)
    assert lfm2_moe.SLOT_STATE == ("conv",) and not lfm2_moe.KV_PARK and not lfm2_moe.PREFIX_REUSE
    assert lfm2_moe.window_pool_tokens(mc) == 0 and lfm2_moe.layer_kinds is None and lfm2_moe.init_lora_bank is None
    # The state follows from --max-slots and the config: no allocator, no flag.
    cache = init_pools(mc, EC)
    assert set(cache) == {"kv", "conv"} and cache["conv"].shape == (8, 4, 2, 256)
    assert lfm2_moe.state_bytes_per_slot(mc) == cache["conv"].nbytes // 4
    # Only the attention layers own rows of the pool; two KV heads of 64 to a 128-lane row.
    assert cache["kv"].shape == (2 * (4 * MAX_PAGES + 1), PAGE, 2, 128)
    assert cache["kv"].nbytes // (cache["kv"].shape[0] // 2 * PAGE) == counts.kv_bytes_per_token(HF, 4)

    # The same keys on another family's config.json stay ignored.
    other = type("Cfg", (), {**HF, "model_type": "llama"})()
    assert ModelConfig.from_hf(other).layer_pattern == ""


# -- (a) the operator alone ----------------------------------------------------------


def _plain_short_conv(u, w_in, w_conv, w_out):
    """The operator on a whole sequence u [S, D] in float64: the sum over
    the taps on a zero-padded sequence."""
    K = w_conv.shape[0]
    B, C, x = np.split(u @ w_in, 3, axis=-1)
    z = np.concatenate([np.zeros((K - 1, u.shape[1])), B * x])
    c = sum(w_conv[k] * z[k : k + u.shape[0]] for k in range(K))
    return (C * c) @ w_out, z[-(K - 1) :]


@pytest.fixture(scope="module")
def conv_weights():
    rng = np.random.default_rng(5)
    D = 32
    return rng.normal(size=(D, 3 * D)) * D**-0.5, rng.normal(size=(3, D)) * 3**-0.5, rng.normal(size=(D, D)) * D**-0.5


@pytest.mark.parametrize(
    "sizes,bucket",
    [((1,) * 9, 1), ((2, 1, 2, 2, 2), 2), ((16, 16, 5), 16), ((7, 9, 4), 12)],
    ids=["chunks_of_one", "fewer_rows_than_taps", "many_rows", "every_chunk_ends_on_padding"],
)
def test_the_convolution_in_chunks_with_a_carried_tail_is_the_whole_sequences(conv_weights, sizes, bucket):
    """Chunks of 1, of fewer rows than taps and of many, each padded to its
    bucket: the rows are the whole sequence's and the tail left behind is
    its last K - 1 rows of `z`; padding rows neither enter the tail nor
    move it."""
    w_in, w_conv, w_out = conv_weights
    S = sum(sizes)
    u = np.random.default_rng(S).normal(size=(S, 32))
    want, want_tail = _plain_short_conv(u, w_in, w_conv, w_out)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tail, got, start = jnp.zeros((1, 2, 32), jnp.float32), [], 0
    for n in sizes:
        padded = np.full((1, bucket, 32), 1e3)  # what a padding row holds must not matter
        padded[0, :n] = u[start : start + n]
        y, tail = shortconv.gated_short_conv(f32(padded), tail, jnp.asarray([n]), f32(w_in), f32(w_conv), f32(w_out))
        got.append(np.asarray(y[0, :n]))
        start += n
    assert np.abs(np.concatenate(got) - want).max() <= 1e-5
    assert np.abs(np.asarray(tail[0]) - want_tail).max() <= 1e-5


def test_the_decode_form_and_the_s_row_form_give_the_same_rows(conv_weights):
    """`gated_short_conv_step` on layer 1 of stacked tails against
    `gated_short_conv` at S = 1 on that layer's tails: the same rows (to
    the order XLA fuses each form's sum over the taps in) and the same
    tails to the bit for the live slots; a slot that is not live keeps its
    tail, and layer 0's tails are not touched."""
    w_in, w_conv, w_out = (jnp.asarray(w, jnp.float32) for w in conv_weights)
    rng = np.random.default_rng(3)
    tails = jnp.asarray(rng.normal(size=(2, 5, 2, 32)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
    live = jnp.asarray([True, False, True, True, False])
    y, after = jax.jit(shortconv.gated_short_conv_step)(tails, jnp.int32(1), u, live, w_in, w_conv, w_out)
    want_y, want_tail = jax.jit(shortconv.gated_short_conv)(u[:, None], tails[1], live.astype(jnp.int32), w_in, w_conv, w_out)
    assert np.abs(np.asarray(y) - np.asarray(want_y[:, 0])).max() <= 1e-6
    assert np.array_equal(np.asarray(after[1]), np.asarray(want_tail))
    assert np.array_equal(np.asarray(after[1])[[1, 4]], np.asarray(tails[1])[[1, 4]])
    assert not np.array_equal(np.asarray(after[1])[[0, 2, 3]], np.asarray(tails[1])[[0, 2, 3]])
    assert np.array_equal(np.asarray(after[0]), np.asarray(tails[0]))


@pytest.mark.parametrize("h,Kv,G", [(64, 2, 2), (64, 8, 4), (32, 4, 1), (128, 2, 4)])
def test_heads_side_by_side_in_a_lane_tile_attend_as_the_narrow_heads_do(h, Kv, G):
    """`pack_kv` / `widen_queries` / `narrow_outputs`: attention over Kv / n
    heads of n * h lanes on widened queries is attention over Kv heads of h
    (the other lanes add exact zeros to a score), with the same bytes a
    token in the pool."""
    n = paged_attention.heads_a_tile(h)
    assert n == max(1, 128 // h)
    rng = np.random.default_rng(h + Kv)
    B, S, H = 2, 12, Kv * G
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, heads, h)), jnp.float32) for heads in (H, Kv, Kv))
    mask = jnp.tril(jnp.ones((S, S), bool))[None]
    want = attention(q, k, v, mask, scale=h**-0.5)
    packed = paged_attention.pack_kv(k, v, n)
    assert packed.shape == (B, S, 2 * Kv // n, n * h) and packed.size == k.size + v.size
    wide = attention(paged_attention.widen_queries(q, Kv, n), packed[:, :, 0::2], packed[:, :, 1::2], mask, scale=h**-0.5)
    got = paged_attention.narrow_outputs(wide, Kv, n)
    assert got.shape == want.shape and np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-6


# -- (b) chunked prefill carries the tail; decode goes on from it ------------------


def generated(d, slot, n, tok0, chunks=2, others=()):
    """*chunks* decode chunks of *slot* behind its *n* prefilled tokens
    (with *others* = {slot: (length, last token)} live beside it): the
    log-probabilities [steps, V] and the tokens chosen."""
    lengths, last, active = np.zeros(4, np.int32), np.zeros(4, np.int32), np.zeros(4, bool)
    lengths[slot], last[slot], active[slot] = n, tok0, True
    for o, (length, tok) in dict(others).items():
        lengths[o], last[o], active[o] = length, tok, True
    lps, toks = [], []
    for _ in range(chunks):
        lp, corr, lengths, last, _ = d.decode(lengths, last, active)
        lps.append(lp[:, slot])
        toks.append(corr[:, slot])
    return np.concatenate(lps), np.concatenate(toks)


@pytest.mark.parametrize(
    "sizes", [(32, 28), (32, 1, 2, 13), (32, 5), (1, 1, 1, 30)],
    ids=["two_chunks", "chunks_of_one_and_of_two", "short_tail", "three_single_rows_first"],
)
def test_chunked_prefill_then_decode_agrees_with_the_reference(eng, steps, source, tokens, sizes):
    """A prompt in chunk calls of many rows, of one row and of fewer rows
    than taps (every chunk behind the first STARTS from the tail the last
    one LEFT in the slot; most are padded to their bucket), then two decode
    chunks through the slot's tails and the pool on the program's own
    greedy choices, against the reference's one pass over the same tokens."""
    d = Driver(eng, steps)
    n, slot = sum(sizes), 2
    lp0, tok0 = d.chunks(slot, tokens[0], sizes)
    lps, toks = generated(d, slot, n, tok0)
    seq = np.concatenate([tokens[0, :n], [tok0], toks[:-1]])[None]
    want, ref = reference_logprobs(source, seq)
    got = np.concatenate([lp0, lps])
    assert got.shape == (9, V)
    assert np.abs(got - want[0, n - 1 :]).max() <= LOGPROB_ABS
    # ... and what the slot holds for every convolution is the reference's last two rows of z.
    assert np.abs(d.tails()[:, slot] - reference.forward(source.get, HF, seq)["tails"][:, 0]).max() <= LOGPROB_ABS
    assert ref["tails"].shape == (8, 1, 2, 256)


def test_the_paged_kernels_route_agrees_with_the_portable_one(eng, tokens):
    """`use_paged_kernel` (on the CPU the kernel's twin): the same chunk
    calls and decode steps through the widened heads give the portable
    gather's logits."""
    outs = []
    for kernel in (False, True):
        mc = eng.model_config.replace(use_paged_kernel=kernel)
        assert lfm2_moe.cached_attention_route(mc, 16, False, True) == ("paged_kernel" if kernel else "xla")
        table = jnp.asarray(1 + np.arange(MAX_PAGES)[None], jnp.int32)
        cache = lfm2_moe.init_paged_cache(mc, MAX_PAGES + 1, PAGE, slots=1)
        got = []
        for start in (0, 16, 32):
            lg, cache = lfm2_moe.prefill_paged(
                eng.params, mc, tokens[:, start : start + 16], {k: cache[k] for k in ("kv", "conv")}, table,
                jnp.asarray([start]), jnp.asarray([15]), slots=jnp.zeros((1,), jnp.int32),
            )
            got.append(np.asarray(lg))
        for pos in range(48, 52):
            lg, cache = lfm2_moe.decode_step_paged(
                eng.params, mc, tokens[:, pos : pos + 1], {k: cache[k] for k in ("kv", "conv")}, table, jnp.asarray([pos]),
                live=LiveRows.first(jnp.ones((1,), bool)),
            )
            got.append(np.asarray(lg))
        outs.append(np.concatenate(got))
    assert np.abs(outs[0] - outs[1]).max() <= LOGPROB_ABS


def test_cold_group_prefill_and_chunked_prefill_agree(eng, steps, source, tokens):
    """Two rows of one cold group call (each from zeros, padded to the
    bucket) leave what chunk calls of the same prompts leave: the same
    log-probabilities, and the same tails in their slots."""
    rows = [tokens[0, :29], tokens[0, 40:63]]
    cold = Driver(eng, steps)
    lp, toks, counters = cold.cold([1, 3], rows, CHUNK)
    chunked = Driver(eng, steps)
    for slot, row in zip((1, 3), rows):
        got, tok = chunked.chunks(slot, row, (16, len(row) - 16))
        assert np.abs(got[0] - lp[(1, 3).index(slot)]).max() <= LOGPROB_ABS and tok == toks[(1, 3).index(slot)]
    a, b = cold.tails(), chunked.tails()
    assert np.abs(a[:, [1, 3]] - b[:, [1, 3]]).max() <= LOGPROB_ABS
    assert not a[:, [0, 2]].any()  # nobody's slots: untouched
    want, _ = reference_logprobs(source, rows[0][None])
    assert np.abs(lp[0] - want[0, -1]).max() <= LOGPROB_ABS
    # The program's counter: 8 expert layers x 8 experts at most.
    assert 0 < int(counters["moe_hits"]) <= 8 * 8 and set(counters) == {"moe_hits"}


def test_a_call_of_two_slots_gives_each_what_its_own_call_gives(eng, steps, tokens):
    """Two prompts' pieces behind one read of the weights
    (engine/core.py::round_calls): slot 0's second piece, 20 tokens carried
    behind its first 32 and padded up to the call's 32 rows, beside slot 2's
    cold 25 at 0. Each row's log-probabilities, and what each slot keeps
    outside its pages, are what the slots' own calls leave; slot 1's, between
    them, stays zeros."""
    shared, apart = Driver(eng, steps), Driver(eng, steps)
    for d in (shared, apart):
        d.chunk(0, tokens[0], 0, 32)
    got, toks = shared.chunk_rows([0, 2], [tokens[0, 32:52], tokens[0, 60:85]], [32, 0], 32)
    want0, tok0 = apart.chunk(0, tokens[0, 32:52], 32, 20)
    want2, tok2 = apart.chunk(2, tokens[0, 60:85], 0, 25)
    assert toks.tolist() == [tok0, tok2]
    assert np.abs(got[0] - want0[0]).max() <= LOGPROB_ABS and np.abs(got[1] - want2[0]).max() <= LOGPROB_ABS
    for key in ("conv",):
        a, b = np.asarray(shared.cache[key]), np.asarray(apart.cache[key])
        assert np.abs(a[:, [0, 2]] - b[:, [0, 2]]).max() <= LOGPROB_ABS and a[:, [0, 2]].any() and not a[:, 1].any()


def test_a_slot_that_is_not_live_keeps_its_tail_bit_for_bit(eng, steps, tokens):
    """A decode chunk with slots 0 and 2 live and 1 and 3 idle: the idle
    slots' tails are bit for bit what they were (slot 1 holds a parked
    prompt's, slot 3 nothing), and slot 2 generates what it generates alone."""
    d = Driver(eng, steps)
    _, tok0 = d.chunks(0, tokens[0], (32, 9))
    d.chunks(1, tokens[0, 50:], (20,))
    _, tok2 = d.chunks(2, tokens[0, 10:], (32, 32, 3))
    before = d.tails()
    lps, toks = generated(d, 2, 67, tok2, others={0: (41, tok0)})
    after = d.tails()
    assert np.array_equal(after[:, [1, 3]], before[:, [1, 3]]) and before[:, 1].any() and not before[:, 3].any()
    assert not np.array_equal(after[:, [0, 2]], before[:, [0, 2]])
    alone = Driver(eng, steps)
    alone.chunks(2, tokens[0, 10:], (32, 32, 3))
    lps_alone, toks_alone = generated(alone, 2, 67, tok2)
    assert np.array_equal(toks, toks_alone) and np.abs(lps - lps_alone).max() <= LOGPROB_ABS


def test_a_slot_used_again_starts_from_a_zero_tail(eng, steps, tokens):
    """A second prompt in a slot that held another (prefilled AND decoded)
    computes what it computes in a slot nobody used: cold and chunked."""
    fresh = Driver(eng, steps)
    want_chunked, tok_c = fresh.chunks(0, tokens[0, 30:], (32, 11))
    want_cold, tok_g, _ = fresh.cold([1, 3], [tokens[0, 5:30], tokens[0, 60:70]], CHUNK)
    used = Driver(eng, steps)
    for slot in (0, 1, 3):
        _, tok = used.chunks(slot, tokens[0], (32, 20))
        generated(used, slot, 52, tok, chunks=1)
    got_chunked, tok = used.chunks(0, tokens[0, 30:], (32, 11))
    assert np.array_equal(got_chunked, want_chunked) and tok == tok_c
    got_cold, toks, _ = used.cold([1, 3], [tokens[0, 5:30], tokens[0, 60:70]], CHUNK)
    assert np.array_equal(got_cold, want_cold) and np.array_equal(toks, tok_g)


@pytest.mark.parametrize("fault", ["", "tails_one_slot_on", "two_live_rows_swapped"])
def test_the_benchmarks_logits_check_decodes_at_the_serving_slot_count_and_sees_a_row_or_a_tail_at_another_slot(
    tmp_path, monkeypatch, fault,
):
    """`perfbench/families/lfm2_moe.py::logits` (what decides `correct` on
    the chip) at `--max-slots 8`: five sessions scattered among three idle
    slots, decoded together through `LiveRows`. Sound, every part holds and
    no idle slot's noise moved; a decode form that leaves each layer's tails
    one slot further on, or hands two live slots each other's rows, fails
    the parts that name it (the reference is per session, so another
    session's row is a deviation off, not a rounding)."""
    from kubeai_tpu.engine import coldstart

    monkeypatch.setattr(coldstart, "setup_compile_cache", lambda *a, **k: None)  # a test process places no cache
    step = shortconv.gated_short_conv_step

    def faulty(tails, j, u, live, *w):
        out, tails = step(tails, j, u, live, *w)
        if fault == "tails_one_slot_on":
            tails = tails.at[j].set(jnp.roll(tails[j], 1, axis=0))
        else:
            out = out.at[jnp.asarray([1, 2])].set(out[jnp.asarray([2, 1])])  # slots 1 and 2 are live below
        return out, tails

    if fault:
        monkeypatch.setattr(shortconv, "gated_short_conv_step", faulty)
    path = _checkpoint(str(tmp_path), {**HF, "num_hidden_layers": 6})
    got = family_file.logits(path, "2147483659", {"engine_args": ["--max-slots", "8"], "page_size": PAGE, "logits_chunk": 64})
    parts = got["compared"]
    assert got["sample"]["slots"] == 8 and got["sample"]["long_slot"] == 2 and got["sample"]["cold_slots"] == [1, 4, 6, 7]
    assert parts["decode"]["rows"] == 5 * family_file.DECODE_STEPS and parts["idle_tails"]["slots"] == 3
    if not fault:
        assert got["ok"] and all(part["ok"] for part in parts.values()), parts
    elif fault == "tails_one_slot_on":
        assert not got["ok"] and not parts["tails"]["ok"] and not parts["idle_tails"]["ok"] and not parts["decode"]["ok"], parts
    else:
        assert not got["ok"] and not parts["decode"]["ok"] and parts["idle_tails"]["ok"] and parts["prefill_chunked"]["ok"], parts


def test_decode_without_live_rows_takes_every_row_as_live(eng, tokens):
    """The family's own entry point, as a caller outside the engine uses
    it: rows are slots, in order."""
    mc = eng.model_config
    cache = init_pools(mc, EC)
    table = jnp.asarray(1 + np.arange(4 * MAX_PAGES).reshape(4, MAX_PAGES), jnp.int32)
    toks = jnp.asarray(tokens[0, :4, None], jnp.int32)
    lengths = jnp.zeros((4,), jnp.int32)
    plain, c1 = lfm2_moe.decode_step_paged(eng.params, mc, toks, cache, table, lengths)
    live = LiveRows.first(jnp.ones((4,), bool))
    ordered, c2 = lfm2_moe.decode_step_paged(eng.params, mc, toks, cache, table, lengths, live=live)
    assert np.array_equal(np.asarray(plain), np.asarray(ordered))
    assert np.array_equal(np.asarray(c1["conv"]), np.asarray(c2["conv"])) and np.asarray(c1["conv"]).any()
    with pytest.raises(ValueError, match="LoRA"):
        lfm2_moe.decode_step_paged(eng.params, mc, toks, cache, table, lengths, lora=object())
    with pytest.raises(ValueError, match="without the paged pool"):
        lfm2_moe.apply(eng.params, mc, toks, lengths[:, None])


def generate(eng, prompt, n):
    """(tokens, their log-probs) of a greedy request through the scheduler."""
    req = eng.submit(prompt, SamplingParams(max_tokens=n, temperature=0.0, logprobs=5))
    toks, lps = [], []
    while True:
        ev = req.out.get(timeout=300)
        if ev[0] == "token" and ev[1] >= 0:
            toks.append(ev[1])
            lps.append(ev[3])
        elif ev[0] == "done":
            return toks, lps
        elif ev[0] == "error":
            raise RuntimeError(ev[1])


@pytest.mark.parametrize("n_prompt,n_new", [(20, 30), (75, 12)], ids=["cold_group_then_decode", "chunked_then_decode"])
def test_the_served_path_reports_the_references_log_probs(eng, source, n_prompt, n_new):
    """Through the scheduler and the three step programs, twice in a row
    so that the second request takes a slot the first one used: each
    token's log-prob against the reference on the sequence the engine
    produced, every page back in the pool, and nothing reused."""
    prompt = [1] + np.random.default_rng(n_prompt).integers(32, 127, n_prompt - 1).tolist()
    eng.start()
    try:
        pages0, cached0 = eng._pool.available(), eng.m_prefix_cached.value()
        runs = [generate(eng, prompt, n_new) for _ in range(2)]
        assert eng.m_state_used.value() == 0 and eng.m_state_total.value() == EC.max_slots
        perf = eng._perf_debug_section()
        assert perf["state_bytes_per_slot"] == 8 * 2 * 256 * 4 and perf["kv_bytes_per_token"] == counts.kv_bytes_per_token(HF, 4)
    finally:
        eng.stop()
    assert runs[0][0] == runs[1][0] and len(runs[0][0]) == n_new
    toks, lps = runs[1]
    want, _ = reference_logprobs(source, np.asarray(prompt + toks)[None])
    for i, (tok, lp) in enumerate(zip(toks, lps)):
        row = want[0, n_prompt - 1 + i].astype(np.float64)
        row = row - np.log(np.exp(row[:259] - row[:259].max()).sum()) - row[:259].max()  # over the ids the tokenizer emits
        assert abs(lp - row[tok]) <= 5 * LOGPROB_ABS, i
        assert tok == int(row[:259].argmax())
    assert eng._pool.available() == pages0
    assert eng.m_prefix_cached.value() == cached0  # the same prompt twice: no hit


# -- (c) each part of the mathematics left out fails the bound --------------------


@pytest.fixture(scope="module")
def program_run(eng, steps, tokens):
    """Two chunk calls and two decode chunks, once for the comparison and
    its controls: (log-probabilities from the last prompt position on, the
    sequence they are of)."""
    d = Driver(eng, steps)
    lp0, tok0 = d.chunks(2, tokens[0], (32, 28))
    lps, toks = generated(d, 2, 60, tok0)
    return np.concatenate([lp0, lps]), np.concatenate([tokens[0, :60], [tok0], toks[:-1]])[None]


@pytest.mark.parametrize("variant", [v for v in reference.VARIANTS if v])
def test_a_reference_with_one_part_of_the_mathematics_left_out_fails_the_bound(source, program_run, variant):
    """A `B * x` rounded through bfloat16, a gate left out, taps that read
    one row further back or in the other order, the q/k norms dropped or
    applied behind the rotation, the selection bias left in the weights or
    out of the choice: each moves the log-probabilities past ten bounds."""
    got, seq = program_run
    bad, ref = reference_logprobs(source, seq, variant=variant)
    assert np.abs(got - bad[0, 59:]).max() > LOGPROB_ABS * 10
    if variant == "no_selection_bias":
        sound = reference.forward(source.get, HF, seq)
        d = reference.choice_disagreements(sound["choices"], ref["choices"], ref["select"])
        assert d["disagree"] > 0.05 * d["compared"]


# -- (d) one, two and three periods ---------------------------------------------


@pytest.mark.parametrize("layers,dense", [(6, 2), (14, 2), (8, 0), (5, 1)], ids=["one_period", "three_periods", "no_dense_layer", "a_period_of_two"])
def test_a_stack_of_other_depths_builds_and_agrees(tmp_path, layers, dense):
    """(Two periods behind two dense layers: every test above.) One period
    scans once, three thrice; no leading dense layer: the periods start at
    layer 0 (`c c a c`); one dense layer and five layers: `c | c a | c a`."""
    hf = {**HF, "num_hidden_layers": layers, "num_dense_layers": dense}
    if layers == 5:
        hf["layer_types"] = ["conv", "conv", "full_attention", "conv", "full_attention"]
    path = _checkpoint(str(tmp_path), hf)
    eng = load_engine_from_path(path, EC, dtype="float32", overlap=False, warmup=False)
    try:
        mc = eng.model_config
        assert lfm2_moe.layout(mc) == {6: (2, 4, 1), 14: (2, 4, 3), 8: (0, 4, 2), 5: (1, 2, 2)}[layers]
        d = Driver(eng, build_step_functions(mc, EC, n_valid_vocab=V))
        toks = np.random.default_rng(layers).integers(0, 259, 70)
        lp0, tok0 = d.chunks(1, toks, (32, 32, 6))
        lps, chosen = generated(d, 1, 70, tok0, chunks=1)
        seq = np.concatenate([toks, [tok0], chosen[:-1]])[None]
        want, _ = reference_logprobs(SafetensorsSource(path), seq, hf=hf)
        assert np.abs(np.concatenate([lp0, lps]) - want[0, 69:]).max() <= LOGPROB_ABS
    finally:
        eng.stop()


# -- (e) loader, refusals, counts ------------------------------------------------


def test_the_loaders_tree_is_init_params_tree(eng):
    from kubeai_tpu.engine.coldstart import padded_vocab_size, param_shapes

    mc = eng.model_config
    assert mc.vocab_size == padded_vocab_size(HF["vocab_size"])
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), param_shapes(mc))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), eng.params)
    assert got == want and "lm_head" not in got
    assert got["moe"]["br"] == ((8, 8), "float32") and got["experts"]["we_d"] == ((8, 8, 32, 256), "float32")
    assert got["conv"]["conv_w"] == ((8, 3, 256), "float32") and got["attn"]["q_norm"] == ((2, 64), "float32")
    assert got["dense"]["wg"] == ((2, 256, 96), "float32")


@pytest.mark.parametrize(
    "change,match",
    [
        ({"conv_bias": True}, "conv_bias"),
        ({"layer_types": ["conv", "sliding_attention"] * 5}, "layer_types"),
        ({"layer_types": ["conv"] * 4}, "each of the 10"),
        ({"use_expert_bias": False}, "use_expert_bias"),
        ({"conv_L_cache": 1}, "conv_L_cache"),
        ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "rope_type"),
    ],
)
def test_what_the_config_asks_and_the_family_does_not_run_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match="lfm2_moe: .*" + match):
        ModelConfig.from_hf(type("Cfg", (), {**HF, **change})())


@pytest.mark.parametrize(
    "kw,match",
    [
        (dict(quantization="int8"), "quantization"),
        (dict(tp=4), "tensor-parallel"),
        (dict(replace=dict(kv_cache_dtype="fp8")), "kv_cache_dtype"),
        (dict(replace=dict(tie_word_embeddings=False)), "untied"),
        (dict(replace=dict(layer_pattern="cccccccccc")), "both a conv and a full_attention"),
        (dict(replace=dict(first_k_dense_replace=10)), "without an expert layer"),
        (dict(replace=dict(num_kv_heads=1, num_heads=4, head_dim=64)), "128-lane rows"),
    ],
)
def test_what_the_family_does_not_serve_is_refused_at_load(eng, kw, match):
    kw = dict(kw)
    mc = eng.model_config.replace(**kw.pop("replace", {}))
    with pytest.raises(ValueError, match="lfm2_moe: .*" + match):
        lfm2_moe.refuse_unsupported(mc, **kw)


PUBLISHED = os.path.join(ROOT, "perfbench", "configs", "lfm2-24b-a2b-bf16.json")


def _published(**over):
    with open(PUBLISHED) as f:
        cfg = json.load(f)
    return {**{k: v for k, v in cfg.items() if k not in ("source", "reduced", "assumed", "serving", "rehearsal")}, **over}


@pytest.mark.parametrize("which", ["toy", "published_cut", "published_whole"])
def test_param_counts_are_the_benchmarks_counts(eng, which):
    hf = {"toy": HF, "published_cut": _published(), "published_whole": _published(num_hidden_layers=40)}[which]
    mc = ModelConfig.from_hf(type("Cfg", (), hf)())
    total, active = param_counts(mc)
    assert total == counts.params_held(hf) and active == counts.active_params(hf)
    assert counts.state_bytes_per_slot(hf, jnp.dtype(mc.dtype).itemsize) == lfm2_moe.state_bytes_per_slot(mc)
    if which == "toy":
        assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(eng.params)) == total


def test_the_published_model_is_24b_with_2b_active_and_the_cut_is_what_the_issue_reckoned():
    """ISSUE 51's bytes, from the family's own counts: 23.84G parameters
    of which a token is multiplied by 2.33G; the cut of 10 layers 5.27G =
    10.53 GB in bf16, 4 KiB of keys and values a token, 64 KiB of tails a
    slot."""
    whole, cut = _published(num_hidden_layers=40), _published()
    assert counts.kinds(whole) == {"conv": 30, "attn": 10} and counts.kinds(cut) == {"conv": 8, "attn": 2}
    assert counts.layer_counts(cut) == (2, 8)
    assert abs(counts.params_held(whole) / 1e9 - 23.84) < 0.01 and abs(counts.active_params(whole) / 1e9 - 2.33) < 0.01
    assert counts.conv_params(cut) == 16_785_408 and counts.attention_params(cut) == 10_487_936
    assert counts.expert_params(cut) == 9_437_184 and counts.dense_ffn_params(cut) == 72_353_792
    assert abs(counts.params_held(cut) * 2 / 1e9 - 10.53) < 0.01 and abs(counts.active_params(cut) / 1e9 - 0.737) < 0.001
    assert counts.kv_bytes_per_token(cut, 2) == 4096 and counts.state_bytes_per_slot(cut, 2) == 65536
    assert counts.attention_flops_per_pair(cut) == 8192 and counts.conv_projection_flops_per_token(cut) == 33_554_432
