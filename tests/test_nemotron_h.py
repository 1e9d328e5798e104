"""`model_type: nemotron_h` (models/nemotron_h.py: Mamba-2 mixers whose
state lives by slot beside the paged pool, attention without rope, experts
in a latent space of which a chip may hold a share) against the plain
reference (perfbench/families/nemotron_h_reference.py, which imports
nothing of the program), on the CPU at a small size: hidden 64, 11 blocks
`MEMEMEM*EME` (the published pattern's first eleven), 8 mixer heads of 8 in
2 groups, state 16, chunk 8, 4 query / 2 KV heads of 16, page 8, 8 experts
top-3 in a latent space of 32, vocab 384; seeded random weights from the
family's own plan, through the loader.

Bounds, each with its reason. The program runs in float32 here (conftest:
"highest" matmul precision), the reference too, so what separates them is
summation order (the chunked form against the recurrence, a grouped matmul
against a loop over experts): read 3e-6 on log-probabilities of size 6.
LOGPROB_ABS = 5e-5 leaves an order of magnitude; a fault in the mathematics
(the controls) moves them by 1e-3 and more. The comparisons go through
`build_step_functions`, whose programs hand back log-probabilities of the
top N tokens: N is the whole vocabulary here, so every logit is compared,
less its row's log-sum-exp on both sides.
"""

import dataclasses
import functools
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
from families import nemotron_h_counts as counts  # noqa: E402
from families import nemotron_h_reference as reference  # noqa: E402
from kubeai_tpu.engine.core import EngineConfig, build_step_functions, init_pools, table_width  # noqa: E402
from kubeai_tpu.engine.sampling import SamplingParams  # noqa: E402
from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path  # noqa: E402
from kubeai_tpu.models import family, llama, nemotron_h  # noqa: E402
from kubeai_tpu.models.base import LiveRows, ModelConfig  # noqa: E402
from kubeai_tpu.obs.perf import param_counts  # noqa: E402
from kubeai_tpu.ops import ssm  # noqa: E402

LOGPROB_ABS = 5e-5
PAGE, CHUNK, V = 8, 32, 384
PATTERN = "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"

HF = {
    "model_type": "nemotron_h", "vocab_size": V, "hidden_size": 64, "num_hidden_layers": 11,
    # Longer than the depth, as a checkpoint cut in depth keeps it.
    "hybrid_override_pattern": PATTERN, "mtp_hybrid_override_pattern": "*E", "num_nextn_predict_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "attention_bias": False,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "expand": 1, "mamba_hidden_act": "silu", "mamba_proj_bias": False, "use_conv_bias": True, "use_bias": False,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "moe_intermediate_size": 32, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1, "mlp_hidden_act": "relu2", "mlp_bias": False,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-05, "rope_theta": 10000, "partial_rotary_factor": 1, "sliding_window": None,
    "residual_in_fp32": False, "tie_word_embeddings": False, "max_position_embeddings": 512,
}
EC = EngineConfig(
    max_slots=4, max_seq_len=160, page_size=PAGE, prefill_buckets=(8, 16, CHUNK), decode_chunk=4,
    prefill_group_cap=2, top_logprobs_k=V, prefix_cache_min=16,
)
MAX_PAGES = 160 // PAGE


def _checkpoint(tmp_path_factory, name, hf):
    d = tmp_path_factory.mktemp(name)
    hf_path = os.path.join(d, "hf.json")
    with open(hf_path, "w") as f:
        json.dump(hf, f)
    path = os.path.join(d, "ckpt")
    children.child_checkpoint(path, hf_path, "7")
    return path


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return _checkpoint(tmp_path_factory, "nemotron_h", HF)


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


def reference_logprobs(source, toks, **kw):
    want = reference.forward(source.get, HF, toks, **kw)
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

    def state(self):
        return {k: np.asarray(v) for k, v in self.cache.items() if k in ("ssm", "conv")}


def test_the_family_is_chosen_by_model_type_alone(eng):
    mc = eng.model_config
    assert mc.model_type == "nemotron_h" and family(mc) is nemotron_h
    assert family(ModelConfig()) is llama
    assert mc.layer_pattern == "MEMEMEM*EME" and nemotron_h.kinds(mc) == {"M": 5, "*": 1, "E": 5}
    assert (mc.mamba_num_heads, mc.mamba_head_dim, mc.ssm_groups, mc.ssm_state_size, mc.conv_kernel, mc.ssm_chunk) == (8, 8, 2, 16, 4, 8)
    assert (mc.n_routed_experts, mc.router_experts, mc.num_experts_per_tok, mc.moe_latent_size) == (8, 0, 3, 32)
    assert nemotron_h.held_share(mc) is None and not nemotron_h.KV_PARK and not nemotron_h.PREFIX_REUSE
    # The state follows from --max-slots and the config: no allocator, no flag.
    cache = init_pools(mc, EC)
    assert cache["ssm"].shape == (5, 4, 8, 8, 16) and cache["ssm"].dtype == jnp.float32
    assert cache["conv"].shape == (5, 4, 3, 64 + 2 * 2 * 16)
    assert nemotron_h.state_bytes_per_slot(mc) == (cache["ssm"].nbytes + cache["conv"].nbytes) // 4
    assert cache["kv"].shape[0] == 1 * (4 * MAX_PAGES + 1)  # one `*` block's pages

    # The same keys on another family's config.json stay ignored.
    class Cfg:
        pass

    other = Cfg()
    other.__dict__.update({**HF, "model_type": "llama", "intermediate_size": 128})
    assert ModelConfig.from_hf(other).layer_pattern == ""


# -- (a) chunked prefill carries the state; decode goes on from it -----------------


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


@pytest.mark.parametrize("sizes", [(32, 28), (32, 32, 13), (32, 5)], ids=["two_chunks", "three_chunks", "short_tail"])
def test_chunked_prefill_then_decode_agrees_with_the_reference(eng, steps, source, tokens, sizes):
    """A prompt in two and in three chunk calls (every chunk behind the
    first STARTS from the state the last one LEFT in the slot; the last is
    padded to its bucket), then two decode chunks through the slot's state
    and the pool on the program's own greedy choices, against the
    reference's one pass over the same tokens."""
    d = Driver(eng, steps)
    n, slot = sum(sizes), 2
    lp0, tok0 = d.chunks(slot, tokens[0], sizes)
    lps, toks = generated(d, slot, n, tok0)
    seq = np.concatenate([tokens[0, :n], [tok0], toks[:-1]])[None]
    want, _ = reference_logprobs(source, seq)
    got = np.concatenate([lp0, lps])
    assert got.shape == (9, V)
    assert np.abs(got - want[0, n - 1 :]).max() <= LOGPROB_ABS


def test_cold_group_prefill_and_chunked_prefill_agree(eng, steps, source, tokens):
    """Two rows of one cold group call (each from zeros, padded to the
    bucket) leave what chunk calls of the same prompts leave: the same
    log-probabilities, and the same state and tail in their slots."""
    rows = [tokens[0, :29], tokens[0, 40:63]]
    cold = Driver(eng, steps)
    lp, toks, counters = cold.cold([1, 3], rows, CHUNK)
    chunked = Driver(eng, steps)
    for slot, row in zip((1, 3), rows):
        got, tok = chunked.chunks(slot, row, (16, len(row) - 16))
        assert np.abs(got[0] - lp[(1, 3).index(slot)]).max() <= LOGPROB_ABS and tok == toks[(1, 3).index(slot)]
    a, b = cold.state(), chunked.state()
    for k in ("ssm", "conv"):
        assert np.abs(a[k][:, [1, 3]] - b[k][:, [1, 3]]).max() <= LOGPROB_ABS
        assert not a[k][:, [0, 2]].any()  # nobody's slots: untouched
    want, _ = reference_logprobs(source, rows[0][None])
    assert np.abs(lp[0] - want[0, -1]).max() <= LOGPROB_ABS
    # The program's counters: 5 expert blocks x 8 held experts at most, nothing absent.
    assert 0 < int(counters["moe_hits"]) <= 5 * 8 and int(counters["moe_absent"]) == 0


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
    for key in ("ssm", "conv"):
        a, b = np.asarray(shared.cache[key]), np.asarray(apart.cache[key])
        assert np.abs(a[:, [0, 2]] - b[:, [0, 2]]).max() <= LOGPROB_ABS and a[:, [0, 2]].any() and not a[:, 1].any()


def test_the_state_the_program_leaves_is_the_references(eng, steps, source, tokens):
    """After a chunked prefill the slot holds, for every `M` block, the
    state the reference's recurrence reaches token by token."""
    d = Driver(eng, steps)
    d.chunks(1, tokens[0], (32, 32, 7))
    want = reference.forward(source.get, HF, tokens[:, :71])["states"]  # [n_M, 1, H, P, N]
    assert np.abs(d.state()["ssm"][:, 1] - want[:, 0]).max() <= LOGPROB_ABS


def test_idle_rows_among_live_ones_move_no_state(eng, steps, tokens):
    """A decode chunk with slots 0 and 2 live and 1 and 3 idle: the idle
    slots' state and tail are bit for bit what they were (slot 1 holds a
    parked prompt's state, slot 3 nothing), and slot 2 generates what it
    generates alone."""
    d = Driver(eng, steps)
    _, tok0 = d.chunks(0, tokens[0], (32, 9))
    d.chunks(1, tokens[0, 50:], (20,))
    _, tok2 = d.chunks(2, tokens[0, 10:], (32, 32, 3))
    before = d.state()
    lps, toks = generated(d, 2, 67, tok2, others={0: (41, tok0)})
    after = d.state()
    for k in ("ssm", "conv"):
        assert np.array_equal(after[k][:, [1, 3]], before[k][:, [1, 3]])
        assert not np.array_equal(after[k][:, [0, 2]], before[k][:, [0, 2]])
    alone = Driver(eng, steps)
    alone.chunks(2, tokens[0, 10:], (32, 32, 3))
    lps_alone, toks_alone = generated(alone, 2, 67, tok2)
    assert np.array_equal(toks, toks_alone) and np.abs(lps - lps_alone).max() <= LOGPROB_ABS


def test_a_decode_chunk_through_the_step_kernel_is_the_portable_routes(eng, steps, tokens, monkeypatch):
    """The decode program with the chip's one-pass kernel in its `M` blocks
    (interpret mode; the test steers the dispatcher, the program has no
    option): two live slots beside a parked one and an empty one give the
    portable route's log-probabilities and tokens, the five blocks' state
    within the file's limit, the idle slots' bit for bit; and the process
    records what it compiled for `/debug/engine`."""
    def run(d):
        _, tok0 = d.chunks(0, tokens[0], (32, 9))
        d.chunks(1, tokens[0, 50:], (20,))
        _, tok2 = d.chunks(2, tokens[0, 10:], (32, 32, 3))
        before = d.state()
        return before, *generated(d, 2, 67, tok2, others={0: (41, tok0)}), d.state()

    _, want_lps, want_toks, want = run(Driver(eng, steps))
    monkeypatch.setattr(ssm, "chosen_blocks", {})
    monkeypatch.setattr(ssm, "kernel_takes", lambda states: True)
    monkeypatch.setattr(ssm, "ssd_step_kernel", functools.partial(ssm.ssd_step_kernel, interpret=True))
    before, lps, toks, got = run(Driver(eng, build_step_functions(eng.model_config, EC, n_valid_vocab=V)))
    assert np.array_equal(toks, want_toks) and np.abs(lps - want_lps).max() <= LOGPROB_ABS
    for k in ("ssm", "conv"):
        assert np.abs(got[k] - want[k]).max() <= LOGPROB_ABS
        assert np.array_equal(got[k][:, [1, 3]], before[k][:, [1, 3]])
    assert not np.array_equal(got["ssm"][:, [0, 2]], before["ssm"][:, [0, 2]])
    assert ssm.chosen_blocks == {"B=4 H=8 P=8 N=16 G=2 float32": {
        "heads_per_tile": 8, "tile_bytes": 8 * 8 * 16 * 4,
        "pass": "one: a tile is rewritten where it lay and y = S C formed from it",
    }}
    assert eng._perf_debug_section()["ssm_kernel_blocks"] == ssm.chosen_blocks


def test_the_engine_reports_no_step_kernel_off_the_chip(eng, steps, tokens, monkeypatch):
    """`/debug/engine` -> `perf.ssm_kernel_blocks` beside `mla_kernel_blocks`:
    empty on the CPU backend, where the portable step is what compiles."""
    monkeypatch.setattr(ssm, "chosen_blocks", {})
    d = Driver(eng, build_step_functions(eng.model_config, EC, n_valid_vocab=V))
    _, tok = d.chunks(0, tokens[0], (32, 9))
    generated(d, 0, 41, tok, chunks=1)
    perf = eng._perf_debug_section()
    assert perf["ssm_kernel_blocks"] == {} and perf["mla_kernel_blocks"] == {}


def test_a_slot_used_again_starts_from_zeros(eng, steps, tokens):
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


def test_decode_without_live_rows_takes_every_row_as_live(eng, tokens):
    """The family's own entry point, as a caller outside the engine uses
    it: rows are slots, in order."""
    mc = eng.model_config
    cache = init_pools(mc, EC)
    table = jnp.asarray(1 + np.arange(4 * MAX_PAGES).reshape(4, MAX_PAGES), jnp.int32)
    toks = jnp.asarray(tokens[0, :4, None], jnp.int32)
    lengths = jnp.zeros((4,), jnp.int32)
    plain, c1 = nemotron_h.decode_step_paged(eng.params, mc, toks, cache, table, lengths)
    live = LiveRows.first(jnp.ones((4,), bool))
    ordered, c2 = nemotron_h.decode_step_paged(eng.params, mc, toks, cache, table, lengths, live=live)
    assert np.array_equal(np.asarray(plain), np.asarray(ordered))
    assert np.array_equal(np.asarray(c1["ssm"]), np.asarray(c2["ssm"]))
    with pytest.raises(ValueError, match="LoRA"):
        nemotron_h.decode_step_paged(eng.params, mc, toks, cache, table, lengths, lora=object())
    with pytest.raises(ValueError, match="without the paged pool"):
        nemotron_h.apply(eng.params, mc, toks, lengths[:, None])


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


# -- (b) a chip's share of the experts ------------------------------------------

SHARE_HF = {**HF, "num_hidden_layers": 8, "n_routed_experts": 4, "router_experts": 16, "num_experts_per_tok": 5}


def test_the_shares_add_up(tmp_path_factory):
    """Sixteen experts over four chips. Each chip routes over all sixteen,
    normalises over all five chosen and returns ITS four experts' part
    through its own up-projection (linear, so partial sums add) plus the
    shared expert, which every chip computes alike. The four parts with the
    shared expert counted once are the uncut reference's block output."""
    whole_hf = {**SHARE_HF, "n_routed_experts": 16, "router_experts": 16}
    whole = SafetensorsSource(_checkpoint(tmp_path_factory, "whole", whole_hf))
    p = "backbone.layers.1.mixer."
    u = jnp.asarray(np.random.default_rng(3).normal(size=(24, 64)), jnp.float32)
    f32 = lambda name: jnp.asarray(np.asarray(whole.get(name)).astype(np.float32))  # noqa: E731
    mm = lambda x, name: jnp.dot(x, f32(name).T)  # noqa: E731
    want, _, _ = reference.expert_block(f32, mm, whole_hf, p, u)
    shared = mm(jnp.square(jax.nn.relu(mm(u, p + "shared_experts.up_proj.weight"))), p + "shared_experts.down_proj.weight")
    total, absent, hits = shared, 0, 0
    for first in (0, 4, 8, 12):
        hf = {**SHARE_HF, "experts_first": first}
        mc = ModelConfig.from_hf(type("Cfg", (), hf)()).replace(dtype="float32")
        assert nemotron_h.held_share(mc) == (first, 4, 16)
        w = {k: jnp.asarray(v) for k, v in nemotron_h._block_tensors(whole.get, mc, 1, np.float32).items()}
        w = {k: jnp.swapaxes(v, -1, -2) if k in ("we_1", "we_2") else v for k, v in w.items()}
        assert w["we_1"].shape == (4, 32, 32) and w["wr"].shape == (64, 16)
        part, hit, gone, idx = nemotron_h.expert_block(mc, u, w)
        # The same share, by the reference.
        ref_part, _, _ = reference.expert_block(f32, mm, hf, p, u)
        assert np.abs(np.asarray(part) - np.asarray(ref_part)).max() <= LOGPROB_ABS
        total = total + (part - shared)
        absent, hits = absent + int(gone), hits + int(hit)
        assert int(gone) == int(((idx < first) | (idx >= first + 4)).sum())
    assert np.abs(np.asarray(total) - np.asarray(want)).max() <= LOGPROB_ABS
    # Routing that leans on one chip: every token sends four of its five
    # choices to experts 12..15 (96 rows where a pass holds 64), so the
    # second pass runs, and nothing is left out.
    from kubeai_tpu.ops.moe import held_capacity

    assert held_capacity(24 * 5, 4, 16) == 64
    forced = jnp.tile(jnp.asarray([[12, 13, 3, 14, 15]], jnp.int32), (24, 1))
    part, hit, gone, _ = nemotron_h.expert_block(mc, u, w, forced=forced)
    ref_part, _, _ = reference.expert_block(f32, mm, hf, p, u, forced=forced)
    assert np.abs(np.asarray(part) - np.asarray(ref_part)).max() <= LOGPROB_ABS
    assert (int(hit), int(gone)) == (4, 24)
    # Every assignment was some chip's: 24 tokens x 5 choices, each absent on three chips of four.
    assert absent == 3 * 24 * 5 and 0 < hits <= 16


def test_a_share_is_served_and_counted(tmp_path_factory):
    """A checkpoint that holds experts 4..7 of a router of 16, through the
    loader and the engine: tokens come back, `moe_hits` is of HELD experts,
    the absent assignments are counted, and no prefix is ever reused."""
    hf = {**SHARE_HF, "experts_first": 4}
    path = _checkpoint(tmp_path_factory, "share", hf)
    eng = load_engine_from_path(path, EC, dtype="float32", overlap=False, warmup=False)
    try:
        assert eng.cfg.prefix_cache_min == 0  # the family's rule, not the operator's flag
        eng.start()
        prompt = [1] + np.random.default_rng(4).integers(32, 127, 40).tolist()  # five pages and a tail
        labels = {"phase": "decode"}
        series = (eng.m_moe_assign, eng.m_moe_absent, eng.m_moe_possible, eng.m_moe_hit)
        before = [m.value(labels=labels) for m in series]  # the registry is the process's: other engines counted too
        prefix = (eng.m_prefix_cached.value(), eng.m_prefix_lookup.value())
        outs = [generate(eng, prompt, 6)[0] for _ in range(2)]
        assert outs[0] == outs[1] and len(outs[0]) == 6
        assert (eng.m_prefix_cached.value(), eng.m_prefix_lookup.value()) == prefix  # nothing looked up, nothing hit
        assigned, absent, possible, hit = (m.value(labels=labels) - b for m, b in zip(series, before))
        assert 0 < absent < assigned and assigned % (3 * 5 * EC.max_slots) == 0  # 3 `E` blocks x 5 choices x every slot's row
        assert possible > 0 and possible % (4 * 3 * EC.decode_chunk) == 0  # HELD experts x `E` blocks x a chunk's steps
        assert 0 < hit <= possible
        perf = eng._perf_debug_section()
        assert perf["state_bytes_per_slot"] == nemotron_h.state_bytes_per_slot(eng.model_config) > 0
        assert perf["kv_bytes_per_token"] == 2 * 2 * 16 * 4  # one `*` block, float32
        assert eng.m_state_total.value() == EC.max_slots and eng.m_state_used.value() == 0
    finally:
        eng.stop()


# -- (c) what the family refuses, by name ----------------------------------------


@pytest.mark.parametrize(
    "change,match",
    [
        ({"n_group": 2}, "n_group"),
        ({"topk_group": 2}, "n_group"),
        ({"hybrid_override_pattern": "M-M*EME-MEME"}, "other than M"),
        ({"hybrid_override_pattern": "ME*"}, "each of the 11"),
        ({"moe_latent_size": None}, "latent"),
        ({"n_shared_experts": 2}, "n_shared_experts"),
        ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
        ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
        ({"attention_bias": True}, "attention_bias"),
        ({"mamba_proj_bias": True}, "mamba_proj_bias"),
        ({"use_conv_bias": False}, "use_conv_bias"),
        ({"residual_in_fp32": True}, "residual_in_fp32"),
        ({"sliding_window": 4096}, "sliding_window"),
        ({"mamba_num_heads": 7}, "n_groups"),
        ({"router_experts": 16, "experts_first": 12}, "not among"),
    ],
)
def test_from_hf_refuses_by_name_what_the_family_cannot_run(change, match):
    with pytest.raises(ValueError, match="nemotron_h: .*" + match):
        ModelConfig.from_hf(type("Cfg", (), {**HF, **change})())


@pytest.mark.parametrize(
    "kw,match",
    [
        (dict(quantization="int8"), "quantization"),
        (dict(tp=4), "tensor-parallel"),
        (dict(replace=dict(kv_cache_dtype="fp8")), "kv_cache_dtype"),
        (dict(replace=dict(tie_word_embeddings=True)), "tied"),
        (dict(replace=dict(layer_pattern="MEMEME")), r"none of \['\*'\]"),
        (dict(replace=dict(layer_pattern="M*M*")), r"none of \['E'\]"),
    ],
)
def test_refuse_unsupported(eng, kw, match):
    kw = dict(kw)
    mc = eng.model_config.replace(**kw.pop("replace", {}))
    with pytest.raises(ValueError, match="nemotron_h: .*" + match):
        nemotron_h.refuse_unsupported(mc, **kw)


# -- (d) counts --------------------------------------------------------------------

PUBLISHED = os.path.join(ROOT, "perfbench", "configs", "nemotron3-super-120b-a12b-bf16.json")


def _published():
    with open(PUBLISHED) as f:
        cfg = json.load(f)
    return {k: v for k, v in cfg.items() if k not in ("source", "reduced", "assumed", "serving", "rehearsal")}


@pytest.mark.parametrize("which", ["toy", "toy_share", "published_cut"])
def test_param_counts_are_the_benchmarks(eng, which):
    hf = {"toy": HF, "toy_share": {**SHARE_HF, "experts_first": 4}, "published_cut": None}[which] or _published()
    mc = ModelConfig.from_hf(type("Cfg", (), hf)())
    total, active = param_counts(mc)
    assert total == counts.params_held(hf) and active == pytest.approx(counts.active_params(hf))
    assert counts.state_bytes_per_slot(hf, jnp.dtype(mc.dtype).itemsize) == nemotron_h.state_bytes_per_slot(mc)
    if which == "toy":
        held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(eng.params))
        assert held == total


def test_the_published_cut_is_what_the_issue_reckoned():
    """The bytes of ISSUE 40's cut, from the family's own plan."""
    hf = _published()
    assert counts.kinds(hf) == {"M": 5, "*": 1, "E": 5}
    assert counts.mixer_params(hf) == 109_640_064 and counts.attention_params(hf) == 35_655_680
    assert counts.expert_params(hf) == 5_505_024 and counts.experts_per_token_here(hf) == 5.5
    assert abs(counts.params_held(hf) * 2 / 1e9 - 9.30) < 0.01
    assert counts.state_bytes_per_slot_block(hf, 2) == {"state": 4_194_304, "tail": 61_440}
    assert counts.kv_bytes_per_token(hf, 2) == 1024
    assert counts.ssm_chunked_flops_per_token(hf) == 6_553_600
