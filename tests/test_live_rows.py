"""Decode runs its layers on the live slots' rows first and tells the paged
attention kernel how many they are (`models/base.py::LiveRows`,
`ops/paged_attention.py`'s `live_rows`): what a live slot gets must not
depend on who else is live, an idle slot's carries must not move, and the
host counts the rows it dispatched by the mask it uploaded. On the CPU the
kernel is its twin (`use_paged_kernel=True`), which honours the count as
the library's reference does."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine.core import (
    EngineConfig, build_step_functions, build_test_engine, engine_dims, init_pools, table_width,
)
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.metrics import default_registry
from kubeai_tpu.models import family
from kubeai_tpu.models.base import LiveRows, ModelConfig
from kubeai_tpu.ops import paged_attention as pa

B, K, PAGE, PROMPT = 5, 3, 8, 16

DENSE = ModelConfig(
    vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    dtype="float32", max_position=256,
)
SMALLTHINKER = ModelConfig.from_hf(SimpleNamespace(
    model_type="smallthinker", vocab_size=272, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_num_primary_experts=8, moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1], sliding_window_size=32,
    rope_theta=1500000, rope_scaling=None, rms_norm_eps=1e-06,
    tie_word_embeddings=False, max_position_embeddings=512,
)).replace(dtype="float32")
DEEPSEEK = ModelConfig.from_hf(SimpleNamespace(
    model_type="deepseek_v3", vocab_size=272, hidden_size=64, intermediate_size=128,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, head_dim=8,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2, moe_intermediate_size=32,
    first_k_dense_replace=1, norm_topk_prob=True, routed_scaling_factor=2.448,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    rope_interleave=True, rope_theta=1000000, rope_scaling=None, rms_norm_eps=1e-06,
    tie_word_embeddings=False, max_position_embeddings=4096, hidden_act="silu",
)).replace(dtype="float32")
CONFIGS = {"dense": DENSE, "smallthinker": SMALLTHINKER, "deepseek": DEEPSEEK}
EC = EngineConfig(max_slots=B, max_seq_len=64, page_size=PAGE, prefill_buckets=(PROMPT,), decode_chunk=K)

MASKS = {
    "first_rows_idle": [False, False, True, True, True],
    "alternating": [True, False, True, False, True],
    "one_live": [False, False, False, True, False],
    "all_live": [True] * B,
    "none_live": [False] * B,
}


class Programs:
    """One family's step programs over its toy configuration, every slot
    prefilled with a prompt of its own and one all-live decode chunk
    behind it (so the admission merge is done): `chunk(active)` runs the
    NEXT chunk from copies of that state and returns (the program's
    outputs, the carries it started from)."""

    def __init__(self, mc: ModelConfig):
        self.mc = mc = mc.replace(use_paged_kernel=True)
        self.model = family(mc)
        self.params = self.model.init_params(mc, jax.random.key(0))
        self.sf = build_step_functions(mc, EC, n_valid_vocab=259)
        max_pages, _, hist_width = engine_dims(EC)
        width = table_width(mc, EC)
        # A slot's pages, the same in both halves of a two-table row
        # (positions stay under one window: nothing is handed back).
        self.tables = np.zeros((B, width), np.int32)
        for half in range(width // max_pages):
            self.tables[:, half * max_pages : half * max_pages + 4] = 1 + np.arange(B * 4).reshape(B, 4)
        rng = np.random.default_rng(5)
        prompts = rng.integers(1, 259, (B, PROMPT)).astype(np.int32)
        cache = init_pools(mc, EC)
        adm_toks = jnp.zeros((B,), jnp.int32)
        Kb = EC.max_logit_bias
        for b in range(B):
            *_, cache, adm_toks, _ = self.sf.prefill_batch_jit(
                self.params, prompts[b : b + 1], np.full((1,), PROMPT - b, np.int32), self.tables[b : b + 1],
                np.asarray([b], np.int32), np.asarray([b + 1], np.uint32),
                np.zeros((1,), np.float32), np.ones((1,), np.float32), np.zeros((1,), np.int32),
                np.zeros((1, Kb), np.int32), np.zeros((1, Kb), np.float32), adm_toks, cache,
            )
        self.adm_toks = adm_toks
        # Rows 1 and 3 sample, the others are greedy; row 2 asks for the
        # top alternatives, row 4 sets a penalty: every gate of the
        # epilogue is open, in slot order, beside the permuted layers.
        self.temp = np.asarray([0.0, 0.9, 0.0, 0.7, 0.0], np.float32)
        self.want_top = np.asarray([False, False, True, False, False])
        self.presence = np.asarray([0.0, 0.0, 0.0, 0.0, 0.5], np.float32)
        state = (
            cache, jnp.zeros((B, hist_width), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), jax.random.key_data(jax.random.split(jax.random.key(0), B)),
        )
        out = self._decode(
            state, np.ones((B,), bool), adm_mask=np.ones((B,), bool),
            adm_len=(PROMPT - np.arange(B)).astype(np.int32),
        )
        self.state = out[4:9]

    def _decode(self, state, active, adm_mask=None, adm_len=None):
        cache, hist, lengths, last, keys = jax.tree.map(lambda a: jnp.array(a, copy=True), state)
        Kb = EC.max_logit_bias
        return self.sf.decode_jit(
            self.params, cache, self.tables.copy(), hist, lengths, last, keys,
            np.asarray(active, bool), self.temp, np.ones((B,), np.float32), np.zeros((B,), np.int32),
            self.presence, np.zeros((B,), np.float32), self.want_top,
            (PROMPT - np.arange(B)).astype(np.int32), np.zeros((B, Kb), np.int32), np.zeros((B, Kb), np.float32),
            np.zeros((B,), bool) if adm_mask is None else adm_mask,
            np.zeros((B,), np.int32) if adm_len is None else adm_len,
            (1 + np.arange(B)).astype(np.uint32), self.adm_toks,
        )

    def chunk(self, active):
        return jax.device_get(self._decode(self.state, active)), jax.device_get(self.state)


@pytest.fixture(scope="module")
def programs():
    built = {}

    def get(name):
        if name not in built:
            built[name] = Programs(CONFIGS[name])
        return built[name]

    return get


@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("name", list(CONFIGS))
def test_a_live_slot_gets_what_it_gets_with_every_slot_live_and_an_idle_one_stays_put(programs, name, mask):
    p = programs(name)
    active = np.asarray(MASKS[mask])
    (want, _), (got, before) = p.chunk(np.ones((B,), bool)), p.chunk(active)
    # corr, its log-prob, then the top alternatives' ids and log-probs
    # ([K, B, ...]; zeros unless a LIVE slot asked for them).
    asked = bool((active & p.want_top).any())
    for w, g in zip(want[: 4 if asked else 2], got[:4]):
        np.testing.assert_array_equal(g[:, active], w[:, active])
    assert np.isfinite(got[1]).all() and np.isfinite(got[3]).all()
    _, hist0, lengths0, last0, _ = before
    _, hist, lengths, last, _ = got[4:9]
    idle = ~active
    np.testing.assert_array_equal(lengths[idle], lengths0[idle])
    np.testing.assert_array_equal(last[idle], last0[idle])
    np.testing.assert_array_equal(hist[idle], hist0[idle])
    np.testing.assert_array_equal(lengths[active], lengths0[active] + K)
    # An idle slot's token is its last one, repeated (nothing reads it).
    np.testing.assert_array_equal(got[0][:, idle], np.broadcast_to(last0[idle], (K, idle.sum())))


@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("name", list(CONFIGS))
def test_the_model_step_on_live_rows_first_is_the_step_in_slot_order_on_every_live_row(programs, name, mask):
    """The seam itself against the call without `live` (what the step was
    before the rows moved): the live rows' logits in slot order and every
    row finite. Bit for bit for the dense family; the expert families'
    two programs (one more gather apart) are fused apart on the CPU and
    sum in another order, 2e-7 on logits of 0.1 (the test above holds
    them to the bit inside ONE program)."""
    p = programs(name)
    active = jnp.asarray(MASKS[mask])
    cache, _, lengths, last, _ = p.state
    pools = {k: v for k, v in cache.items() if k.startswith("kv")}
    step = jax.jit(
        lambda pool, live, table, last, lengths: p.model.decode_step_paged(
            p.params, p.mc, last[:, None], pool, table, lengths, live=live
        )[0]
    )
    live = LiveRows.first(active)
    rows = (jnp.asarray(p.tables), last, lengths)
    want = np.asarray(step(pools, None, *rows))
    got = np.asarray(step(pools, live, *live.take(*rows)))  # as decode_fn hands them over
    np.testing.assert_allclose(
        got[np.asarray(active)], want[np.asarray(active)], rtol=0, atol=0 if name == "dense" else 2e-6,
    )
    assert np.isfinite(got).all()


@pytest.mark.parametrize("mask", list(MASKS))
def test_live_rows_come_first_in_slot_order_and_all_live_is_the_identity(mask):
    active = np.asarray(MASKS[mask])
    live = jax.jit(LiveRows.first)(jnp.asarray(active))
    order, inverse = np.asarray(live.order), np.asarray(live.inverse)
    n = int(active.sum())
    assert int(live.count) == n
    np.testing.assert_array_equal(order[:n], np.flatnonzero(active))
    np.testing.assert_array_equal(order[n:], np.flatnonzero(~active))
    np.testing.assert_array_equal(order[inverse], np.arange(B))
    x = jnp.arange(B * 3).reshape(B, 3)
    (taken,) = live.take(x)
    np.testing.assert_array_equal(np.asarray(live.restore(taken)), np.asarray(x))
    assert live.take(None, x)[0] is None
    if mask in ("all_live", "none_live"):
        np.testing.assert_array_equal(order, np.arange(B))


@pytest.mark.parametrize("n_live", [0, 1, 2, 4])
@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
def test_the_twin_with_a_count_is_the_reference_on_the_first_rows_and_zeros_past_them(n_live, window):
    pytest.importorskip("jax.experimental.pallas.ops.tpu.ragged_paged_attention")
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention.kernel import ref_ragged_paged_attention

    n, H, Kv, h, P, ps, mp = 4, 8, 2, 128, 1 + 4 * 4, 16, 4
    rng = np.random.default_rng(n_live)
    q = jnp.asarray(rng.standard_normal((n, H, h)), jnp.float32)
    kv_pages = jnp.asarray(rng.standard_normal((P, ps, 2 * Kv, h)), jnp.float32)
    table = jnp.asarray(rng.choice(np.arange(1, P), size=(n, mp), replace=False).astype(np.int32))
    kv_lens = jnp.asarray([17, 42, 1, 64], jnp.int32)
    cu = jnp.arange(n + 1, dtype=jnp.int32)
    count = jnp.asarray([n_live], jnp.int32)
    got = np.asarray(pa._cpu_twin(q, kv_pages, kv_lens, table, cu, count, sm_scale=h**-0.5, sliding_window=window))
    assert got.shape == (n, H, h)
    np.testing.assert_array_equal(got[n_live:], 0)
    if n_live:
        want = ref_ragged_paged_attention(
            q, kv_pages, kv_lens, table, cu, count, sm_scale=h**-0.5, sliding_window=window,
        )
        assert want.shape == (n_live, H, h)
        np.testing.assert_allclose(got[:n_live], np.asarray(want), rtol=2e-5, atol=2e-5)

    # The wrapper: the same rows, zeros past the count (and, at none, row
    # 0 walked and masked: the kernel is never told 0).
    seen = {}
    twin = pa._cpu_twin

    def record(*a, **k):
        seen["num_seqs"] = a[5]
        return twin(*a, **k)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(pa, "_cpu_twin", record)
        out = pa.paged_attention_ragged(
            q[:, None], kv_pages, table, kv_lens, sliding_window=window, live_rows=jnp.asarray(n_live, jnp.int32),
        )
    np.testing.assert_array_equal(np.asarray(seen["num_seqs"]), [max(n_live, 1)])
    np.testing.assert_array_equal(np.asarray(out[:, 0]), got)


def test_a_count_is_refused_for_more_than_one_query_row_a_slot_and_none_changes_nothing():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 3, 4, 128)), jnp.float32)
    kv_pages = jnp.asarray(rng.standard_normal((9, 16, 4, 128)), jnp.float32)
    table = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
    lens = jnp.asarray([10, 30], jnp.int32)
    with pytest.raises(ValueError, match="one query row a slot"):
        pa.paged_attention_ragged(q, kv_pages, table, lens, live_rows=jnp.asarray(1))
    whole = pa.paged_attention_ragged(q[:, :1], kv_pages, table, lens - 2)
    counted = pa.paged_attention_ragged(q[:, :1], kv_pages, table, lens - 2, live_rows=jnp.asarray(2))
    np.testing.assert_array_equal(np.asarray(counted), np.asarray(whole))


def _rows() -> dict:
    counter = default_registry.counter("kubeai_engine_decode_rows_total", "")
    return {state: counter.value(labels={"state": state}) for state in ("live", "idle")}


def _chunks() -> float:
    counter = default_registry.counter("kubeai_engine_decode_epilogue_chunks_total", "")
    return sum(counter.value(labels={"part": "penalties", "ran": ran}) for ran in ("0", "1"))


def test_the_counter_counts_rows_times_steps_of_every_dispatched_chunk_by_the_mask_it_uploaded():
    slots, chunk = 4, 2
    eng = build_test_engine(EngineConfig(
        max_slots=slots, max_seq_len=128, page_size=16, prefill_buckets=(16, 32), decode_chunk=chunk,
    ))
    rows0, chunks0 = _rows(), _chunks()
    eng.start()
    try:
        reqs = [
            eng.submit([7 + i] * 9, SamplingParams(max_tokens=n, temperature=0.0))
            for i, n in enumerate((3, 11))
        ]
        for req in reqs:
            while (ev := req.out.get(timeout=120))[0] != "done":
                assert ev[0] == "token", ev
        section = eng._perf_debug_section()["decode_rows"]
    finally:
        eng.stop()
    rows, chunks = _rows(), _chunks() - chunks0
    live, idle = rows["live"] - rows0["live"], rows["idle"] - rows0["idle"]
    assert chunks > 0
    assert live + idle == slots * chunk * chunks
    # Two requests in four slots: never more than half the rows live,
    # and at least the tokens that were generated behind the first.
    assert (3 - 1) + (11 - 1) <= live <= 2 * chunk * chunks
    assert section["live"] == int(rows["live"]) and section["idle"] == int(rows["idle"])
    assert section["live_share"] == round(rows["live"] / (rows["live"] + rows["idle"]), 4)
