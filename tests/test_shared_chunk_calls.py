"""An admission round chooses its chunk calls by what a call is measured to
cost (engine/core.py: call_seconds, prefill_plan, round_calls,
Engine.call_cost): a call reads every weight once whatever its rows, so
where that read costs more than the rows of the padding a prompt's tail is
one padded wide call, and two prompts' pieces share one call of two slots.
The planner as a pure function over three cost pairs, then engines on the
CPU with the cost INJECTED (the CPU's own is UNKNOWN_DEVICE, never
measured: the plans of fewest rows, no shared call): prompts through shared
calls stream what each streams apart, in every family whose state a shared
call could mix up; a family that promises a hit the bits of its cold
prefill (REUSE_WHOLE_PREFILL_CALLS) shares no call and keeps them."""

import dataclasses
import os
import sys
import threading

import jax
import numpy as np
import pytest

from kubeai_tpu.engine.core import (
    UNKNOWN_DEVICE, Engine, EngineConfig, call_seconds, pair_rows, prefill_plan, round_calls, wide_chunk,
)
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import family
from kubeai_tpu.models.base import ModelConfig

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_named_scopes as toys  # noqa: E402  (one toy configuration a family)
from test_prefill_grouping import OneRound  # noqa: E402

# (seconds to read the weights once, seconds a row) of three deployments:
# lfm2 as measured on the chip (PERF.md section 6, PR 51), dense int8
# Mistral as reckoned, and a device whose peaks are not known.
COSTS = {"weight_bound": (13.6e-3, 9.9e-3 / 1024), "flop_bound": (8.8e-3, 75e-3 / 1024), "unknown_device": UNKNOWN_DEVICE}
SERVING = EngineConfig(max_slots=24, max_seq_len=32768)  # the published buckets: six, ending at 1024
DENSE = ModelConfig(
    vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2, dtype="float32",
    max_position=256,
)
PAIRS = pair_rows(SERVING, DENSE)


def rows_first_plan(cfg: EngineConfig, left: int) -> list[tuple[int, int]]:
    """The plan as it was before a call had a cost (PR 43 to PR 53): wide
    calls, a call of the largest bucket, the tail's bucket."""
    top, wide = max(cfg.prefill_buckets), wide_chunk(cfg)
    plan = [(wide, wide)] * (left // wide)
    left %= wide
    if left > top:
        plan.append((top, top))
        left -= top
    if left:
        plan.append((next(b for b in cfg.prefill_buckets if left <= b), left))
    return plan


def price(plan, cost) -> float:
    return sum(call_seconds(cost, 1, rows) for rows, _ in plan)


# -- a prompt's pieces -----------------------------------------------------------


def test_a_device_of_unknown_peaks_plans_as_before_at_every_length():
    assert all(prefill_plan(SERVING, n) == prefill_plan(SERVING, n, UNKNOWN_DEVICE) == rows_first_plan(SERVING, n) for n in range(1, 8193))


@pytest.mark.parametrize("name", sorted(COSTS))
def test_a_plan_covers_its_tokens_once_pads_only_a_tail_and_is_no_dearer_than_before(name):
    cost = COSTS[name]
    shapes = {*SERVING.prefill_buckets, wide_chunk(SERVING)}
    for n in [*range(1, 4200), 5632, 8192, 24576, 32767]:
        plan = prefill_plan(SERVING, n, cost)
        before = rows_first_plan(SERVING, n)
        assert sum(real for _, real in plan) == n
        assert all(rows == real for rows, real in plan[:-1]) and 0 < plan[-1][1] <= plan[-1][0]
        assert {rows for rows, _ in plan} <= shapes
        assert [rows for rows, _ in plan] == sorted((rows for rows, _ in plan), reverse=True)
        assert len(plan) <= len(before) and price(plan, cost) <= price(before, cost)
        if plan != before:  # ... and it moves only for less
            assert price(plan, cost) < price(before, cost)
        # A hit cut at an edge of the cold plan leaves the cold plan's tail (REUSE_WHOLE_PREFILL_CALLS).
        edge = 0
        for j, (rows, _) in enumerate(plan[:-1]):
            edge += rows
            assert prefill_plan(SERVING, n - edge, cost) == plan[j + 1 :]


def test_where_the_read_costs_more_than_the_padding_a_tail_is_one_wide_call():
    weights, flops = COSTS["weight_bound"], COSTS["flop_bound"]
    assert prefill_plan(SERVING, 1500, weights) == [(2048, 1500)]
    assert prefill_plan(SERVING, 1025, weights) == [(2048, 1025)]
    assert prefill_plan(SERVING, 1024, weights) == [(1024, 1024)]
    assert prefill_plan(SERVING, 3100, weights) == [(2048, 2048), (2048, 1052)]
    assert prefill_plan(SERVING, 2100, weights) == [(2048, 2048), (64, 52)]
    # Bound by FLOPs, the cut of fewest rows stays ...
    assert prefill_plan(SERVING, 1500, flops) == [(1024, 1024), (512, 476)]
    # ... but for two calls of 1024 rows, which are one of 2048 for a read less.
    assert prefill_plan(SERVING, 1800, flops) == [(2048, 1800)] and rows_first_plan(SERVING, 1800) == [(1024, 1024), (1024, 776)]
    moved = [n for n in range(1, 8193) if prefill_plan(SERVING, n, flops) != rows_first_plan(SERVING, n)]
    assert all(1536 < n % 2048 for n in moved) and len(moved) == 4 * 511


# -- a round's calls --------------------------------------------------------------


def _rounds(seed: int, n: int):
    """The prompt lengths of *n* rounds of 1-6 prompts drawn as fleet-sat draws them."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        yield np.clip(np.exp(rng.normal(np.log(1500), 0.8, rng.integers(1, 7))), 256, 6000).astype(int).tolist()


@pytest.mark.parametrize("name", sorted(COSTS))
def test_a_rounds_layout_keeps_every_prompts_pieces_in_order_and_apart(name):
    cost = COSTS[name]
    shared = 0
    for lengths in _rounds(7, 300):
        plans = [prefill_plan(SERVING, n, cost) for n in lengths]
        spare = frozenset(i for i, n in enumerate(lengths) if n <= 1024)
        calls = round_calls(plans, cost, PAIRS, spare=spare)
        seen = {i: [] for i in range(len(plans))}
        for rows, members in calls:
            assert 1 <= len(members) <= 2 and len({i for i, _ in members}) == len(members)  # never two pieces of one prompt
            assert len(members) == 1 or rows in PAIRS
            for i, k in members:
                assert plans[i][k][0] <= rows  # padded up, never cut
                seen[i].append(k)
            if len(members) == 2:
                shared += 1
                apart = sum(call_seconds(cost, 1, plans[i][k][0]) for i, k in members)
                assert call_seconds(cost, 2, rows) < apart  # shared only for less
        for i, ks in seen.items():
            if i in spare:  # laid out only where it shares a call
                assert ks in ([], [0])
                assert not ks or any(len(m) == 2 and (i, 0) in m for _, m in calls)
            else:
                assert ks == list(range(len(plans[i])))  # every piece once, in order
    # Bound by FLOPs too, two pieces of the same rows share a call (a read saved for no padding); a device of unknown peaks shares none.
    assert (shared > 0) == (name != "unknown_device")


def test_a_round_pairs_neighbours_widest_first_wherever_that_costs_less():
    cost = COSTS["weight_bound"]
    plans = [[(2048, 2048)], [(1024, 1024)], [(1024, 1000)], [(32, 20)]]
    # Widest first, each with the next: a read saved for 1024 rows of padding, then one for 992.
    assert round_calls(plans, cost, PAIRS) == [(2048, [(0, 0), (1, 0)]), (1024, [(2, 0), (3, 0)])]
    # Where the padding costs more than the read, apart: 2048 + 512 is 1536 rows for one read.
    assert round_calls([[(2048, 2048)], [(512, 400)]], cost, PAIRS) == [(2048, [(0, 0)]), (512, [(1, 0)])]
    # A prompt that reads what another of its round writes starts behind that one's last piece.
    plans = [[(2048, 2048), (2048, 2048), (512, 300)], [(2048, 1500)], [(512, 400)]]
    calls = round_calls(plans, cost, PAIRS, first=[0, 3, 0])
    assert calls == [(2048, [(0, 0)]), (512, [(2, 0)]), (2048, [(0, 1)]), (512, [(0, 2)]), (2048, [(1, 0)])]
    assert round_calls(plans, cost, PAIRS)[0] == (2048, [(0, 0), (1, 0)])
    # Two short cold prompts share a call of the narrowest compiled pair; one alone keeps its cold call.
    assert round_calls([[(128, 100)], [(256, 200)]], cost, PAIRS, spare=frozenset({0, 1})) == [(512, [(1, 0), (0, 0)])]
    assert round_calls([[(128, 100)]], cost, PAIRS, spare=frozenset({0})) == []
    # No program of two slots (one slot a deployment, or a family that reuses whole calls), no shared call.
    assert all(len(m) == 1 for _, m in round_calls(plans, cost, ()))
    assert pair_rows(dataclasses.replace(SERVING, max_slots=1), DENSE) == () == pair_rows(SERVING, toys.SMALLTHINKER)
    assert PAIRS == (512, 1024, 2048) == pair_rows(SERVING, toys.NEMOTRON_H) == pair_rows(SERVING, toys.LFM2_MOE)


# -- engines on the CPU with the cost injected ---------------------------------------

PAGE = 8
EC = EngineConfig(max_slots=4, max_seq_len=256, page_size=PAGE, prefill_buckets=(8, 16, 32), decode_chunk=4, prefix_cache_min=16)
INJECTED = (1.0, 1.0 / 48)  # a read costs 48 rows: a wide call (64) pairs with one of 32 and up
FAMILIES = {
    "dense": DENSE, "short_convolution": toys.LFM2_MOE, "window_experts": toys.SMALLTHINKER, "state_space": toys.NEMOTRON_H,
}


def _prompt(seed: int, n: int) -> list[int]:
    return [1] + np.random.default_rng(seed).integers(32, 127, n - 1).tolist()


def _stream(req) -> list[tuple[int, float]]:
    out = []
    while True:
        ev = req.out.get(timeout=300)
        if ev[0] == "token":
            if ev[1] >= 0:
                out.append((ev[1], ev[3]))
        elif ev[0] == "done":
            return out
        else:
            raise RuntimeError(ev[1])


def _engine(mc, cost, cfg=EC):
    eng = Engine(mc, family(mc).init_params(mc, jax.random.key(43)), ByteTokenizer(), cfg)
    eng.call_cost = cost
    calls = []
    chunk_jit = eng._prefill_chunk_jit

    def spy(params, tokens, starts, last_idx, tables, slots, *rest, **kw):
        calls.append((tokens.shape, np.asarray(starts).tolist(), (np.asarray(last_idx) + 1).tolist(), np.asarray(slots).tolist()))
        if eng._wpages is not None:
            assert all(eng._wpages.held(int(s)) <= eng._wpages.cap for s in slots)
        return chunk_jit(params, tokens, starts, last_idx, tables, slots, *rest, **kw)

    eng._prefill_chunk_jit = spy
    return eng, calls


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_prompts_through_shared_calls_stream_what_each_streams_apart(name):
    """150 tokens ([64, 64, 32]), 100 ([64, 64]: the padded wide call, not
    [32, 8]), 120 ([64, 64]) and a cold 20 in ONE round. Wave 0 holds three
    first pieces of 64 rows and the short prompt: two calls of two slots,
    the 20 a row that starts at 0 beside the 120's first piece. Wave 1 holds
    three carried pieces: two sharing, one alone. Then the 150's tail. The
    family that reuses whole calls (window_experts) cuts by the same cost
    and makes the same pieces, a call each."""
    mc = FAMILIES[name]
    shares = not family(mc).REUSE_WHOLE_PREFILL_CALLS
    assert shares == (name != "window_experts")
    prompts = [_prompt(1, 150), _prompt(2, 100), _prompt(3, 20), _prompt(4, 120)]
    sp = SamplingParams(max_tokens=6, temperature=0.0, logit_bias=((257, -100.0),))
    eng, calls = _engine(mc, INJECTED)
    series = (("chunk", "1"), ("chunk", "2"), ("group", "1"))
    before = {k: eng.m_prefill_calls.value(labels={"kind": k[0], "slots": k[1]}) for k in series}
    pad0, tok0 = eng.m_pad_prefill.value(), eng.m_prefill.value()
    eng.start()
    try:
        together = [_stream(r) for r in OneRound(eng).submit(prompts, sp)]
    finally:
        eng.stop()
    after = {k: eng.m_prefill_calls.value(labels={"kind": k[0], "slots": k[1]}) for k in series}
    debug = eng._perf_debug_section()
    assert debug["prefill_call_cost"]["read_weights_ms"] == 1000.0 and debug["prefill_call_cost"]["plan_1500"][-1] == (32, 28)
    if shares:
        assert calls == [
            ((2, 64), [0, 0], [64, 64], [0, 1]), ((2, 64), [0, 0], [64, 20], [3, 2]),
            ((2, 64), [64, 64], [64, 36], [0, 1]), ((1, 64), [64], [56], [3]),
            ((1, 32), [128], [22], [0]),
        ]
        assert {k: after[k] - before[k] for k in series} == {("chunk", "1"): 2, ("chunk", "2"): 3, ("group", "1"): 0}
        assert eng.m_prefill.value() - tok0 == 390 and eng.m_pad_prefill.value() - pad0 == 3 * 2 * 64 + 64 + 32 - 390
        assert debug["prefill_calls"]["chunkx2"] >= 3 and debug["prefill_call_cost"]["two_slot_rows"] == [16, 32, 64]
    else:
        assert calls == [
            ((1, 64), [0], [64], [0]), ((1, 64), [0], [64], [1]), ((1, 64), [0], [64], [3]),
            ((1, 64), [64], [64], [0]), ((1, 64), [64], [36], [1]), ((1, 64), [64], [56], [3]),
            ((1, 32), [128], [22], [0]),
        ]
        assert {k: after[k] - before[k] for k in series} == {("chunk", "1"): 7, ("chunk", "2"): 0, ("group", "1"): 1}
        assert debug["prefill_call_cost"]["two_slot_rows"] == []

    # Apart: an engine of unknown peaks (the CPU's own cost), one prompt at a time, nothing reused.
    apart_eng, apart_calls = _engine(mc, UNKNOWN_DEVICE, cfg=dataclasses.replace(EC, prefix_cache_min=0))
    assert apart_eng.call_cost == UNKNOWN_DEVICE == Engine(mc, apart_eng.params, ByteTokenizer(), EC).call_cost
    apart_eng.start()
    try:
        apart = [_stream(apart_eng.submit(p, sp)) for p in prompts]
    finally:
        apart_eng.stop()
    # The cuts of fewest rows, a slot a call; the 20 went the cold way.
    assert [c[0] for c in apart_calls] == [(1, 64), (1, 64), (1, 32), (1, 64), (1, 32), (1, 8), (1, 64), (1, 32), (1, 32)]
    for got, want in zip(together, apart):
        # Six tokens: the first from the shared call, five decoded on what it left in the slot (pages, tails, state).
        assert len(want) == 6 and [t for t, _ in got] == [t for t, _ in want]
        np.testing.assert_allclose([lp for _, lp in got], [lp for _, lp in want], atol=2e-5)


def test_a_hit_on_a_family_that_reuses_whole_calls_runs_the_very_calls_its_cold_prefill_ends_with():
    """smallthinker (REUSE_WHOLE_PREFILL_CALLS) under the injected cost: a
    prompt's hit is cut at an edge of ITS cold plan as the cost cuts it,
    and what is left runs in calls of ONE slot at the prompt's own rows,
    whoever shares the round: the program, shape and offsets its cold
    prefill ends with, so the same bits (models/deepseek.py). 150 tokens
    are [64, 64, 32]: cut at 128. 100 tokens are [64, 64] and no longer
    [64, 32, 8]: cut at 64 and not at 96. 180 tokens are [64, 64, 64]: cut
    at 128."""
    mc = FAMILIES["window_experts"]
    assert family(mc).REUSE_WHOLE_PREFILL_CALLS and pair_rows(EC, mc) == () and pair_rows(EC, DENSE) == (16, 32, 64)
    assert [rows for rows, _ in prefill_plan(EC, 100)] == [64, 32, 8] and [rows for rows, _ in prefill_plan(EC, 100, INJECTED)] == [64, 64]
    sp = SamplingParams(max_tokens=2, temperature=0.0)
    long = _prompt(5, 150)
    prompts = [long, long[:100], long[:140] + _prompt(6, 40)]
    eng, calls = _engine(mc, INJECTED)
    eng.start()
    try:
        eng.generate(long, sp, timeout=300)
        assert [c[0] for c in calls] == [(1, 64), (1, 64), (1, 32)]
        del calls[:]
        cached0 = eng.m_prefix_cached.value()
        hits = [_stream(r) for r in OneRound(eng).submit(prompts, sp)]
    finally:
        eng.stop()
    assert calls == [((1, 64), [64], [36], [1]), ((1, 64), [128], [52], [2]), ((1, 32), [128], [22], [0])]
    assert eng.m_prefix_cached.value() - cached0 == 128 + 64 + 128
    # Cold, each alone on an engine that caches nothing: the calls end alike, and the stream is the same to the bit.
    cold_eng, cold_calls = _engine(mc, INJECTED, cfg=dataclasses.replace(EC, prefix_cache_min=0))
    cold_eng.start()
    try:
        for prompt, hit, tail in zip(prompts, hits, (calls[2], calls[0], calls[1])):
            del cold_calls[:]
            cold = _stream(cold_eng.submit(prompt, sp))
            assert [c[:3] for c in cold_calls[-1:]] == [tail[:3]]  # shape, offset, real tokens
            assert len(hit) == 2 and hit == cold
    finally:
        cold_eng.stop()


def test_warm_up_measures_the_cost_where_the_device_is_known_and_nothing_else_sets_it():
    """ONE source of the cost. An engine starts at UNKNOWN_DEVICE whatever
    its device (no reckoning from the peaks: they under-price a family's
    rows) and stays there unless warm-up measures: on the CPU (peaks
    unknown) no time of a test machine plans anything; where the two peaks
    are known warm-up runs every program, then the two widest one-slot
    chunk calls four times each, turn about, and takes the line through
    them."""
    from kubeai_tpu.obs import perf as perf_obs

    mc = FAMILIES["dense"]
    eng, calls = _engine(mc, UNKNOWN_DEVICE)
    eng.warmup()
    assert eng.call_cost == UNKNOWN_DEVICE and not [c for c in calls if c[0][0] == 1 and c[1] != [0]]
    measured = eng._measure_call_cost()
    assert measured == UNKNOWN_DEVICE or (measured[0] >= 0 and measured[1] > 0)

    known = perf_obs.DeviceEnv(kind="a chip of the tables", peak_flops=197e12, hbm_gbps=819.0)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(perf_obs, "detect_device", lambda: known)
        eng = Engine(mc, family(mc).init_params(mc, jax.random.key(43)), ByteTokenizer(), EC)
    assert eng.call_cost == UNKNOWN_DEVICE and all(eng._perf_constants())
    timed = []
    step = eng._step

    def spy(member, shape, *args, **kw):
        timed.append((member, shape))
        return step(member, shape, *args, **kw)

    eng._step = spy
    eng.warmup()
    n = len(eng._table.programs.calls())
    assert timed[n:] == [("prefill_chunk_jit", (1, 32)), ("prefill_chunk_jit", (1, 64))] * 4
    assert eng.call_cost == UNKNOWN_DEVICE or (eng.call_cost[0] >= 0 and eng.call_cost[1] > 0)
    debug = eng._perf_debug_section()["prefill_call_cost"]
    assert sorted(debug) == ["plan_1500", "read_weights_ms", "row_us", "two_slot_rows"]
    assert debug["read_weights_ms"] == round(eng.call_cost[0] * 1e3, 3)


def test_a_gang_follower_replays_a_call_of_two_slots():
    from test_gang_protocol import SECRET, _sync, connect_pair  # noqa: F401

    from kubeai_tpu.engine.core import build_test_engine
    from kubeai_tpu.engine.gang import GangPublisher

    cfg = EngineConfig(max_slots=4, max_seq_len=256, prefill_buckets=(16, 32, 64), prefix_cache_min=0)
    follower_eng = build_test_engine(cfg)
    pub = GangPublisher(1, port=0, host="127.0.0.1", secret=SECRET)
    fol = connect_pair(pub)
    leader = Engine(follower_eng.model_config, follower_eng.params, follower_eng.tokenizer, cfg, publisher=pub)
    leader.call_cost = INJECTED
    seen = []
    real_publish = pub.publish

    def spying_publish(op, scalars=None, arrays=None):
        if op == "prefill_chunk":
            seen.append((scalars, {k: v.shape for k, v in arrays.items()}))
        real_publish(op, scalars, arrays)

    pub.publish = spying_publish
    t = threading.Thread(target=follower_eng.run_follower, args=(fol,), daemon=True)
    t.start()
    leader.start()
    try:
        sp = SamplingParams(max_tokens=5, temperature=0.0)
        for r in OneRound(leader).submit([_prompt(8, 150), _prompt(9, 120)], sp):
            _stream(r)
        assert [a["tokens"] for _, a in seen] == [(2, 128), (1, 32)] and not any(s for s, _ in seen)
        assert seen[0][1]["starts"] == seen[0][1]["slots"] == seen[0][1]["seeds"] == (2,)
        # The follower's carries converge on the leader's: it ran the same two-slot program.
        want = np.asarray(jax.device_get(leader._lengths))
        np.testing.assert_array_equal(_sync(lambda: follower_eng._lengths, want), want)
        np.testing.assert_array_equal(np.asarray(jax.device_get(follower_eng._last_tokens)), np.asarray(jax.device_get(leader._last_tokens)))
    finally:
        leader.stop()
        t.join(timeout=20)
    assert not t.is_alive()
