"""How one admission round's same-bucket cold prompts are cut into prefill
calls: `prefill_group_cap` rows while that many prompts are left, then one
row a prompt, on the two row counts warm-up compiled. No call computes a
row nobody sent, so a round's padding is its prompts' bucket tails; and a
prompt admitted in company streams what it streams alone."""

import threading

import numpy as np
import pytest

from kubeai_tpu.engine.core import EngineConfig, build_test_engine
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.obs.recorder import default_recorder

CAP = 8
BUCKETS = (16, 32, 64, 128)


def _config(**kw) -> EngineConfig:
    return EngineConfig(
        max_slots=20, max_seq_len=256, prefill_buckets=BUCKETS,
        prefill_group_cap=CAP, decode_chunk=4, **kw,
    )


def _greedy(max_tokens: int) -> SamplingParams:
    # Never the end of the stream (id 257): every request runs its length.
    return SamplingParams(temperature=0.0, max_tokens=max_tokens, logit_bias=((257, -100.0),))


def _prompt(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


def _stream(req, timeout: float = 120) -> list[tuple[int, float]]:
    """(token id, log-prob) of every token the request streamed."""
    out = []
    while True:
        ev = req.out.get(timeout=timeout)
        if ev[0] == "token":
            if ev[1] >= 0:
                out.append((ev[1], ev[3]))
        elif ev[0] == "done":
            return out
        else:
            raise RuntimeError(ev[1])


class OneRound:
    """Holds the scheduler at the door of its admission round while a test
    submits, so that everything submitted is planned in ONE round."""

    def __init__(self, eng):
        self.eng = eng
        self._open = threading.Event()
        self._open.set()
        self._parked = threading.Event()
        inner = eng._admit_waiting

        def gated():
            if not self._open.is_set():
                self._parked.set()
                assert self._open.wait(120)
            return inner()

        eng._admit_waiting = gated

    def submit(self, prompts, params) -> list:
        self._parked.clear()
        self._open.clear()
        try:
            assert self._parked.wait(60), "the scheduler never came to its admission round"
            return [self.eng.submit(p, params) for p in prompts]
        finally:
            self._open.set()


@pytest.fixture(scope="module")
def served():
    eng = build_test_engine(_config())
    eng.warmup()
    eng.start()
    try:
        yield eng, OneRound(eng), eng._jit_cache_entries()
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def alone():
    """The same weights with the prefix cache off: every prompt sent here
    is a cold one-row call, whatever was sent before it."""
    eng = build_test_engine(_config(prefix_cache_min=0))
    eng.start()
    try:
        yield eng
    finally:
        eng.stop()


def _group_steps(bucket: int) -> list[dict]:
    steps = [s for s in default_recorder.engine_steps() if s["kind"] == "prefill_group"]
    steps.reverse()  # oldest first: the order of dispatch
    return [s for s in steps if s["bucket"] == bucket]


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6, 7, 8, 9, 16, 17])
def test_a_round_of_same_bucket_prompts_runs_as_full_groups_and_single_rows(served, n):
    eng, one_round, warmed = served
    # Lengths 17-31: bucket 32, and too short to hold a whole page, so
    # nothing of them is ever found in the prefix cache.
    prompts = [_prompt(1000 * n + i, 17 + (5 * i + n) % 15) for i in range(n)]
    tails = [32 - len(p) for p in prompts]
    default_recorder.clear()
    pad0 = eng.m_pad_prefill.value()
    rows0 = {k: eng.m_prefill_rows.value(labels={"kind": k}) for k in ("real", "duplicate")}
    compiled0 = eng.m_recompiles.value()

    reqs = one_round.submit(prompts, _greedy(2))
    for r in reqs:
        assert len(_stream(r)) == 2

    steps = _group_steps(32)
    want = [CAP] * (n // CAP) + [1] * (n % CAP)
    assert [s["batch"] for s in steps] == want
    # Every call's padding is the bucket tails of its own rows, in the
    # order the prompts were planned.
    at = 0
    for s in steps:
        assert s["pad_tokens"] == sum(tails[at : at + s["batch"]])
        assert s["prompt_tokens"] == sum(len(p) for p in prompts[at : at + s["batch"]])
        at += s["batch"]
    assert eng.m_pad_prefill.value() - pad0 == sum(tails)
    assert eng.m_prefill_rows.value(labels={"kind": "real"}) - rows0["real"] == n
    assert eng.m_prefill_rows.value(labels={"kind": "duplicate"}) - rows0["duplicate"] == 0
    # Both row counts were compiled by warm-up: nothing compiled since.
    assert eng._jit_cache_entries() == warmed
    assert eng.m_recompiles.value() == compiled0


def _streams_together_and_alone(served, alone, prompts):
    """Sends *prompts* in one round, compares each stream with the one the
    prompt gets alone, and returns the round's prefill steps in order."""
    eng, one_round, warmed = served
    sp = _greedy(9)
    default_recorder.clear()
    together = [_stream(r) for r in one_round.submit(prompts, sp)]
    steps = list(reversed(default_recorder.engine_steps()))
    for prompt, got in zip(prompts, together):
        want = _stream(alone.submit(prompt, sp))
        assert len(want) == 9  # the first token and the eight after it
        assert [t for t, _ in got] == [t for t, _ in want]
        assert [lp for _, lp in got] == pytest.approx([lp for _, lp in want], abs=1e-4)
    assert eng._jit_cache_entries() == warmed
    return [s for s in steps if s["kind"].startswith("prefill")]


def test_three_prompts_admitted_together_stream_what_each_streams_alone(served, alone):
    prompts = [_prompt(71, 40), _prompt(72, 57), _prompt(73, 33)]  # bucket 64, under a page
    steps = _streams_together_and_alone(served, alone, prompts)
    assert [(s["kind"], s["bucket"], s["batch"]) for s in steps] == [("prefill_group", 64, 1)] * 3
    assert [s["slots"] for s in steps] == sorted(s["slots"] for s in steps)  # planned order


def test_a_prompt_that_reuses_a_page_of_its_round_is_prefilled_after_the_page_is_written(served, alone):
    """The first and the second prompt share their first page (64 tokens):
    the first is cold and writes it, the second was planned on top of it
    and goes the chunked way, after every cold call of the round; the
    third is cold and was planned after the second."""
    head = _prompt(81, 64)
    prompts = [head + _prompt(82, 30), head + _prompt(83, 41), _prompt(84, 99)]
    steps = _streams_together_and_alone(served, alone, prompts)
    assert [(s["kind"], s["bucket"], s["batch"]) for s in steps[:2]] == [("prefill_group", 128, 1)] * 2
    assert [s["prompt_tokens"] for s in steps[:2]] == [94, 99]
    assert [(s["kind"], s["prompt_tokens"], s["reuse_tokens"]) for s in steps[2:]] == [("prefill_chunked", 105, 64)]


def test_debug_engine_carries_the_rows_pair(served):
    eng, _, _ = served
    rows = eng._perf_debug_section()["prefill_rows"]
    assert set(rows) == {"real", "duplicate"}
    assert rows["real"] == eng.m_prefill_rows.value(labels={"kind": "real"}) > 0
    assert rows["duplicate"] == 0
