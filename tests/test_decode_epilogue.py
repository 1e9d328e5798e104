"""The decode step's optional epilogue (top-N alternatives, sampling
candidates, penalties) runs only in the chunks where an active slot asked
for it. Mixed batches: a request that needs one part joins others that are
decoding and leaves before they end, so the gate opens and shuts around
them; every stream is the one its request gets without the company, and
the counter says which chunks ran what. The gates are evaluated once a
dispatch, so every case runs at chunks of 1, 3 and 4 steps."""

import re

import jax
import numpy as np
import pytest

from kubeai_tpu.engine.core import Engine, EngineConfig
from kubeai_tpu.engine.sampling import EPILOGUE_PARTS, SamplingParams
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import llama
from kubeai_tpu.models.base import ModelConfig

CFG = ModelConfig(
    vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, dtype="float32", max_position=1024,
)


def _prompt(seed: int, n: int = 20) -> list[int]:
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


# Hosts decode for a long time; guests are short.
GREEDY_A = (_prompt(11), SamplingParams(temperature=0.0, max_tokens=300))
GREEDY_B = (_prompt(12, 27), SamplingParams(temperature=0.0, max_tokens=300))
SAMPLED_HOST = (  # never draws the end of the stream (id 257)
    _prompt(13),
    SamplingParams(
        temperature=0.8, top_p=0.9, seed=77, max_tokens=300, logit_bias=((257, -100.0),)
    ),
)
WANTS_TOP = (_prompt(21), SamplingParams(temperature=0.0, max_tokens=9, logprobs=True))
SAMPLED = (
    _prompt(22), SamplingParams(temperature=0.8, top_p=0.9, seed=1234, max_tokens=9)
)
PENALIZED = (
    _prompt(23),
    SamplingParams(
        temperature=0.0, max_tokens=9, presence_penalty=0.7, frequency_penalty=0.4
    ),
)

# name -> (hosts, guests one after another, parts whose gate must open and
# shut again while the hosts decode, parts that stay open throughout)
CASES = {
    "logprobs": ([GREEDY_A, GREEDY_B], [WANTS_TOP], ["top_logprobs"], []),
    "sampled": ([GREEDY_A, GREEDY_B], [SAMPLED], ["candidates"], []),
    "penalties": ([GREEDY_A, GREEDY_B], [PENALIZED], ["penalties"], []),
    # The seeded sampled stream is the host here: the other two gates
    # flip around it.
    "around_sampled": (
        [SAMPLED_HOST, GREEDY_A], [WANTS_TOP, PENALIZED],
        ["top_logprobs", "penalties"], ["candidates"],
    ),
}


class _Engines:
    """One engine a chunk length, built on first use; the streams of a
    set of requests decoding together with no guest, computed once."""

    def __init__(self):
        self._engines: dict[int, Engine] = {}
        self._baselines: dict = {}

    def get(self, chunk: int) -> Engine:
        if chunk not in self._engines:
            eng = Engine(
                CFG, llama.init_params(CFG, jax.random.key(31)), ByteTokenizer(),
                EngineConfig(
                    max_slots=4, max_seq_len=512, prefill_buckets=(32, 64),
                    page_size=16, decode_chunk=chunk,
                ),
            )
            eng.start()
            self._engines[chunk] = eng
        return self._engines[chunk]

    def baseline(self, chunk: int, requests: list) -> list:
        key = (chunk, tuple(id(r) for r in requests))
        if key not in self._baselines:
            eng = self.get(chunk)
            reqs = [eng.submit(list(p), sp) for p, sp in requests]
            self._baselines[key] = [_drain(r) for r in reqs]
        return self._baselines[key]

    def stop(self):
        for eng in self._engines.values():
            eng.stop()


@pytest.fixture(scope="module")
def engines():
    e = _Engines()
    yield e
    e.stop()


def _drain(req, first: int | None = None) -> list:
    """(token, logprob, top alternatives) of a request's stream: all of it,
    or its first *first* tokens."""
    out = []
    while first is None or len(out) < first:
        ev = req.out.get(timeout=120)
        if ev[0] == "token":
            if ev[1] >= 0:
                out.append((ev[1], ev[3], ev[4]))
        elif ev[0] == "done":
            assert first is None, "a host ended before its guests had come"
            break
        else:
            raise RuntimeError(ev[1])
    return out


def _assert_same_stream(got: list, want: list):
    """Tokens and the alternatives' ids are equal; log-probs agree to the
    last bits: on the CPU a row's float32 sums differ by an ulp with what
    the OTHER rows of the batch hold (measured on the parent commit too:
    1e-6 at most, with or without a guest that asks for anything)."""
    assert [t for t, _, _ in got] == [t for t, _, _ in want]
    np.testing.assert_allclose([lp for _, lp, _ in got], [lp for _, lp, _ in want], rtol=0, atol=5e-6)
    for (_, _, top), (_, _, top_want) in zip(got, want):
        assert (top is None) == (top_want is None)
        if top is not None:
            assert [i for i, _ in top] == [i for i, _ in top_want]
            np.testing.assert_allclose([lp for _, lp in top], [lp for _, lp in top_want], rtol=0, atol=5e-6)


class _GateLog:
    """Stands in for the engine's counter: keeps, per dispatched chunk, the
    `ran` of each part, and counts on."""

    def __init__(self, counter):
        self.counter = counter
        self.chunks: list[dict[str, str]] = []

    def inc(self, amount=1.0, labels=None):
        if not self.chunks or labels["part"] in self.chunks[-1]:
            self.chunks.append({})
        self.chunks[-1][labels["part"]] = labels["ran"]
        self.counter.inc(amount, labels=labels)

    def of(self, part: str) -> str:
        return "".join(c[part] for c in self.chunks)


@pytest.mark.parametrize("chunk", [3, 1, 4], ids=["plain", "chunk1", "chunk4"])
@pytest.mark.parametrize("case", list(CASES))
def test_a_guest_joins_and_leaves_and_every_stream_is_its_own(engines, monkeypatch, case, chunk):
    hosts, guests, flipping, steady = CASES[case]
    eng = engines.get(chunk)
    alone = [engines.baseline(chunk, [g])[0] for g in guests]
    hosts_alone = engines.baseline(chunk, hosts)
    log = _GateLog(eng.m_epilogue)
    monkeypatch.setattr(eng, "m_epilogue", log)

    # Both hosts are queued within ONE turn of the scheduler (the thunk runs on
    # its thread), so one round admits them and they end in one chunk: admitted
    # a round apart, the host that holds a steady gate open would end a chunk
    # before the other and the gate's log would end in a `0`.
    host_reqs = eng._await_aux(eng._submit_aux(lambda: [eng.submit(list(p), sp) for p, sp in hosts]), what="the hosts")
    heads = [_drain(r, first=4) for r in host_reqs]  # the hosts are decoding
    in_company = []
    for p, sp in guests:
        in_company.append(_drain(eng.submit(list(p), sp)))
    tails = [_drain(r) for r in host_reqs]

    # The guest's tokens, log-probs and alternatives at every position.
    for (_, sp), got, want in zip(guests, in_company, alone):
        _assert_same_stream(got, want)
        assert len(got) == sp.max_tokens
        if sp.logprobs:
            assert all(top is not None and len(top) == 5 for _, _, top in got)
            # The chosen greedy token leads its alternatives, at its log-prob.
            assert all(top[0] == (tok, lp) for tok, lp, top in got)
        else:
            assert all(top is None for _, _, top in got)
    # The hosts' streams, as without the guests.
    for head, tail, want in zip(heads, tails, hosts_alone):
        _assert_same_stream(head + tail, want)
    # The gate was shut, opened for its guest, and shut again before the
    # hosts ended.
    assert len(log.chunks) > 20
    for part in flipping:
        assert re.fullmatch("0+1+0+", log.of(part)), (part, log.of(part))
    for part in steady:
        assert set(log.of(part)) == {"1"}, (part, log.of(part))
    for part in set(EPILOGUE_PARTS) - set(flipping) - set(steady):
        assert set(log.of(part)) == {"0"}, (part, log.of(part))


def test_the_counter_moves_by_one_a_part_and_chunk(engines):
    eng = engines.get(3)
    value = lambda part, ran: eng.m_epilogue.value({"part": part, "ran": ran})  # noqa: E731
    before = {(p, r): value(p, r) for p in EPILOGUE_PARTS for r in "01"}
    toks = _drain(eng.submit(_prompt(5), SamplingParams(temperature=0.0, max_tokens=12, logprobs=True)))
    assert len(toks) == 12
    moved = {k: value(*k) - v for k, v in before.items()}
    # 12 tokens: the first from the prefill, 11 from chunks of 3 steps; the
    # pipelined loop may have one more chunk in flight when the last lands.
    n = moved[("top_logprobs", "1")]
    assert n in (4, 5)
    assert moved == {
        ("top_logprobs", "1"): n, ("top_logprobs", "0"): 0,
        ("candidates", "1"): 0, ("candidates", "0"): n,
        ("penalties", "1"): 0, ("penalties", "0"): n,
    }
