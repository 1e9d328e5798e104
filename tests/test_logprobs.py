"""Chosen-token logprobs: engine events must carry log p(token|prefix)
that matches an independent model forward, and the OpenAI server must
surface them in both API shapes."""

import json
import urllib.request

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


@pytest.fixture(scope="module")
def engine():
    params = llama.init_params(CFG, jax.random.key(31))
    eng = Engine(
        CFG, params, ByteTokenizer(),
        EngineConfig(max_slots=2, max_seq_len=256, prefill_buckets=(32, 64, 128),
                     page_size=16, decode_chunk=4),
    )
    eng.start()
    yield eng
    eng.stop()


def drain_with_logprobs(req):
    toks, lps = [], []
    while True:
        ev = req.out.get(timeout=120)
        if ev[0] == "token":
            if ev[1] >= 0:
                toks.append(ev[1])
                lps.append(ev[3])
        elif ev[0] == "done":
            return toks, lps
        else:
            raise RuntimeError(ev[1])


def test_logprobs_match_independent_forward(engine):
    """Greedy run: each emitted token's logprob must equal
    log_softmax(logits at its position)[token] from a from-scratch
    no-cache forward over the full sequence."""
    prompt = np.random.default_rng(1).integers(1, 200, 24).tolist()
    req = engine.submit(list(prompt), SamplingParams(temperature=0.0, max_tokens=8))
    toks, lps = drain_with_logprobs(req)
    assert len(toks) == 8 and all(lp is not None for lp in lps)

    seq = prompt + toks
    tokens = jnp.asarray([seq], jnp.int32)
    pos = jnp.arange(len(seq), dtype=jnp.int32)[None, :]
    logits, _ = llama.apply(engine.params, CFG, tokens, pos)
    logits = logits.at[..., 259:].set(-jnp.inf)  # engine's pad mask
    lp_all = jax.nn.log_softmax(logits, axis=-1)
    for j, (tok, lp) in enumerate(zip(toks, lps)):
        want = float(lp_all[0, len(prompt) - 1 + j, tok])
        assert lp == pytest.approx(want, abs=2e-3), f"token {j}"


def test_logprobs_present_for_sampled(engine):
    prompt = np.random.default_rng(2).integers(1, 200, 16).tolist()
    req = engine.submit(
        list(prompt), SamplingParams(temperature=0.9, max_tokens=6, seed=3)
    )
    toks, lps = drain_with_logprobs(req)
    assert len(toks) == 6
    assert all(lp is not None and lp <= 0.0 for lp in lps)


@pytest.fixture(scope="module")
def server(engine):
    from kubeai_tpu.engine.server import EngineServer

    srv = EngineServer(engine, "m", host="127.0.0.1", port=0)
    srv.start()
    yield srv
    srv.stop()


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def test_completions_api_logprobs(server):
    out = _post(server.port, "/v1/completions", {
        "model": "m", "prompt": "hello world", "max_tokens": 5,
        "temperature": 0, "logprobs": 1,
    })
    lp = out["choices"][0]["logprobs"]
    assert len(lp["tokens"]) == len(lp["token_logprobs"]) == 5
    assert all(isinstance(x, float) and x <= 0.0 for x in lp["token_logprobs"])
    # And absent when not requested.
    out2 = _post(server.port, "/v1/completions", {
        "model": "m", "prompt": "hello world", "max_tokens": 3, "temperature": 0,
    })
    assert "logprobs" not in out2["choices"][0]


def test_chat_api_logprobs(server):
    out = _post(server.port, "/v1/chat/completions", {
        "model": "m", "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4, "temperature": 0, "logprobs": True,
    })
    content = out["choices"][0]["logprobs"]["content"]
    assert len(content) == 4
    assert all(c["logprob"] <= 0.0 for c in content)
    # Token strings are the tokens' OWN text, not stream deltas: with the
    # byte tokenizer every generated token decodes to exactly one char.
    assert all(len(c["token"]) == 1 for c in content)


def test_completions_logprobs_zero_is_valid(server):
    """OpenAI semantics: logprobs=0 still returns chosen-token logprobs
    (zero alternatives) — 0 must not be treated as 'disabled'."""
    out = _post(server.port, "/v1/completions", {
        "model": "m", "prompt": "abc", "max_tokens": 3,
        "temperature": 0, "logprobs": 0,
    })
    assert len(out["choices"][0]["logprobs"]["token_logprobs"]) == 3


def test_streaming_logprobs(server):
    body = json.dumps({
        "model": "m", "messages": [{"role": "user", "content": "hey"}],
        "max_tokens": 3, "temperature": 0, "logprobs": True, "stream": True,
    }).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/v1/chat/completions", data=body,
        headers={"Content-Type": "application/json"},
    )
    lps = []
    with urllib.request.urlopen(req, timeout=120) as resp:
        for line in resp:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            choice = json.loads(line[6:])["choices"][0]
            for c in (choice.get("logprobs") or {}).get("content", []):
                lps.append(c["logprob"])
    assert len(lps) == 3
    assert all(lp <= 0.0 for lp in lps)


def test_top_logprobs_completions_and_chat(server):
    """OpenAI top-N alternatives (r5: previously a documented gap):
    completions `logprobs: N` returns per-position token->logprob maps
    of size <= N whose best entry is at least the chosen logprob; chat
    `top_logprobs: N` returns entry lists; N beyond the engine cap 400s."""
    out = _post(server.port, "/v1/completions", {
        "model": "m", "prompt": "top lp", "max_tokens": 4,
        "temperature": 0.0, "logprobs": 3,
    })
    lp = out["choices"][0]["logprobs"]
    assert len(lp["top_logprobs"]) == len(lp["token_logprobs"]) >= 1
    for chosen_lp, top in zip(lp["token_logprobs"], lp["top_logprobs"]):
        assert 1 <= len(top) <= 3
        best = max(top.values())
        assert best >= chosen_lp - 1e-5
    out = _post(server.port, "/v1/chat/completions", {
        "model": "m", "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4, "temperature": 0.0,
        "logprobs": True, "top_logprobs": 2,
    })
    content = out["choices"][0]["logprobs"]["content"]
    assert content and all(
        1 <= len(e["top_logprobs"]) <= 2 and "token" in e["top_logprobs"][0]
        for e in content
    )
    # Greedy: the chosen token IS the argmax, so it heads the top list.
    assert content[0]["top_logprobs"][0]["logprob"] == content[0]["logprob"]
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server.port, "/v1/completions", {
            "model": "m", "prompt": "x", "max_tokens": 2, "logprobs": 50,
        })
    assert ei.value.code == 400
