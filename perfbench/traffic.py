"""The one traffic generator: a traffic mix is a data file under
`perfbench/traffic/`, this module turns it and `--seed` into requests.

Every seed gets THE SAME SET of sizes and arrival gaps in another order
(and other prompt bytes): sizes are the quantiles of the distribution the
file names, laid out in blocks of `block` requests, and the seed only
permutes inside a block. So two runs differ in order, never in the amount
of work, and any `block` consecutive requests hold the whole distribution.

Tokens are the engine's byte tokenizer's: a prompt of N tokens is BOS plus
N-1 ASCII bytes, so lengths are exact without a tokenizer.

A file has:
  loop            "closed" (clients, each sends its next request when the
                  last one ended) or "open" (arrivals on a schedule)
  clients         closed loop: how many
  rate_rps        open loop: mean arrivals per second; "arrivals": "poisson"
  ramp_s          seconds the load runs before the window opens (set-up)
  drain_s         seconds in-flight requests get after the window closes
  block           requests per block (see above)
  prompt_tokens,  {"dist": "lognormal", "median", "sigma", "min", "max"} or
  output_tokens   {"dist": "uniform", "min", "max"}
  max_total_tokens  prompt + output is clipped to it (the engine's max-seq-len)
  sessions        optional: {"documents_per_client", "document_tokens",
                  "asks_per_document", "question_tokens", "doc_block"
                  (documents per block, see above), "stagger_s"
                  (client c starts c * stagger_s into the ramp, so the
                  clients' cold prefills do not fall together)}: each client asks
                  each of its documents several times, a different question
                  after the same document (shared prefix, closed loop only)
  rehearsal       overrides applied by --rehearse (CPU, tiny)
"""

from __future__ import annotations

import json
import math
import os
import random
import statistics
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
# Letters, digits and space: one byte, one token each, nothing the byte
# tokenizer's chat template or a UTF-8 decoder treats specially.
ALPHABET = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 "


@dataclass
class Request:
    prompt: str
    max_tokens: int
    tag: str = ""
    due_s: float | None = None  # open loop: seconds from the load's start

    @property
    def prompt_tokens(self) -> int:
        return len(self.prompt) + 1  # BOS


@dataclass
class Plan:
    loop: str
    ramp_s: float
    drain_s: float
    # closed loop without sessions: one shared list the clients pull from;
    # with sessions: one list per client. open loop: one list with due_s.
    shared: list[Request] = field(default_factory=list)
    per_client: list[list[Request]] = field(default_factory=list)
    clients: int = 0
    stagger_s: float = 0.0  # sessions: client c starts c * stagger_s late


def load(name: str, rehearse: bool = False) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        spec = json.load(f)
    if rehearse:
        spec = {**spec, **spec.get("rehearsal", {})}
    return spec


def quantiles(dist: dict, n: int) -> list[int]:
    """The n mid-quantiles of *dist*, as whole token counts."""
    lo, hi = dist["min"], dist["max"]
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            z = statistics.NormalDist().inv_cdf(q)
            v = dist["median"] * math.exp(dist["sigma"] * z)
        elif dist["dist"] == "uniform":
            v = lo + q * (hi - lo)
        else:
            raise ValueError(f"unknown dist {dist['dist']!r}")
        out.append(int(min(max(round(v), lo), hi)))
    return out


def text(rng: random.Random, tokens: int) -> str:
    """ASCII text that tokenizes to *tokens* ids with its BOS."""
    return "".join(rng.choices(ALPHABET, k=max(tokens - 1, 1)))


def _pairs(spec: dict, n_blocks: int, seed: int) -> list[tuple[int, int]]:
    """(prompt, output) sizes: per block the same multiset, paired by a
    shuffle fixed in the file (`sizes_seed`), ordered by the run's seed."""
    k = spec["block"]
    fixed = random.Random(spec.get("sizes_seed", 0))
    prompts = quantiles(spec["prompt_tokens"], k)
    outputs = quantiles(spec["output_tokens"], k)
    fixed.shuffle(outputs)
    cap = spec["max_total_tokens"]
    block = [(min(p, cap - o), o) for p, o in zip(prompts, outputs)]
    order = random.Random(seed * 7919 + 1)
    out = []
    for _ in range(n_blocks):
        b = list(block)
        order.shuffle(b)
        out.extend(b)
    return out


def build(spec: dict, seed: int, seconds: float) -> Plan:
    rng = random.Random(seed)
    ramp, drain = float(spec.get("ramp_s", 0)), float(spec.get("drain_s", 60))
    plan = Plan(loop=spec["loop"], ramp_s=ramp, drain_s=drain)
    if spec["loop"] == "open":
        rate = float(spec["rate_rps"])
        # Whole blocks only: pick `block` so that rate x (ramp + run_seconds)
        # is a multiple of it and the last block ends with the window.
        n = int(math.ceil(rate * (ramp + seconds) / spec["block"] - 1e-9))
        pairs = _pairs(spec, n, seed)
        # Poisson arrivals: per block the exponential's quantiles as
        # gaps, shuffled, so every block lasts exactly block / rate.
        k = spec["block"]
        gaps_block = [-math.log(1 - (i + 0.5) / k) for i in range(k)]
        scale = k / (rate * sum(gaps_block))
        order = random.Random(seed * 104729 + 2)
        t, reqs = 0.0, []
        for b in range(n):
            gaps = list(gaps_block)
            order.shuffle(gaps)
            for j, g in enumerate(gaps):
                t += g * scale
                p, o = pairs[b * k + j]
                reqs.append(Request(text(rng, p), o, "open", due_s=t))
        plan.shared = reqs  # the pacer stops at the window's end
        return plan
    plan.clients = int(spec["clients"])
    sess = spec.get("sessions")
    if not sess:
        pairs = _pairs(spec, int(spec.get("blocks", 64)), seed)
        plan.shared = [Request(text(rng, p), o, "closed") for p, o in pairs]
        return plan
    asks, docs = int(sess["asks_per_document"]), int(sess["documents_per_client"])
    cap = spec["max_total_tokens"]
    plan.stagger_s = float(sess.get("stagger_s", 0))
    k = int(sess.get("doc_block", docs))  # documents per block
    for c in range(plan.clients):
        # A block of `doc_block` documents holds the whole distribution of
        # document, question and answer sizes; which question and answer
        # sizes go with which document is fixed in the file (`sizes_seed`).
        # The run's seed orders the documents inside each block, so any
        # stretch of a client's script is the same work for every seed.
        fixed = random.Random(spec.get("sizes_seed", 0) * 1009 + c)
        doc_sizes = quantiles(sess["document_tokens"], k)
        q_sizes = quantiles(sess["question_tokens"], k * asks)
        o_sizes = quantiles(spec["output_tokens"], k * asks)
        for sizes in (q_sizes, o_sizes):
            fixed.shuffle(sizes)
        order = random.Random(seed * 15485863 + c)
        script = []
        for _ in range(-(-docs // k)):
            block = list(range(k))
            order.shuffle(block)
            for d in block:
                dsize = doc_sizes[d]
                document = text(rng, dsize)
                for a in range(asks):
                    q, o = q_sizes[d * asks + a], o_sizes[d * asks + a]
                    q = min(q, cap - o - dsize)
                    # BOS + document + question: the document's pages are
                    # the same ids every ask, which is what the prefix
                    # cache keys on.
                    prompt = document + text(rng, q + 1)
                    script.append(Request(prompt, o, "miss" if a == 0 else "hit"))
        plan.per_client.append(script)
    return plan
