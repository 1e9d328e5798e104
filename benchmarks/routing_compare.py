"""Routing-strategy comparison: RoundRobin vs LeastLoad vs PrefixHash.

Reproduces the reference's flagship benchmark methodology
(ref: docs/benchmarks/prefix-aware-load-balancing.md — same multi-turn
workload against the same replicas under each routing strategy,
reporting req/s, mean/p50 TTFT, ITL, and token throughput) against THIS
framework's full local stack: Manager + LocalRuntime spawn N real
engine-server replicas, and each strategy run only flips the Model's
loadBalancing.strategy. PrefixHash's edge comes from the engine's
cross-slot prefix cache: a conversation routed back to the same replica
skips re-prefilling its history.

    python benchmarks/routing_compare.py [--replicas 2] [--conversations 8]
        [--turns 3] [--max-tokens 32] [--dataset sharegpt.json] [--json out.json]

CPU note: without a TPU attached this runs the engines on CPU — relative
strategy differences are meaningful, absolute numbers are not.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_tiny_checkpoint() -> str:
    """HF-format tiny Llama checkpoint for the engine replicas (shared
    with the e2e suite — one source of truth for the shapes)."""
    from kubeai_tpu.engine.weights import save_tiny_test_checkpoint

    path = tempfile.mkdtemp(prefix="routing-compare-ckpt-")
    save_tiny_test_checkpoint(path)
    return path


def run_strategy(mgr, store, ckpt: str, strategy: str, args) -> dict:
    from kubeai_tpu.api import model_types as mt
    from kubeai_tpu.api.core_types import KIND_POD
    from kubeai_tpu.api.model_types import LoadBalancing, Model, ModelSpec, PrefixHash
    from kubeai_tpu.runtime.store import ObjectMeta

    from benchmarks.loadgen import load_sharegpt, run_benchmark

    name = f"bench-{strategy.lower()}"
    store.create(
        mt.KIND_MODEL,
        Model(
            meta=ObjectMeta(name=name),
            spec=ModelSpec(
                url=f"file://{ckpt}",
                engine=mt.ENGINE_TPU,
                resource_profile="cpu:1",
                min_replicas=args.replicas,
                # Seq space must hold the longest history: pad + all turns.
                args=[
                    "--max-seq-len",
                    str(max(1024, 2 * args.prefix_pad_chars + 512)),
                    "--max-slots", "4",
                ] + (
                    # Pool sizing is the regime switch (r5 measurement):
                    # with the default pool (~slots' worth of pages) a
                    # replica can hold only a handful of conversations,
                    # so at conversations >> replicas the prefix cache
                    # thrashes regardless of routing and LeastLoad's
                    # balance wins. The reference's benchmark regime has
                    # KV capacity for its conversations; --kv-pages
                    # reproduces that (size for conversations/replicas x
                    # history tokens / page_size).
                    ["--kv-pages", str(args.kv_pages)] if args.kv_pages else []
                ),
                load_balancing=LoadBalancing(strategy=strategy, prefix_hash=PrefixHash()),
            ),
        ),
    )
    deadline = time.time() + 240
    while time.time() < deadline:
        pods = store.list(KIND_POD, selector={mt.LABEL_MODEL: name})
        if len(pods) == args.replicas and all(p.status.ready for p in pods):
            break
        time.sleep(0.5)
    else:
        raise RuntimeError(f"{name}: replicas never became ready")

    from benchmarks.loadgen import synthetic_turns

    dataset = load_sharegpt(args.dataset) if args.dataset else None
    base_url = f"http://127.0.0.1:{mgr.api.port}/openai"
    # Warmup: compile prefill/decode on every replica outside the timed
    # window (the reference's runners discard warmup too). DISJOINT
    # prompts — warmup must not seed the prefix cache with the timed
    # run's conversations, or PrefixHash gets a contaminated head start.
    run_benchmark(
        base_url, name, conversations=args.replicas * 2, turns=1, max_tokens=4,
        dataset=[synthetic_turns(f"warmup-{i}", 1) for i in range(args.replicas * 2)],
    )
    summary = run_benchmark(
        base_url,
        name,
        conversations=args.conversations,
        turns=args.turns,
        max_tokens=args.max_tokens,
        dataset=dataset,
        request_rate=args.request_rate,
        max_concurrency=args.max_concurrency,
        prefix_pad_chars=args.prefix_pad_chars,
    )
    summary["strategy"] = strategy
    # Engine-side evidence for WHY a strategy wins: prompt tokens whose
    # prefill was skipped via cross-slot prefix-cache hits vs tokens
    # actually prefilled, summed over the replicas
    # (kubeai_engine_prefix_cached_tokens_total — the counter the
    # VERDICT asked to publish alongside the table).
    cached = prefilled = 0
    for p in store.list(KIND_POD, selector={mt.LABEL_MODEL: name}):
        port = p.meta.annotations.get(mt.ANNOTATION_MODEL_POD_PORT)
        if not port:
            continue
        try:
            import urllib.request

            from kubeai_tpu.metrics.registry import parse_prometheus_text

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                parsed = parse_prometheus_text(resp.read().decode())
            cached += sum(
                v for _, v in parsed.get("kubeai_engine_prefix_cached_tokens_total", [])
            )
            prefilled += sum(
                v for _, v in parsed.get("kubeai_engine_prefill_tokens_total", [])
            )
        except OSError:
            pass
    summary["prefix_cached_tokens"] = int(cached)
    summary["prefilled_tokens"] = int(prefilled)
    summary["prefix_hit_pct"] = round(
        100 * cached / max(cached + prefilled, 1), 1
    )

    store.delete(mt.KIND_MODEL, name)
    deadline = time.time() + 60
    while time.time() < deadline:
        if not store.list(KIND_POD, selector={mt.LABEL_MODEL: name}):
            break
        time.sleep(0.2)
    return summary


def render_table(rows: list[dict]) -> str:
    head = (
        "| strategy | req/s | mean TTFT (ms) | p50 TTFT (ms) | TPOT (ms) "
        "| out tok/s | prefix-cache hit |"
    )
    sep = "|---|---|---|---|---|---|---|"
    lines = [head, sep]
    for r in rows:
        lines.append(
            f"| {r['strategy']} | {r['req_per_s']} | {r['ttft_ms']['mean']} "
            f"| {r['ttft_ms']['p50']} | {r['tpot_ms']} | {r['output_tok_per_s']} "
            f"| {r.get('prefix_hit_pct', 0)}% |"
        )
    return "\n".join(lines)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--conversations", type=int, default=8)
    parser.add_argument("--turns", type=int, default=3)
    parser.add_argument("--max-tokens", type=int, default=32)
    parser.add_argument("--dataset", default=None, help="ShareGPT-format JSON")
    parser.add_argument("--request-rate", type=float, default=0.0)
    parser.add_argument("--max-concurrency", type=int, default=0)
    parser.add_argument(
        "--prefix-pad-chars", type=int, default=0,
        help="long unique context in each conversation's first turn — the "
             "re-prefill-dominated regime where prefix affinity pays",
    )
    parser.add_argument(
        "--kv-pages", type=int, default=0,
        help="per-replica KV pool pages (0 = engine default, which holds "
             "only ~max-slots conversations — see the pool-sizing note)",
    )
    parser.add_argument(
        "--strategies", default="RoundRobin,LeastLoad,PrefixHash",
        help="comma-separated strategy list",
    )
    parser.add_argument("--json", default=None, help="also write results JSON here")
    args = parser.parse_args()

    from kubeai_tpu.config.system import System
    from kubeai_tpu.manager import Manager

    import shutil

    ckpt = make_tiny_checkpoint()
    system = System().default_and_validate()
    mgr = Manager(system, local_runtime=True, host="127.0.0.1", port=0)
    # A CPU comparison: every replica is its own process, a chip belongs
    # to one process at a time, and LocalRuntime cannot yet give each
    # pod its own chip (ROADMAP B6). The replicas share the engine
    # CLI's compile cache (engine/coldstart.py), so later strategies'
    # replicas reuse the first's compiled programs.
    mgr.local_runtime.extra_env["JAX_PLATFORMS"] = "cpu"
    mgr.start()
    rows = []
    try:
        for strategy in [s.strip() for s in args.strategies.split(",") if s.strip()]:
            print(f"# running {strategy} ...", file=sys.stderr, flush=True)
            rows.append(run_strategy(mgr, mgr.store, ckpt, strategy, args))
    finally:
        mgr.stop()
        shutil.rmtree(ckpt, ignore_errors=True)

    print(render_table(rows))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
