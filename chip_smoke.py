#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py              # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4    # the sharded path and what it is compared with

One chip: serves Qwen2.5-7B-Instruct at its published widths (random
weights from --seed, int8) through the normal entry points — operator
(`python -m kubeai_tpu.manager --local`) -> OpenAI proxy ->
scale-from-zero -> engine subprocess -> checkpoint loader — answers a
handful of requests (streaming and not, concurrent, one over 1024 tokens,
one repeated prefix), reads back what the engine process says it ran on
and ran, stops the operator, and in a fresh child compares kernel-route
logits with the float32 portable route on the same weights.

Four chips (`--chips 4`): the same widths at 8 layers in bf16, served by
`python -m kubeai_tpu.engine.server` at --tensor-parallel-size 1 and then
4, one process each, the same prompts, first-position top-5 log-probs
compared; every chip must hold its quarter of the weights and the pool.

The last line of stdout is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
with the device as the engine process that answered reported it. Every
earlier line is one JSON object for one phase. Any failed check, any
child's non-zero exit or any phase that did not run is a non-zero exit
and no result. With no TPU visible it fails in its first phase.

This process never imports jax: a chip belongs to one process at a time,
and every phase that needs it is a child that has exited (or been
stopped) before the next one starts.

`--rehearse` walks every phase on the CPU at a tiny size to find wrong
paths and arguments before chip time is spent. It can not pass: the
device checks fail, the exit code is non-zero and no result is printed.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# The repo's own scrape parser (no jax behind it). Without the repo
# beside this file there is nothing to smoke.
from kubeai_tpu.metrics.registry import parse_prometheus_text  # noqa: E402

# Qwen/Qwen2.5-7B-Instruct, config.json as published.
QWEN25_7B = dict(
    vocab_size=152064, hidden_size=3584, intermediate_size=18944,
    num_layers=28, num_heads=28, num_kv_heads=4, rope_theta=1e6,
    rms_norm_eps=1e-6, qkv_bias=True, max_position=32768,
    tie_word_embeddings=False,
)
# --rehearse only: the same family at a size the CPU walks in seconds.
REHEARSAL = dict(
    vocab_size=384, hidden_size=128, intermediate_size=256,
    num_layers=4, num_heads=8, num_kv_heads=4, rope_theta=1e6,
    rms_norm_eps=1e-6, qkv_bias=True, max_position=32768,
    tie_word_embeddings=False,
)
MODEL = "qwen2.5-7b-instruct-smoke"
BYTE_EOS = 257  # engine/tokenizer.py ByteTokenizer (no tokenizer files)
TP_LAYERS = 8  # --chips 4: bf16 weights of 8 layers (5.9 GB) fit one chip
REF_LAYERS = 4  # logits check: depth that loads and compiles in about a minute

# Kernel route (bf16 activations, flash + ragged paged kernels) against
# the portable route in float32 at highest matmul precision, on the same
# int8 weights. Why these bounds: the logits of this random model have a
# standard deviation near 1.2 and reach 5-6; bf16 keeps 8 bits, so a
# logit of that size is rounded to 1/32, and each of the layers below it
# rounds its activations the same way. Measured at these widths and
# REF_LAYERS (PERF.md, PR 21): max 0.086 / mean 0.0136 on the CPU twins,
# max 0.060 / mean 0.0096 on the chip. The bounds leave about three
# times that.
LOGITS_MAX_ABS = 0.25
LOGITS_MEAN_ABS = 0.04
# tp=4 against tp=1, both bf16 on the kernel route: the same rounding,
# plus four partial sums added in another order. Log-probs of the first
# generated position, ids in both top-5 lists. Measured on four v5e
# chips (PERF.md, PR 21): 0.0176.
TP_LOGPROB_ABS = 0.15


class SmokeFailure(Exception):
    """A phase could not run to its end (as opposed to a failed check,
    which is recorded and lets the remaining phases run)."""


class Smoke:
    def __init__(self, args):
        self.args = args
        self.failed: list[str] = []
        self.procs: list[subprocess.Popen] = []
        self.workdir = os.path.abspath(args.workdir)
        self.widths = dict(REHEARSAL if args.rehearse else QWEN25_7B)
        self.platform = "cpu" if args.rehearse else "tpu"
        self.t0 = time.monotonic()

    # -- reporting ---------------------------------------------------------

    def emit(self, phase: str, **fields) -> None:
        print(json.dumps({"phase": phase, **fields}), flush=True)

    def check(self, name: str, ok: bool, detail=None) -> bool:
        if not ok:
            self.failed.append(name)
            print(
                json.dumps({"check": name, "ok": False, "detail": detail}),
                flush=True,
            )
        return ok

    # -- processes ---------------------------------------------------------

    def child_env(self, platform: str | None = None) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = platform or self.platform
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        # Marks every process of this run (engine pods inherit it), so
        # the final sweep finds what an unclean stop left behind.
        env["CHIP_SMOKE_RUN"] = self.workdir
        if self.args.rehearse and self.args.chips > 1:
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={self.args.chips}"
        return env

    def run_child(self, mode: str, *argv: str, platform: str | None = None, timeout=900) -> dict:
        """Run one of this file's child modes to its end; its last
        stdout line is its JSON result."""
        cmd = [sys.executable, os.path.abspath(__file__), "--child", mode, *argv]
        log_path = os.path.join(self.workdir, f"child-{mode}.log")
        with open(log_path, "wb") as err:
            proc = subprocess.run(
                cmd, env=self.child_env(platform), stdout=subprocess.PIPE,
                stderr=err, timeout=timeout,
            )
        if proc.returncode != 0:
            raise SmokeFailure(
                f"child {mode} exited {proc.returncode}: {tail(log_path)}"
            )
        return json.loads(proc.stdout.decode().strip().splitlines()[-1])

    def spawn(self, name: str, cmd: list[str], extra_env: dict | None = None) -> tuple[subprocess.Popen, str]:
        log_path = os.path.join(self.workdir, f"{name}.log")
        env = self.child_env()
        env.update(extra_env or {})
        with open(log_path, "wb") as out:
            proc = subprocess.Popen(
                cmd, env=env, stdout=out, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        self.procs.append(proc)
        return proc, log_path

    def stop(self, proc: subprocess.Popen, grace: float = 60.0) -> None:
        """SIGTERM (the operator drains and kills its pods, the engine
        server drains), then the process group if it lingers."""
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(timeout=10)

    def sweep(self) -> list[int]:
        """Kill whatever this run started that is still alive (engine
        pods are in sessions of their own). Returns the pids found."""
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
        found = []
        marker = f"CHIP_SMOKE_RUN={self.workdir}".encode()
        for pid in os.listdir("/proc"):
            if not pid.isdigit() or int(pid) == os.getpid():
                continue
            try:
                with open(f"/proc/{pid}/environ", "rb") as f:
                    if marker not in f.read().split(b"\0"):
                        continue
                os.kill(int(pid), signal.SIGKILL)
                found.append(int(pid))
            except (OSError, PermissionError):
                continue
        return found

    # -- phases ------------------------------------------------------------

    def phase_device(self) -> dict:
        """What jax finds, asked in a child that exits again. Without a
        TPU this is where the run ends."""
        t = time.monotonic()
        dev = self.run_child("device", timeout=300)
        self.emit("device", seconds=round(time.monotonic() - t, 1), **dev)
        self.check("device.platform_is_tpu", dev["platform"] == "tpu", dev)
        self.check(
            "device.count", dev["count"] == self.args.chips,
            f"jax sees {dev['count']} devices, --chips {self.args.chips}",
        )
        return dev

    def phase_checkpoint(self, layers: int, why_cut: str = "--layers") -> tuple[str, dict]:
        """Seeded random weights as an HF-format safetensors directory
        (config.json model_type qwen2, q/k/v biases, no tokenizer files),
        for the real loader to read back. *why_cut* is printed when
        *layers* is below the published depth."""
        widths = dict(self.widths)
        per_layer, fixed = checkpoint_bytes(widths)
        reduced = {}
        free = shutil.disk_usage(self.workdir).free
        margin = 3 << 30  # compile cache, logs, the filesystem's own needs
        fit = int((free - margin - fixed) // per_layer)
        if fit < layers:
            if fit < 1:
                raise SmokeFailure(f"{free / 2**30:.1f} GiB free: no room for a checkpoint")
            reduced["num_layers"] = {
                "published": widths["num_layers"], "used": fit,
                "why": f"{free / 2**30:.1f} GiB of disk free, "
                       f"{(fixed + layers * per_layer) / 2**30:.1f} GiB needed",
            }
            layers = fit
        elif layers < widths["num_layers"]:
            reduced["num_layers"] = {
                "published": widths["num_layers"], "used": layers,
                "why": why_cut,
            }
        widths["num_layers"] = layers
        path = os.path.join(self.workdir, "ckpt")
        t = time.monotonic()
        out = self.run_child(
            "checkpoint", path, json.dumps(widths), str(self.args.seed),
            platform="cpu", timeout=900,
        )
        self.emit(
            "checkpoint", seconds=round(time.monotonic() - t, 1), path=path,
            widths=widths, reduced=reduced, seed=self.args.seed, **out,
        )
        return path, widths

    # .. one chip ..........................................................

    def one_chip(self) -> dict:
        a = self.args
        ckpt, widths = self.phase_checkpoint(a.layers or self.widths["num_layers"])
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            ROOT, ".jax_compile_cache"
        )
        cache_before = cache_entries(cache_dir)
        slots, seq_len = (4, 2048) if a.rehearse else (32, 2048)
        manifest = os.path.join(self.workdir, "model.yaml")
        with open(manifest, "w") as f:
            json.dump(  # JSON is YAML
                {
                    "apiVersion": "kubeai.org/v1", "kind": "Model",
                    "metadata": {"name": MODEL},
                    "spec": {
                        "url": f"file://{ckpt}", "engine": "TPUEngine",
                        "features": ["TextGeneration"],
                        "resourceProfile": "cpu:1" if a.rehearse else "tpu-v5e-1x1:1",
                        "minReplicas": 0, "maxReplicas": 1,
                        "scaleDownDelaySeconds": 900,
                        "loadBalancing": {"strategy": "PrefixHash"},
                        "args": [
                            "--quantization", "int8", "--warmup",
                            "--max-slots", str(slots), "--max-seq-len", str(seq_len),
                        ],
                    },
                },
                f,
            )
        port = free_port()
        pod_logs = os.path.join(self.workdir, "pods")
        operator, op_log = self.spawn(
            "operator",
            [sys.executable, "-m", "kubeai_tpu.manager", "--local",
             "--host", "127.0.0.1", "--port", str(port), "--models", manifest],
            {"KUBEAI_POD_LOGS": pod_logs},
        )
        base = f"127.0.0.1:{port}"
        try:
            # The API comes up before --models is applied: wait for the
            # model itself to be listed.
            wait_until(
                lambda: MODEL in http_get_or_none(base, "/openai/v1/models"),
                60, "operator lists the model", operator, op_log,
            )
            served = self.serve_requests(base, operator, op_log, pod_logs, slots)
        finally:
            t = time.monotonic()
            self.stop(operator)
            stop_s = round(time.monotonic() - t, 1)
        left = self.sweep()
        self.check("operator.stopped_its_pods", not left, f"still alive after the operator exited: {left}")
        text = open(op_log, errors="replace").read()
        native = (
            "native" if "native fasthash loaded" in text
            else "python-fallback" if "native fasthash unavailable" in text
            else "not-loaded"
        )
        self.check("operator.hash_library_reported", native != "not-loaded", tail(op_log))
        cache_after = cache_entries(cache_dir)
        self.emit(
            "stop", seconds=stop_s, router_hash=native,
            compile_cache={"dir": cache_dir, "entries_before": cache_before,
                           "entries_after": cache_after},
        )
        self.phase_logits(ckpt, widths)
        return served["device"]

    def serve_requests(self, base: str, operator, op_log: str, pod_logs: str, slots: int) -> dict:
        no_eos = {str(BYTE_EOS): -100}  # random weights would emit it by chance

        def completion(prompt, max_tokens, stream=False, **kw):
            return request(
                base, "/openai/v1/completions",
                {"model": MODEL, "prompt": prompt, "max_tokens": max_tokens,
                 "temperature": 0, "logit_bias": no_eos, **kw},
                stream=stream, expect_prompt_tokens=len(prompt.encode()) + 1,
            )

        def chat(content, max_tokens, stream=False):
            template = f"<|user|>\n{content}\n<|assistant|>\n"  # ByteTokenizer's
            return request(
                base, "/openai/v1/chat/completions",
                {"model": MODEL, "messages": [{"role": "user", "content": content}],
                 "max_tokens": max_tokens, "temperature": 0, "logit_bias": no_eos},
                stream=stream, expect_prompt_tokens=len(template.encode()) + 1,
            )

        results: list[dict] = []

        def record(name, res):
            res["name"] = name
            results.append(res)
            self.check(f"request.{name}", res["ok"], res.get("error"))
            return res

        # 1. Scale from zero: this request launches the engine pod and
        # waits out its load and warm-up. Watch for a pod that dies.
        first_prompt = "The quick brown fox"
        box: dict = {}
        t_first = time.monotonic()
        th = threading.Thread(
            target=lambda: box.update(completion(first_prompt, 16, stream=True)),
            daemon=True,
        )
        th.start()
        while th.is_alive():
            th.join(timeout=2.0)
            if operator.poll() is not None:
                raise SmokeFailure(f"operator exited {operator.returncode}: {tail(op_log)}")
            if re.search(r"pod process \S+ exited", open(op_log, errors="replace").read()):
                raise SmokeFailure(
                    f"engine pod died during start: {tail_dir(pod_logs)}"
                )
        first = record("scale_from_zero_stream", box)
        if not first["ok"]:
            raise SmokeFailure(f"first request failed: {first.get('error')}")
        engine = engine_address(base)
        recompiles_warm = metric(http_get(engine, "/metrics"), "kubeai_engine_jit_recompiles_total")

        t_req = time.monotonic()
        # 2. The same request again, alone: no whole page to reuse (under
        # 64 tokens with its answer), so the same programs run on the
        # same inputs and temperature 0 must give the same bytes —
        # streamed or not.
        again = record("repeat_nonstream", completion(first_prompt, 16))
        self.check(
            "temperature0.byte_identical",
            first["ok"] and again["ok"] and first["text"] == again["text"],
            {"first": first.get("text"), "again": again.get("text")},
        )
        # 3. A burst: grouped prefills (up to 8 to a group) and decode
        # chunks with many slots active; chat and completions, streamed
        # and not, prompts of one bucket and of several.
        burst = []
        for i in range(12):
            words = " ".join(f"w{i}x{j}" for j in range(3 + (i % 4) * 9))
            if i % 2:
                burst.append((f"burst{i}_chat", lambda w=words, s=i % 4 == 1: chat(w, 24, stream=s)))
            else:
                burst.append((f"burst{i}", lambda w=words, s=i % 4 == 0: completion(w, 24, stream=s)))
        for name, res in run_concurrently(burst):
            record(name, res)
        # 4. Over 1024 tokens: longer than the largest prefill bucket, so
        # the prompt is prefilled in chunks.
        long_prompt = " ".join(f"tok{j}" for j in range(220))[:1300]
        record("long_prompt_chunked", completion(long_prompt, 8))
        # 5. A shared prefix of several pages, then the same prefix with
        # another ending: the second prefill starts from the cached pages.
        prefix = "In a hole in the ground there lived a hobbit. " * 8
        record("prefix_first", completion(prefix + "one", 8))
        record("prefix_second", completion(prefix + "two", 8, stream=True))
        requests_s = time.monotonic() - t_req

        # What the engine process that answered says it ran on and ran.
        dbg = json.loads(http_get(engine, "/debug/engine?limit=512"))
        pipeline = json.loads(http_get(engine, "/debug/pipeline"))
        metrics_text = http_get(engine, "/metrics")
        perf = dbg["perf"]
        device = {
            "platform": perf["platform"], "kind": perf["device"],
            "count": perf["visible_devices"],
        }
        self.check("engine.platform_is_tpu", device["platform"] == "tpu", device)
        self.check("engine.device_count", device["count"] == 1, device)
        self.check(
            "engine.peaks_resolved",
            perf["peak_flops"] is not None and perf["hbm_gbps"] is not None,
            f"no peak FLOP/s / HBM GB/s on record for device kind {perf['device']!r}",
        )
        memory = perf["memory"]
        self.check(
            "engine.peak_bytes_in_use",
            bool(memory) and all(m.get("peak_bytes_in_use") for m in memory), memory,
        )
        steps = dbg["steps"]
        kernels: dict[str, dict[str, int]] = {}
        for s in steps:
            by = kernels.setdefault(s["kind"], {})
            by[s.get("kernel")] = by.get(s.get("kernel"), 0) + 1
        self.check(
            "kernel_route.flash_prefill",
            kernels.get("prefill_group", {}).get("flash", 0) > 0, kernels,
        )
        self.check(
            "kernel_route.ragged_prefill",
            kernels.get("prefill_group", {}).get("ragged", 0) > 0
            and kernels.get("prefill_chunked", {}).get("ragged", 0) > 0, kernels,
        )
        self.check(
            "kernel_route.ragged_decode",
            set(kernels.get("decode_chunk", {})) == {"ragged"}, kernels,
        )
        groups = [s for s in steps if s["kind"] == "prefill_group"]
        self.check(
            "exercised.grouped_prefill", any(s["batch"] > 1 for s in groups),
            [s["batch"] for s in groups],
        )
        chunked = [s for s in steps if s["kind"] == "prefill_chunked"]
        self.check(
            "exercised.chunked_prefill",
            any(s["prompt_tokens"] > 1024 and s["reuse_tokens"] == 0 for s in chunked), chunked,
        )
        self.check(
            "exercised.prefix_hit",
            any(s["reuse_tokens"] > 0 for s in chunked)
            and metric(metrics_text, "kubeai_engine_prefix_cached_tokens_total") > 0, chunked,
        )
        decodes = [s for s in steps if s["kind"] == "decode_chunk"]
        self.check(
            "exercised.concurrent_decode",
            any(len(s["slots"]) >= min(8, slots) and s["steps"] == 8 for s in decodes),
            sorted({len(s["slots"]) for s in decodes}),
        )
        n_ok = metric(metrics_text, "kubeai_engine_requests_total", outcome="ok")
        n_bad = sum(
            metric(metrics_text, "kubeai_engine_requests_total", outcome=o)
            for o in ("error", "cancelled")
        )
        # (The operator's canary prober may have added requests of its own.)
        self.check(
            "requests.none_failed", n_bad == 0 and n_ok >= len(results),
            {"ok": n_ok, "failed": n_bad, "sent": len(results)},
        )
        recompiles_end = metric(metrics_text, "kubeai_engine_jit_recompiles_total")
        self.check(
            "recompiles.flat_after_warmup",
            recompiles_warm > 0 and recompiles_end == recompiles_warm,
            {"after_warmup": recompiles_warm, "at_end": recompiles_end},
        )
        cold = dbg["cold_start"]
        warm_errors = (cold["attrs"].get("warm_compile") or {}).get("errors")
        self.check("warm_compile.no_errors", not warm_errors, warm_errors)
        self.check(
            "cold_start.phases",
            all(p in cold["phases"] for p in ("load", "build", "warmup")), cold["phases"],
        )
        self.emit(
            "serve",
            setup_seconds={
                **{k: v.get("duration_s") for k, v in cold["phases"].items()},
                "engine_ready": cold.get("ready_s"),
                "first_token_from_zero_replicas": first["first_token_s"],
                "first_response_from_zero_replicas": round(first["total_s"], 1),
            },
            requests_seconds=round(requests_s, 1),
            cold_start=cold, device=device, memory=memory,
            weight_bytes=perf["weight_bytes"], peak_flops=perf["peak_flops"],
            hbm_gbps=perf["hbm_gbps"], kernels=kernels,
            requests=[
                {k: r.get(k) for k in ("name", "status", "prompt_tokens", "completion_tokens", "streamed", "total_s")}
                for r in results
            ],
            recompiles={"after_warmup": recompiles_warm, "at_end": recompiles_end},
            prefix_cached_tokens=metric(metrics_text, "kubeai_engine_prefix_cached_tokens_total"),
            stall=pipeline.get("causes"),
        )
        return {"device": device}

    def phase_logits(self, ckpt: str, widths: dict) -> None:
        """Prefill, chunked prefill and decode logits through the kernel
        route against the float32 portable route, on the first layers of
        the same checkpoint, through the same loader."""
        depth = min(REF_LAYERS, widths["num_layers"])
        shallow = os.path.join(self.workdir, f"ckpt-{depth}-layers")
        os.makedirs(shallow, exist_ok=True)
        for name in os.listdir(ckpt):
            if name.endswith(".safetensors"):
                link = os.path.join(shallow, name)
                if not os.path.lexists(link):
                    os.symlink(os.path.join(ckpt, name), link)
        with open(os.path.join(ckpt, "config.json")) as f:
            cfg = json.load(f)
        cfg["num_hidden_layers"] = depth
        with open(os.path.join(shallow, "config.json"), "w") as f:
            json.dump(cfg, f)
        t = time.monotonic()
        out = self.run_child("logits", shallow, str(self.args.seed), timeout=900)
        self.emit(
            "logits", seconds=round(time.monotonic() - t, 1), layers=depth,
            tolerance={"max_abs": LOGITS_MAX_ABS, "mean_abs": LOGITS_MEAN_ABS}, **out,
        )
        self.check("logits.platform_is_tpu", out["platform"] == "tpu", out["platform"])
        for name, c in out["compared"].items():
            self.check(
                f"logits.{name}",
                c["finite"] and c["max_abs"] <= LOGITS_MAX_ABS and c["mean_abs"] <= LOGITS_MEAN_ABS,
                c,
            )

    # .. four chips ........................................................

    def four_chips(self) -> dict:
        a = self.args
        ckpt, widths = self.phase_checkpoint(
            min(a.layers or TP_LAYERS, self.widths["num_layers"]),
            why_cut=f"bf16 weights must fit one chip beside a pool for the tp=1 "
                    f"comparison ({TP_LAYERS} layers: 5.9 GB of 16)",
        )
        slots, seq_len = (4, 2048) if a.rehearse else (16, 2048)
        prompts = {
            "short": "The quick brown fox",  # ragged-kernel prefill
            "bucket512": " ".join(f"w{j}" for j in range(90))[:400],  # flash prefill
            "chunked": " ".join(f"tok{j}" for j in range(220))[:1300],  # > 1024 tokens
        }
        runs = {}
        for tp in (1, a.chips):
            port = free_port()
            t = time.monotonic()
            server, log = self.spawn(
                f"engine-tp{tp}",
                [sys.executable, "-m", "kubeai_tpu.engine.server",
                 "--model", ckpt, "--served-model-name", MODEL,
                 "--host", "127.0.0.1", "--port", str(port),
                 "--tensor-parallel-size", str(tp),
                 "--max-slots", str(slots), "--max-seq-len", str(seq_len)],
                # No --warmup here: each server compiles the few shapes
                # its three requests use. The tp=1 start would otherwise
                # wait for the background AOT compile of all 14 (127 s
                # on four charged chips, PR 21) — tp>1 never runs it.
                {"KUBEAI_COLDSTART_OVERLAP": "0"},
            )
            base = f"127.0.0.1:{port}"
            try:
                wait_until(lambda: http_get_or_none(base, "/readyz"), 900, f"tp={tp} /readyz", server, log)
                ready_s = time.monotonic() - t
                answers = {}
                for name, prompt in prompts.items():
                    res = request(
                        base, "/v1/completions",
                        {"model": MODEL, "prompt": prompt, "max_tokens": 4,
                         "temperature": 0, "logprobs": 5,
                         "logit_bias": {str(BYTE_EOS): -100}},
                        expect_prompt_tokens=len(prompt.encode()) + 1,
                    )
                    self.check(f"tp{tp}.request.{name}", res["ok"], res.get("error"))
                    answers[name] = res
                dbg = json.loads(http_get(base, "/debug/engine?limit=512"))
                metrics_text = http_get(base, "/metrics")
            finally:
                self.stop(server)
            left = self.sweep()
            self.check(f"tp{tp}.stopped", not left, left)
            perf = dbg["perf"]
            kernels = sorted({(s["kind"], s.get("kernel")) for s in dbg["steps"]})
            runs[tp] = {
                "answers": answers, "perf": perf, "kernels": kernels,
                "param_bytes": metric(metrics_text, "kubeai_engine_param_bytes_global"),
            }
            device = {
                "platform": perf["platform"], "kind": perf["device"],
                "count": perf["visible_devices"],
            }
            self.check(f"tp{tp}.platform_is_tpu", device["platform"] == "tpu", device)
            self.check(f"tp{tp}.device_count", device["count"] == a.chips, device)
            self.check(f"tp{tp}.serving_devices", perf["devices"] == tp, perf["devices"])
            # The programs contain the kernels: the step records name the
            # route llama.apply compiled, and off the CPU that route is
            # the Pallas call itself (no twin).
            self.check(
                f"tp{tp}.kernels_in_programs",
                {("prefill_group", "flash"), ("prefill_group", "ragged"),
                 ("prefill_chunked", "ragged"), ("decode_chunk", "ragged")} <= set(kernels),
                kernels,
            )
            # Every serving chip holds its share: weights split tp ways
            # (but for norms and biases) and the pool on its head axis.
            pool = pool_bytes(widths, slots, seq_len)
            share = (runs[tp]["param_bytes"] + pool) / tp
            in_use = [m.get("bytes_in_use") for m in perf["memory"]]
            serving = sorted((b or 0) for b in in_use)[-tp:]
            self.check(
                f"tp{tp}.bytes_in_use_per_chip",
                all(b is not None for b in in_use)
                and all(0.85 * share <= b <= 1.3 * share for b in serving),
                {"bytes_in_use": in_use, "expected_share": int(share)},
            )
            self.emit(
                f"serve_tp{tp}", setup_seconds={"engine_ready": round(ready_s, 1)},
                device=device, serving_devices=perf["devices"], kernels=kernels,
                memory=perf["memory"], param_bytes=runs[tp]["param_bytes"],
                pool_bytes=pool, expected_share_per_chip=int(share),
                cold_start=dbg.get("cold_start"),
            )
        worst = 0.0
        for name in prompts:
            one, four = runs[1]["answers"][name], runs[a.chips]["answers"][name]
            if not (one["ok"] and four["ok"]):
                continue
            top1, top4 = one["first_top_logprobs"], four["first_top_logprobs"]
            common = set(top1) & set(top4)
            diffs = [abs(top1[k] - top4[k]) for k in common]
            worst = max([worst, *diffs])
            self.check(
                f"tp_logits.{name}",
                one["first_token"] in top4 and four["first_token"] in top1
                and len(common) >= 3 and max(diffs) <= TP_LOGPROB_ABS,
                {"tp1": top1, f"tp{a.chips}": top4},
            )
        self.emit(
            "tp_compare", max_abs_logprob_diff=round(worst, 4),
            tolerance=TP_LOGPROB_ABS, positions="first generated position, top-5",
        )
        perf = runs[a.chips]["perf"]
        return {"platform": perf["platform"], "kind": perf["device"], "count": perf["visible_devices"]}

    # -- main --------------------------------------------------------------

    def main(self) -> int:
        shutil.rmtree(self.workdir, ignore_errors=True)
        os.makedirs(self.workdir)
        try:
            self.phase_device()
            if self.failed and not self.args.rehearse:
                raise SmokeFailure("no TPU of the size asked for; nothing else was run")
            device = self.one_chip() if self.args.chips == 1 else self.four_chips()
        except SmokeFailure as e:
            print(json.dumps({"failed_phase": str(e)}), flush=True)
            return 1
        finally:
            self.sweep()
            if not self.args.keep:
                shutil.rmtree(os.path.join(self.workdir, "ckpt"), ignore_errors=True)
        self.emit("total", seconds=round(time.monotonic() - self.t0, 1), failed_checks=self.failed)
        if self.args.rehearse:
            print(json.dumps({"rehearsal": "never a result", "failed_checks": self.failed}), flush=True)
            return 3
        if self.failed:
            return 1
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0


# ---------------------------------------------------------------------------
# Parent-side helpers (no jax).


def tail(path: str, n: int = 2000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError as e:
        return f"<{e}>"


def tail_dir(path: str) -> str:
    if not os.path.isdir(path):
        return f"<no {path}>"
    return "\n".join(f"== {n}\n{tail(os.path.join(path, n))}" for n in sorted(os.listdir(path)))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def checkpoint_bytes(w: dict) -> tuple[int, int]:
    """(bytes per layer, bytes outside the layers) of the bf16 checkpoint."""
    D, F = w["hidden_size"], w["intermediate_size"]
    hd = D // w["num_heads"]
    q, kv = w["num_heads"] * hd, w["num_kv_heads"] * hd
    per_layer = 2 * (D * q + 2 * D * kv + q * D + 3 * D * F + 2 * D + q + 2 * kv)
    return per_layer, 2 * (2 * w["vocab_size"] * D + D)


def pool_bytes(w: dict, slots: int, seq_len: int, page: int = 64) -> int:
    """The engine's bf16 paged pool (core.engine_dims, llama.init_paged_cache)."""
    pages = slots * -(-seq_len // page) + 1
    hd = w["hidden_size"] // w["num_heads"]
    return w["num_layers"] * pages * page * 2 * w["num_kv_heads"] * hd * 2


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if n.endswith("-cache"))


def http_get(base: str, path: str, timeout: float = 30) -> str:
    host, port = base.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        if resp.status != 200:
            raise SmokeFailure(f"GET {base}{path}: {resp.status} {body[:300]}")
        return body
    finally:
        conn.close()


def http_get_or_none(base: str, path: str) -> str:
    """The body of a 200, or "" while the server is not up yet."""
    try:
        return http_get(base, path, timeout=5)
    except (OSError, SmokeFailure):
        return ""


def wait_until(cond, seconds: float, what: str, proc, log_path: str) -> None:
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if cond():
            return
        if proc.poll() is not None:
            raise SmokeFailure(f"{what}: process exited {proc.returncode}: {tail(log_path)}")
        time.sleep(0.5)
    raise SmokeFailure(f"{what}: not within {seconds:.0f}s: {tail(log_path)}")


def engine_address(base: str) -> str:
    """host:port of the one engine pod, as the operator's router sees it."""
    endpoints = json.loads(http_get(base, "/debug/endpoints"))["models"].get(MODEL, [])
    if len(endpoints) != 1:
        raise SmokeFailure(f"expected one engine endpoint, found {endpoints}")
    return endpoints[0]["address"]


def metric(text: str, name: str, **labels) -> float:
    """Sum of a Prometheus series' samples whose labels include *labels*."""
    return sum(
        value for have, value in parse_prometheus_text(text).get(name, [])
        if all(have.get(k) == v for k, v in labels.items())
    )


def request(base: str, path: str, body: dict, stream: bool = False,
            expect_prompt_tokens: int | None = None, timeout: float = 900) -> dict:
    """One OpenAI request; ok means a 200 with the token counts asked for."""
    host, port = base.split(":")
    if stream:
        body = {**body, "stream": True, "stream_options": {"include_usage": True}}
    out: dict = {"ok": False, "streamed": stream}
    t0 = time.monotonic()
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200:
            out["error"] = resp.read().decode(errors="replace")[:500]
            return out
        chat = "chat" in path
        if stream:
            text, usage, finish = "", None, None
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data:") or line == "data: [DONE]":
                    continue
                ev = json.loads(line[5:])
                for ch in ev.get("choices", []):
                    piece = (ch.get("delta", {}).get("content") if chat else ch.get("text")) or ""
                    if piece and "first_token_s" not in out:
                        out["first_token_s"] = round(time.monotonic() - t0, 2)
                    text += piece
                    finish = ch.get("finish_reason") or finish
                usage = ev.get("usage") or usage
        else:
            doc = json.loads(resp.read())
            choice = doc["choices"][0]
            text = choice["message"]["content"] if chat else choice["text"]
            usage, finish = doc.get("usage"), choice.get("finish_reason")
            lp = choice.get("logprobs")
            if lp and not chat:
                out["first_token"] = lp["tokens"][0]
                out["first_top_logprobs"] = lp["top_logprobs"][0]
        out.update(
            text=text, finish_reason=finish, total_s=round(time.monotonic() - t0, 2),
            prompt_tokens=(usage or {}).get("prompt_tokens"),
            completion_tokens=(usage or {}).get("completion_tokens"),
        )
        problems = []
        if out["completion_tokens"] != body["max_tokens"]:
            problems.append(f"completion_tokens {out['completion_tokens']} != {body['max_tokens']}")
        if expect_prompt_tokens is not None and out["prompt_tokens"] != expect_prompt_tokens:
            problems.append(f"prompt_tokens {out['prompt_tokens']} != {expect_prompt_tokens}")
        if finish != "length":
            problems.append(f"finish_reason {finish!r}")
        out["ok"] = not problems
        if problems:
            out["error"] = "; ".join(problems)
        return out
    except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    finally:
        conn.close()


def run_concurrently(named_calls) -> list[tuple[str, dict]]:
    results: dict[str, dict] = {}

    def run(name, call):
        results[name] = call()

    threads = [threading.Thread(target=run, args=nc, daemon=True) for nc in named_calls]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=900)
    return [
        (name, results.get(name, {"ok": False, "error": "no answer within 900s"}))
        for name, _ in named_calls
    ]


# ---------------------------------------------------------------------------
# Children: each is its own process and may import jax.


def child_device() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def child_checkpoint(path: str, widths_json: str, seed: str) -> dict:
    """Write the checkpoint: one safetensors shard per layer, made on all
    cores (numpy draws outside the interpreter lock)."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    from kubeai_tpu.engine.weights import write_hf_config
    from kubeai_tpu.models.base import ModelConfig

    w = json.loads(widths_json)
    cfg = ModelConfig(**w, dtype="bfloat16")
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    q, kv = cfg.num_heads * cfg.head_dim_, cfg.num_kv_heads * cfg.head_dim_
    write_hf_config(path, cfg)

    def draw(rng, *shape, scale):
        # Uniform on [-a, a] with a = scale * sqrt(3): the variance of a
        # normal(0, scale) at a third of the cost of drawing one.
        a = np.float32(scale * 3**0.5)
        x = rng.random(shape, dtype=np.float32)
        x *= 2 * a
        x -= a
        return x.astype(ml_dtypes.bfloat16)

    def layer(i: int) -> int:
        rng = np.random.default_rng([int(seed), i])
        p = f"model.layers.{i}."
        lin = lambda out, inp: draw(rng, out, inp, scale=inp**-0.5)  # noqa: E731
        tensors = {
            p + "input_layernorm.weight": np.ones((D,), ml_dtypes.bfloat16),
            p + "post_attention_layernorm.weight": np.ones((D,), ml_dtypes.bfloat16),
            p + "self_attn.q_proj.weight": lin(q, D),
            p + "self_attn.k_proj.weight": lin(kv, D),
            p + "self_attn.v_proj.weight": lin(kv, D),
            p + "self_attn.o_proj.weight": lin(D, q),
            p + "self_attn.q_proj.bias": draw(rng, q, scale=0.1),
            p + "self_attn.k_proj.bias": draw(rng, kv, scale=0.1),
            p + "self_attn.v_proj.bias": draw(rng, kv, scale=0.1),
            p + "mlp.gate_proj.weight": lin(F, D),
            p + "mlp.up_proj.weight": lin(F, D),
            p + "mlp.down_proj.weight": lin(D, F),
        }
        save_file(tensors, os.path.join(path, f"model-layer-{i:03d}.safetensors"))
        return sum(t.nbytes for t in tensors.values())

    def outside() -> int:
        rng = np.random.default_rng([int(seed), 10_000])
        tensors = {
            "model.embed_tokens.weight": draw(rng, V, D, scale=0.02),
            "model.norm.weight": np.ones((D,), ml_dtypes.bfloat16),
            "lm_head.weight": draw(rng, V, D, scale=0.02),
        }
        save_file(tensors, os.path.join(path, "model-outside-layers.safetensors"))
        return sum(t.nbytes for t in tensors.values())

    with ThreadPoolExecutor(max_workers=min(os.cpu_count() or 1, 12)) as pool:
        jobs = [pool.submit(outside)] + [pool.submit(layer, i) for i in range(cfg.num_layers)]
        nbytes = sum(j.result() for j in jobs)
    return {"bytes": nbytes, "shards": cfg.num_layers + 1}


def child_logits(path: str, seed: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeai_tpu.engine.core import EngineConfig
    from kubeai_tpu.engine.weights import load_engine_from_path
    from kubeai_tpu.models import llama

    # The loader only: no background compile of step functions this
    # child never runs (it starts one whenever a compile cache is on).
    eng = load_engine_from_path(
        path, EngineConfig(max_slots=4, max_seq_len=512), quantization="int8",
        overlap=False, warmup=False,
    )
    params, kcfg = eng.params, eng.model_config
    rcfg = kcfg.replace(dtype="float32", use_flash_prefill=False, use_paged_kernel=False)
    B, S, page, max_pages = 4, 256, 64, 8
    rng = np.random.default_rng(int(seed))
    tokens = jnp.asarray(rng.integers(0, 259, (B, S)), jnp.int32)
    nxt = jnp.asarray(rng.integers(0, 259, (B, 1)), jnp.int32)
    lengths = jnp.asarray([256, 200, 256, 131], jnp.int32)
    tables = jnp.asarray(1 + np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages))
    half = jnp.full((B,), S // 2, jnp.int32)

    def route(cfg):
        """(cold prefill, prefill in two chunks, one decode step) logits."""
        pool = llama.init_paged_cache(cfg, B * max_pages + 1, page)
        cold, pool = jax.jit(
            lambda p, t, c: llama.prefill_paged_cold(p, cfg, t, c, tables, lengths)
        )(params, tokens, pool)
        step, _ = jax.jit(
            lambda p, t, c: llama.decode_step_paged(p, cfg, t, c, tables, lengths)
        )(params, nxt, pool)
        chunk = jax.jit(
            lambda p, t, c, start, last: llama.prefill_paged(p, cfg, t, c, tables, start, last)
        )
        pool2 = llama.init_paged_cache(cfg, B * max_pages + 1, page)
        _, pool2 = chunk(params, tokens[:, : S // 2], pool2, 0 * half, half - 1)
        chunked, _ = chunk(params, tokens[:, S // 2 :], pool2, half, half - 1)
        return {
            "prefill_cold": np.asarray(cold[:, 0]),
            "prefill_chunked": np.asarray(chunked[:, 0]),
            "decode": np.asarray(step[:, 0]),
        }

    got = route(kcfg)
    with jax.default_matmul_precision("highest"):
        want = route(rcfg)
    # The chunked prefill ends at position S-1 for every row; the cold
    # one at each row's own length. Compare like with like.
    with jax.default_matmul_precision("highest"):
        pool = llama.init_paged_cache(rcfg, B * max_pages + 1, page)
        full = jnp.full((B,), S, jnp.int32)
        want_full, _ = jax.jit(
            lambda p, t, c: llama.prefill_paged_cold(p, rcfg, t, c, tables, full)
        )(params, tokens, pool)
    want["prefill_chunked"] = np.asarray(want_full[:, 0])
    compared = {}
    for name in got:
        d = np.abs(got[name].astype(np.float64) - want[name].astype(np.float64))
        compared[name] = {
            "finite": bool(np.isfinite(got[name]).all() and np.isfinite(want[name]).all()),
            "shape": list(got[name].shape),
            "max_abs": float(d.max()), "mean_abs": float(d.mean()),
            "ref_std": float(want[name].std()), "ref_max_abs": float(np.abs(want[name]).max()),
            "argmax_agree": float((got[name].argmax(-1) == want[name].argmax(-1)).mean()),
        }
    dev = jax.devices()[0]
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "kernel_route": {"flash": kcfg.use_flash_prefill, "paged_kernel": kcfg.use_paged_kernel},
        "sample": {"rows": B, "prompt_tokens": S, "lengths": [256, 200, 256, 131]},
        "compared": compared,
    }


CHILDREN = {"device": child_device, "checkpoint": child_checkpoint, "logits": child_logits}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=[1, 4])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--layers", type=int, default=0,
        help="cut depth (printed under `reduced`); default: all 28, or 8 with --chips 4",
    )
    parser.add_argument("--workdir", default=os.path.join(ROOT, ".chip_smoke"))
    parser.add_argument("--keep", action="store_true", help="keep the checkpoint")
    parser.add_argument("--rehearse", action="store_true", help="tiny, on the CPU, never a result")
    parser.add_argument("--child", nargs=argparse.REMAINDER, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        print(json.dumps(CHILDREN[args.child[0]](*args.child[1:])), flush=True)
        return 0
    return Smoke(args).main()


if __name__ == "__main__":
    sys.exit(main())
