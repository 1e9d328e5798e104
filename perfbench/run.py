#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 perfbench/run.py --workload <name> --rehearse [--trace 1]   # CPU, tiny, never a chip result

One process, off jax (a chip belongs to one process at a time; every phase
that needs it is a child that has ended before the next starts):

 1. write the cell's seeded checkpoint (published config.json + random
    safetensors from --seed);
 2. start the operator (`python -m kubeai_tpu.manager --local`) with the
    cell's Model manifest at minReplicas 0 and send the first request to
    zero replicas;
 3. probes (three prompts, alone, temperature 0), then the cell's traffic
    starts and runs `ramp_s` before the window opens: set-up ends there;
 4. the window: --seconds of the traffic, with /metrics scraped at both
    edges and once a second; a compile inside it fails the run;
 5. the probes again, the engine's own account of its device and memory;
 6. stop the operator, see every pod gone;
 7. the logits check of the configuration's family (perfbench/families/:
    for the dense decoder, kernel-route logits against the float32
    portable route), in a child;
 8. with --trace 1: reduce the trace the ENGINE took of itself
    (/debug/profile) in a child on the CPU backend;
 9. check the result against BENCHMARK.json (resultline.py) and print it,
    once, as the last line.

Every earlier stdout line is one JSON object for one phase. Children log to
files under the work directory. Any exception stops the children, prints the
reason on an earlier line and exits non-zero with no result.
"""

from __future__ import annotations

import argparse
import glob
import importlib
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

T_PROCESS_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import engine_io  # noqa: E402
import loadgen  # noqa: E402
import peaks as peaks_mod  # noqa: E402
import resultline  # noqa: E402
import traffic  # noqa: E402

# First-position top-5 log-probs of a probe before and after the window,
# where the two took different prefill routes (cold against a cache hit):
# both bf16 on the kernel route, summed in another order. Measured between
# tp=1 and tp=4 on the chip (PERF.md, PR 21): 0.0176. Same bound as there.
PROBE_LOGPROB_ABS = 0.15
NOT_HF_KEYS = ("source", "reduced", "assumed", "serving", "rehearsal")


class RunFailure(Exception):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def tail(path: str, n: int = 1500) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError as e:
        return f"<{e}>"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def percentile(values: list[float], p: float) -> float:
    """Nearest rank on the sorted values."""
    v = sorted(values)
    return v[min(int(len(v) * p / 100.0), len(v) - 1)]


class Context:
    """What the per-layer readers read (perfbench/readers/__init__.py)."""

    trace = None
    trace_t0 = trace_t1 = 0.0


class Run:
    def __init__(self, args):
        self.args = args
        self.rehearsal = args.rehearse
        self.bench = resultline.load_benchmark(ROOT)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if args.workload not in cells:
            raise RunFailure(f"workload {args.workload!r} is not in BENCHMARK.json ({sorted(cells)})")
        self.cell = cells[args.workload]
        cfg_entry = next(c for c in self.bench["configs"] if c["name"] == self.cell["config"])
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.serving = dict(self.config["serving"])
        self.hf = {k: v for k, v in self.config.items() if k not in NOT_HF_KEYS}
        if self.rehearsal:
            reh = self.config["rehearsal"]
            self.hf.update(reh["hf_overrides"])
            self.serving.update({k: v for k, v in reh.items() if k != "hf_overrides"})
            self.serving["logits_check_layers"] = reh["logits_check_layers"]
        self.platform = "cpu" if self.rehearsal else "tpu"
        self.model = self.serving["model_name"]
        self.workdir = os.path.join(ROOT, ".perfbench_work", args.workload)
        self.procs: list[subprocess.Popen] = []
        self.notes: list[str] = []  # why correct is false
        with open(os.path.join(HERE, "trace.json")) as f:
            self.trace_spec = json.load(f)

    # -- processes ---------------------------------------------------------

    def child_env(self, platform: str) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = platform
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        # A fixed path inside the checkout (the path is part of the cache's
        # key, and a Pallas program's key depends on the checkout's path).
        env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_compile_cache"))
        env["PERFBENCH_RUN"] = self.workdir  # marks every process of this run
        return env

    def run_child(self, mode: str, *argv: str, platform: str, timeout: float) -> dict:
        log_path = os.path.join(self.workdir, f"child-{mode}.log")
        with open(log_path, "wb") as err:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "children.py"), mode, *argv],
                env=self.child_env(platform), stdout=subprocess.PIPE, stderr=err, timeout=timeout,
            )
        if proc.returncode != 0:
            raise RunFailure(f"child {mode} exited {proc.returncode}: {tail(log_path)}")
        return json.loads(proc.stdout.decode().strip().splitlines()[-1])

    def stop(self, proc: subprocess.Popen, grace: float = 25.0) -> None:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(timeout=10)

    def sweep(self) -> list[int]:
        """Kill whatever this run started that is still alive (engine pods
        are in sessions of their own) and wait for it. Returns the pids."""
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait(timeout=10)
        found = []
        marker = f"PERFBENCH_RUN={self.workdir}".encode()
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
        deadline = time.monotonic() + 10
        while found and time.monotonic() < deadline and any(os.path.exists(f"/proc/{p}") for p in found):
            time.sleep(0.1)
        return found

    # -- phases ------------------------------------------------------------

    def work_file(self, name: str, obj) -> str:
        path = os.path.join(self.workdir, name)
        with open(path, "w") as f:
            json.dump(obj, f)
        return path

    def phase_checkpoint(self) -> str:
        path = os.path.join(self.workdir, "ckpt")
        hf_path = self.work_file("hf_config.json", self.hf)
        t = time.monotonic()
        out = self.run_child("checkpoint", path, hf_path, str(self.args.seed), platform="cpu", timeout=600)
        emit("checkpoint", seconds=time.monotonic() - t, **out)
        return path

    def start_operator(self, ckpt: str) -> tuple[subprocess.Popen, str, str]:
        manifest = os.path.join(self.workdir, "model.yaml")
        with open(manifest, "w") as f:
            json.dump({  # JSON is YAML
                "apiVersion": "kubeai.org/v1", "kind": "Model",
                "metadata": {"name": self.model},
                "spec": {
                    "url": f"file://{ckpt}", "engine": "TPUEngine",
                    "features": ["TextGeneration"],
                    "resourceProfile": self.serving["resource_profile"],
                    "minReplicas": 0, "maxReplicas": 1, "scaleDownDelaySeconds": 900,
                    "loadBalancing": {"strategy": self.serving["load_balancing"]},
                    "args": self.serving["engine_args"],
                },
            }, f)
        port = free_port()
        env = self.child_env(self.platform)
        env["KUBEAI_POD_LOGS"] = os.path.join(self.workdir, "pods")
        # What the program would keep under fixed paths in /tmp stays in
        # the run's work directory: two checkouts share nothing.
        for var, sub in (
            ("KUBEAI_HISTORY_DIR", "history"), ("KUBEAI_INCIDENT_DIR", "incidents"),
            ("KUBEAI_PROFILE_DIR", "profile"), ("KUBEAI_ADAPTER_STAGING_DIR", "adapters"),
            ("KUBEAI_MODEL_STAGING_DIR", "models"),
        ):
            env[var] = os.path.join(self.workdir, sub)
        if self.args.trace:
            # The pod inherits the operator's environment (LocalRuntime):
            # the ENGINE process takes the trace, of itself.
            env["KUBEAI_DEBUG_PROFILE"] = "1"
        log_path = os.path.join(self.workdir, "operator.log")
        with open(log_path, "wb") as out:
            proc = subprocess.Popen(
                [sys.executable, "-m", "kubeai_tpu.manager", "--local", "--host", "127.0.0.1",
                 "--port", str(port), "--models", manifest],
                env=env, stdout=out, stderr=subprocess.STDOUT, start_new_session=True, cwd=ROOT,
            )
        self.procs.append(proc)
        base = f"127.0.0.1:{port}"
        deadline = time.monotonic() + 60
        # The API answers before --models is applied: wait for the model.
        while self.model not in engine_io.http_get_or_none(base, "/openai/v1/models"):
            if proc.poll() is not None:
                raise RunFailure(f"operator exited {proc.returncode}: {tail(log_path)}")
            if time.monotonic() > deadline:
                raise RunFailure(f"operator did not list the model in 60 s: {tail(log_path)}")
            time.sleep(0.25)
        return proc, base, log_path

    def one(self, base: str, prompt: str, max_tokens: int, tag: str, **extra) -> loadgen.Record:
        req = traffic.Request(prompt, max_tokens, tag)
        rec = loadgen.Record(req, time.monotonic())
        return loadgen.send(base, self.model, req, rec, timeout=1100, extra=extra or None)

    def first_request(self, base: str, operator, op_log: str) -> loadgen.Record:
        """To zero replicas: launches the pod and waits out load, compile or
        cache reads, and warm-up. Watches for a pod that dies (no TPU)."""
        box: list = []
        th = threading.Thread(
            target=lambda: box.append(self.one(base, "The quick brown fox", 16, "scale_from_zero")),
            daemon=True,
        )
        th.start()
        while th.is_alive():
            th.join(timeout=1.0)
            if operator.poll() is not None:
                raise RunFailure(f"operator exited {operator.returncode}: {tail(op_log)}")
            if re.search(r"pod process \S+ exited", tail(op_log, 20000)):
                pods = os.path.join(self.workdir, "pods")
                logs = "\n".join(tail(p) for p in sorted(glob.glob(os.path.join(pods, "*"))))
                raise RunFailure(f"engine pod died during start (no accelerator?): {logs}")
        rec = box[0]
        if not rec.ok:
            raise RunFailure(f"first request failed: {rec.error}")
        return rec

    def probe_prompts(self) -> dict[str, tuple[str, int]]:
        """One prompt per prefill route. `bucketed` holds no whole page
        with its answer, so nothing of it is ever cached and both sends
        run the same programs on the same inputs."""
        import random

        rng = random.Random(self.args.seed * 31 + 7)
        prefix = traffic.text(rng, 400)
        return {
            "bucketed": (traffic.text(rng, 40), 12),
            "chunked": (traffic.text(rng, 1300 if not self.rehearsal else 1100), 12),
            "prefix_a": (prefix + traffic.text(rng, 30), 12),
            "prefix_b": (prefix + traffic.text(rng, 30), 12),
        }

    def probes(self, base: str, when: str) -> dict[str, loadgen.Record]:
        out = {}
        for name, (prompt, n) in self.probe_prompts().items():
            rec = self.one(base, prompt, n, f"probe_{name}", logprobs=5)
            if not rec.ok:
                self.notes.append(f"probe {name} {when}: {rec.error}")
            out[name] = rec
        return out

    def compare_probes(self, before: dict, after: dict) -> dict:
        report = {}
        for name in before:
            b, a = before[name], after[name]
            if not (b.ok and a.ok):
                report[name] = "failed"
                continue
            if b.text == a.text:
                report[name] = "identical"
                continue
            tb = (b.usage or {}).get("first_top_logprobs") or {}
            ta = (a.usage or {}).get("first_top_logprobs") or {}
            common = set(tb) & set(ta)
            worst = max((abs(tb[k] - ta[k]) for k in common), default=None)
            same_route = name == "bucketed"
            if same_route or worst is None or len(common) < 3 or worst > PROBE_LOGPROB_ABS:
                self.notes.append(
                    f"probe {name}: streams differ before/after the window "
                    f"(common top-5 ids {len(common)}, worst log-prob difference {worst})"
                )
                report[name] = f"DIFFERENT (worst {worst})"
            else:
                report[name] = f"within rounding of another prefill route (worst log-prob difference {worst:.4f})"
        return report

    def profile(self, engine: str, ctx: Context, box: dict) -> None:
        n = self.trace_spec["profile_seconds"]
        try:
            ctx.trace_t0 = time.monotonic()
            box["result"] = json.loads(engine_io.http_get(engine, f"/debug/profile?seconds={n}", timeout=n + 240))
            # The call returns only after the trace is written (20 s and
            # more): the traced interval is about the n seconds after it began.
            ctx.trace_t1 = ctx.trace_t0 + n
            box["call_s"] = time.monotonic() - ctx.trace_t0
        except Exception as e:  # noqa: BLE001 - reported by the caller's thread
            box["error"] = f"{type(e).__name__}: {e}"

    # -- the run -----------------------------------------------------------

    def main(self) -> int:
        a = self.args
        if not os.path.isdir(os.path.join(ROOT, "kubeai_tpu")):
            raise RunFailure("no kubeai_tpu/ beside perfbench/: nothing to measure")
        shutil.rmtree(self.workdir, ignore_errors=True)
        os.makedirs(self.workdir)
        spec = traffic.load(self.cell["traffic"], self.rehearsal)
        plan = traffic.build(spec, a.seed, a.seconds)
        ckpt = self.phase_checkpoint()
        operator, base, op_log = self.start_operator(ckpt)
        ctx = Context()
        ctx.rehearsal, ctx.hf, ctx.serving = self.rehearsal, self.hf, self.serving
        try:
            t = time.monotonic()
            ctx.first = self.first_request(base, operator, op_log)
            engine = engine_io.engine_address(base, self.model)
            perf = json.loads(engine_io.http_get(engine, "/debug/engine?limit=1"))["perf"]
            device = {"platform": perf["platform"], "kind": perf["device"], "count": perf["visible_devices"]}
            emit("scale_from_zero", seconds=time.monotonic() - t, first_token_s=ctx.first.token_times[0] - ctx.first.sent, device=device)
            if device["platform"] != self.platform or device["count"] < self.cell["chips"]:
                raise RunFailure(f"the engine runs on {device}, the cell asks for {self.cell['chips']} {self.platform} chip(s)")
            ctx.peaks = peaks_mod.peaks(device["kind"]) if not self.rehearsal else peaks_mod.PEAKS["TPU v5 lite"]
            probes_before = self.probes(base, "before")
            load = loadgen.Load(base, self.model, plan, a.seconds)
            load.start()
            time.sleep(max(load.t_open - time.monotonic(), 0))
            ctx.before = engine_io.scrape(engine)
            setup_s = load.t_open - T_PROCESS_START
            emit("window_open", setup_s=setup_s, ramp_s=plan.ramp_s)
            ctx.polls = []
            prof_box: dict = {}
            prof_thread = None
            prof_at = load.t_open + self.trace_spec["start_after_fraction"] * a.seconds
            while time.monotonic() < load.t_close:
                if a.trace and prof_thread is None and time.monotonic() >= prof_at:
                    prof_thread = threading.Thread(target=self.profile, args=(engine, ctx, prof_box), daemon=True)
                    prof_thread.start()
                ctx.polls.append(engine_io.scrape(engine))
                time.sleep(max(min(1.0, load.t_close - time.monotonic()), 0))
            ctx.after = engine_io.scrape(engine)
            records = load.finish()
            if prof_thread is not None:
                prof_thread.join(timeout=300)
            ctx.window_s = ctx.after.at - ctx.before.at
            recompiles = (
                ctx.before.value("kubeai_engine_jit_recompiles_total"),
                ctx.after.value("kubeai_engine_jit_recompiles_total"),
            )
            if recompiles[0] <= 0 or recompiles[1] != recompiles[0]:
                raise RunFailure(f"a program compiled inside the measured window: jit_recompiles_total {recompiles}")
            probes_after = self.probes(base, "after")
            probe_report = self.compare_probes(probes_before, probes_after)
            ctx.debug_engine = json.loads(engine_io.http_get(engine, "/debug/engine?limit=64"))
            pipeline = json.loads(engine_io.http_get(engine, "/debug/pipeline"))
            memory = ctx.debug_engine["perf"]["memory"]
            device["memory_peak_bytes"] = max((m.get("peak_bytes_in_use") or 0 for m in memory), default=0)
        finally:
            t = time.monotonic()
            self.stop(operator)
            left = self.sweep()
            emit("stop", seconds=time.monotonic() - t, left_behind=left)
        if left:
            self.notes.append(f"processes still alive after the operator exited: {left}")

        # The window's requests: due inside it. Everything else (ramp) is
        # warm-up, though its tokens delivered inside the window count as
        # the window's work.
        ctx.all_records = records
        ctx.records = window = [r for r in records if load.t_open <= r.due < load.t_close]
        failed = [r for r in window if not r.ok]
        for r in failed[:5]:
            self.notes.append(f"request failed: {r.error or 'no answer before the drain limit'}")
        if load.exhausted:
            raise RunFailure("the traffic plan ran out of requests before the window closed")
        # A stall of the whole service shows as a stretch of the window in
        # which no client got a token (diagnosis only; nothing reads it).
        stamps = sorted(t for r in records for t in r.token_times if load.t_open <= t < load.t_close)
        silence = max((b - c for c, b in zip(stamps, stamps[1:])), default=None)
        emit(
            "window", seconds=a.seconds, sent=len(window), failed=len(failed), all_requests=len(records),
            longest_silence_s=silence,
            recompiles=recompiles, probes=probe_report, prompt_tokens=sum(r.prompt_tokens for r in window),
            stall_causes=pipeline.get("causes"),
        )

        t = time.monotonic()
        logits = self.phase_logits(ckpt)
        emit("logits", seconds=time.monotonic() - t, **logits)
        if not logits["ok"] or logits["platform"] != self.platform:
            self.notes.append(f"logits check: {logits['compared']} on {logits['platform']}")

        metrics: dict[str, dict] = {}
        may_miss: set[str] = set()
        obj = {
            "correct": not self.notes, "attempted": len(window), "failed": len(failed),
            "metrics": metrics, "device": device,
        }
        if a.trace:
            self.phase_trace(ctx, prof_box, device, obj, pipeline)
            want = resultline.declared(self.bench, a.workload, True)
            for name, unit in want.items():
                value = self.read_layer_metric(name, ctx)
                if value is None:
                    may_miss.add(name)
                    emit("layer_metric_not_read", name=name)
                else:
                    metrics[name] = {"value": value, "unit": unit}
            if not metrics:
                raise RunFailure("no per-layer metric could be read")
        else:
            want = resultline.declared(self.bench, a.workload, False)
            values = self.end_to_end(window, records, load, setup_s)
            for name, unit in want.items():
                metrics[name] = {"value": values[name], "unit": unit}
        if self.notes:
            emit("not_correct", reasons=self.notes)
        if not a.keep:
            shutil.rmtree(os.path.join(self.workdir, "ckpt"), ignore_errors=True)
            shutil.rmtree(os.path.join(self.workdir, "profile"), ignore_errors=True)
        line = resultline.check(
            obj, a.workload, bool(a.trace), self.cell["chips"], rehearsal=self.rehearsal,
            may_miss=may_miss, bench=self.bench,
        )
        emit("total", seconds=time.monotonic() - T_PROCESS_START)
        sys.stdout.write(line + "\n")
        sys.stdout.flush()
        return 0

    def end_to_end(self, window, records, load, setup_s: float) -> dict[str, float]:
        a = self.args
        in_window = sum(
            1 for r in records for t in r.token_times if load.t_open <= t < load.t_close
        )
        ttft = [1000.0 * (r.token_times[0] - r.due) for r in window if r.token_times]
        gaps = [
            1000.0 * (b - c) for r in window for c, b in zip(r.token_times, r.token_times[1:])
        ]
        pace = [t[1] for t in map(loadgen.ttft_tpot_ms, window) if t is not None]
        if not ttft or not gaps or not pace:
            raise RunFailure("no request of the window streamed a token")
        out = {
            "setup_s": setup_s,
            # All tokens delivered inside the window over its seconds.
            "output_tok_s": in_window / a.seconds,
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p90_ms": percentile(ttft, 90),
            "itl_p99_ms": percentile(gaps, 99),
            # Mean over the window's requests of each one's time per output
            # token after its first: the pace a user reads at, stalls included.
            "tpot_mean_ms": sum(pace) / len(pace),
        }
        emit("end_to_end", n_ttft=len(ttft), n_gaps=len(gaps), **out)
        return out

    def phase_logits(self, ckpt: str) -> dict:
        """The family's logits check (perfbench/families/) on the same seed's
        checkpoint at `logits_check_layers` layers, through the same loader:
        the full checkpoint's shards where the family's plan for the shallower
        model is the same, written anew where a layer depends on the depth."""
        depth = min(self.serving["logits_check_layers"], self.hf["num_hidden_layers"])
        shallow = os.path.join(self.workdir, f"ckpt-{depth}-layers")
        hf_path = self.work_file("hf_config_cut.json", {**self.hf, "num_hidden_layers": depth})
        serving_path = self.work_file("serving.json", self.serving)
        cut = self.run_child("checkpoint", shallow, hf_path, str(self.args.seed), ckpt, platform="cpu", timeout=600)
        out = self.run_child("logits", shallow, str(self.args.seed), serving_path, platform=self.platform, timeout=900)
        return {"cut": cut, **out}

    def phase_trace(self, ctx: Context, box: dict, device: dict, obj: dict, pipeline: dict) -> None:
        if "result" not in box:
            raise RunFailure(f"the engine's /debug/profile gave no trace: {box.get('error', 'never called')}")
        files = glob.glob(os.path.join(box["result"]["trace_dir"], "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise RunFailure(f"expected one .xplane.pb under {box['result']['trace_dir']}, found {files}")
        t = time.monotonic()
        n = self.trace_spec["profile_seconds"]
        tr = self.run_child("trace", files[0], self.platform, str(n), platform="cpu", timeout=600)
        ctx.trace = tr
        if not 0.5 * n <= tr["window_s"] <= 2.0 * n:
            raise RunFailure(f"the trace covers {tr['window_s']} s, {n} s were asked for ({tr['window_from']})")
        device["window_s"], device["busy_s"] = tr["window_s"], tr["busy_s"]
        # The operations with the most time, by the program they ran in
        # (whole runs of a program inside the traced interval).
        per = [
            (f"{op} in {mod.split('(')[0]}", v[0])
            for mod, ops_ in tr["ops_in_modules_s"].items() for op, v in ops_.items()
        ]
        merged: dict[str, float] = {}
        for name, sec in per:
            merged[name] = merged.get(name, 0.0) + sec
        ops = sorted(merged.items(), key=lambda kv: -kv[1])[:10]
        cause = pipeline.get("dominant_cause")
        label = f"host: {cause} (the window's dominant stall cause; gaps are not attributed one by one)" if cause else "unattributed"
        obj["breakdown"] = {
            "device_ops": [[name[:200], sec] for name, sec in ops],
            "idle_gaps": [[f"at +{at:.4f}s, {label}"[:200], dur] for at, dur in tr["gaps_s"][:5]],
        }
        emit(
            "trace", seconds=time.monotonic() - t, bytes=tr["bytes"], profile_call_s=box.get("call_s"),
            window_s=tr["window_s"], busy_s=tr["busy_s"], window_from=tr["window_from"],
            busy_is=tr["busy_is"], devices=tr["devices"], n_gaps=tr["n_gaps"],
            modules=sorted(tr["modules_s"].items(), key=lambda kv: -kv[1][0])[:12],
            ops=sorted(tr["ops_s"].items(), key=lambda kv: -kv[1][0])[:40],
        )
        if self.args.keep:  # for a hand-read: python3 perfbench/trace_reduce.py <file>
            with open(os.path.join(self.workdir, "trace-reduced.json"), "w") as f:
                json.dump(tr, f)

    def read_layer_metric(self, name: str, ctx: Context):
        path = os.path.join(HERE, "layer_metrics", name + ".json")
        with open(path) as f:
            spec = json.load(f)
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("params", {}))
        return None if value is None else float(value)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=int, default=None)
    parser.add_argument("--trace", type=int, default=0, choices=[0, 1])
    parser.add_argument("--rehearse", action="store_true", help="CPU, tiny widths: walks every phase, never a chip result")
    parser.add_argument("--keep", action="store_true", help="keep the checkpoint and the trace")
    args = parser.parse_args()
    if args.seconds is None:
        args.seconds = 8 if args.rehearse else resultline.load_benchmark(ROOT)["run_seconds"]
    run = None
    try:
        run = Run(args)
        return run.main()
    except BaseException as e:  # noqa: BLE001 - nothing may follow but the exit code
        if run is not None:
            try:
                run.sweep()
            except Exception:  # noqa: BLE001
                pass
        print(json.dumps({"failed": f"{type(e).__name__}: {e}"[:4000]}), flush=True)
        return 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # No interpreter shutdown: daemon threads of the load generator may
    # still hold sockets, and nothing may print after the result.
    os._exit(code)
