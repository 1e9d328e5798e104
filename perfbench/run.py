#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>
    python3 perfbench/run.py --workload <name> --rehearse [--trace 1|2]   # CPU, tiny, never a chip result

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

--trace 2 is --trace 0 up to the moment the window has closed and its
numbers are taken (the same operator command and environment, the same
plan, the same scrapes at the same edges, `end_to_end` over the same
records). Then the same traffic goes on: once no record that an end-to-end
metric of the cell still reads is open, the engine traces itself with the
Python tracer off (perfbench/trace_in_run.json) under /metrics polls of
the tail's own; the load stops, and the run goes on as --trace 0 does. The
trace child and the per-layer readers run on the CPU backend beside the
logits child, which holds the chip. The last line carries both kinds of
metric: counters and client records over the measured window, device-trace
metrics over the tail's trace, whose interval is the `profile.window`
event.

Every earlier stdout line is one JSON object for one phase. Children log to
files under the work directory. Any exception stops the children, prints the
reason on an earlier line and exits non-zero with no result.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import math
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


class TailView:
    """A Context whose `before` / `polls` / `after` / `window_s` are the
    tail's (`--trace 2`), for a reader that brackets the traced seconds
    with polls; everything else, and whatever the reader keeps on it, is
    the run's one Context."""

    def __init__(self, ctx: Context, **tail):
        self.__dict__.update(_ctx=ctx, _tail=tail)

    def __getattr__(self, name):
        tail = self.__dict__["_tail"]
        return tail[name] if name in tail else getattr(self.__dict__["_ctx"], name)

    def __setattr__(self, name, value):
        setattr(self.__dict__["_ctx"], name, value)


# What of a request each end-to-end metric (`Run.end_to_end`) still reads
# once the window has closed: nothing (tokens delivered before `t_close`),
# the first token of every request due in the window, or its whole life.
# The tail's trace begins when no such record is open.
READS_AFTER_CLOSE = {
    "setup_s": None, "output_tok_s": None, "ttft_p50_ms": "first_token", "tpot_mean_ms": "whole_life",
}


def build_plan(spec: dict, seed: int, seconds: float, tail_s: float = 0.0):
    """The cell's plan, with requests for *tail_s* seconds beyond the
    window (`--trace 2`). A longer plan has the shorter one as its prefix,
    so the window gets what a --trace 0 run sends: an open loop gets whole
    blocks more, a closed loop's shared list more blocks in proportion
    (sessions' scripts are as long as the file says: each client's is
    drawn after the one before)."""
    if tail_s and spec["loop"] == "closed" and not spec.get("sessions"):
        span = float(spec.get("ramp_s", 0)) + seconds
        spec = {**spec, "blocks": math.ceil(int(spec.get("blocks", 64)) * (span + tail_s) / span)}
    return traffic.build(spec, seed, seconds + tail_s)


def open_records(metrics, records, t_open: float, t_close: float) -> tuple[int, float]:
    """(records of the window that a metric of *metrics* still reads and
    that are still open, when the last one that is closed closed)."""
    reads = {READS_AFTER_CLOSE[m] for m in metrics} - {None}
    still, last = 0, t_close
    for r in records:
        if not reads or not t_open <= r.due < t_close:
            continue
        ended = r.done is not None or r.error is not None
        if "whole_life" in reads:
            closed = (r.done or r.sent or r.due) if ended else None
        else:
            closed = r.token_times[0] if r.token_times else ((r.done or r.sent or r.due) if ended else None)
        if closed is None:
            still += 1
        else:
            last = max(last, closed)
    return still, last


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
        self.tail_spec: dict = {}
        if args.trace == 2:
            with open(os.path.join(HERE, "trace_in_run.json")) as f:
                self.tail_spec = json.load(f)

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
        # The pod inherits the operator's environment (LocalRuntime): the
        # ENGINE process takes the trace, of itself. Set in every mode, so
        # that a --trace 2 run starts what a --trace 0 run starts: the
        # gate is read only when /debug/profile is called.
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

    def profile(self, engine: str, ctx: Context, box: dict, n: float | None = None, query: str = "") -> None:
        n = self.trace_spec["profile_seconds"] if n is None else n
        try:
            ctx.trace_t0 = time.monotonic()
            box["result"] = json.loads(engine_io.http_get(engine, f"/debug/profile?seconds={n}{query}", timeout=n + 240))
            # The call returns only after the trace is written (20 s and
            # more): the traced interval is about the n seconds after it began.
            ctx.trace_t1 = ctx.trace_t0 + n
            box["call_s"] = time.monotonic() - ctx.trace_t0
        except Exception as e:  # noqa: BLE001 - reported by the caller's thread
            box["error"] = f"{type(e).__name__}: {e}"

    def traced_tail(self, load, engine: str, ctx: Context, box: dict, plan) -> dict:
        """--trace 2, the window closed and its edge scraped: the load goes
        on; wait until no record that a declared end-to-end metric still
        reads is open (never past the traffic's `drain_s`), start and stop
        the profiler once for nothing, then have the engine trace itself
        with the Python tracer off under polls of the tail's own, and stop
        the load. The capture's call is still out when this returns."""
        spec = self.tail_spec
        n = spec["profile_seconds"]
        want = resultline.declared(self.bench, self.args.workload, 0)
        while True:
            still, last_closed = open_records(want, load.snapshot(), load.t_open, load.t_close)
            if not still:
                break
            if time.monotonic() > load.t_close + plan.drain_s:
                raise RunFailure(f"{still} record(s) of the window still open {plan.drain_s} s after it closed: no trace beside them")
            time.sleep(0.5)
        query = f"&python_tracer={spec['python_tracer']}"
        # The profiler's first start in a process costs what no later one
        # does: it falls into a capture that is thrown away.
        warm = json.loads(engine_io.http_get(engine, f"/debug/profile?seconds={spec['warm_seconds']}{query}", timeout=240))
        shutil.rmtree(warm["trace_dir"], ignore_errors=True)
        time.sleep(spec["settle_seconds"])  # what its stop held back has been delivered
        before = engine_io.scrape(engine)
        thread = threading.Thread(target=self.profile, args=(engine, ctx, box, n, query), daemon=True)
        t_begin = time.monotonic()
        thread.start()
        polls = []
        # One poll a second through the traced seconds and a second more
        # (the capture's own start lies between the call and the interval).
        while time.monotonic() < t_begin + n + 1.0:
            time.sleep(max(min(1.0, t_begin + n + 1.0 - time.monotonic()), 0))
            polls.append(engine_io.scrape(engine))
        load.end_sending()
        after = polls.pop()
        ctx.tail_view = TailView(ctx, before=before, polls=polls, after=after, window_s=after.at - before.at)
        return {
            "thread": thread, "t_begin": t_begin, "warm": warm, "last_closed": last_closed,
            "recompiles": after.value("kubeai_engine_jit_recompiles_total"), "polls": len(polls) + 2,
        }

    def tail_traced(self, load, ctx: Context, box: dict, tail: dict) -> dict:
        """The capture's call has returned (it writes the trace while the
        load drains): what the reply names, and the `trace` line's account
        of the tail."""
        n = self.tail_spec["profile_seconds"]
        result = box.get("result") or {}
        # The interval began when the profiler had started, not at the call.
        ctx.trace_t0 += result.get("start_seconds") or 0.0
        ctx.trace_t1 = ctx.trace_t0 + n
        ctx.trace_path = result.get("xplane")
        ctx.window_event = result.get("window_event") or self.tail_spec["window_event_default"]
        ctx.window_event_rx = "^" + re.escape(ctx.window_event) + "$"
        # What tracing costs while it is on: tokens delivered to the clients
        # a second inside the traced seconds, beside the measured window's.
        stamps = [t for r in load.snapshot() for t in r.token_times]
        per_s = lambda lo, hi: sum(1 for t in stamps if lo <= t < hi) / (hi - lo)  # noqa: E731
        return {
            "recompiles": tail["recompiles"],
            "last_record_closed_s": tail["last_closed"] - load.t_close, "capture_began_s": tail["t_begin"] - load.t_close,
            "warm_capture": {k: tail["warm"].get(k) for k in ("start_seconds", "stop_seconds")},
            "capture": {k: result.get(k) for k in ("start_seconds", "stop_seconds", "bytes")},
            "python_tracer": result.get("python_tracer"), "polls": tail["polls"],
            "traced_tok_s": per_s(ctx.trace_t0, ctx.trace_t1), "window_tok_s": per_s(load.t_open, load.t_close),
        }

    def layers_in_run(self, ctx: Context, box: dict, device: dict, tail: dict, metrics: dict, may_miss: set, out: dict) -> None:
        """--trace 2, after the operator has gone: the trace child on the
        tail's trace (its interval is the event the engine's reply named),
        every idle gap put down to the scheduler segments beside it, and
        every per-layer metric of the cell. A thread's body: what goes
        wrong is left in *out* for the run's thread to raise."""
        try:
            import idle_attribution
            from readers import idle_by_host

            t = time.monotonic()
            n = self.tail_spec["profile_seconds"]
            tr = self.reduce_trace(ctx, box, device, n, ctx.window_event_rx)
            if not tr["window_from"].startswith("host event"):
                raise RunFailure(f"the trace holds no {ctx.window_event!r} event: its interval would be {tr['window_from']}")
            table = idle_by_host.reduce(ctx, quiet=True)
            gaps = table.get("gaps") or []
            out["breakdown"] = {
                "device_ops": [[name[:200], sec] for name, sec in self.top_device_ops(tr)],
                # Each gap with ITS OWN causes; where the program wrote no
                # segment, where it lay and nothing more.
                "idle_gaps": [[idle_attribution.label(g), g["seconds"]] for g in gaps[:5]]
                or [[f"at +{at:.4f}s, unattributed"[:200], dur] for at, dur in tr["gaps_s"][:5]],
            }
            self.emit_trace(t, tr, box, **tail, idle_by_host={k: v for k, v in table.items() if k != "gaps"})
            self.read_layer_metrics(ctx, metrics, may_miss)
        except BaseException as e:  # noqa: BLE001 - raised by the run's thread
            out["error"] = f"{type(e).__name__}: {e}"

    # -- the run -----------------------------------------------------------

    def main(self) -> int:
        a = self.args
        if not os.path.isdir(os.path.join(ROOT, "kubeai_tpu")):
            raise RunFailure("no kubeai_tpu/ beside perfbench/: nothing to measure")
        shutil.rmtree(self.workdir, ignore_errors=True)
        os.makedirs(self.workdir)
        spec = traffic.load(self.cell["traffic"], self.rehearsal)
        # The longest wait for the window's records, and the trace.
        tail_s = float(spec.get("drain_s", 60)) + 3 * self.tail_spec["profile_seconds"] if a.trace == 2 else 0
        plan = build_plan(spec, a.seed, a.seconds, tail_s)
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
            load = loadgen.Load(base, self.model, plan, a.seconds, hold=a.trace == 2)
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
                if a.trace == 1 and prof_thread is None and time.monotonic() >= prof_at:
                    prof_thread = threading.Thread(target=self.profile, args=(engine, ctx, prof_box), daemon=True)
                    prof_thread.start()
                ctx.polls.append(engine_io.scrape(engine))
                time.sleep(max(min(1.0, load.t_close - time.monotonic()), 0))
            ctx.after = engine_io.scrape(engine)
            if a.trace == 2:
                # The scheduler's account of the window while nothing has
                # traced yet, then the tail: the same traffic, traced.
                pipeline_at_close = (time.monotonic(), json.loads(engine_io.http_get(engine, "/debug/pipeline")))
                tail = self.traced_tail(load, engine, ctx, prof_box, plan)
                prof_thread = tail["thread"]
            records = load.finish()
            if prof_thread is not None:
                prof_thread.join(timeout=300)
            if a.trace == 2:
                tail = self.tail_traced(load, ctx, prof_box, tail)
            ctx.window_s = ctx.after.at - ctx.before.at
            recompiles = (
                ctx.before.value("kubeai_engine_jit_recompiles_total"),
                ctx.after.value("kubeai_engine_jit_recompiles_total"),
            )
            if recompiles[0] <= 0 or recompiles[1] != recompiles[0]:
                raise RunFailure(f"a program compiled inside the measured window: jit_recompiles_total {recompiles}")
            if a.trace == 2 and tail["recompiles"] != recompiles[1]:
                raise RunFailure(f"a program compiled in the traced tail: jit_recompiles_total {recompiles[1]} -> {tail['recompiles']}")
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
        kept = {}
        if a.trace == 2:
            # Where the silence lay, and the engine's slowest steps that
            # ended inside the window (diagnosis only: a stall leaves a record).
            at = max(zip(stamps, stamps[1:]), key=lambda cb: cb[1] - cb[0], default=(None,))[0]
            fetched, pipeline = pipeline_at_close
            kept = {
                "longest_silence_at_s": None if at is None else at - load.t_open,
                "slowest_steps": [
                    {"at_s": round(fetched - s["age_s"] - load.t_open, 3), "kind": s["kind"], "total_ms": s["total_ms"], "ms": s["ms"]}
                    for s in pipeline.get("slowest_steps", ())
                    if load.t_open <= fetched - s["age_s"] < load.t_close + 1.0
                ],
            }
        emit(
            "window", seconds=a.seconds, sent=len(window), failed=len(failed), all_requests=len(records),
            longest_silence_s=silence,
            recompiles=recompiles, probes=probe_report, prompt_tokens=sum(r.prompt_tokens for r in window),
            stall_causes=pipeline.get("causes"), **kept,
        )

        metrics: dict[str, dict] = {}
        may_miss: set[str] = set()
        layers: dict = {}
        layer_thread = None
        if a.trace == 2:
            # On the CPU backend, beside the logits child, which holds the chip.
            layer_thread = threading.Thread(
                target=self.layers_in_run, args=(ctx, prof_box, device, tail, metrics, may_miss, layers), daemon=True,
            )
            layer_thread.start()
        t = time.monotonic()
        logits = self.phase_logits(ckpt)
        if layer_thread is not None:
            layer_thread.join(timeout=900)
            if layer_thread.is_alive() or "error" in layers:
                raise RunFailure(f"reading the tail's trace: {layers.get('error', 'not done 900 s after the logits check')}")
        emit("logits", seconds=time.monotonic() - t, **logits)
        if not logits["ok"] or logits["platform"] != self.platform:
            self.notes.append(f"logits check: {logits['compared']} on {logits['platform']}")

        obj = {
            "correct": not self.notes, "attempted": len(window), "failed": len(failed),
            "metrics": metrics, "device": device,
        }
        if a.trace == 1:
            self.phase_trace(ctx, prof_box, device, obj, pipeline)
            self.read_layer_metrics(ctx, metrics, may_miss)
        if a.trace != 1:
            want = resultline.declared(self.bench, a.workload, 0)
            values = self.end_to_end(window, records, load, setup_s)
            for name, unit in want.items():
                metrics[name] = {"value": values[name], "unit": unit}
        if "breakdown" in layers:
            obj["breakdown"] = layers["breakdown"]
        if self.notes:
            emit("not_correct", reasons=self.notes)
        if not a.keep:
            shutil.rmtree(os.path.join(self.workdir, "ckpt"), ignore_errors=True)
            shutil.rmtree(os.path.join(self.workdir, "profile"), ignore_errors=True)
        line = resultline.check(
            obj, a.workload, a.trace, self.cell["chips"], rehearsal=self.rehearsal,
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

    def reduce_trace(self, ctx: Context, box: dict, device: dict, n: float | None = None, window_event: str = "") -> dict:
        """The trace child on the one .xplane.pb the engine wrote; sets
        `ctx.trace` and the device's `window_s` / `busy_s`."""
        if "result" not in box:
            raise RunFailure(f"the engine's /debug/profile gave no trace: {box.get('error', 'never called')}")
        files = glob.glob(os.path.join(box["result"]["trace_dir"], "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise RunFailure(f"expected one .xplane.pb under {box['result']['trace_dir']}, found {files}")
        n = self.trace_spec["profile_seconds"] if n is None else n
        argv = [files[0], self.platform, str(n)] + ([window_event] if window_event else [])
        tr = self.run_child("trace", *argv, platform="cpu", timeout=600)
        ctx.trace = tr
        if not 0.5 * n <= tr["window_s"] <= 2.0 * n:
            raise RunFailure(f"the trace covers {tr['window_s']} s, {n} s were asked for ({tr['window_from']})")
        device["window_s"], device["busy_s"] = tr["window_s"], tr["busy_s"]
        return tr

    @staticmethod
    def top_device_ops(tr: dict) -> list[tuple[str, float]]:
        """The operations with the most time, by the program they ran in
        (whole runs of a program inside the traced interval)."""
        per = [
            (f"{op} in {mod.split('(')[0]}", v[0])
            for mod, ops_ in tr["ops_in_modules_s"].items() for op, v in ops_.items()
        ]
        merged: dict[str, float] = {}
        for name, sec in per:
            merged[name] = merged.get(name, 0.0) + sec
        return sorted(merged.items(), key=lambda kv: -kv[1])[:10]

    def phase_trace(self, ctx: Context, box: dict, device: dict, obj: dict, pipeline: dict) -> None:
        t = time.monotonic()
        tr = self.reduce_trace(ctx, box, device)
        ops = self.top_device_ops(tr)
        cause = pipeline.get("dominant_cause")
        label = f"host: {cause} (the window's dominant stall cause; gaps are not attributed one by one)" if cause else "unattributed"
        obj["breakdown"] = {
            "device_ops": [[name[:200], sec] for name, sec in ops],
            "idle_gaps": [[f"at +{at:.4f}s, {label}"[:200], dur] for at, dur in tr["gaps_s"][:5]],
        }
        self.emit_trace(t, tr, box)

    def emit_trace(self, t: float, tr: dict, box: dict, **more) -> None:
        """The `trace` phase line; *more* goes between the trace's own
        numbers and its lists of programs and operations."""
        emit(
            "trace", seconds=time.monotonic() - t, bytes=tr["bytes"], profile_call_s=box.get("call_s"),
            window_s=tr["window_s"], busy_s=tr["busy_s"], window_from=tr["window_from"],
            busy_is=tr["busy_is"], devices=tr["devices"], n_gaps=tr["n_gaps"], **more,
            modules=sorted(tr["modules_s"].items(), key=lambda kv: -kv[1][0])[:12],
            ops=sorted(tr["ops_s"].items(), key=lambda kv: -kv[1][0])[:40],
        )
        if self.args.keep:  # for a hand-read: python3 perfbench/trace_reduce.py <file>
            with open(os.path.join(self.workdir, "trace-reduced.json"), "w") as f:
                json.dump(tr, f)

    def read_layer_metrics(self, ctx: Context, metrics: dict, may_miss: set) -> None:
        """Every per-layer metric declared for the cell, into *metrics*;
        the names whose reader found nothing to read into *may_miss*."""
        before = len(metrics)
        for name, unit in resultline.declared(self.bench, self.args.workload, 1).items():
            value = self.read_layer_metric(name, ctx)
            if value is None:
                may_miss.add(name)
                emit("layer_metric_not_read", name=name)
            else:
                metrics[name] = {"value": value, "unit": unit}
        if len(metrics) == before:
            raise RunFailure("no per-layer metric could be read")

    def read_layer_metric(self, name: str, ctx: Context):
        if name in self.tail_spec.get("tail_view", ()):
            ctx = ctx.tail_view
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
    parser.add_argument("--trace", type=int, default=0, choices=[0, 1, 2])
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
