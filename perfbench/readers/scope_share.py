"""Share of a program's device time spent in operations traced under some
`jax.named_scope`s of the model step (kubeai_tpu/models/llama.py,
engine/sampling.py, the step functions of engine/core.py): the seconds of
the operations under `scope` (several as `a|b`) over the seconds of all
operations inside whole runs of the programs matching `module`, in the
traced interval, in percent. Which scope an operation ran under is not in
its event but in the program the trace keeps (perfbench/scope_reduce.py,
run once per trace in a CPU-backend child of its own; its result is kept on
the context for the other scopes). The context does not say where the trace
file is: it is the run's own, under the work directory's `profile/`, of the
size the trace child reported. None where there is no trace, the program is
not in it, or it carries none of the scopes (a program from before PR 24)."""

import glob
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("embed", "attn", "ffn", "lm_head", "sampling", "logprobs")


def trace_file(ctx):
    """The .xplane.pb that `ctx.trace` was reduced from: the newest one
    under .perfbench_work/*/profile/ whose size is the one the trace child
    read (run.py empties its work directory before a run and keeps one
    trace), or None."""
    pattern = os.path.join(os.path.dirname(HERE), ".perfbench_work", "*", "profile", "**", "*.xplane.pb")
    found = [p for p in glob.glob(pattern, recursive=True) if os.path.getsize(p) == ctx.trace.get("bytes")]
    return max(found, key=os.path.getmtime, default=None)


def reduce(ctx):
    if getattr(ctx, "scope_shares", None) is None:
        ctx.scope_shares = {}
        path = trace_file(ctx) if getattr(ctx, "trace", None) else None
        if path is None or "ops_in_modules_s" not in ctx.trace:
            return ctx.scope_shares
        ops_path = path + ".ops.json"
        with open(ops_path, "w") as f:
            json.dump(ctx.trace["ops_in_modules_s"], f)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "scope_reduce.py"), path, ops_path, ",".join(SCOPES)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
            )
            if proc.returncode == 0:
                ctx.scope_shares = json.loads(proc.stdout.decode().strip().splitlines()[-1])
            else:
                ctx.scope_error = proc.stderr.decode(errors="replace")[-800:]
        except (OSError, subprocess.TimeoutExpired, ValueError, IndexError) as e:
            ctx.scope_error = f"{type(e).__name__}: {e}"
        finally:
            os.unlink(ops_path)
        # Like run.py's phases: one JSON line, for the hand-read of where a
        # program's time goes (PERF.md section 5).
        print(json.dumps({"phase": "scopes", "programs": ctx.scope_shares, "error": getattr(ctx, "scope_error", None)}), flush=True)
    return ctx.scope_shares


def read(ctx, module, scope):
    rx = re.compile(module)
    wanted = scope.split("|")
    total = part = named = 0.0
    for program, r in reduce(ctx).items():
        if rx.search(program):
            total += r["total_s"]
            part += sum(r["by_scope_s"].get(s, 0.0) for s in wanted)
            named += sum(r["by_scope_s"].values())
    if total <= 0 or named <= 0:
        return None
    return 100.0 * part / total
