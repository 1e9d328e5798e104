"""Like `moe_scopes`, for a stack with two kinds of attention layer
(kubeai_tpu/models/smallthinker.py): `attn.full` and `attn.window`, each
with `attn.kernel` inside around the call that reads pages, and the expert
scopes `moe`, `moe.router`, `moe.dispatch`, `moe.experts`, `moe.combine`.

`scope_reduce.py` files an operation under the LAST scope of a list in its
op_name, so one list cannot tell `attn.full/attn.kernel` from
`attn.window/attn.kernel`. Two reductions of the same trace can: WITHOUT
`attn.kernel` in the list an operation inside a kernel scope files under
its layer's kind, WITH it under `attn.kernel`; the difference of a kind's
seconds between the two is the time under that kind's `attn.kernel`, read
by scope, whatever implements the read.

`read(ctx, module, kind)`: percent of the seconds of all operations inside
whole runs of the programs matching `module` that ran under `attn.<kind>`
(kernel included). `seconds(ctx, module, kind, kernel)`: (seconds under
`attn.<kind>`, or under its `attn.kernel` alone; all seconds; whole runs).
None where there is no trace, the program is not in it, or it carries
neither scope (a program of another family, or from before PR 36)."""

import json
import os
import re
import subprocess
import sys

from readers import scope_share, trace_common

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = (
    "embed", "attn.full", "attn.window", "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe",
    "lm_head", "sampling", "logprobs",
)
PASSES = {"layers": LAYERS, "kernels": ("attn.kernel",) + LAYERS}


def reduce(ctx):
    if getattr(ctx, "swa_scope_shares", None) is None:
        ctx.swa_scope_shares = {}
        path = scope_share.trace_file(ctx) if getattr(ctx, "trace", None) else None
        if path is None or "ops_in_modules_s" not in ctx.trace:
            return ctx.swa_scope_shares
        ops_path = path + ".swa-ops.json"
        with open(ops_path, "w") as f:
            json.dump(ctx.trace["ops_in_modules_s"], f)
        error = None
        try:
            for name, scopes in PASSES.items():
                proc = subprocess.run(
                    [sys.executable, os.path.join(HERE, "scope_reduce.py"), path, ops_path, ",".join(scopes)],
                    env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
                )
                if proc.returncode != 0:
                    error = proc.stderr.decode(errors="replace")[-800:]
                    ctx.swa_scope_shares = {}
                    break
                ctx.swa_scope_shares[name] = json.loads(proc.stdout.decode().strip().splitlines()[-1])
        except (OSError, subprocess.TimeoutExpired, ValueError, IndexError) as e:
            error, ctx.swa_scope_shares = f"{type(e).__name__}: {e}", {}
        finally:
            os.unlink(ops_path)
        print(json.dumps({"phase": "swa_scopes", "programs": ctx.swa_scope_shares, "error": error}), flush=True)
    return ctx.swa_scope_shares


def _sum(ctx, which, module, scopes):
    rx = re.compile(module)
    total = part = 0.0
    for program, r in reduce(ctx).get(which, {}).items():
        if rx.search(program):
            total += r["total_s"]
            part += sum(r["by_scope_s"].get(s, 0.0) for s in scopes)
    return part, total


def seconds(ctx, module, kind, kernel=False, scopes=None):
    """*scopes*: other scopes' seconds instead of a kind's (`moe.experts`)."""
    wanted = scopes or ("attn." + kind,)
    part, total = _sum(ctx, "layers", module, wanted)
    attn, _ = _sum(ctx, "layers", module, ("attn.full", "attn.window"))
    if total <= 0 or attn <= 0:
        return None
    if kernel:
        part -= _sum(ctx, "kernels", module, wanted)[0]
    _, runs = trace_common.module_runs(ctx.trace, module)
    return part, total, runs


def read(ctx, module, kind):
    got = seconds(ctx, module, kind)
    return None if got is None else 100.0 * got[0] / got[1]
