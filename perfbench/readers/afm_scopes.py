"""Like `moe_scopes`, with the scopes of a gated, QK-normed stack of window
and full layers with post-norms (kubeai_tpu/models/afmoe.py): inside `attn`
the layer's kind, `attn.full` or `attn.window`, and inside that
`attn.qk_norm`, `attn.kernel` and `attn.gate`; `norm.post` around each of
the two norms on a sub-block's output; `ffn` in the dense layers; `moe` with
`moe.router`, `moe.dispatch`, `moe.experts`, `moe.combine`, `moe.shared`. An
operation is filed under the LAST of these in its op_name
(perfbench/scope_reduce.py), so what the family adds to a plain pre-norm
block (`attn.qk_norm|attn.gate|norm.post`) is told apart from the layer it
sits in. One reduction a trace, kept on the context. (The kinds' own shares
and their kernels' time are `swa_scopes`': the two families give those
scopes the same names.)

`read(ctx, module, scope)`: percent of the seconds of all operations inside
whole runs of the programs matching `module` under the scopes `a|b`. None
where there is no trace, the program is not in it, or it carries no
`norm.post` scope (a program of another family, or from before PR 42)."""

import json
import os
import re
import subprocess
import sys

from readers import scope_share

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# scope_reduce files a component `a.b` under the FIRST of these that is
# `a.b` or a prefix `a`: no plain `attn`, which would take `attn.kernel`.
SCOPES = (
    "embed", "attn.qk_norm", "attn.gate", "attn.kernel", "attn.full", "attn.window", "norm.post", "ffn",
    "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared", "moe", "lm_head", "sampling", "logprobs",
)


def reduce(ctx):
    if getattr(ctx, "afm_scope_shares", None) is None:
        ctx.afm_scope_shares = {}
        path = scope_share.trace_file(ctx) if getattr(ctx, "trace", None) else None
        if path is None or "ops_in_modules_s" not in ctx.trace:
            return ctx.afm_scope_shares
        ops_path = path + ".afm-ops.json"
        with open(ops_path, "w") as f:
            json.dump(ctx.trace["ops_in_modules_s"], f)
        error = None
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "scope_reduce.py"), path, ops_path, ",".join(SCOPES)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
            )
            if proc.returncode == 0:
                ctx.afm_scope_shares = json.loads(proc.stdout.decode().strip().splitlines()[-1])
            else:
                error = proc.stderr.decode(errors="replace")[-800:]
        except (OSError, subprocess.TimeoutExpired, ValueError, IndexError) as e:
            error = f"{type(e).__name__}: {e}"
        finally:
            os.unlink(ops_path)
        print(json.dumps({"phase": "afm_scopes", "programs": ctx.afm_scope_shares, "error": error}), flush=True)
    return ctx.afm_scope_shares


def read(ctx, module, scope):
    rx = re.compile(module)
    wanted = scope.split("|")
    total = part = post = 0.0
    for program, r in reduce(ctx).items():
        if rx.search(program):
            total += r["total_s"]
            part += sum(r["by_scope_s"].get(s, 0.0) for s in wanted)
            post += r["by_scope_s"].get("norm.post", 0.0)
    if total <= 0 or post <= 0:
        return None
    return 100.0 * part / total
