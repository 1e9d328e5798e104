"""Like `moe_scopes`, with the scopes of a stack of Mamba-2 mixers,
attention and latent-space experts (kubeai_tpu/models/nemotron_h.py,
ops/ssm.py, ops/moe.py): `ssm` and, inside it, `ssm.in_proj`, `ssm.conv`,
`ssm.scan`, `ssm.gate_norm`, `ssm.out_proj`; `attn` / `attn.kernel`; `moe`
with `moe.latent_down`, `moe.router`, `moe.dispatch`, `moe.experts`,
`moe.combine`, `moe.latent_up`, `moe.shared`. An operation is filed under
the LAST of these in its op_name (perfbench/scope_reduce.py), so a scope's
share here is its own operations only: ask for `ssm|ssm.in_proj|...` to get
the whole mixer. One reduction a trace, kept on the context.

`read(ctx, module, scope)`: percent of the seconds of all operations
inside whole runs of the programs matching `module`. `seconds(ctx, module,
scope)`: (seconds under the scopes, all seconds, whole runs of the
programs), for the roofline readers. None where there is no trace, the
program is not in it, or it carries no `ssm` scope (a program of another
family, or from before PR 40)."""

import json
import os
import re
import subprocess
import sys

from readers import scope_share, trace_common

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SSM = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj", "ssm")
# scope_reduce files a component `a.b` under the FIRST of these that is
# `a.b` or a prefix `a`: the longer names stand before the shorter.
SCOPES = (
    "embed", *SSM, "attn.kernel", "attn", "moe.latent_down", "moe.latent_up", "moe.router", "moe.dispatch",
    "moe.experts", "moe.combine", "moe.shared", "moe", "lm_head", "sampling", "logprobs",
)


def reduce(ctx):
    if getattr(ctx, "ssm_scope_shares", None) is None:
        ctx.ssm_scope_shares = {}
        path = scope_share.trace_file(ctx) if getattr(ctx, "trace", None) else None
        if path is None or "ops_in_modules_s" not in ctx.trace:
            return ctx.ssm_scope_shares
        ops_path = path + ".ssm-ops.json"
        with open(ops_path, "w") as f:
            json.dump(ctx.trace["ops_in_modules_s"], f)
        error = None
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "scope_reduce.py"), path, ops_path, ",".join(SCOPES)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
            )
            if proc.returncode == 0:
                ctx.ssm_scope_shares = json.loads(proc.stdout.decode().strip().splitlines()[-1])
            else:
                error = proc.stderr.decode(errors="replace")[-800:]
        except (OSError, subprocess.TimeoutExpired, ValueError, IndexError) as e:
            error = f"{type(e).__name__}: {e}"
        finally:
            os.unlink(ops_path)
        print(json.dumps({"phase": "ssm_scopes", "programs": ctx.ssm_scope_shares, "error": error}), flush=True)
    return ctx.ssm_scope_shares


def seconds(ctx, module, scope):
    rx = re.compile(module)
    wanted = scope.split("|")
    total = part = mixers = 0.0
    for program, r in reduce(ctx).items():
        if rx.search(program):
            total += r["total_s"]
            part += sum(r["by_scope_s"].get(s, 0.0) for s in wanted)
            mixers += sum(r["by_scope_s"].get(s, 0.0) for s in SSM)
    if total <= 0 or mixers <= 0:
        return None
    _, runs = trace_common.module_runs(ctx.trace, module)
    return part, total, runs


def read(ctx, module, scope):
    got = seconds(ctx, module, scope)
    return None if got is None else 100.0 * got[0] / got[1]
