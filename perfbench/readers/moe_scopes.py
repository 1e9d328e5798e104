"""Like `scope_share`, with the scopes of an expert model's step
(kubeai_tpu/models/deepseek.py, ops/moe.py) beside the dense decoder's:
`moe` and, inside it, `moe.router`, `moe.dispatch`, `moe.experts` (the
grouped matmuls and nothing else), `moe.combine`, `moe.shared`; and
`attn.kernel` told apart from `attn`. An operation is filed under the LAST
of these in its op_name (perfbench/scope_reduce.py), so a scope's share
here is its own operations only: ask for `moe|moe.router|...` to get the
whole expert layer. One reduction a trace, kept on the context.

`read(ctx, module, scope)`: percent of the seconds of all operations
inside whole runs of the programs matching `module`. `seconds(ctx, module,
scope)`: (seconds under the scopes, whole runs of the programs), for the
roofline readers. None where there is no trace, the program is not in it,
or it carries none of these scopes."""

import json
import os
import re
import subprocess
import sys

from readers import scope_share, trace_common

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# scope_reduce files a component `a.b` under the FIRST of these that is
# `a.b` or a prefix `a`: the longer names stand before the shorter.
SCOPES = (
    "embed", "attn.kernel", "attn", "ffn", "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
    "moe.shared", "moe", "lm_head", "sampling", "logprobs",
)


def reduce(ctx):
    if getattr(ctx, "moe_scope_shares", None) is None:
        ctx.moe_scope_shares = {}
        path = scope_share.trace_file(ctx) if getattr(ctx, "trace", None) else None
        if path is None or "ops_in_modules_s" not in ctx.trace:
            return ctx.moe_scope_shares
        ops_path = path + ".moe-ops.json"
        with open(ops_path, "w") as f:
            json.dump(ctx.trace["ops_in_modules_s"], f)
        error = None
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "scope_reduce.py"), path, ops_path, ",".join(SCOPES)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
            )
            if proc.returncode == 0:
                ctx.moe_scope_shares = json.loads(proc.stdout.decode().strip().splitlines()[-1])
            else:
                error = proc.stderr.decode(errors="replace")[-800:]
        except (OSError, subprocess.TimeoutExpired, ValueError, IndexError) as e:
            error = f"{type(e).__name__}: {e}"
        finally:
            os.unlink(ops_path)
        print(json.dumps({"phase": "moe_scopes", "programs": ctx.moe_scope_shares, "error": error}), flush=True)
    return ctx.moe_scope_shares


def seconds(ctx, module, scope):
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
    _, runs = trace_common.module_runs(ctx.trace, module)
    return part, total, runs


def read(ctx, module, scope):
    got = seconds(ctx, module, scope)
    return None if got is None else 100.0 * got[0] / got[1]
