"""Like `moe_scopes`, for ANY family that states its own scopes: the list
`perfbench/scope_reduce.py` files a program's operations under is
`families/<model_type>_counts.py::SCOPES` (the configuration's published
`model_type` names the module: a family brings its scopes as data beside
its counts, not a reader of its own), and `OWN_SCOPES` there are the scopes
no other family's program carries. An operation is filed under the LAST of
the listed scopes in its op_name, so a scope's share here is its own
operations only: ask for `conv|conv.in_proj|...` to get the whole operator.
One reduction a trace, kept on the context.

`read(ctx, module, scope)`: percent of the seconds of all operations inside
whole runs of the programs matching `module`. `seconds(ctx, module, scope)`:
(seconds under the scopes, all seconds, whole runs of the programs), for
`family_rooflines`. None where the family has no counts module or it states
no scopes, there is no trace, the program is not in it, or it carries none
of the family's own scopes (a program of another family, or of a checkout
from before the family's)."""

import importlib
import json
import os
import re
import subprocess
import sys

from readers import scope_share, trace_common

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def counts_of(ctx):
    """families/<model_type>_counts.py of the run's configuration, or None."""
    name = str(ctx.hf.get("model_type"))
    if not re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_]*", name):
        return None
    try:
        return importlib.import_module(f"families.{name}_counts")
    except ImportError:
        return None


def reduce(ctx, counts):
    if getattr(ctx, "family_scope_shares", None) is None:
        ctx.family_scope_shares = {}
        path = scope_share.trace_file(ctx) if getattr(ctx, "trace", None) else None
        if path is None or "ops_in_modules_s" not in ctx.trace:
            return ctx.family_scope_shares
        ops_path = path + ".family-ops.json"
        with open(ops_path, "w") as f:
            json.dump(ctx.trace["ops_in_modules_s"], f)
        error = None
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "scope_reduce.py"), path, ops_path, ",".join(counts.SCOPES)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
            )
            if proc.returncode == 0:
                ctx.family_scope_shares = json.loads(proc.stdout.decode().strip().splitlines()[-1])
            else:
                error = proc.stderr.decode(errors="replace")[-800:]
        except (OSError, subprocess.TimeoutExpired, ValueError, IndexError) as e:
            error = f"{type(e).__name__}: {e}"
        finally:
            os.unlink(ops_path)
        print(json.dumps({"phase": "family_scopes", "programs": ctx.family_scope_shares, "error": error}), flush=True)
    return ctx.family_scope_shares


def seconds(ctx, module, scope):
    counts = counts_of(ctx)
    if counts is None or not getattr(counts, "SCOPES", None):
        return None
    rx = re.compile(module)
    wanted = scope.split("|")
    total = part = own = 0.0
    for program, r in reduce(ctx, counts).items():
        if rx.search(program):
            total += r["total_s"]
            part += sum(r["by_scope_s"].get(s, 0.0) for s in wanted)
            own += sum(r["by_scope_s"].get(s, 0.0) for s in counts.OWN_SCOPES)
    if total <= 0 or own <= 0:
        return None
    _, runs = trace_common.module_runs(ctx.trace, module)
    return part, total, runs


def read(ctx, module, scope):
    got = seconds(ctx, module, scope)
    return None if got is None else 100.0 * got[0] / got[1]
