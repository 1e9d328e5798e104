"""Share of the traced interval in which the device ran nothing while the
scheduler thread was under one of `causes` (`sched.<cause>` on the line
`engine-loop`; `other`: under no segment), in percent. Device and host are
read from the run's one .xplane.pb, on its one clock, both clipped to the
interval `ctx.trace` was reduced over (perfbench/idle_attribution.py, once
per trace in a CPU-backend child; the table is kept on the context and
printed as an `idle_by_host` line). Over all causes the shares add up to
`device_idle_pct` of the same trace.

The file is the one the engine's reply named (`ctx.trace_path`, `--trace
2`) or, for want of a path, the one `scope_share.trace_file` finds by its
size. None where there is no trace, or the program wrote no segment into it
(a program from before PR 24)."""

import json
import os
import subprocess
import sys

from readers import scope_share

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reduce(ctx, quiet=False):
    """The table of `idle_attribution.table`, or {} where there is none."""
    if getattr(ctx, "idle_by_host", None) is None:
        ctx.idle_by_host = {}
        trace = getattr(ctx, "trace", None)
        path = (getattr(ctx, "trace_path", None) or scope_share.trace_file(ctx)) if trace else None
        if path is None:
            return ctx.idle_by_host
        # The same interval as `ctx.trace`: its platform's part of
        # trace.json, with the event the reply named where one did.
        argv = [path, "cpu" if ctx.rehearsal else "tpu", repr(float(ctx.trace_t1 - ctx.trace_t0))]
        if getattr(ctx, "window_event_rx", None):
            argv.append(ctx.window_event_rx)
        error = None
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "idle_attribution.py"), *argv],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
            )
            if proc.returncode == 0:
                ctx.idle_by_host = json.loads(proc.stdout.decode().strip().splitlines()[-1])
            else:
                error = proc.stderr.decode(errors="replace")[-800:]
        except (OSError, subprocess.TimeoutExpired, ValueError, IndexError) as e:
            error = f"{type(e).__name__}: {e}"
        if not quiet or error:
            print(json.dumps({"phase": "idle_by_host", **ctx.idle_by_host, "error": error}), flush=True)
    return ctx.idle_by_host


def read(ctx, causes):
    t = reduce(ctx)
    if not t or not t.get("n_segments") or t["window_s"] <= 0:
        return None
    # Read against the interval the other trace metrics were read over.
    if abs(t["window_s"] - ctx.trace["window_s"]) > 1e-6:
        return None
    return 100.0 * sum(t["idle_by_cause_s"].get(c, 0.0) for c in causes) / t["window_s"]
