"""One small reader per kind of per-layer metric. A metric is a data file
under perfbench/layer_metrics/ that names a reader module of this package
and its parameters; `read(ctx, **params)` returns the number, or None where
there was nothing to read (the harness then leaves the metric out).

`ctx` (run.py's Context) carries what a traced run gathered: `before` and
`after` (engine /metrics scrapes at the window's edges), `polls` (one a
second), `records` (the window's client records), `all_records`,
`window_s`, `debug_engine` (/debug/engine after the window), `first`
(the request sent to zero replicas), `trace` (trace_reduce's output) with
`trace_t0`/`trace_t1` (host clock around the profile call), `hf` (the
published config), `serving`, `peaks`, `rehearsal`.

In a run that traced itself (`--trace 2`) `before` / `after` / `polls` /
`records` / `window_s` are the MEASURED window's (untraced), `trace` and
`trace_t0` / `trace_t1` the tail's, whose requests are in `all_records`
only; `trace_path` and `window_event_rx` are what the engine's reply named;
a reader listed under `tail_view` in perfbench/trace_in_run.json gets the
tail's polls in `before` / `polls` / `after` (run.py's TailView).
"""
