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
a METRIC listed under `tail_view` in perfbench/trace_in_run.json gets the
tail's polls in `before` / `polls` / `after` (run.py's TailView): list there
every metric that divides counters by times of the trace, so that both are
of the same seconds (PR 46), and none that is counters over the window alone
(`window_mfu.*`).

How a cell gets its metrics. An entry of `per_layer` in BENCHMARK.json is
ONE measurement: a file under layer_metrics/ (reader + parameters), what it
moves, and the cells that report it. A PR that adds a cell may not edit an
accepted entry, so it adds an entry of its own (a twin: the accepted file
under a new suffix) ONLY for a measurement whose accepted entry it cannot
edit, and says in CHANGES.md which accepted entry each twin copies; the next
`benchmark` PR folds the twins into their entry's `workloads` list.
`per_layer` may hold 128 entries and held 128 when this was written, 56 of
them twins (PERF.md sections 3 and 7: the groups, and what stops the fold).
A new reading that needs a family's own counts should take the counts
module from the configuration's `model_type` inside ONE reader, not come as
a reader a family (`moe_` / `swa_` / `ssm_` / `afm_rooflines.py` are four
copies of one reading).
"""
