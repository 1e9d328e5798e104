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

How a cell gets its metrics (since the fold, PR 50). An entry of
`per_layer` in BENCHMARK.json is ONE measurement: a file under
layer_metrics/ (reader + parameters), what it moves, and the cells that
report it (`workloads`; every entry has the list, so a new cell takes none
by default). No two entries are the same file with the same `moves`, `unit`,
`better`, `source` and `layer`: selftest.py refuses a second name. So a cell
comes to its metrics in two ways:
 * a measurement the benchmark does not have yet comes as a NEW entry with a
   new file (appended; 72 of the 128 entries were taken when this was
   written, selftest.py prints how many are free);
 * a measurement it has is JOINED: the cell's name goes into the accepted
   entry's `workloads`, under the entry's name, and only where one traced
   run on the chip shows that the reader reads a number on that cell's
   program. That is an edit of an accepted entry: a `benchmark` PR may make
   it; a PR of another kind makes it where the driver lets it (the ledger's
   `benchmark_edited`), and where it may not it leaves the measurement out,
   says so in CHANGES.md and lists it in PERF.md section 7 for the next
   `benchmark` PR. It does NOT copy the file under a suffix (56 such second
   names were folded away: CHANGES.md, PR 50, has old name -> new name for
   a reader of older ledger lines).
A suffix that is left tells apart entries that differ in `moves` (`.rate`,
`.lat`: chat-rate's move `tpot_mean_ms`; `.tput`: `output_tok_s`) or in
their reader (`.moe` / `.swa` / `.ssm` / `.afm` on the four families'
`*_rooflines` / `*_scopes` readings and `window_mfu.*`). Those four readers
are four copies of one reading: a new reading that needs a family's own
counts should take the counts module from the configuration's `model_type`
inside ONE reader, not come as a reader a family. What a metric IS is read
from its entry and its file, never from its name (resultline.py's 105% rule:
unit `%` and a reader whose module name holds `roofline`).
"""
