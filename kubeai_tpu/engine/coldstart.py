"""Cold-start fast path: phase stamps, compile-cache plumbing, and
ahead-of-time (AOT) warm compilation of the engine's step functions.

Scale-from-zero used to pay a strictly serial chain — stage weights,
read the whole checkpoint, convert on host, device_put, jit-compile,
warm up — while the proxy held requests. This module provides the
machinery that collapses it:

- ``setup_compile_cache()`` — the ONE place the persistent compilation
  cache is placed: ``JAX_COMPILATION_CACHE_DIR`` where it is set, the
  checkout's ``.jax_compile_cache`` otherwise. Every process entry
  point (CLI server incl. gang followers, loader warm, bench harnesses)
  calls it, so a shared cache mount turns first-compiles into disk
  reads everywhere.
- ``ColdStartTimeline`` — per-phase stamps (stage/load/compile/warmup
  → ready) surfaced in ``/debug/engine`` and the
  ``kubeai_engine_cold_start_seconds{phase}`` histogram. Sum-of-phases
  exceeding wall-clock is the direct evidence that load and compile
  overlapped.
- ``warm_compile()`` / ``warm_from_checkpoint()`` — bring up the
  engine's EXACT step programs (engine/step_programs.py: one list of
  call shapes, one table of held executables) against abstract
  ``ShapeDtypeStruct`` trees derived from config.json alone — no weights
  needed. With the persistent cache placed the executables land on disk
  twice: in jax's cache, keyed by the lowered text, and in the
  deployment's bundle beside it, from which the next start LOADS them
  without tracing or lowering. The loader Job (``--warm-compile-cache``)
  and a parked replica pre-pay both.
- ``start_background_warm()`` — fill the table on a thread while weights
  stream, so engine start costs ~max(load, programs) instead of their
  sum; the Engine then RUNS the table's executables (they are not
  compiled a second time through its jit calls).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from contextlib import contextmanager

from kubeai_tpu.metrics import default_registry

log = logging.getLogger("kubeai_tpu.engine.coldstart")

# Engine starts span milliseconds (tiny CPU tests) to minutes (big
# checkpoints compiling on a TPU) — the default buckets top out far too
# low to resolve either end.
_COLD_START_BUCKETS = (0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120, 300, 600)

M_COLD_START = default_registry.histogram(
    "kubeai_engine_cold_start_seconds",
    "engine start phase durations, labeled phase=stage|load|compile|"
    "build|warmup plus phase=ready (total start-to-serving wall "
    "clock); phase sums exceeding their span mean phases overlapped",
    buckets=_COLD_START_BUCKETS,
)


def default_compile_cache_dir() -> str:
    """``<checkout>/.jax_compile_cache`` (git-ignored): a fixed path,
    because the path is part of the cache's key and a directory that
    moves never hits."""
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(root, ".jax_compile_cache")


def setup_compile_cache() -> str:
    """Place jax's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` is set (a shared mount, the engine
    image's /cache/jax) the cache lives there, otherwise in
    default_compile_cache_dir(). Returns the directory.

    The ONE writer of ``jax_compilation_cache_dir``, called by the
    process entry points (engine server CLI, loader warm, bench.py,
    profile_engine.py) — library code never places a cache, it only
    reads ``jax.config.jax_compilation_cache_dir`` to see whether one is
    on. Safe to call repeatedly."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache EVERY compilation: the loader-warmed / parked fast path
    # depends on sub-second compiles (small models, per-bucket prefill
    # shapes) being hits too — jax's default 1s floor skips exactly the
    # entries that make warmup cheap.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # The cache latches its directory at first use: a process that
    # already compiled anything would ignore the new one without this.
    compilation_cache.reset_cache()
    return cache_dir


class ColdStartTimeline:
    """Thread-safe per-phase stamps for one engine start.

    Phases may overlap (that is the point: the compile phase runs on a
    background thread while load streams on the caller's), so stamps
    are independent begin/end pairs, not a stack. ``install()`` makes
    the timeline visible at ``/debug/engine`` under ``cold_start``."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self.t0_mono = clock()
        self.t0_wall = time.time()
        self._phases: dict[str, dict] = {}
        self.ready_mono: float | None = None
        self.attrs: dict = {}

    def begin(self, name: str) -> None:
        with self._lock:
            self._phases.setdefault(name, {})["start"] = self._clock()

    def end(self, name: str) -> None:
        now = self._clock()
        with self._lock:
            ph = self._phases.setdefault(name, {})
            ph.setdefault("start", now)
            ph["end"] = now
            dur = ph["end"] - ph["start"]
        M_COLD_START.observe(dur, labels={"phase": name})

    @contextmanager
    def phase(self, name: str):
        self.begin(name)
        try:
            yield self
        finally:
            self.end(name)

    def ready(self) -> None:
        """Stamp serving readiness; observes the total wall clock as
        phase="ready" (idempotent — the first stamp wins)."""
        with self._lock:
            if self.ready_mono is not None:
                return
            self.ready_mono = self._clock()
            total = self.ready_mono - self.t0_mono
        M_COLD_START.observe(total, labels={"phase": "ready"})

    def snapshot(self) -> dict:
        with self._lock:
            phases = {k: dict(v) for k, v in self._phases.items()}
            ready = self.ready_mono
        out_phases = {}
        intervals: list[tuple[float, float]] = []
        phase_sum = 0.0
        for name, ph in phases.items():
            start = ph.get("start")
            end = ph.get("end")
            rec = {"start_s": round(start - self.t0_mono, 4)}
            if end is not None:
                rec["end_s"] = round(end - self.t0_mono, 4)
                rec["duration_s"] = round(end - start, 4)
                phase_sum += end - start
                intervals.append((start, end))
            out_phases[name] = rec
        out = {
            "t0_unix": round(self.t0_wall, 3),
            "phases": out_phases,
            "phase_sum_s": round(phase_sum, 4),
            "attrs": dict(self.attrs),
        }
        if intervals:
            # Interval-union coverage: sum − union is the time at least
            # two phases ran CONCURRENTLY (gaps between serial phases
            # must not mask it — a span-based diff would).
            union = 0.0
            cur_s, cur_e = None, None
            for s, e in sorted(intervals):
                if cur_e is None or s > cur_e:
                    union += cur_e - cur_s if cur_e is not None else 0.0
                    cur_s, cur_e = s, e
                else:
                    cur_e = max(cur_e, e)
            union += cur_e - cur_s
            out["span_s"] = round(max(e for _, e in intervals) - min(s for s, _ in intervals), 4)
            out["overlap_s"] = round(max(phase_sum - union, 0.0), 4)
        if ready is not None:
            out["ready_s"] = round(ready - self.t0_mono, 4)
        return out

    def install(self) -> "ColdStartTimeline":
        """Expose this timeline at /debug/engine (latest install wins —
        one engine start per process is the norm; a parked replica's
        attach installs a fresh timeline over the park-time one)."""
        from kubeai_tpu.obs.recorder import register_engine_debug_section

        register_engine_debug_section("cold_start", self.snapshot)
        return self


# ---------------------------------------------------------------------------
# Abstract shapes from config alone.


def padded_vocab_size(vocab_size: int, tp: int = 1) -> int:
    """The engine's vocab padding target (weights.pad_vocab): tp
    divisibility + MXU-friendly tiling."""
    multiple = max(tp * 128, 128)
    return ((vocab_size + multiple - 1) // multiple) * multiple


def param_shapes(model_config, quantization: str = ""):
    """ShapeDtypeStruct tree of the engine's parameters, derived from
    the model config alone (no weights touched). Mirrors what the
    checkpoint loader produces — the family's init_params builds the identical
    tree structure to params_from_hf, and quantize_model_params is
    traceable — via jax.eval_shape, so nothing is allocated and drift
    with the real loaders is impossible by construction. The config
    must already carry the PADDED vocab (padded_vocab_size)."""
    import jax

    from kubeai_tpu.models import family

    def build():
        params = family(model_config).init_params(model_config, jax.random.key(0))
        if quantization == "int8":
            from kubeai_tpu.engine.weights import quantize_model_params

            params = quantize_model_params(params, model_config)
        return params

    return jax.eval_shape(build)


def warm_compile(
    model_config,
    engine_config=None,
    quantization: str = "",
    n_valid_vocab: int | None = None,
    include_group: bool = True,
) -> dict:
    """Bring up the engine's step programs for *model_config* ×
    *engine_config* ahead of time and return the stats alone: every call
    of the one list (step_programs.StepPrograms.calls: the decode chunk,
    batch-1 and group-cap cold prefill per bucket, a chunk call for every
    bucket and the wide chunk, the coverage Engine.warmup() executes).

    With the persistent compile cache placed (setup_compile_cache) the
    executables land there AND in the deployment's bundle beside it
    (engine/step_programs.py), from which the next start of this tree and
    deployment loads them without tracing or lowering: the loader Job and
    a parked replica call this for that file. The table itself is dropped
    here (an engine start keeps it: start_background_warm). Without a
    cache dir this still validates compilability but benefits nobody else.

    The *model_config* must be the engine's post-padding config; pass
    the tokenizer's vocab as *n_valid_vocab* so the pad-masking branch
    matches the serving process. Per-shape failures are collected, not
    raised — a warm miss must never fail a load."""
    from kubeai_tpu.engine.step_programs import StepPrograms, fill_step_table

    programs = StepPrograms(model_config, engine_config, n_valid_vocab, quantization)
    return fill_step_table(programs, include_group).stats


def warm_from_checkpoint(
    path: str,
    engine_args: list[str] | None = None,
    include_group: bool = True,
) -> dict:
    """Warm the compile cache for the model staged at *path* using only
    its config.json (+ tokenizer files for the exact vocab mask) — the
    loader Job's ``--warm-compile-cache`` step and the parked replica's
    ``--park-config`` both land here. *engine_args* are engine-server
    CLI args (e.g. the Model's spec.args: ``--max-seq-len 512``) so the
    warmed shapes match what the serving pod will actually run."""
    from kubeai_tpu.engine.server import engine_config_from_args, make_engine_arg_parser
    from kubeai_tpu.engine.tokenizer import load_tokenizer
    from kubeai_tpu.engine.weights import apply_backend_flags
    from kubeai_tpu.models.base import ModelConfig

    parser = make_engine_arg_parser(require_model=False)
    args, unknown = parser.parse_known_args(list(engine_args or []))
    if unknown:
        log.info("warm_from_checkpoint ignoring unknown args: %s", unknown)
    cfg_path = path if os.path.isdir(path) else os.path.dirname(path)
    # Mirror the serving path's config pipeline EXACTLY (dtype default,
    # backend flags, tie fallback, vocab padding) — any divergence
    # silently turns the whole warm into cache misses.
    config = apply_backend_flags(ModelConfig.from_json_file(cfg_path))
    try:
        from kubeai_tpu.engine.weights import SafetensorsSource

        if (
            "lm_head.weight" not in SafetensorsSource(cfg_path)
            and not config.tie_word_embeddings
        ):
            config = config.replace(tie_word_embeddings=True)
    except FileNotFoundError:
        pass  # .bin checkpoint: trust config.json (the load does too)
    tp = max(args.tensor_parallel_size, 1)
    config = config.replace(
        vocab_size=padded_vocab_size(config.vocab_size, tp)
    )
    tokenizer = load_tokenizer(cfg_path)
    n_valid = getattr(tokenizer, "vocab_size", config.vocab_size)
    ec = engine_config_from_args(args)
    return warm_compile(
        config, ec,
        quantization=args.quantization,
        n_valid_vocab=n_valid,
        include_group=include_group,
    )


# ---------------------------------------------------------------------------
# Background warm: compile while weights stream.


class BackgroundWarm:
    """Handle to a warm of the step programs running on a daemon thread.
    The launcher stamps the timeline's compile phase around the thread's
    actual lifetime; join() returns what the warm returned (None where it
    raised: a warm failure must never fail the load)."""

    def __init__(self, fn, timeline: ColdStartTimeline | None = None):
        self.result = None
        self._timeline = timeline
        if timeline is not None:
            # The compile phase begins the moment the thread is
            # launched: it runs concurrently with the caller's weight
            # stream, which is exactly the claim the stamps make.
            timeline.begin("compile")

        def run():
            try:
                self.result = fn()
            except Exception as e:  # pragma: no cover - backend-dependent
                log.warning("background warm of the step programs failed: %s", e)
            finally:
                if timeline is not None:
                    timeline.end("compile")

        self._thread = threading.Thread(
            target=run, name="coldstart-warm", daemon=True
        )
        self._thread.start()

    def join(self, timeout: float | None = None):
        self._thread.join(timeout)
        return self.result


def start_background_warm(
    model_config,
    engine_config,
    quantization: str = "",
    n_valid_vocab: int | None = None,
    timeline: ColdStartTimeline | None = None,
) -> BackgroundWarm:
    """Fill the step table (engine/step_programs.py) on a thread while
    the weights stream; join() hands the table to the Engine, which runs
    ITS executables: nothing the warm brought up is compiled again."""
    from kubeai_tpu.engine.step_programs import StepPrograms, fill_step_table

    return BackgroundWarm(
        lambda: fill_step_table(
            StepPrograms(model_config, engine_config, n_valid_vocab, quantization)
        ),
        timeline=timeline,
    )
