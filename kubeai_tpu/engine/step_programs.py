"""The engine's step programs as HELD EXECUTABLES: one list of the call
shapes the serving path can make, one table of compiled programs keyed by
call shape, and a bundle on disk from which a start that this tree and
this deployment have made before LOADS them without tracing or lowering.

- ``StepPrograms`` is the one list. It states the deployment once (the
  ModelConfig and EngineConfig as an Engine serves them, the tokenizer's
  vocab, the quantization), builds the ONE set of jitted step functions
  (core.build_step_functions) that the warm compiler, ``Engine.warmup()``
  and the scheduler's dispatch sites all use, and enumerates every call:
  the decode chunk, batch-1 and group-cap cold prefill per bucket, a
  chunk call for every bucket and the wide chunk, and a chunk call of two
  slots for the three widest of those.
- ``StepTable`` holds ``jax.stages.Compiled`` executables under (step
  function, shape of the argument that varies). The engine's one
  dispatcher (core.Engine._step) looks a call up here: a hit runs the
  held executable (donation and output layout are the executable's own,
  as compiled from the same ``jax.jit``), a miss falls to the jitted
  function, which compiles lazily: a shape the list did not foresee, or
  a call that carries ``lora=`` (another signature, another program). A
  start without a warmer (tp > 1, a gang, no cache directory, a ``.bin``
  checkpoint) has an empty table and behaves as it always did; gang
  followers replay through the jitted functions: the table reaches
  neither.
- ``fill_step_table()`` fills the table once a start: each program is
  loaded from the bundle where its key matches, else lowered and
  compiled (jax's persistent cache still serves that) and the bundle
  rewritten. Any failure to read or load a program is a miss for THAT
  program and never fails a start.

The bundle lives in ``jax.config.jax_compilation_cache_dir``, which the
process already trusts for executables, as
``step-programs-<deployment>-<key>.bundle``: pickled records, one a
program (``jax.experimental.serialize_executable``). ``<key>`` digests
everything that can decide the lowered text (bundle_key). A deployment
keeps its two newest bundles (the two sides of an A/B on one machine
would otherwise rewrite each other's file every start) and older ones
are removed, so the directory does not grow with every edit. Deleting
the directory is always safe: the next start compiles.
"""

from __future__ import annotations

import functools
import glob
import hashlib
import json
import logging
import os
import pickle
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from kubeai_tpu.engine import core
from kubeai_tpu.metrics import default_registry

try:  # what jax's own cache compresses with, where it is installed
    import zstandard
except ImportError:  # pragma: no cover - the image has it
    zstandard = None

log = logging.getLogger("kubeai_tpu.engine.step_programs")

M_STEP_PROGRAMS = default_registry.counter(
    "kubeai_engine_step_programs_total",
    "step programs this process brought up, labeled how=loaded (from the "
    "bundle beside the compile cache: not traced, not lowered) | compiled "
    "(lowered and compiled ahead of time, the bundle rewritten) | lazy "
    "(through a jitted function on its first call)",
)

BUNDLE_VERSION = 1
BUNDLES_A_DEPLOYMENT = 2
LOAD_THREADS = 4
PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# jax.config values that say where things are kept or what is logged,
# never what is lowered; every other value is part of the key.
_NOT_LOWERING = frozenset({
    "jax_compilation_cache_dir", "jax_compilation_cache_max_size",
    "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes",
    "jax_persistent_cache_enable_xla_caches", "jax_raise_persistent_cache_errors",
    "jax_enable_compilation_cache", "jax_explain_cache_misses", "jax_log_compiles",
    "jax_logging_level", "jax_debug_log_modules", "jax_dump_ir_to", "jax_dump_ir_modes",
    "jax_pprint_use_color", "jax_traceback_filtering", "jax_platforms", "jax_platform_name",
})


@dataclass(frozen=True)
class StepCall:
    """One call shape of the serving path: a label for logs and the
    bundle, the StepFunctions member, and the shape of the argument that
    varies (() for the decode chunk, (rows, bucket) of a prefill's
    tokens)."""

    label: str
    member: str
    shape: tuple

    @property
    def key(self) -> tuple:
        return self.member, self.shape


class StepPrograms:
    """The one list of an engine's step programs (module docstring).
    *model_config* is the engine's post-padding config; *n_valid_vocab*
    the tokenizer's vocab, so the pad-masking branch is the serving
    process's. *quantization* decides the abstract parameter tree and is
    part of the bundle's key; an Engine that builds its own list (no
    warmer) never asks for either."""

    def __init__(self, model_config, engine_config=None, n_valid_vocab=None, quantization: str = "", mesh=None):
        self.model_config, self.cfg = core.serving_configs(model_config, engine_config)
        vocab = self.model_config.vocab_size
        self.n_valid_vocab = vocab if n_valid_vocab is None else min(n_valid_vocab, vocab)
        self.quantization = quantization
        self.mesh = mesh

    @functools.cached_property
    def step_functions(self) -> "core.StepFunctions":
        return core.build_step_functions(self.model_config, self.cfg, self.n_valid_vocab, mesh=self.mesh)

    def serves(self, model_config, cfg, n_valid_vocab) -> bool:
        """Whether an Engine of these configs runs this list's programs."""
        return (self.model_config, self.cfg, self.n_valid_vocab) == (model_config, cfg, n_valid_vocab)

    def calls(self, include_group: bool = True) -> list[StepCall]:
        """Every call the serving path can make, the decode chunk first.
        Chunked prefill pads its FINAL chunk to the smallest fitting
        bucket (the calls before it are the largest bucket or the wide
        chunk: core.prefill_plan), so there is a chunk shape per bucket
        and ONE more, the wide chunk, where a prompt can be that long; and
        the three widest of them once more for TWO slots a call (the
        pieces of two prompts behind one read of the weights:
        core.round_calls; none for a family that reuses whole prefill
        calls: core.pair_rows)."""
        cfg = self.cfg
        calls = [StepCall("decode", "decode_jit", ())]
        cap = max(1, min(cfg.prefill_group_cap, cfg.max_slots))
        sizes = (1, cap) if include_group and cap > 1 else (1,)
        for bucket in cfg.prefill_buckets:
            for n_pad in sizes:
                calls.append(StepCall(f"prefill_batch[{n_pad}x{bucket}]", "prefill_batch_jit", (n_pad, bucket)))
        for rows in sorted({*cfg.prefill_buckets, core.wide_chunk(cfg)}):
            calls.append(StepCall(f"prefill_chunk[{rows}]", "prefill_chunk_jit", (1, rows)))
        for rows in core.pair_rows(cfg, self.model_config):
            calls.append(StepCall(f"prefill_chunk[2x{rows}]", "prefill_chunk_jit", (2, rows)))
        return calls

    @functools.cached_property
    def _state_shapes(self):
        """(params, pools, PRNG key data) as ShapeDtypeStruct trees, from
        the configs alone."""
        from kubeai_tpu.engine.coldstart import param_shapes

        B = self.cfg.max_slots
        return (
            param_shapes(self.model_config, self.quantization),
            jax.eval_shape(lambda: core.init_pools(self.model_config, self.cfg)),
            jax.eval_shape(lambda: jax.random.key_data(jax.random.split(jax.random.key(0), B))),
        )

    def abstract_args(self, call: StepCall) -> tuple:
        """The abstract arguments of *call*, in the step function's order."""
        cfg = self.cfg
        params, cache, keys = self._state_shapes
        B, Kb = cfg.max_slots, cfg.max_logit_bias
        hist_width = core.engine_dims(cfg)[2]
        # Columns of a block-table row: two tables side by side for a
        # family with two page budgets a slot.
        cols = core.table_width(self.model_config, cfg)
        i32, f32, u32, b8 = jnp.int32, jnp.float32, jnp.uint32, jnp.bool_
        sds = jax.ShapeDtypeStruct
        if call.member == "decode_jit":
            return (
                params, cache, sds((B, cols), i32), sds((B, hist_width), i32),
                sds((B,), i32), sds((B,), i32), keys,
                sds((B,), b8), sds((B,), f32), sds((B,), f32), sds((B,), i32),
                sds((B,), f32), sds((B,), f32), sds((B,), b8), sds((B,), i32),
                sds((B, Kb), i32), sds((B, Kb), f32),
                sds((B,), b8), sds((B,), i32), sds((B,), u32), sds((B,), i32),
            )
        n, rows = call.shape
        # A cold call's `lengths`; a chunk call's `starts` and `last_idx`.
        per_row = (sds((n,), i32),) * (1 if call.member == "prefill_batch_jit" else 2)
        return (
            params, sds((n, rows), i32), *per_row, sds((n, cols), i32), sds((n,), i32),
            sds((n,), u32), sds((n,), f32), sds((n,), f32), sds((n,), i32),
            sds((n, Kb), i32), sds((n, Kb), f32), sds((B,), i32), cache,
        )


class StepTable:
    """Held executables by call shape (module docstring). ``stats`` is
    what ``/debug/engine`` shows under ``cold_start.warm_compile``."""

    def __init__(self, programs: StepPrograms):
        self.programs = programs
        self.held: dict[tuple, Any] = {}
        self.stats: dict = {"shapes": 0, "loaded": 0, "compiled": 0, "lazy": 0, "seconds": 0.0, "programs": {}}


# ---------------------------------------------------------------------------
# The bundle's key.


def source_digest(root: str | None = None) -> str:
    """A digest of the BYTES of every ``.py`` under *root* (the package),
    by path relative to it: not where the checkout lies, not mtimes."""
    root = root or PACKAGE_ROOT
    h = hashlib.sha256()
    for dirpath, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                data = f.read()
            h.update(os.path.relpath(path, root).encode() + b"\0" + str(len(data)).encode() + b"\0" + data)
    return h.hexdigest()


def _digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()[:20]


def bundle_key(programs: StepPrograms, source_root: str | None = None) -> tuple[str, str]:
    """(deployment, key): *deployment* names the bundle's file (what is
    served: the padded ModelConfig, the EngineConfig, quantization, the
    valid vocab), *key* adds everything else that can decide a program's
    lowered text or its executable: the package's source, jax, jaxlib and
    the backend's version, the device kind and count, the compiler's flags
    and the jax.config values a trace depends on. When in doubt a field is
    IN: a needless miss costs one compiling start, a wrong hit runs
    another program. A program's label and argument signature are checked
    per record (fill_step_table)."""
    import jaxlib
    from jax._src import config as jax_config

    dev = jax.devices()[0]
    deployment = _digest({
        "model_config": repr(programs.model_config), "engine_config": repr(programs.cfg),
        "quantization": programs.quantization, "n_valid_vocab": programs.n_valid_vocab,
    })
    key = _digest({
        "bundle_version": BUNDLE_VERSION, "deployment": deployment,
        "source": source_digest(source_root),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "platform": dev.client.platform, "platform_version": dev.client.platform_version,
        "device_kind": dev.device_kind, "device_count": jax.device_count(),
        "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
        "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "jax_config": {k: repr(v) for k, v in jax.config.values.items() if k not in _NOT_LOWERING},
        # Values set by context managers on this thread (matmul precision).
        "trace_context": repr(jax_config.trace_context()),
    })
    return deployment, key


def bundle_path(cache_dir: str, deployment: str, key: str) -> str:
    return os.path.join(cache_dir, f"step-programs-{deployment}-{key}.bundle")


# ---------------------------------------------------------------------------
# Reading and writing it.


def _pack(data: bytes) -> tuple[str, bytes]:
    if zstandard is not None:
        return "zstd", zstandard.ZstdCompressor(level=3).compress(data)
    return "zlib", zlib.compress(data, 1)


def _unpack(codec: str, data: bytes) -> bytes:
    if codec == "zstd":
        return zstandard.ZstdDecompressor().decompress(data)
    return zlib.decompress(data)


def _record(label: str, compiled) -> tuple:
    from jax.experimental.serialize_executable import serialize

    payload, in_tree, out_tree = serialize(compiled)
    return (label, *_pack(payload), in_tree, out_tree)


def _load_record(record: tuple):
    from jax.experimental.serialize_executable import deserialize_and_load

    _label, codec, data, in_tree, out_tree = record
    return deserialize_and_load(
        _unpack(codec, data), in_tree, out_tree, execution_devices=jax.devices()[:1]
    )


def read_bundle(path: str, key: str) -> dict[str, tuple]:
    """The records of the bundle at *path* by label, {} where there is no
    file, its key is another's or its head cannot be read. A file cut
    short gives the records before the cut."""
    records: dict[str, tuple] = {}
    try:
        with open(path, "rb") as f:
            head = pickle.load(f)
            if head != {"version": BUNDLE_VERSION, "key": key}:
                log.info("step-program bundle %s holds another key", path)
                return {}
            while True:
                try:
                    record = pickle.load(f)
                except EOFError:
                    break
                records[record[0]] = record
    except FileNotFoundError:
        pass
    except Exception as e:  # truncated, unreadable, not a bundle
        log.warning("step-program bundle %s: %s after %d programs", path, e or type(e).__name__, len(records))
    return records


def write_bundle(path: str, key: str, records: list[tuple]) -> int:
    """Write *records* under *key* (temp file + rename), then keep this
    deployment's BUNDLES_A_DEPLOYMENT newest bundles. Returns the bytes."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            pickle.dump({"version": BUNDLE_VERSION, "key": key}, f)
            for record in records:
                pickle.dump(record, f, protocol=pickle.HIGHEST_PROTOCOL)
            size = f.tell()
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    stem = path[: path.rindex("-")]  # step-programs-<deployment>
    others = sorted(glob.glob(stem + "-*.bundle"), key=os.path.getmtime, reverse=True)
    for old in others[BUNDLES_A_DEPLOYMENT:]:
        os.unlink(old)
    return size


def _check_signature(compiled, args: tuple) -> None:
    """Raise unless *compiled* takes exactly *args* (tree, shapes, dtypes)."""
    want, want_tree = jax.tree.flatten((args, {}))
    have, have_tree = jax.tree.flatten(compiled.in_avals)
    if have_tree != want_tree:
        raise ValueError("the argument tree is not the list's")
    for w, h in zip(want, have):
        if (tuple(w.shape), jnp.dtype(w.dtype)) != (tuple(h.shape), jnp.dtype(h.dtype)):
            raise ValueError(f"an argument is {h.shape} {h.dtype}, the list says {w.shape} {w.dtype}")


def _load_stored(programs: StepPrograms, calls: list[StepCall], stored: dict[str, tuple]) -> dict[str, tuple]:
    """{label: (the loaded executable or None, seconds)} for *calls*, all
    of which the bundle holds. Decompressing and deserializing run outside
    the interpreter lock, so a few threads load beside the weight stream;
    an entry that cannot be loaded, or whose arguments are not the list's,
    is a miss for that program (logged here, compiled by the caller)."""
    if not calls:
        return {}
    programs.abstract_args(calls[0])  # the shapes, once, before the threads ask

    def load(call: StepCall) -> tuple:
        t = time.monotonic()
        try:
            compiled = _load_record(stored[call.label])
            _check_signature(compiled, programs.abstract_args(call))
        except Exception as e:
            log.warning("step program %s: the bundle's entry is a miss (%s)", call.label, e)
            compiled = None
        return compiled, time.monotonic() - t

    with ThreadPoolExecutor(max_workers=LOAD_THREADS, thread_name_prefix="step-programs") as pool:
        return dict(zip((c.label for c in calls), pool.map(load, calls)))


def fill_step_table(programs: StepPrograms, include_group: bool = True) -> StepTable:
    """Bring up every program of the list (module docstring): from the
    bundle where the compile-cache directory holds this key's, else
    ``.lower().compile()`` against abstract arguments, after which the
    bundle is rewritten. Per-program failures are collected in
    ``stats["errors"]``, not raised: a warm miss must never fail a load
    (the engine then compiles that shape lazily)."""
    t0 = time.monotonic()
    table = StepTable(programs)
    stats = table.stats
    errors: list[str] = []
    cache_dir = jax.config.jax_compilation_cache_dir
    path = key = None
    stored: dict[str, tuple] = {}
    if cache_dir:
        try:
            deployment, key = bundle_key(programs)
            path = bundle_path(cache_dir, deployment, key)
            stored = read_bundle(path, key)
        except Exception as e:  # an unreadable directory: compile, write nothing
            log.warning("no step-program bundle under %s: %s", cache_dir, e)
            path = None
    calls = programs.calls(include_group)
    loads = _load_stored(programs, [c for c in calls if c.label in stored], stored)
    records: list[tuple] = []
    for call in calls:
        t = time.monotonic()
        compiled, seconds = loads.get(call.label, (None, 0.0))
        record, how = stored.get(call.label), "loaded"
        if compiled is None:
            record, how = None, "compiled"
            try:
                fn = getattr(programs.step_functions, call.member)
                compiled = fn.lower(*programs.abstract_args(call)).compile()
            except Exception as e:  # pragma: no cover - depends on backend
                log.warning("warm compile of %s failed: %s", call.label, e)
                errors.append(f"{call.label}: {e}")
                continue
            if path is not None:
                try:
                    record = _record(call.label, compiled)
                except Exception as e:
                    log.warning("step program %s cannot be bundled: %s", call.label, e)
            seconds += time.monotonic() - t
        table.held[call.key] = compiled
        if record is not None:
            records.append(record)
        stats[how] += 1
        stats["programs"][call.label] = {"how": how, "seconds": round(seconds, 3)}
        M_STEP_PROGRAMS.inc(labels={"how": how})
    stats["shapes"] = len(table.held)
    if path is not None:
        try:
            if stats["compiled"] and records:
                t = time.monotonic()
                size = write_bundle(path, key, records)
                stats["bundle"] = {"path": path, "bytes": size, "written_s": round(time.monotonic() - t, 3)}
            elif stored:
                os.utime(path)  # the newest of its deployment's
                stats["bundle"] = {"path": path, "bytes": os.path.getsize(path)}
        except OSError as e:
            log.warning("step-program bundle %s not written: %s", path, e)
    stats["seconds"] = round(time.monotonic() - t0, 3)
    if errors:
        stats["errors"] = errors
    log.info(
        "step programs: %d loaded, %d compiled in %.1fs (%d failed)",
        stats["loaded"], stats["compiled"], stats["seconds"], len(errors),
    )
    return table
