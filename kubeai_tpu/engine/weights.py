"""Checkpoint loading: HF-format directories -> sharded engine params.

The counterpart of the reference's model-loader staging + engine weight
load (ref: components/model-loader/load.sh downloads; the engine container
does the actual load). Here loading and sharding are one step: safetensors
are memory-mapped, converted per-tensor, and device_put directly with
their target NamedSharding so a tp=N mesh never materializes the full
model on one chip.
"""

from __future__ import annotations

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.engine.core import Engine, EngineConfig
from kubeai_tpu.engine.tokenizer import load_tokenizer
from kubeai_tpu.models import family
from kubeai_tpu.models.base import ModelConfig
from kubeai_tpu.parallel import llama_param_specs, make_mesh, shard_tree


def load_state_dict(path: str) -> dict[str, np.ndarray]:
    """Load all *.safetensors (or pytorch_model.bin) under *path* into a
    name->array dict. Arrays are lazily materialized numpy views."""
    sd: dict[str, np.ndarray] = {}
    st_files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    if st_files:
        from safetensors import safe_open

        for f in st_files:
            with safe_open(f, framework="np") as reader:
                for name in reader.keys():
                    sd[name] = reader.get_tensor(name)
        return sd
    bin_files = sorted(glob.glob(os.path.join(path, "pytorch_model*.bin")))
    if bin_files:
        import torch

        for f in bin_files:
            for name, t in torch.load(f, map_location="cpu", weights_only=True).items():
                sd[name] = t.float().numpy() if t.dtype == torch.bfloat16 else t.numpy()
        return sd
    raise FileNotFoundError(f"no safetensors or pytorch_model.bin under {path}")


def pad_vocab(params, config: ModelConfig, multiple: int) -> tuple[dict, ModelConfig]:
    """Pad embedding/lm_head vocab dim to a multiple (tp divisibility +
    friendly MXU tiling). Padded columns carry zero weights (logit 0.0);
    the engine masks logits beyond the tokenizer vocab to -inf before
    sampling so they can never be emitted."""
    V = config.vocab_size
    target = ((V + multiple - 1) // multiple) * multiple
    if target == V:
        return params, config
    pad = target - V
    params = dict(params)
    # Host (numpy) trees stay on host — the quantizing loader depends on it.
    xp = np if isinstance(params["embed"], np.ndarray) else jnp
    params["embed"] = xp.pad(params["embed"], ((0, pad), (0, 0)))
    if "lm_head" in params:
        params["lm_head"] = xp.pad(params["lm_head"], ((0, 0), (0, pad)))
    return params, config.replace(vocab_size=target)


def quantize_model_params(params: dict, config: ModelConfig) -> dict:
    """Weight-only int8: per-output-channel scales on the projection
    weights, per-row scales on the embedding. Dense models only (MoE
    expert einsums keep their dtype); norms and the router stay small and
    full precision."""
    from kubeai_tpu.ops.quant import quantize, quantize_rows

    out = dict(params)
    out["embed"] = quantize_rows(params["embed"])
    if "lm_head" in params:
        out["lm_head"] = quantize(params["lm_head"], contract_axis=-2)
    layers = dict(params["layers"])
    targets = ("wq", "wk", "wv", "wo") + (
        () if config.num_experts > 0 else ("wg", "wu", "wd")
    )
    for t in targets:
        layers[t] = quantize(layers[t], contract_axis=-2)
    out["layers"] = layers
    return out


def apply_backend_flags(config: ModelConfig) -> ModelConfig:
    """Backend-dependent serving flags (TPU: flash prefill + paged
    kernel). Shared by load_engine_from_path AND the AOT warm compiler
    (coldstart.warm_from_checkpoint) — a warmer that skipped these
    would trace different programs on TPU and every warmed cache entry
    would silently miss."""
    if jax.default_backend() == "tpu":
        return config.replace(
            use_flash_prefill=True,
            use_paged_kernel=config.sliding_window == 0,
        )
    return config


class SafetensorsSource:
    """Random-access view over a checkpoint's *.safetensors shards:
    indexes every shard's tensor names (header reads only — tensor data
    stays on disk until asked for) and serves tensors by name. The
    streaming loader's read side: one parameter group's tensors are
    materialized at a time, so peak host memory is one stacked group,
    not the model. A shard is mapped only for the length of one get():
    a mapping held open keeps every page ever read resident, which by
    the end of a load is the whole checkpoint (15 GB for a 7B model,
    charged to the process on a sandboxed host)."""

    def __init__(self, path: str):
        from safetensors import safe_open

        self.files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
        if not self.files:
            raise FileNotFoundError(f"no *.safetensors under {path}")
        self._index: dict[str, str] = {}
        for f in self.files:
            with safe_open(f, framework="np") as reader:
                self._index.update(dict.fromkeys(reader.keys(), f))

    def __contains__(self, name: str) -> bool:
        return name in self._index

    def get(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        with safe_open(self._index[name], framework="np") as reader:
            return reader.get_tensor(name)  # a copy: outlives the mapping

    def names(self):
        return self._index.keys()


def stream_params_from_hf(
    source: "SafetensorsSource",
    config: ModelConfig,
    tp: int = 1,
    quantization: str = "",
    mesh=None,
) -> tuple[dict, ModelConfig]:
    """Streaming counterpart of params_from_hf + pad_vocab +
    quantize_model_params: each parameter group (one stacked-layer
    weight, the embedding, the head) is read, converted, vocab-padded,
    quantized, and device_put with its target sharding BEFORE the next
    group is touched, and within a stacked group each layer is
    converted and quantized on its own (a few layers at a time, on
    threads: numpy works outside the interpreter lock) and written
    straight into its row of the stacked array — host memory peaks at a
    few layers' float32 copies plus one group in its final dtype, not at
    the whole group in float32 several times over (a 7B int8 load
    peaked at 45 GB of host memory that way, on a 40 GiB host), and HBM
    starts filling while the tail of the checkpoint is still being read.
    Returns (device params, config with the padded vocab).

    Single-process only (a gang rank must assemble global arrays from
    the full host tree — load_engine_from_path falls back there)."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from jax.sharding import NamedSharding

    from kubeai_tpu.ops.quant import quantize, quantize_rows

    from kubeai_tpu.engine.coldstart import padded_vocab_size

    dtype = jnp.dtype(config.dtype)
    L = config.num_layers
    V = config.vocab_size
    pad = padded_vocab_size(V, tp) - V
    out_config = config.replace(vocab_size=V + pad) if pad else config
    specs = llama_param_specs(out_config) if mesh is not None else None
    quant_dense = ("wq", "wk", "wv", "wo") + (
        () if config.num_experts > 0 else ("wg", "wu", "wd")
    )
    int8 = quantization == "int8"

    def put(host, *key_path):
        """device_put ONE converted group, with its target sharding when
        a tp mesh is given."""
        if mesh is not None:
            spec = specs
            for k in key_path:
                spec = spec[k]
            return jax.device_put(host, NamedSharding(mesh, spec))
        return jax.device_put(host)

    def conv(a):
        return np.asarray(a, dtype)

    def stack(fmt, transpose=True, quant=False):
        """One stacked group [L, ...], each layer converted (and
        quantized: per-layer, per-output-channel scales, exactly what
        quantizing the stacked array gives) on its own."""

        out: dict = {}  # allocated by whichever layer finishes first

        def one(i):
            w = np.asarray(source.get(fmt.format(i)))
            w = conv(w.T if transpose else w)
            parts = quantize(w, contract_axis=-2) if quant else {"w": w}
            for k, a in parts.items():
                # Straight into its row of the stacked array: a list of
                # layers stacked afterwards would hold the group twice.
                with alloc:
                    if k not in out:
                        out[k] = np.empty((L, *a.shape), a.dtype)
                out[k][i] = a

        alloc = threading.Lock()
        with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as workers:
            list(workers.map(one, range(L)))  # list(): re-raise a layer's error
        return out if quant else out["w"]

    embed = conv(np.asarray(source.get("model.embed_tokens.weight")))
    if pad:
        embed = np.pad(embed, ((0, pad), (0, 0)))
    params: dict = {
        "embed": put(quantize_rows(embed) if int8 else embed, "embed"),
        "final_norm": put(conv(np.asarray(source.get("model.norm.weight"))), "final_norm"),
    }
    del embed
    layers: dict = {}

    def put_layer(key, fmt, transpose=True):
        layers[key] = put(
            stack(fmt, transpose=transpose, quant=int8 and key in quant_dense),
            "layers", key,
        )

    put_layer("ln1", "model.layers.{}.input_layernorm.weight", transpose=False)
    put_layer("wq", "model.layers.{}.self_attn.q_proj.weight")
    put_layer("wk", "model.layers.{}.self_attn.k_proj.weight")
    put_layer("wv", "model.layers.{}.self_attn.v_proj.weight")
    put_layer("wo", "model.layers.{}.self_attn.o_proj.weight")
    if config.qkv_bias:
        put_layer("bq", "model.layers.{}.self_attn.q_proj.bias", transpose=False)
        put_layer("bk", "model.layers.{}.self_attn.k_proj.bias", transpose=False)
        put_layer("bv", "model.layers.{}.self_attn.v_proj.bias", transpose=False)
    if config.post_norms:
        put_layer("ln1b", "model.layers.{}.post_attention_layernorm.weight", transpose=False)
        put_layer("ln2", "model.layers.{}.pre_feedforward_layernorm.weight", transpose=False)
        put_layer("ln2b", "model.layers.{}.post_feedforward_layernorm.weight", transpose=False)
    else:
        put_layer("ln2", "model.layers.{}.post_attention_layernorm.weight", transpose=False)
    if config.num_experts > 0:
        E = config.num_experts

        def stack_experts(which):
            out = []
            for li in range(L):
                per = [
                    np.asarray(
                        source.get(
                            f"model.layers.{li}.block_sparse_moe.experts.{e}.{which}.weight"
                        )
                    ).T
                    for e in range(E)
                ]
                out.append(np.stack(per))
            return conv(np.stack(out))

        put_layer("wr", "model.layers.{}.block_sparse_moe.gate.weight")
        layers["wg"] = put(stack_experts("w1"), "layers", "wg")
        layers["wu"] = put(stack_experts("w3"), "layers", "wu")
        layers["wd"] = put(stack_experts("w2"), "layers", "wd")
    else:
        put_layer("wg", "model.layers.{}.mlp.gate_proj.weight")
        put_layer("wu", "model.layers.{}.mlp.up_proj.weight")
        put_layer("wd", "model.layers.{}.mlp.down_proj.weight")
    params["layers"] = layers
    if not out_config.tie_word_embeddings:
        head = conv(np.asarray(source.get("lm_head.weight")).T)
        if pad:
            head = np.pad(head, ((0, 0), (0, pad)))
        params["lm_head"] = put(
            quantize(head, contract_axis=-2) if int8 else head, "lm_head"
        )
        del head
    return params, out_config


def load_engine_from_path(
    path: str,
    engine_config: EngineConfig | None = None,
    tp: int = 1,
    dtype: str = "bfloat16",
    quantization: str = "",
    publisher=None,
    timeline=None,
    stream: bool | None = None,
    overlap: bool | None = None,
    warmup: bool | None = None,
) -> Engine:
    """Build an Engine from an HF-format checkpoint directory.

    Cold-start fast path (single-process): safetensors tensors are
    converted and device_put per-parameter as they are read
    (stream_params_from_hf) while the step programs are brought up on a
    background thread (engine/coldstart.py, engine/step_programs.py):
    LOADED from the deployment's bundle beside the compile cache where
    this tree and deployment have started before, lowered and compiled
    otherwise, so start costs ~max(load, programs) instead of their sum.
    The Engine is handed that table and runs ITS executables; a start
    without the thread (tp > 1, a gang, a .bin checkpoint, overlap off)
    has an empty table and compiles through its jit calls. Phase stamps
    land on *timeline* (a fresh one is created and installed at
    /debug/engine when omitted). Knobs: KUBEAI_STREAM_WEIGHTS=0 restores
    the whole-checkpoint load; KUBEAI_COLDSTART_OVERLAP is auto (overlap
    when a persistent compile cache is placed: the regime where the
    thread's work outlives the process), 1 forces, 0 disables;
    KUBEAI_ENGINE_WARMUP=1 pre-dispatches every step shape before
    returning (and the *warmup* arg overrides the env).

    When the process is one rank of a multi-host gang
    (jax.process_count() > 1), the tp mesh spans the GLOBAL device set:
    every rank loads the checkpoint, contributes its addressable weight
    shards (shard_tree), and the Engine allocates global device state —
    the serial path; streaming/overlap apply to single-process starts.
    Rank 0 additionally passes *publisher* (engine/gang.py) so its
    dispatches fan out to the follower ranks."""
    # Failpoint: chaos tests make cold starts fail/stall here (the
    # crashloop-at-weight-load scenario the controller must absorb).
    from kubeai_tpu.engine.coldstart import (
        ColdStartTimeline,
        start_background_warm,
    )
    from kubeai_tpu.faults import fault

    fault("weights.load")
    # Placed by the process entry point (coldstart.setup_compile_cache);
    # None for in-process engines that never asked for one.
    cache_dir = jax.config.jax_compilation_cache_dir
    if quantization:
        if quantization != "int8":
            raise ValueError(f"unsupported quantization {quantization!r} (supported: int8)")
        if tp > 1:
            raise ValueError("int8 quantization currently supports tensor-parallel-size 1")
    timeline = (timeline or ColdStartTimeline()).install()
    config = apply_backend_flags(
        ModelConfig.from_json_file(path).replace(dtype=dtype)
    )
    model = family(config)  # by the checkpoint's model_type, nothing else
    model.refuse_unsupported(config, quantization=quantization, tp=tp)
    multiproc = jax.process_count() > 1
    if stream is None:
        stream = os.environ.get("KUBEAI_STREAM_WEIGHTS", "1") != "0"
    if overlap is None:
        # "auto": with a persistent compile cache the thread loads
        # the step programs from the bundle beside it (or compiles and
        # writes both); without one every start would lower and compile
        # beside the loader's threads, so the programs are left to the
        # engine's first calls. "1" forces it on, "0" off.
        knob = os.environ.get("KUBEAI_COLDSTART_OVERLAP", "auto")
        overlap = knob == "1" or (knob != "0" and bool(cache_dir))
    if warmup is None:
        warmup = os.environ.get("KUBEAI_ENGINE_WARMUP", "0") == "1"
    ec = engine_config or EngineConfig()
    tokenizer = load_tokenizer(path)

    # Open the safetensors shard index even when streaming is off:
    # header reads are ~free and resolve tie_word_embeddings BEFORE the
    # warm compiler launches (a warmer guessing the wrong param-tree
    # structure would trace programs that can never hit).
    source = None
    try:
        source = SafetensorsSource(path)
    except FileNotFoundError:
        source = None  # pytorch_model.bin checkpoints take the old path
    use_stream = stream and not multiproc and source is not None
    if source is not None and "lm_head.weight" not in source and not config.tie_word_embeddings:
        config = config.replace(tie_word_embeddings=True)

    mesh = None
    if tp > 1 or multiproc:
        if multiproc:
            # The gang mesh must take tp/num_processes devices from EACH
            # process — jax.devices() is process-major, so a naive
            # devices[:tp] prefix would land entirely on rank 0 and
            # followers could not address their shards.
            n_proc = jax.process_count()
            if tp <= 1:
                tp = jax.device_count()  # bare gang pods: span the slice
            if tp % n_proc != 0:
                raise ValueError(
                    f"--tensor-parallel-size must be a multiple of the gang "
                    f"size (tp={tp}, processes={n_proc})"
                )
            per = tp // n_proc
            devs = []
            for p in range(n_proc):
                mine = [d for d in jax.devices() if d.process_index == p][:per]
                if len(mine) < per:
                    raise ValueError(
                        f"process {p} has {len(mine)} devices; tp={tp} needs "
                        f"{per} per process"
                    )
                devs += mine
            mesh = make_mesh(tp=tp, devices=devs)
        else:
            mesh = make_mesh(tp=tp)

    warmer = step_table = None
    if overlap and not multiproc and tp == 1 and source is not None:
        # The padded config the engine will serve with is fully known
        # before any tensor data is read — kick off AOT compilation of
        # the step functions NOW, concurrent with the weight stream.
        # tp==1 only: the warmer lowers unsharded programs, which can
        # never match a tp-sharded engine's executables (pure waste).
        # Safetensors only: a .bin checkpoint can't resolve
        # tie_word_embeddings (the param-tree structure) until the full
        # torch load, so a warm launched now could trace the wrong tree.
        from kubeai_tpu.engine.coldstart import padded_vocab_size

        warm_config = config.replace(
            vocab_size=padded_vocab_size(config.vocab_size, tp)
        )
        warmer = start_background_warm(
            warm_config, ec,
            quantization=quantization,
            n_valid_vocab=getattr(tokenizer, "vocab_size", config.vocab_size),
            timeline=timeline,
        )

    with timeline.phase("load"):
        if use_stream and model.stream_params_from_hf is not None:
            # A family that streams its own tree (models/deepseek.py).
            from kubeai_tpu.engine.coldstart import padded_vocab_size

            padded = padded_vocab_size(config.vocab_size, tp)
            params = model.stream_params_from_hf(source, config, pad=padded - config.vocab_size)
            config = config.replace(vocab_size=padded)
        elif use_stream:
            params, config = stream_params_from_hf(
                source, config, tp=tp, quantization=quantization, mesh=mesh
            )
        else:
            sd = load_state_dict(path)
            if "lm_head.weight" not in sd and not config.tie_word_embeddings:
                config = config.replace(tie_word_embeddings=True)
            # int8: build + quantize on host so full-precision weights
            # never touch HBM, then device_put the int8 tree ONCE
            # (leaving it numpy would re-upload the model on every
            # jitted step). Multi-process: stay on host until
            # shard_tree assembles the global arrays.
            params = model.params_from_hf(
                sd, config, to_device=quantization != "int8" and not multiproc
            )
            params, config = pad_vocab(params, config, multiple=max(tp * 128, 128))
            if quantization == "int8":
                params = quantize_model_params(params, config)
                params = jax.device_put(params)
            if mesh is not None:
                params = shard_tree(params, llama_param_specs(config), mesh)

    if warmer is not None:
        # The Engine runs the table's executables, so it waits for
        # them; by now the warm has had the whole load to run, so on real
        # checkpoints this wait is ~max(load, programs) - load.
        step_table = warmer.join()
        if step_table is not None:
            timeline.attrs["warm_compile"] = step_table.stats

    def build(m=None):
        # Engine construction (device-state allocation + jit wrapper
        # setup) gets its own stamp so the phase timeline has no
        # unattributed gap between compile and warmup.
        timeline.begin("build")
        eng = Engine(
            config, params, tokenizer, ec, mesh=m, publisher=publisher,
            step_table=step_table,
        )
        timeline.end("build")
        if warmup and not multiproc:
            with timeline.phase("warmup"):
                timeline.attrs["warmup"] = eng.warmup()
        eng.cold_start_timeline = timeline
        return eng

    if mesh is not None:
        # The engine allocates its device state with shardings on this
        # mesh and pins them on its step functions' outputs.
        with mesh:
            return build(mesh)
    return build()


def save_tiny_test_checkpoint(path: str, seed: int = 0, num_heads: int = 4, num_kv_heads: int = 2) -> "ModelConfig":
    """Write the canonical tiny-Llama HF checkpoint used by e2e tests and
    benchmarks (one source of truth: the e2e suite and
    benchmarks/routing_compare.py must exercise the same shapes). The
    head counts are overridable for high-tp gang tests: sharding the KV
    pool over tp requires 2*num_kv_heads % tp == 0 (the 8-device dryrun
    gang uses num_kv_heads=4 for tp=8)."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=num_heads, num_kv_heads=num_kv_heads, dtype="float32",
    )
    torch.manual_seed(seed)
    hf = LlamaForCausalLM(
        LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=num_heads,
            num_key_value_heads=num_kv_heads,
            tie_word_embeddings=False,
        )
    )
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    save_hf_checkpoint(path, cfg, sd)
    return cfg


def write_peft_checkpoint(path, config: "ModelConfig", rank=4, alpha=8, seed=0, targets=("q_proj", "v_proj")):
    """Minimal PEFT-format adapter dir (adapter_config.json +
    adapter_model.safetensors) — the fixture generator for LoRA tests,
    the gang dryrun, and adapter demos. Lives here (not in tests/) so
    non-pytest consumers don't drag the test suite's imports in."""
    import json

    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "adapter_config.json"), "w") as f:
        json.dump({"r": rank, "lora_alpha": alpha, "target_modules": list(targets)}, f)
    rng = np.random.default_rng(seed)
    tensors = {}
    dims = {
        "q_proj": (config.hidden_size, config.num_heads * config.head_dim_),
        "k_proj": (config.hidden_size, config.num_kv_heads * config.head_dim_),
        "v_proj": (config.hidden_size, config.num_kv_heads * config.head_dim_),
        "o_proj": (config.num_heads * config.head_dim_, config.hidden_size),
    }
    for li in range(config.num_layers):
        for t in targets:
            din, dout = dims[t]
            A = rng.normal(0, 0.1, (rank, din)).astype(np.float32)
            B = rng.normal(0, 0.1, (dout, rank)).astype(np.float32)
            base = f"base_model.model.model.layers.{li}.self_attn.{t}"
            tensors[base + ".lora_A.weight"] = A
            tensors[base + ".lora_B.weight"] = B
    save_file(tensors, os.path.join(path, "adapter_model.safetensors"))
    return tensors


def write_hf_config(path: str, config: ModelConfig) -> None:
    """Write the HF-format config.json for *config*: Llama-shaped, or
    Qwen2-shaped (the loader then expects q/k/v bias tensors) when the
    config has qkv_bias."""
    os.makedirs(path, exist_ok=True)
    qwen2 = config.qkv_bias
    cfg = {
        "architectures": ["Qwen2ForCausalLM" if qwen2 else "LlamaForCausalLM"],
        "model_type": "qwen2" if qwen2 else "llama",
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "num_hidden_layers": config.num_layers,
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads,
        "rope_theta": config.rope_theta,
        "rms_norm_eps": config.rms_norm_eps,
        "max_position_embeddings": config.max_position,
        "tie_word_embeddings": config.tie_word_embeddings,
    }
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg, f, indent=1)


def save_hf_checkpoint(path: str, config: ModelConfig, state_dict: dict[str, np.ndarray]):
    """Write a minimal HF-format checkpoint dir (config.json + one
    safetensors file). Used by tests and the model-loader."""
    from safetensors.numpy import save_file

    write_hf_config(path, config)
    save_file(state_dict, os.path.join(path, "model.safetensors"))
