"""Model families. A family is ONE module of this package and one row of
`MODULES`; nothing but the published `model_type` chooses it, and every
other package reaches it through `family(config)` and the names `SEAM`
declares. `models/shared.py` holds what the families whose every call
goes through the paged pool have in common; `docs/tpu-serving.md`,
"Adding a family", walks through a new one."""

import importlib
from collections.abc import Callable

from kubeai_tpu.models.base import ModelConfig

__all__ = ["ModelConfig", "family", "family_of", "MODULES", "SEAM"]

# model_type -> the module that runs it. Every other model_type is the
# dense family's (`llama.py`: Llama, Mistral, Qwen2, Gemma, Mixtral: one
# module's dialects). Read by `family_of` and by nothing else.
MODULES = {
    "deepseek_v3": "deepseek",
    "smallthinker": "smallthinker",
    "nemotron_h": "nemotron_h",
    "afmoe": "afmoe",
    "lfm2_moe": "lfm2_moe",
}
DENSE = "llama"

# What a family module gives: name -> (type, what it is). Every module
# states every name; where the type allows None, a family with no use for
# the callable states None. `family_of` holds a module to this the first
# time it is looked up.
Fn, FnOrNone = Callable, (Callable, type(None))
SEAM: dict[str, tuple[object, str]] = {
    # The model and its weights.
    "config_keys": (Fn, "(get) -> ModelConfig fields from the family's own config.json keys; refuses by name what the module does not compute"),
    "init_params": (Fn, "(config, key, dtype=None) -> random parameters in the tree the loaders build"),
    "params_from_hf": (Fn, "(state_dict, config, dtype=None, to_device=True) -> the tree from an HF state dict"),
    "stream_params_from_hf": (
        FnOrNone,
        "(source, config, pad=0) -> the tree, streamed layer by layer onto the device; "
        "None: engine/weights.py's own streamed load (sharded, quantized) builds the tree",
    ),
    "param_counts": (Fn, "(config) -> (parameters held, parameters a token is multiplied by): obs/perf.py's roofline and MFU"),
    "refuse_unsupported": (Fn, "(config, quantization='', tp=1): raises, by name, for what the family does not run"),
    "init_lora_bank": (
        FnOrNone,
        "(config, n_adapters, rank, dtype=None) -> the zeroed adapter bank; "
        "None: the family runs no LoRA adapter and its entry points refuse one",
    ),
    # The cache.
    "init_paged_cache": (
        Fn,
        "(config, num_pages, page_size, dtype=None, ...) -> the cache dict: the pool `kv` (any other pool `kv*`) and "
        "SLOT_STATE's keys; takes `window_pages` where window_pool_tokens is not 0, `slots` where SLOT_STATE is not empty",
    ),
    "window_pool_tokens": (
        Fn,
        "(config) -> the window whose layers keep a page pool and a block table of their own (engine/paging.py::WindowPages); "
        "0: one page budget a slot. Where it is not 0 the engine counts the attention pairs its masks leave "
        "(obs/perf.py::PerfModel.attn_flops_per_pair)",
    ),
    "layer_kinds": (FnOrNone, "(config) -> (full layers, window layers); None where window_pool_tokens is always 0"),
    "SLOT_STATE": (
        tuple,
        "keys of the cache dict that hold state by SLOT beside the pools, each [n, slots, ...]; "
        "not empty: the prefill entry points take `slots`, the slot of every row",
    ),
    "KV_PARK": (bool, "a slot's pages can be parked, restored and handed off (engine/kvstate.py)"),
    "PREFIX_REUSE": (bool, "a prefix found in the page cache is used; False: the engine looks nothing up and registers nothing"),
    "REUSE_WHOLE_PREFILL_CALLS": (
        bool,
        "a prefix hit is cut to an edge between two calls of the prompt's cold plan, so that cold and cached give "
        "the same bits (models/deepseek.py says why); False: a hit is used to the page",
    ),
    # The step.
    "apply": (Fn, "(params, config, tokens, positions, cache, page_table, ...) -> (logits, cache): the forward pass under the entry points"),
    "prefill_paged_cold": (
        Fn,
        "(params, config, tokens, cache, page_table, lengths, lora=, lora_rows=, tp_mesh=) -> "
        "(logits [B, 1, V] at lengths-1, cache): whole prompts from position 0",
    ),
    "prefill_paged": (
        Fn,
        "(params, config, tokens, cache, page_table, start, last_idx, lora=, lora_rows=, tp_mesh=) -> "
        "(logits [B, 1, V] at last_idx, cache): a chunk behind what is cached",
    ),
    "decode_step_paged": (
        Fn,
        "(params, config, tokens, cache, page_table, lengths, lora=, lora_rows=, tp_mesh=, live=) -> (logits [B, 1, V] in "
        "slot order, cache): one token a row, the rows in `live`'s order (models/base.py::LiveRows: live slots first)",
    ),
    "cached_attention_route": (Fn, "(config, S, left_aligned, paged) -> 'flash' | 'paged_kernel' | 'xla': what a cached call of S queries a row compiles to"),
    "PAGED_KERNEL_LABEL": (str, "what the engine's step records call the family's 'paged_kernel' route"),
}

_held: dict[str, object] = {}  # module name -> the module, once held to SEAM


def family_of(model_type: str):
    """The module that runs *model_type*, held to `SEAM`."""
    name = MODULES.get(model_type, DENSE)
    if name not in _held:
        module = importlib.import_module(f"{__name__}.{name}")
        for attr, (kind, what) in SEAM.items():
            if attr not in vars(module) or not isinstance(vars(module)[attr], kind):
                raise TypeError(f"kubeai_tpu/models/{name}.py does not state `{attr}` as models/__init__.py::SEAM declares it: {what}")
        _held[name] = module
    return _held[name]


def family(config: ModelConfig):
    """The model module of *config*'s family."""
    return family_of(config.model_type)
