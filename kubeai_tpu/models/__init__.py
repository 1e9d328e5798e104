"""Model families. The engine reaches a model through ONE lookup,
`family(config)`: the module that runs the configuration's `model_type`.
A family module gives `init_params`, `params_from_hf`, `init_paged_cache`,
`cached_attention_route`, `prefill_paged_cold`, `prefill_paged`,
`decode_step_paged`, `refuse_unsupported`, `window_pool_tokens` and the
rules `REUSE_WHOLE_PREFILL_CALLS` and `KV_PARK`; nothing but the
published `model_type` chooses it. `decode_step_paged` takes the
dispatch's `live` (`models/base.py::LiveRows`) with every per-row
argument already in its order, live rows first (the engine's decode
program gathers them: tokens, lengths, adapter rows, the block table):
a family puts the hidden state back in slot order before its final norm
(`live.restore`) and does with `live.count` what its kernel can (the
ragged kernel stops there, `models/deepseek.py` says what its does).

A family whose sequences keep state that is not pages says so with
`SLOT_STATE = True` (`models/nemotron_h.py`): its `init_paged_cache` takes
`slots` and gives arrays with a slot axis beside the pool, and its two
prefill entry points take `slots`, the slot of every row. Such a family
may also state `PREFIX_REUSE = False`: the engine then looks up and
registers no prefix for it (and `REUSE_WHOLE_PREFILL_CALLS` is moot)."""

from kubeai_tpu.models.base import ModelConfig

__all__ = ["ModelConfig", "family"]


def family(config: ModelConfig):
    """The model module of *config*'s family: `models/deepseek.py` for
    `deepseek_v3`, `models/smallthinker.py` for `smallthinker`,
    `models/nemotron_h.py` for `nemotron_h`, `models/afmoe.py` for `afmoe`,
    `models/llama.py` for every dense or Mixtral-style decoder it has
    always run (Llama, Mistral, Qwen2, Gemma, Mixtral)."""
    if config.model_type == "deepseek_v3":
        from kubeai_tpu.models import deepseek

        return deepseek
    if config.model_type == "smallthinker":
        from kubeai_tpu.models import smallthinker

        return smallthinker
    if config.model_type == "nemotron_h":
        from kubeai_tpu.models import nemotron_h

        return nemotron_h
    if config.model_type == "afmoe":
        from kubeai_tpu.models import afmoe

        return afmoe
    from kubeai_tpu.models import llama

    return llama
