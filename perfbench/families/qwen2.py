"""`model_type: qwen2` (Qwen2ForCausalLM): the dense decoder with q/k/v
biases, which modeling_qwen2 hardcodes and the config.json does not state."""

from families import _dense_decoder

outside_plan = _dense_decoder.outside_plan
logits = _dense_decoder.logits


def layer_plan(hf: dict, i: int) -> list[tuple]:
    return _dense_decoder.layer_plan(hf, i, qkv_bias=True)
