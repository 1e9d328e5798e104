"""`model_type: mistral` (MistralForCausalLM): the dense decoder, no biases."""

from families._dense_decoder import layer_plan, logits, outside_plan  # noqa: F401
