"""The seam between the engine and `kubeai_tpu/models/`: one table
(`MODULES`: model_type -> module) and one declaration (`SEAM`: what a
family module gives). Every family is held to both here, from a minimal
published config of its `model_type`; a module that lacks a declared name
is refused when it is first looked up."""

import os
import sys
import types

import pytest

from kubeai_tpu import models
from kubeai_tpu.models import MODULES, SEAM, ModelConfig, family, family_of
from kubeai_tpu.obs import perf

COMMON = dict(
    vocab_size=272, hidden_size=64, intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, max_position_embeddings=256,
)
# module -> the least a published config.json of its model_type says.
PUBLISHED = {
    "llama": dict(COMMON, model_type="gemma2", query_pre_attn_scalar=16, sliding_window=32),
    "deepseek": dict(
        COMMON, model_type="deepseek_v3", n_routed_experts=8, n_shared_experts=1, moe_intermediate_size=32,
        num_experts_per_tok=2, first_k_dense_replace=1, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
    ),
    "smallthinker": dict(
        COMMON, model_type="smallthinker", moe_primary_router_apply_softmax=True, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, moe_ffn_hidden_size=32, sliding_window_size=32,
        sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
    ),
    "nemotron_h": dict(
        COMMON, model_type="nemotron_h", hybrid_override_pattern="ME*M", mamba_num_heads=8, mamba_head_dim=8,
        ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8, n_routed_experts=4, n_shared_experts=1,
        num_experts_per_tok=2, moe_intermediate_size=32, moe_latent_size=32, moe_shared_expert_intermediate_size=48,
    ),
    "afmoe": dict(
        COMMON, model_type="afmoe", layer_types=["sliding_attention"] * 3 + ["full_attention"], sliding_window=32,
        num_dense_layers=1, num_experts=8, num_shared_experts=1, num_experts_per_tok=2, moe_intermediate_size=32,
    ),
    "lfm2_moe": dict(
        {k: v for k, v in COMMON.items() if k != "head_dim"}, model_type="lfm2_moe", hidden_size=256,
        layer_types=["conv", "full_attention", "conv", "conv"], conv_L_cache=3, num_dense_layers=1, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, rope_parameters={"rope_theta": 1000000.0, "rope_type": "default"},
    ),
}
# ... and a field of the module's own `config_keys` that it must have set.
OWN_KEY = {
    "llama": ("post_norms", True), "deepseek": ("kv_lora_rank", 32), "smallthinker": ("sliding_window_layout", (0, 1, 1, 1)),
    "nemotron_h": ("layer_pattern", "ME*M"), "afmoe": ("rope_layout", (1, 1, 1, 0)),
    "lfm2_moe": ("layer_pattern", "cacc"),
}
NAMES = sorted(PUBLISHED)


def config_of(name: str) -> ModelConfig:
    return ModelConfig.from_hf(types.SimpleNamespace(**PUBLISHED[name]))


def test_every_module_of_the_package_is_a_row_of_the_table():
    here = os.path.dirname(models.__file__)
    on_disk = {f[:-3] for f in os.listdir(here) if f.endswith(".py")} - {"__init__", "base", "shared"}
    assert on_disk == set(MODULES.values()) | {models.DENSE} == set(NAMES)


@pytest.mark.parametrize("name", NAMES)
def test_a_module_states_every_declared_name_with_its_type(name):
    module = family(config_of(name))
    for attr, (kind, what) in SEAM.items():
        assert attr in vars(module), f"{name}.{attr} is not stated ({what})"
        assert isinstance(vars(module)[attr], kind), f"{name}.{attr} is not a {kind}"
    assert all(isinstance(k, str) for k in module.SLOT_STATE)
    # What the rules promise of each other.
    assert (module.layer_kinds is None) == (name not in ("smallthinker", "afmoe"))
    assert (module.init_lora_bank is None) == (module.stream_params_from_hf is not None) == (name != "llama")


@pytest.mark.parametrize("name", NAMES)
def test_a_published_config_reaches_its_module_and_its_own_keys(name):
    mc = config_of(name)
    assert family(mc).__name__ == f"kubeai_tpu.models.{name}" and family(mc) is family_of(PUBLISHED[name]["model_type"])
    field, value = OWN_KEY[name]
    assert getattr(mc, field) == value
    family(mc).refuse_unsupported(mc)
    if family(mc).init_lora_bank is None:  # ... and its entry points refuse an adapter bank, by the family's name
        with pytest.raises(ValueError, match=f"{mc.model_type}: LoRA adapters are not supported"):
            family(mc).decode_step_paged(None, mc, None, None, None, None, lora={})


@pytest.mark.parametrize("name", NAMES)
def test_the_observability_package_asks_the_module_for_its_counts(name, monkeypatch):
    mc = config_of(name)
    module = family(mc)
    assert perf.param_counts(mc) == module.param_counts(mc)
    total, active = module.param_counts(mc)
    assert 0 < active <= total
    monkeypatch.setattr(module, "param_counts", lambda config: (7.0, 3.0))
    model = perf.PerfModel.from_model_config(mc)
    assert (model.param_count, model.active_params) == (7.0, 3.0)
    # The engine counts attention pairs where a window pool exists, and only there.
    assert (model.attn_flops_per_pair > 0) == (module.window_pool_tokens(mc) > 0) == (module.layer_kinds is not None)


def test_a_module_that_lacks_a_rule_is_refused_by_name(monkeypatch):
    from kubeai_tpu.models import deepseek

    standin = types.ModuleType("kubeai_tpu.models.standin")
    vars(standin).update({k: v for k, v in vars(deepseek).items() if k in SEAM and k != "KV_PARK"})
    monkeypatch.setitem(sys.modules, standin.__name__, standin)
    monkeypatch.setitem(MODULES, "standin_type", "standin")
    with pytest.raises(TypeError, match=r"models/standin\.py does not state `KV_PARK`"):
        family(ModelConfig(model_type="standin_type"))
    standin.KV_PARK = "yes"  # stated, and not as declared
    with pytest.raises(TypeError, match=r"models/standin\.py does not state `KV_PARK`"):
        family(ModelConfig(model_type="standin_type"))
    standin.KV_PARK = False
    assert family(ModelConfig(model_type="standin_type")) is standin
    monkeypatch.delitem(models._held, "standin")
