"""jax.named_scope in the model step (embed, attn, attn.kernel, ffn,
lm_head, sampling, logprobs) names operations for a device trace and must
change nothing else: the step programs lower to the same text, metadata
aside, with the scopes and without them."""

import contextlib
import functools
import re

import jax
import pytest

from kubeai_tpu.engine.coldstart import warm_compile
from kubeai_tpu.engine.core import EngineConfig
from kubeai_tpu.models.base import ModelConfig

SCOPES = ("embed", "attn", "attn.kernel", "ffn", "lm_head", "sampling", "logprobs")


def _lowered_programs(scoped: bool, mc: ModelConfig | None = None) -> list[tuple[str, str]]:
    """(text without debug info, text with it) of every program the warm
    compile lowers: the decode chunk, the prefill buckets, the chunked
    prefill. Nothing is compiled."""
    texts: list[tuple[str, str]] = []

    def record(lowered, *a, **k):
        texts.append((lowered.as_text(), lowered.as_text(debug_info=True)))

    with pytest.MonkeyPatch.context() as m:
        m.setattr(jax.stages.Lowered, "compile", record)
        if not scoped:
            m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        mc = mc or ModelConfig(
            vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, num_kv_heads=2, dtype="float32", max_position=256,
            # The kernel routes (XLA twins on the CPU): attn.kernel is
            # around the call either way.
            use_paged_kernel=True, use_flash_prefill=True,
        )
        cfg = EngineConfig(
            max_slots=2, max_seq_len=64, page_size=16, prefill_buckets=(16, 32),
            decode_chunk=2,
        )
        out = warm_compile(mc, cfg, n_valid_vocab=259)
    assert "errors" not in out, out
    return texts


@pytest.fixture(scope="module")
def scoped_programs():
    return _lowered_programs(True)


def test_scopes_change_metadata_only(scoped_programs):
    plain = _lowered_programs(False)
    assert len(scoped_programs) == len(plain) >= 4  # decode, 2 buckets x 2 sizes, chunk
    for i, ((s_text, s_debug), (p_text, p_debug)) in enumerate(zip(scoped_programs, plain)):
        assert s_text == p_text, f"program {i}: the computation changed with the scopes"
        assert s_debug != p_debug, f"program {i}: the scopes left no trace in the metadata"


@pytest.mark.parametrize("scope", SCOPES)
def test_every_scope_names_operations(scoped_programs, scope):
    # A location reads loc("attn/attn.kernel/dot_general"(...)): the scope
    # is one component of the operation's name.
    rx = re.compile(r'["/]' + re.escape(scope) + "/")
    for _, debug_text in scoped_programs[:2]:  # the decode chunk, a prefill
        assert rx.search(debug_text), scope


# -- the expert family's module (models/deepseek.py, ops/moe.py) ---------------

MOE_SCOPES = (
    "embed", "attn", "attn.kernel", "ffn", "moe", "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
    "moe.shared", "lm_head", "sampling", "logprobs",
)
DEEPSEEK = ModelConfig(
    model_type="deepseek_v3", vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=3, num_heads=4,
    num_kv_heads=4, dtype="float32", max_position=256, num_experts_per_tok=2, n_routed_experts=8,
    n_shared_experts=1, moe_intermediate_size=32, first_k_dense_replace=1, routed_scaling_factor=2.448,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
)


@pytest.fixture(scope="module")
def scoped_moe_programs():
    return _lowered_programs(True, DEEPSEEK)


def test_scopes_change_metadata_only_in_the_expert_family(scoped_moe_programs):
    plain = _lowered_programs(False, DEEPSEEK)
    assert len(scoped_moe_programs) == len(plain) >= 4
    for i, ((s_text, s_debug), (p_text, p_debug)) in enumerate(zip(scoped_moe_programs, plain)):
        assert s_text == p_text, f"program {i}: the computation changed with the scopes"
        assert s_debug != p_debug, f"program {i}: the scopes left no trace in the metadata"


@pytest.mark.parametrize("scope", MOE_SCOPES)
def test_every_scope_names_operations_in_the_expert_family(scoped_moe_programs, scope):
    rx = re.compile(r'["/]' + re.escape(scope) + "/")
    for _, debug_text in scoped_moe_programs[:2]:  # the decode chunk, a prefill
        assert rx.search(debug_text), scope


# -- the window family's module (models/smallthinker.py) -----------------------

SWA_SCOPES = (
    "embed", "attn.full", "attn.window", "attn.kernel", "moe", "moe.router", "moe.dispatch", "moe.experts",
    "moe.combine", "lm_head", "sampling", "logprobs",
)
SMALLTHINKER = ModelConfig(
    model_type="smallthinker", vocab_size=272, hidden_size=64, intermediate_size=0, num_layers=4, num_heads=4,
    num_kv_heads=2, head_dim=16, dtype="float32", max_position=256, num_experts_per_tok=2, n_routed_experts=8,
    moe_intermediate_size=32, sliding_window_size=32, sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
    use_paged_kernel=True, use_flash_prefill=True,
)


@pytest.fixture(scope="module")
def scoped_swa_programs():
    return _lowered_programs(True, SMALLTHINKER)


def test_scopes_change_metadata_only_in_the_window_family(scoped_swa_programs):
    plain = _lowered_programs(False, SMALLTHINKER)
    assert len(scoped_swa_programs) == len(plain) >= 4
    for i, ((s_text, s_debug), (p_text, p_debug)) in enumerate(zip(scoped_swa_programs, plain)):
        assert s_text == p_text, f"program {i}: the computation changed with the scopes"
        assert s_debug != p_debug, f"program {i}: the scopes left no trace in the metadata"


@pytest.mark.parametrize("scope", SWA_SCOPES)
def test_every_scope_names_operations_in_the_window_family(scoped_swa_programs, scope):
    rx = re.compile(r'["/]' + re.escape(scope) + "/")
    for _, debug_text in scoped_swa_programs[:2]:  # the decode chunk, a prefill
        assert rx.search(debug_text), scope
    # Each kind's kernel scope sits inside its layer's scope: a reader tells them apart by the path.
    for kind in ("attn.full", "attn.window"):
        assert re.search(r'["/]' + re.escape(kind) + "/attn.kernel/", scoped_swa_programs[0][1]), kind


# -- the state-space family's module (models/nemotron_h.py, ops/ssm.py) ----------

SSM_SCOPES = (
    "embed", "ssm", "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj", "attn", "attn.kernel",
    "moe", "moe.latent_down", "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.latent_up", "moe.shared",
    "lm_head", "sampling", "logprobs",
)
NEMOTRON_H = ModelConfig(
    model_type="nemotron_h", vocab_size=272, hidden_size=64, intermediate_size=0, num_layers=4, num_heads=4,
    num_kv_heads=2, head_dim=16, dtype="float32", max_position=256, layer_pattern="ME*M", mamba_num_heads=8,
    mamba_head_dim=8, ssm_state_size=16, ssm_groups=2, conv_kernel=4, ssm_chunk=8, num_experts_per_tok=3,
    n_routed_experts=4, router_experts=16, experts_first=4, n_shared_experts=1, moe_intermediate_size=32,
    moe_latent_size=32, moe_shared_intermediate_size=48, routed_scaling_factor=2.5,
    use_paged_kernel=True, use_flash_prefill=True,
)


@pytest.fixture(scope="module")
def scoped_ssm_programs():
    return _lowered_programs(True, NEMOTRON_H)


def test_scopes_change_metadata_only_in_the_state_space_family(scoped_ssm_programs):
    plain = _lowered_programs(False, NEMOTRON_H)
    assert len(scoped_ssm_programs) == len(plain) >= 4
    for i, ((s_text, s_debug), (p_text, p_debug)) in enumerate(zip(scoped_ssm_programs, plain)):
        assert s_text == p_text, f"program {i}: the computation changed with the scopes"
        assert s_debug != p_debug, f"program {i}: the scopes left no trace in the metadata"


@pytest.mark.parametrize("scope", SSM_SCOPES)
def test_every_scope_names_operations_in_the_state_space_family(scoped_ssm_programs, scope):
    rx = re.compile(r'["/]' + re.escape(scope) + "/")
    for _, debug_text in scoped_ssm_programs[:2]:  # the decode chunk, a prefill
        assert rx.search(debug_text), scope
    # The mixer's parts sit inside `ssm`, the experts' inside `moe`: a reader tells them by the path.
    for inner in ("ssm/ssm.scan", "ssm/ssm.conv", "moe/moe.latent_down", "attn/attn.kernel"):
        assert re.search(r'["/]' + re.escape(inner) + "/", scoped_ssm_programs[0][1]), inner
    # ... the share's grouped matmuls inside its loop over passes (ops/moe.py::_held_part).
    assert re.search(r'["/]moe/while/body/moe\.experts/', scoped_ssm_programs[0][1])


@pytest.fixture(scope="module")
def scoped_ssm_programs_through_the_kernel():
    """The same programs with the chip's step kernel in the `M` blocks of
    the decode program (interpret mode: the test steers the dispatcher)."""
    from kubeai_tpu.ops import ssm

    with pytest.MonkeyPatch.context() as m:
        m.setattr(ssm, "kernel_takes", lambda states: True)
        m.setattr(ssm, "ssd_step_kernel", functools.partial(ssm.ssd_step_kernel, interpret=True))
        return _lowered_programs(True, NEMOTRON_H)


@pytest.mark.parametrize("route", ["portable", "kernel"])
def test_whatever_moves_the_stacked_state_in_decode_stands_under_ssm_scan(request, route):
    """`ssm_decode_roofline` takes its time by scope: an operation of the
    decode program that carries the slots' stacked state [n_M, slots, H, P,
    N] and is not the program's own plumbing (the scan, its body's call,
    their returns) is under `ssm/ssm.scan`; on the kernel's route that
    operation is the call of the kernel, one a block (its own body is
    attributed through the call)."""
    fixture = "scoped_ssm_programs" if route == "portable" else "scoped_ssm_programs_through_the_kernel"
    debug_text = request.getfixturevalue(fixture)[0][1]  # the decode chunk
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', debug_text, flags=re.M))
    state = "tensor<2x2x8x8x16xf32>"  # two `M` blocks, two slots, 8 heads of 8 x 16
    plumbing = re.compile(r"^(%\S+ = )?(func\.func|func\.call @closed_call|call @closed_call|stablehlo\.while|stablehlo\.return|return)[ (_]")
    moved, calls = 0, 0
    for function in debug_text.split("func.func")[1:]:
        if function.lstrip().startswith("private @ssd_step_kernel"):
            continue
        for line in map(str.strip, function.split("\n")):
            at = re.search(r"loc\((#loc\d+)\)$", line)
            if state not in line or at is None or plumbing.match(line):
                continue
            assert re.search(r"(^|/)ssm/ssm\.scan(/|$)", locs[at.group(1)]), (line[:120], locs[at.group(1)])
            moved += 1
            calls += "call @ssd_step_kernel" in line
    assert moved >= 2 and calls == (2 if route == "kernel" else 0)


# -- the gated window family's module (models/afmoe.py) ---------------------------

AFM_SCOPES = (
    "embed", "attn", "attn.qk_norm", "attn.full", "attn.window", "attn.kernel", "attn.gate", "norm.post", "ffn", "moe",
    "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared", "lm_head", "sampling", "logprobs",
)
AFMOE = ModelConfig(
    model_type="afmoe", vocab_size=272, hidden_size=64, intermediate_size=128, num_layers=8, num_heads=4, num_kv_heads=2,
    head_dim=16, dtype="float32", max_position=256, num_experts_per_tok=2, n_routed_experts=8, n_shared_experts=1,
    moe_intermediate_size=32, first_k_dense_replace=2, routed_scaling_factor=2.826, embed_scale=True, sliding_window_size=32,
    sliding_window_layout=(1, 1, 1, 0) * 2, rope_layout=(1, 1, 1, 0) * 2, use_paged_kernel=True, use_flash_prefill=True,
)


@pytest.fixture(scope="module")
def scoped_afm_programs():
    return _lowered_programs(True, AFMOE)


def test_scopes_change_metadata_only_in_the_gated_window_family(scoped_afm_programs):
    plain = _lowered_programs(False, AFMOE)
    assert len(scoped_afm_programs) == len(plain) >= 4
    for i, ((s_text, s_debug), (p_text, p_debug)) in enumerate(zip(scoped_afm_programs, plain)):
        assert s_text == p_text, f"program {i}: the computation changed with the scopes"
        assert s_debug != p_debug, f"program {i}: the scopes left no trace in the metadata"


@pytest.mark.parametrize("scope", AFM_SCOPES)
def test_every_scope_names_operations_in_the_gated_window_family(scoped_afm_programs, scope):
    rx = re.compile(r'["/]' + re.escape(scope) + "/")
    for _, debug_text in scoped_afm_programs[:2]:  # the decode chunk, a prefill
        assert rx.search(debug_text), scope
    # What the family adds sits INSIDE its layer's kind, the kind inside `attn`, and the post-norms
    # outside both: a reader tells them apart by the path (readers/afm_scopes.py, swa_scopes.py).
    for inner in (
        "attn/attn.full/attn.kernel", "attn/attn.window/attn.kernel", "attn/attn.window/attn.qk_norm", "attn/attn.full/attn.gate",
        "moe/moe.shared",
    ):
        assert re.search(r'["/]' + re.escape(inner) + "/", scoped_afm_programs[0][1]), inner
    assert not re.search(r'["/]attn[./][^"]*norm\.post/', scoped_afm_programs[0][1])
    # The periods behind the first are one scanned body.
    assert "stablehlo.while" in scoped_afm_programs[1][0]


# -- the short-convolution family's module (models/lfm2_moe.py, ops/shortconv.py) --

LFM_SCOPES = (
    "embed", "conv", "conv.in_proj", "conv.gate", "conv.taps", "conv.out_proj", "attn", "attn.qk_norm", "attn.kernel", "ffn",
    "moe", "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "lm_head", "sampling", "logprobs",
)
LFM2_MOE = ModelConfig(
    model_type="lfm2_moe", vocab_size=272, hidden_size=128, intermediate_size=96, num_layers=10, num_heads=2, num_kv_heads=2,
    dtype="float32", max_position=256, num_experts_per_tok=2, n_routed_experts=8, moe_intermediate_size=32,
    first_k_dense_replace=2, layer_pattern="ccacccaccc", conv_kernel=3, rope_theta=1e6, tie_word_embeddings=True,
    use_paged_kernel=True, use_flash_prefill=True,
)


@pytest.fixture(scope="module")
def scoped_lfm_programs():
    return _lowered_programs(True, LFM2_MOE)


def test_scopes_change_metadata_only_in_the_short_convolution_family(scoped_lfm_programs):
    plain = _lowered_programs(False, LFM2_MOE)
    assert len(scoped_lfm_programs) == len(plain) >= 4
    for i, ((s_text, s_debug), (p_text, p_debug)) in enumerate(zip(scoped_lfm_programs, plain)):
        assert s_text == p_text, f"program {i}: the computation changed with the scopes"
        assert s_debug != p_debug, f"program {i}: the scopes left no trace in the metadata"


@pytest.mark.parametrize("scope", LFM_SCOPES)
def test_every_scope_names_operations_in_the_short_convolution_family(scoped_lfm_programs, scope):
    rx = re.compile(r'["/]' + re.escape(scope) + "/")
    for _, debug_text in scoped_lfm_programs[:2]:  # the decode chunk, a prefill
        assert rx.search(debug_text), scope
    # The operator's parts sit INSIDE `conv`, the heads' norms and the kernel inside `attn`: a reader
    # tells them apart by the path (perfbench/readers/family_scopes.py, families/lfm2_moe_counts.py).
    for inner in ("conv/conv.in_proj", "conv/conv.gate", "conv/conv.taps", "conv/conv.out_proj", "attn/attn.qk_norm", "attn/attn.kernel"):
        assert re.search(r'["/]' + re.escape(inner) + "/", scoped_lfm_programs[0][1]), inner
    # The periods behind the dense layers are one scanned body.
    assert "stablehlo.while" in scoped_lfm_programs[1][0]
