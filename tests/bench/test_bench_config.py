"""The reader that turns a configuration file into the program's
``ModelConfig``: today's dense files as before, published-key files of the
registry's MoE, SSM and hybrid models at their smoke sizes, and a refusal
that names the key for whatever the program cannot run as published."""
import copy
import dataclasses
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import harness, published  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig  # noqa: E402
from repro.models import abstract_params  # noqa: E402


def cfg(name):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as fh:
        return json.load(fh)


PRECISION = {"params": "float32", "compute": "bfloat16",
             "kv_cache": "bfloat16"}

# What the harness built from each file before the reader existed.
PINNED = {
    "yi-6b-l4": ModelConfig(
        name="yi-6b-l4", family="dense", n_layers=4, d_model=4096,
        n_heads=32, n_kv_heads=4, head_dim=128, d_ff=11008,
        vocab_size=64000, rope_theta=5000000.0, norm_eps=1e-05,
        tie_embeddings=False, param_dtype="float32",
        compute_dtype="bfloat16", source="https://arxiv.org/abs/2403.04652"),
    "stablelm-2-1.6b-l4": ModelConfig(
        name="stablelm-2-1.6b-l4", family="dense", n_layers=4, d_model=2048,
        n_heads=32, n_kv_heads=32, head_dim=64, d_ff=5632,
        vocab_size=100352, rope_theta=10000.0, norm_eps=1e-05,
        tie_embeddings=False, param_dtype="float32",
        compute_dtype="bfloat16",
        source="https://huggingface.co/stabilityai/stablelm-2-1_6b"),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_files_give_the_config_the_harness_built_before(name):
    got = harness.program_config(cfg(name))
    assert dataclasses.asdict(got) == dataclasses.asdict(PINNED[name])
    assert got == PINNED[name]


# Published-key files at the registry's smoke sizes. The registry's
# jamba-v0.1-52b runs Mamba-2 mixers, so its file names the mixer's head
# size as Mamba-2 hybrids publish it (``mamba_d_head``). The program
# renormalises its top-k gates and has no q/k norm, where OLMoE and Jamba as
# published do not renormalise and OLMoE normalises q and k: the files of the
# registry's models state the program's values.
SMOKE_FILES = {
    "olmoe-1b-7b": {
        "model_type": "olmoe", "hidden_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
        "intermediate_size": 64, "num_experts": 4, "num_experts_per_tok": 2,
        "vocab_size": 512, "rope_theta": 10000, "rms_norm_eps": 1e-6,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "norm_topk_prob": True, "qk_norm": False},
    "mamba2-370m": {
        "model_type": "mamba2", "hidden_size": 128, "num_hidden_layers": 2,
        "vocab_size": 512, "state_size": 16, "expand": 2, "head_dim": 32,
        "num_heads": 8, "n_groups": 1, "conv_kernel": 4, "chunk_size": 16,
        "use_conv_bias": True, "use_bias": False, "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "tie_word_embeddings": True},
    "jamba-v0.1-52b": {
        "model_type": "jamba", "hidden_size": 128, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "intermediate_size": 256, "num_experts": 4, "num_experts_per_tok": 2,
        "norm_topk_prob": True,
        "expert_layer_period": 2, "expert_layer_offset": 1,
        "attn_layer_period": 2, "attn_layer_offset": 1,
        "mamba_d_state": 16, "mamba_expand": 2, "mamba_d_head": 32,
        "mamba_d_conv": 4, "mamba_chunk_size": 16, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "vocab_size": 512, "rope_theta": 10000,
        "rms_norm_eps": 1e-6, "sliding_window": None,
        "tie_word_embeddings": False},
}


def smoke_file(arch, **change):
    smoke = get_smoke_config(arch)
    out = {"name": smoke.name, "source": smoke.source,
           "precision": PRECISION, **copy.deepcopy(SMOKE_FILES[arch])}
    for k, v in change.items():
        if v is None and k in out:
            del out[k]
        else:
            out[k] = v
    return out


@pytest.mark.parametrize("arch", sorted(SMOKE_FILES))
def test_published_keys_give_the_registry_smoke_config(arch):
    smoke = get_smoke_config(arch)
    got = published.from_config(smoke_file(arch))
    # ``remat`` is a training switch, not a key of the model
    assert dataclasses.replace(got, remat=smoke.remat) == smoke
    assert harness.same_layout(abstract_params(got)[0],
                               abstract_params(smoke)[0]) is None


def test_families_and_sub_configs():
    moe = published.from_config(smoke_file("olmoe-1b-7b"))
    ssm = published.from_config(smoke_file("mamba2-370m"))
    hyb = published.from_config(smoke_file("jamba-v0.1-52b"))
    assert (moe.family, moe.moe, moe.d_ff) == (
        "moe", MoEConfig(n_experts=4, top_k=2, d_ff_expert=64), 0)
    assert (ssm.family, ssm.n_heads, ssm.head_dim, ssm.attn_every) == (
        "ssm", 0, 0, 0)
    assert ssm.ssm == SSMConfig(d_state=16, expand=2, head_dim=32,
                                chunk_size=16, conv_width=4)
    assert (hyb.family, hyb.attn_every, hyb.moe_every, hyb.d_ff) == (
        "hybrid", 2, 2, 256)
    assert [hyb.layer_is_attn(i) for i in range(4)] == [False, True] * 2
    assert [hyb.layer_is_moe(i) for i in range(4)] == [False, True] * 2


def test_a_sliding_window_and_its_global_layers():
    base = dict(cfg("yi-6b-l4"), sliding_window=512, sliding_window_pattern=2)
    got = published.from_config(base)
    assert (got.attn_window, got.global_attn_every) == (512, 2)
    assert [got.window_for_layer(i) for i in range(4)] == [512, None] * 2


# The published config of AI21-Jamba2-Mini as the model catalog holds it
# (https://huggingface.co/ai21labs/AI21-Jamba2-Mini/blob/main/config.json).
JAMBA2_MINI = {
    "attn_layer_offset": 4, "attn_layer_period": 8, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 14336, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 256, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 32, "num_experts": 16,
    "num_experts_per_tok": 2, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_mamba_kernels": True, "vocab_size": 65536}


@pytest.mark.parametrize("arch, change, key", [
    ("olmoe-1b-7b", {"rope_scaling_kind": "yarn"}, "rope_scaling_kind"),
    ("jamba-v0.1-52b", {"attn_layer_period": 8, "attn_layer_offset": 4},
     "attn_layer_offset"),
    ("jamba-v0.1-52b", {"mamba_dt_rank": 256}, "mamba_dt_rank"),
    ("jamba-v0.1-52b", {"expert_layer_offset": 0}, "expert_layer_offset"),
    ("jamba-v0.1-52b", {"mamba_d_head": None}, "mamba_d_head"),
    ("jamba-v0.1-52b", {"mamba_n_groups": 8}, "mamba_n_groups"),
    ("jamba-v0.1-52b", {"attn_layer_offset": None}, "attn_layer_offset"),
    ("olmoe-1b-7b", {"hidden_act": "gelu"}, "hidden_act"),
    ("olmoe-1b-7b", {"norm_topk_prob": False}, "norm_topk_prob"),
    ("olmoe-1b-7b", {"num_local_experts": 4}, "num_local_experts"),
    ("mamba2-370m", {"num_heads": 16}, "num_heads"),
    ("mamba2-370m", {"intermediate_size": 256}, "intermediate_size"),
    ("mamba2-370m", {"d_ssm": 256}, "d_ssm"),
    # left out, where the model_type's published default is not the
    # program's: OLMoE and Jamba keep the top-k gates as they are, OLMoE
    # normalises q and k
    ("olmoe-1b-7b", {"norm_topk_prob": None}, "norm_topk_prob"),
    ("olmoe-1b-7b", {"qk_norm": None}, "qk_norm"),
    ("jamba-v0.1-52b", {"norm_topk_prob": None}, "norm_topk_prob"),
    ("olmoe-1b-7b", {"model_type": "qwen2_moe"}, "model_type"),
    # an attention model typed as Mamba-2, which publishes no attention
    ("olmoe-1b-7b", {"model_type": "mamba2"}, "partial_rotary_factor"),
])
def test_refused_keys_are_named(arch, change, key):
    with pytest.raises(published.Refused) as err:
        published.from_config(smoke_file(arch, **change))
    assert err.value.key == key and repr(key) in str(err.value)


@pytest.mark.parametrize("arch, left_out", [
    ("mamba2-370m", ("n_groups", "use_conv_bias", "use_bias", "hidden_act")),
    ("olmoe-1b-7b", ("hidden_act",)),
])
def test_keys_left_out_at_the_programs_published_default(arch, left_out):
    got = published.from_config(smoke_file(
        arch, **{k: None for k in left_out}))
    assert got == published.from_config(smoke_file(arch))


@pytest.mark.parametrize("change, key", [
    ({"partial_rotary_factor": 0.25}, "partial_rotary_factor"),
    ({"use_qkv_bias": True}, "use_qkv_bias"),
    ({"norm_type": "layernorm"}, "norm_type"),
    ({"rope_scaling": {"type": "linear", "factor": 2.0}}, "rope_scaling"),
    ({"num_hidden_layerz": 4}, "num_hidden_layerz"),
])
def test_dense_values_the_program_lacks_are_refused(change, key):
    with pytest.raises(published.Refused) as err:
        published.from_config({**cfg("stablelm-2-1.6b-l4"), **change})
    assert err.value.key == key


def test_the_catalog_jamba2_mini_is_refused_key_by_key():
    """What the program lacks for AI21-Jamba2-Mini, in the order the
    reader meets it: Mamba-1's dt rank, attention without rotary
    positions, attention at layer 4 of each 8, and top-k gates kept as the
    router gives them."""
    f = {"name": "AI21-Jamba2-Mini", "source": "catalog",
         "precision": PRECISION, **JAMBA2_MINI}
    for key, fill in [("mamba_dt_rank", {"mamba_d_head": 64}),
                      ("rope_theta", {"rope_theta": 10000}),
                      ("attn_layer_offset", {"attn_layer_offset": 7}),
                      ("norm_topk_prob", {"norm_topk_prob": True})]:
        with pytest.raises(published.Refused) as err:
            published.from_config(f)
        assert err.value.key == key
        f = {k: v for k, v in f.items() if k != key}
        f.update(fill)
    got = published.from_config(f)
    assert (got.family, got.attn_every, got.moe_every) == ("hybrid", 8, 2)


def _stub_reader(monkeypatch, module):
    import types
    mod = types.ModuleType(module)
    mod.from_config = lambda c: ("read by", module, c["name"])
    monkeypatch.setitem(sys.modules, module, mod)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_a_file_without_a_reader_keeps_the_benchmarks_own(monkeypatch,
                                                           name):
    """A module the program adds later does not take over a file that
    names no reader."""
    _stub_reader(monkeypatch, "repro.configs.published")
    assert harness.program_config(cfg(name)) == PINNED[name]


def test_a_file_names_its_reader(monkeypatch):
    _stub_reader(monkeypatch, "repro.configs.some_family")
    f = dict(cfg("yi-6b-l4"), reader="repro.configs.some_family")
    assert harness.program_config(f) == (
        "read by", "repro.configs.some_family", "yi-6b-l4")
