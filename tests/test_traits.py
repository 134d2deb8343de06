"""The one table of what a slot's past is and which feature can take it
(``models.traits``): each trait of a configuration, each feature that handles a
slot's past, and what ``refuse`` says of the pair. Construction only."""

import re

import pytest

from opendiloco_tpu.models.llama import LlamaConfig
from opendiloco_tpu.models.traits import REFUSALS, TRAITS, refuse

_SMALL = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
          "num_attention_heads": 4, "max_position_embeddings": 128}
# a tiny configuration of each family (the family tests' own), by the trait it brings
CONFIGS = {
    "hybrid": {
        **_SMALL, "model_type": "granitemoehybrid", "hidden_size": 32, "intermediate_size": 16,
        "shared_intermediate_size": 24, "num_hidden_layers": 4, "num_key_value_heads": 2,
        "layer_types": ["mamba", "mamba", "attention", "mamba"], "position_embedding_type": "nope",
        "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16, "mamba_d_conv": 4,
        "mamba_n_groups": 1, "mamba_chunk_size": 8, "mamba_expand": 2, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "num_experts": 8, "num_experts_per_tok": 2,
    },
    "cca": {
        **_SMALL, "model_type": "zaya", "head_dim": 8, "num_key_value_heads": 2,
        "layer_types": ["hybrid"] * 3, "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
        "router_hidden_size": 16, "num_experts": 8, "num_experts_per_tok": 1,
        "moe_intermediate_size": 32, "tie_word_embeddings": True,
        "rope_parameters": {"hybrid": {"rope_theta": 5e6}},
    },
    "eva": {
        **_SMALL, "model_type": "evabyte", "num_key_value_heads": 4, "attention_class": "eva",
        "chunk_size": 2, "window_size": 8, "num_pred_heads": 2, "norm_add_unit_offset": True,
        "fp32_skip_add": True, "fp32_logits": True, "rope_theta": 1e5,
    },
    "sparse": {
        **_SMALL, "model_type": "KeyeVL2", "head_dim": 16, "num_key_value_heads": 2,
        "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
        "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 8, "q_chunk_size": 8, "topk": 12},
    },
    "sliding": {
        **_SMALL, "model_type": "laguna", "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "num_hidden_layers": 4, "num_key_value_heads": 2,
        "head_dim": 16, "sliding_window": 3,
        "layer_types": ["full_attention", "sliding_attention", "full_attention", "sliding_attention"],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"], "mlp_only_layers": [0],
        "num_attention_heads_per_layer": [4, 6, 4, 6], "gating": "per-head",
        "rope_parameters": {
            "full_attention": {"rope_theta": 5e5, "rope_type": "default", "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 1e4, "partial_rotary_factor": 1},
        },
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    },
    "latent": {
        **_SMALL, "model_type": "glm4_moe_lite", "moe_intermediate_size": 32, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "first_k_dense_replace": 1, "n_routed_experts": 8, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "topk_method": "noaux_tc", "norm_topk_prob": True,
    },
    # a minicpm_sala stack brings two: lightning layers beside plain grouped-query
    # ones (no ``sparse_config``) bring the one, ``minicpm4`` layers alone the other
    "linear": {
        **_SMALL, "model_type": "minicpm_sala", "num_key_value_heads": 2, "head_dim": 16,
        "mixer_types": ["lightning-attn", "minicpm4", "lightning-attn"], "qk_norm": True,
        "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16, "attn_use_output_gate": True,
    },
    "blocks": {
        **_SMALL, "model_type": "minicpm_sala", "num_key_value_heads": 2, "head_dim": 16,
        "mixer_types": ["minicpm4"] * 3, "qk_norm": True, "attn_use_output_gate": True,
        "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 8, "topk": 4,
                          "init_blocks": 1, "window_size": 16, "dense_len": 32},
    },
    # a solar_open2 stack: kda layers beside plain gated grouped-query ones
    "kda": {
        **_SMALL, "model_type": "solar_open2", "num_key_value_heads": 2, "head_dim": 16,
        "moe_intermediate_size": 32, "gqa_layers": [0], "use_gqa_gate": True,
        "kda_allow_neg_eigval": True, "n_routed_experts": 8, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                               "num_kv_heads": None},
    },
}
# what a refusal calls each trait
NAMED = {"hybrid": "Mamba-2 layers", "cca": "CCA", "eva": "EVA attention",
         "sparse": "learned sparse attention", "sliding": "sliding layers",
         "latent": "latent attention", "linear": "lightning linear-attention layers",
         "blocks": "attention under a selection by blocks",
         "kda": "kda linear-attention layers"}
# the traits each feature handles, so refuses nothing for: every other is refused
TAKES = {
    "prefix_reuse": (), "page_out": (), "page_in": (),
    "continued_prefill": ("sparse", "sliding", "latent", "linear", "blocks", "kda"),
    "attn_impl": ("hybrid", "cca"),
}


def test_the_table_covers_every_feature_and_names_only_traits():
    assert tuple(CONFIGS) == tuple(NAMED) == TRAITS and set(TAKES) == set(REFUSALS)
    for feature, (_, reasons) in REFUSALS.items():
        assert set(reasons) == set(TRAITS) - set(TAKES[feature]), feature


@pytest.mark.parametrize("feature", list(TAKES))
@pytest.mark.parametrize("trait", list(CONFIGS))
def test_a_feature_refuses_a_trait_by_name_or_takes_it(trait, feature):
    cfg = LlamaConfig.from_dict(CONFIGS[trait])
    assert cfg.traits == (trait,)
    if trait in TAKES[feature]:
        return refuse(cfg, feature, "this")
    with pytest.raises(ValueError, match=f"^this is refused for a .* with {NAMED[trait]}"):
        refuse(cfg, feature, "this")
    with pytest.raises(ValueError, match=f"^{re.escape(REFUSALS[feature][0])} is refused for a "):
        refuse(cfg, feature)  # under the feature's own name where the caller gives none


@pytest.mark.parametrize("feature", list(TAKES))
def test_a_plain_llama_is_refused_nothing(feature):
    cfg = LlamaConfig.from_dict(_SMALL)
    assert cfg.traits == ()
    refuse(cfg, feature)


def test_of_several_traits_the_first_of_the_features_own_order_is_named():
    """dots3's stack is latent, under an indexer, with sliding layers: what
    handles rows names the indexer first (as the scheduler did), the flash and
    ring kernels the latent rows (as ``forward`` did), and the continued
    prefill takes all three."""
    raw = {
        **CONFIGS["latent"], "model_type": "dots3_note", "num_hidden_layers": 4,
        "layer_types": ["full_attention", "full_attention", "sliding_attention", "sliding_attention"],
        "index_n_heads": 2, "index_head_dim": 16, "index_topk": 6, "q_chunk_size": 4,
        "sliding_window_size": 3, "swa_num_attention_heads": 2, "swa_q_lora_rank": 16,
        "swa_kv_lora_rank": 24, "swa_qk_nope_head_dim": 8, "swa_qk_rope_head_dim": 8,
        "swa_v_head_dim": 8, "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    }
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.traits == ("sparse", "sliding", "latent")
    with pytest.raises(ValueError, match="kv_tier is refused for a configuration with learned sparse"):
        refuse(cfg, "page_out", "kv_tier")
    with pytest.raises(ValueError, match="attn_impl='ring' is refused for a configuration with latent"):
        refuse(cfg, "attn_impl", "attn_impl='ring'")
    refuse(cfg, "continued_prefill")
