"""Operations and bytes of the GLM-4.7-Flash block, computed from shapes: the
parameter counts of a file cut to one chip's share, what the latent decode
attention needs for a step, and what the routed FFN needs for a call at the
experts' own width. As in ``costs.py``, what the mathematics requires is
counted and nothing else: rows of padding that a kernel multiplies, dead ring
blocks and slots that hold no sequence are time spent, never work credited.
"""

from __future__ import annotations

import functools

from odbench import costs_routed


def latent_row_dim(cfg: dict) -> int:
    """Values of a cached row: the normed latent, then the shared rotated key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_attention_param_count(cfg: dict) -> int:
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (
        d * rq + rq  # q_a_proj and its norm
        + rq * nh * qk  # q_b_proj
        + d * latent_row_dim(cfg) + r  # kv_a_proj and the latent's norm
        + r * nh * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])  # kv_b_proj
        + nh * cfg["v_head_dim"] * d  # o_proj
    )


def expert_param_count(cfg: dict) -> int:
    """One expert, routed or shared: gate, up and down at the experts' width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layer_rest_param_count(cfg: dict) -> int:
    """What an expert layer holds outside its routed experts: the attention,
    the router over all the published experts and its selection bias, the
    shared experts, the two norms."""
    d = cfg["hidden_size"]
    width = cfg.get("num_experts", cfg["n_routed_experts"])
    return (
        latent_attention_param_count(cfg) + d * width + width
        + cfg["n_shared_experts"] * expert_param_count(cfg) + 2 * d
    )


def dense_layer_param_count(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return latent_attention_param_count(cfg) + 3 * d * cfg["intermediate_size"] + 2 * d


def param_count(cfg: dict) -> int:
    """Parameters held: the leading dense layers, each expert layer with the
    ``n_routed_experts`` experts held, the untied embedding and head, the
    final norm (no prediction module: the serving forward does not build it)."""
    dense = cfg["first_k_dense_replace"]
    expert_layer = expert_layer_rest_param_count(cfg) + cfg["n_routed_experts"] * expert_param_count(cfg)
    return (
        2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
        + dense * dense_layer_param_count(cfg)
        + (cfg["num_hidden_layers"] - dense) * expert_layer
    )


def latent_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    """One token's rows in the latent ring, over the layers."""
    return cfg["num_hidden_layers"] * latent_row_dim(cfg) * bytes_per_el


def mla_decode_cost(cfg: dict, layer_rows: float, slots: int, bytes_per_el: int = 2):
    """-> (flops, bytes) of one decode step's latent attention in the
    absorbed form, all layers: ``layer_rows`` live latent rows in total over
    the slots **and** the layers (the sum of the slots' live rows, the step's
    own among them, times the layers: what the program's ``serve_decode``
    span carries as ``latent_rows``), one query token in each of ``slots``.

    FLOPs: every head scores a row over all its ``R + rope`` values and
    weighs its first ``R``: ``heads x (R + rope + R)`` MACs a row. Bytes: a
    live row **once** a layer (it serves as key and as value, for every
    head), the absorbed query in and the latent sum out (``heads x (R + rope)``
    and ``heads x R`` a slot and layer), and the step's new row written. A
    kernel that read a row once for the scores and once for the values, or
    once a head, would be credited no more."""
    nh, r, dl = cfg["num_attention_heads"], cfg["kv_lora_rank"], latent_row_dim(cfg)
    layers = cfg["num_hidden_layers"]
    flops = 2.0 * layer_rows * nh * (dl + r)
    row_bytes = layer_rows * dl * bytes_per_el
    per_slot = (nh * (dl + r) + dl) * bytes_per_el  # q in, sum out, the row written
    return flops, float(row_bytes + layers * slots * per_slot)


# the routed FFN at the experts' own width (``intermediate_size`` is the
# leading dense layer's here): the shared count under this configuration's key
routed_ffn_cost = functools.partial(costs_routed.routed_ffn_cost, width_key="moe_intermediate_size")
