"""Operations and bytes of the granite-4.0-h hybrid, computed from shapes:
the parameter counts of a file cut to one chip's share, and what the Mamba-2
mixer needs for a call. As in ``costs.py``, what the mathematics requires is
counted and nothing else: rows of padding that a program multiplies, and
slots that hold no sequence, are time spent, never work credited.
"""

from __future__ import annotations


def _mamba(cfg: dict):
    """-> (d_inner, conv channels, in_proj width, heads, state size, conv width)."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return h * p, h * p + 2 * n, 2 * h * p + 2 * n + h, h, n, cfg["mamba_d_conv"]


def mamba_mixer_param_count(cfg: dict) -> int:
    d = cfg["hidden_size"]
    d_inner, channels, width, h, _, k = _mamba(cfg)
    return (
        d * width  # in_proj: z | xBC | dt
        + channels * k + channels  # the depthwise conv and its bias
        + 3 * h  # dt_bias, A_log, D
        + d_inner  # the gated norm
        + d_inner * d  # out_proj
    )


def attention_mixer_param_count(cfg: dict) -> int:
    d, nh, nkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // nh
    return 2 * d * nh * dh + 2 * d * nkv * dh


def expert_param_count(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def ffn_rest_param_count(cfg: dict) -> int:
    """What every layer holds beside its mixer and its experts: the shared
    MLP, the router over all the published experts, the two norms."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["shared_intermediate_size"] + d * cfg.get(
        "num_experts", cfg["num_local_experts"]
    ) + 2 * d


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def param_count(cfg: dict) -> int:
    """Parameters held: each layer's mixer, shared MLP, router, norms and the
    ``num_local_experts`` experts held; the tied embedding; the final norm."""
    mixers = {"mamba": mamba_mixer_param_count(cfg), "attention": attention_mixer_param_count(cfg)}
    per_layer = ffn_rest_param_count(cfg) + cfg["num_local_experts"] * expert_param_count(cfg)
    n = sum(mixers[kind] + per_layer for kind in layer_kinds(cfg))
    return n + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def ssm_state_bytes_per_slot(cfg: dict, conv_bytes_per_el: int = 2) -> int:
    """One slot's recurrent state (float32) and conv tail over the Mamba-2 layers."""
    _, channels, _, h, n, k = _mamba(cfg)
    per_layer = h * cfg["mamba_d_head"] * n * 4 + (k - 1) * channels * conv_bytes_per_el
    return layer_kinds(cfg).count("mamba") * per_layer


def ssm_mixer_cost(cfg: dict, tokens: float, sequences: float, decode: bool, bytes_per_el: int = 2):
    """-> (flops, bytes) of the Mamba-2 mixers of all layers for one call over
    ``tokens`` live tokens in ``sequences`` sequences: a prefill (one
    sequence of ``tokens``) or a decode step (``tokens`` == ``sequences``
    live slots, one token each).

    FLOPs per token and layer: the two projections (``D x width`` and
    ``d_inner x D`` MACs), the conv (``K`` MACs a channel), the state update
    and read-out as the recurrence states them (``S = a S + dt u (x) B`` is 2
    multiply-adds, ``S C`` one, per state element), the gate and the norm
    (a few per channel, left out). The chunked form a prefill runs does
    other arithmetic for the same result; it is credited with the
    recurrence's. Bytes: the mixer's weights once a call, in the dtype the
    matmuls read; each token's input row read and output row written; per
    sequence the recurrent state (float32) and conv tail written, and in a
    decode step read as well."""
    d = cfg["hidden_size"]
    d_inner, channels, width, h, n, k = _mamba(cfg)
    layers = layer_kinds(cfg).count("mamba")
    state_el = d_inner * n
    per_token = 2.0 * (d * width + d_inner * d) + 2.0 * k * channels + 2.0 * 3 * state_el
    flops = layers * tokens * per_token
    weight_bytes = (d * width + d_inner * d + channels * (k + 1) + 3 * h + d_inner) * bytes_per_el
    state_bytes = state_el * 4 + (k - 1) * channels * bytes_per_el
    nbytes = layers * (
        weight_bytes
        + tokens * 2 * d * bytes_per_el
        + sequences * state_bytes * (2 if decode else 1)
    )
    return flops, float(nbytes)

