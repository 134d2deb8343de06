"""Operations and bytes of the ZAYA1 block, computed from shapes: the parameter
counts, what the cache and the per-slot state hold, what CCA's projections and
convolutions need for a call, and what the routed FFN needs at the experts'
own width. As in ``costs.py``, what the mathematics requires is counted and
nothing else: rows of padding that a program multiplies and slots that hold no
sequence are time spent, never work credited.
"""

from __future__ import annotations

import functools

from odbench import costs_routed


def _sizes(cfg: dict):
    """-> (hidden, query heads, KV heads, a head's size, q and k side by side)."""
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    dh = cfg["head_dim"]
    return cfg["hidden_size"], nh, nkv, dh, (nh + nkv) * dh


def attention_param_count(cfg: dict) -> int:
    """q, k, the two value projections (half the KV heads each) and o."""
    d, nh, nkv, dh, z = _sizes(cfg)
    return d * z + 2 * d * (nkv // 2) * dh + nh * dh * d


def convolution_param_count(cfg: dict) -> int:
    """The depthwise taps and bias, the grouped maps (a Dh x Dh a head and
    tap) and bias."""
    _, nh, nkv, dh, z = _sizes(cfg)
    return cfg["cca_time0"] * z + z + (nh + nkv) * cfg["cca_time1"] * dh * dh + z


def router_param_count(cfg: dict) -> int:
    """Down-projection and bias, gamma, the norm, two square maps with biases,
    the map to the experts, the selection bias."""
    d, r, e = cfg["hidden_size"], cfg["router_hidden_size"], cfg["num_experts"]
    return d * r + r + r + r + 2 * (r * r + r) + r * e + e


def expert_param_count(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_param_count(cfg: dict) -> int:
    """A layer: the above, all its experts, two norms, the eight vectors of
    the two residuals and k's temperature."""
    d, _, nkv, _, _ = _sizes(cfg)
    return (
        attention_param_count(cfg) + convolution_param_count(cfg) + router_param_count(cfg)
        + cfg["num_experts"] * expert_param_count(cfg) + 2 * d + 8 * d + nkv
    )


def param_count(cfg: dict) -> int:
    """Parameters held: the layers, the tied embedding, the final norm."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * layer_param_count(cfg) + cfg["vocab_size"] * d + d


def kv_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    """One token's K and V rows over the layers (heads of ``head_dim``, which
    is not ``hidden_size / num_attention_heads`` here)."""
    _, _, nkv, dh, _ = _sizes(cfg)
    return 2 * cfg["num_hidden_layers"] * nkv * dh * bytes_per_el


def state_values(cfg: dict) -> int:
    """Values a layer keeps of a slot's last token: q and k before the
    convolutions, the same between the two, the values the next token takes."""
    _, _, nkv, dh, z = _sizes(cfg)
    return 2 * z + (nkv // 2) * dh


def state_bytes_per_slot(cfg: dict, bytes_per_el: int = 2) -> int:
    return cfg["num_hidden_layers"] * state_values(cfg) * bytes_per_el


def cca_mix_cost(cfg: dict, tokens: float, sequences: float, decode: bool, bytes_per_el: int = 2):
    """-> (flops, bytes) of CCA's projections and convolutions of all layers
    (what runs under the program's ``odtp_cca`` scope: everything before the
    attention proper) for one call over ``tokens`` live tokens in
    ``sequences`` sequences: a prefill (one sequence of ``tokens``) or a
    decode step (``tokens`` == ``sequences`` live slots, one token each).

    FLOPs per token and layer: the projections of q, k and the two halves of
    v (``D`` MACs a value), the depthwise convolution (a MAC a tap and value
    of z), the grouped one (``Dh`` MACs a tap and value); the means, the
    normalisation, the rotation and the input norm are a few operations a
    value and are left out. Bytes: the projections' and convolutions' weights
    once a call; each token's input row read and its q, k and v written; per
    sequence the state written, and in a decode step read as well."""
    d, nh, nkv, dh, z = _sizes(cfg)
    layers = cfg["num_hidden_layers"]
    width = z + nkv * dh  # q, k and v side by side
    per_token = 2.0 * (d * width + cfg["cca_time0"] * z + cfg["cca_time1"] * dh * z)
    flops = layers * tokens * per_token
    weight_bytes = (d * width + convolution_param_count(cfg)) * bytes_per_el
    nbytes = layers * (
        weight_bytes
        + tokens * (d + width) * bytes_per_el
        + sequences * state_values(cfg) * bytes_per_el * (2 if decode else 1)
    )
    return flops, float(nbytes)


# the routed FFN at the experts' own width, under this configuration's key
routed_ffn_cost = functools.partial(costs_routed.routed_ffn_cost, width_key="moe_intermediate_size")
