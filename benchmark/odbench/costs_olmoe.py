"""Operations and bytes of the OLMoE block, computed from shapes: what
``costs.py`` counts for the dense block, for a configuration whose FFN is
``num_experts`` routed experts of width ``intermediate_size``, with
``num_experts_per_tok`` per token, and whose q and k pass a norm of their
own. As there, what the mathematics requires is counted and nothing else:
rows of padding that a kernel multiplies are time spent, never work credited.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    return d, f, nh, nkv, d // nh


def layer_param_count(cfg: dict) -> int:
    d, f, nh, nkv, dh = _sizes(cfg)
    return (
        2 * d + nh * dh + nkv * dh  # input, post-attention, q and k norms
        + 2 * d * nh * dh  # q, o
        + 2 * d * nkv * dh  # k, v
        + d * cfg["num_experts"]  # router
        + cfg["num_experts"] * 3 * d * f  # every expert's gate, up, down
    )


def param_count(cfg: dict) -> int:
    """Parameters held: every expert of every layer, embedding, final norm
    and the head (once more when untied)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    n = v * d + cfg["num_hidden_layers"] * layer_param_count(cfg) + d
    if not cfg.get("tie_word_embeddings", False):
        n += d * v
    return n


def active_matmul_param_count(cfg: dict) -> int:
    """Parameters that multiply one token: attention, the router, the
    token's ``num_experts_per_tok`` experts, and the head (the embedding is
    a gather and the norms are not matmuls)."""
    d, f, nh, nkv, dh = _sizes(cfg)
    per_layer = (
        2 * d * nh * dh + 2 * d * nkv * dh + d * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * 3 * d * f
    )
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def routed_ffn_cost(cfg: dict, pairs: float, experts_hit: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the routed FFN for ``pairs`` token-expert pairs
    that reached ``experts_hit`` experts (both summed over the layers of a
    call, as the program counts them).

    FLOPs: the gate, up and down projections of each pair, ``d x f`` MACs
    each. Bytes: the three matrices of every expert that was hit, once, in
    the dtype the kernel reads, plus each pair's input row read and output
    row written (the ``f``-wide intermediate between the projections is a
    choice of the implementation and is left out)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    flops = 2.0 * 3 * pairs * d * f
    weight_bytes = experts_hit * 3 * d * f * bytes_per_el
    activation_bytes = pairs * 2 * d * bytes_per_el
    return flops, float(weight_bytes + activation_bytes)
