"""Operations and bytes an algorithm needs, computed from shapes.

These are what the kernels' roofline shares and the model's utilization are
taken against. They count what the mathematics requires: recomputation
(rematerialized forwards, a backward kernel that rebuilds the scores twice)
is time spent, never work credited.
"""

from __future__ import annotations


def param_count(cfg: dict) -> int:
    """Parameters of the dense RMSNorm/RoPE/GQA/SwiGLU decoder ``cfg`` (HF
    ``config.json`` keys), tied or untied head."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    layers, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    dh = d // nh
    per_layer = (
        2 * d  # two norms
        + d * nh * dh  # q
        + 2 * d * nkv * dh  # k, v
        + nh * dh * d  # o
        + 3 * d * f  # gate, up, down
    )
    n = v * d + layers * per_layer + d
    if not cfg.get("tie_word_embeddings", False):
        n += d * v
    return n


def matmul_param_count(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: all
    but the embedding gather (the tied head still multiplies, so it counts
    once) and the norms."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    n = param_count(cfg) - layers * 2 * d - d  # norms
    if not cfg.get("tie_word_embeddings", False):
        n -= v * d  # the gather-only embedding; lm_head stays
    return n


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per token: 6 per matmul parameter plus causal
    attention (forward 2 matmuls of ``seq * d`` MACs per layer at half
    occupancy, backward twice that): ``6 * L * d * seq``. The arithmetic of
    ``bench.py:model_flops_per_token``, with the norms left out of N."""
    attn = 6 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq
    return 6.0 * matmul_param_count(cfg) + attn


def flash_train_cost(cfg: dict, batch: int, seq: int, bytes_per_el: int = 2):
    """-> (flops, bytes) that causal attention's forward and backward need
    for one train step over ``batch x seq`` tokens, all layers.

    FLOPs: 7 matmuls of ``seq^2 * head_dim`` MACs per head at causal half
    occupancy (forward QK^T, PV; backward the scores again, dV, dP, dQ, dK).
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv (the small per-row statistics are left out)."""
    layers, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    dh = cfg["hidden_size"] // nh
    macs_per_matmul = batch * nh * seq * seq * dh / 2.0
    flops = layers * 7 * 2.0 * macs_per_matmul
    q_el = batch * seq * nh * dh
    kv_el = batch * seq * nkv * dh
    fwd = 2 * q_el + 2 * kv_el
    bwd = 4 * q_el + 4 * kv_el
    return flops, float(layers * (fwd + bwd) * bytes_per_el)


def paged_decode_cost(cfg: dict, live_rows: float, slots: int, bytes_per_el: int = 2):
    """-> (flops, bytes) of one decode step's attention over the cache, all
    layers: ``live_rows`` K/V rows in total over the slots (the sum of the
    slots' cache lengths), one query token per slot.

    FLOPs: QK^T and PV, ``head_dim`` MACs per head and row each. Bytes: every
    live K and V row once (queries and outputs are ``slots`` rows and are
    counted too, though they are noise)."""
    layers, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    dh = cfg["hidden_size"] // nh
    flops = layers * 2 * 2.0 * live_rows * nh * dh
    kv_bytes = layers * 2 * live_rows * nkv * dh * bytes_per_el
    qo_bytes = layers * 2 * slots * nh * dh * bytes_per_el
    return flops, float(kv_bytes + qo_bytes)


def kv_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    dh = cfg["hidden_size"] // nh
    return 2 * cfg["num_hidden_layers"] * nkv * dh * bytes_per_el


def roofline_seconds(flops: float, nbytes: float, peak) -> tuple[float, str]:
    """The least time the chip could take, and which bound gives it."""
    t_flops = flops / peak.bf16_flops
    t_bytes = nbytes / peak.hbm_bytes_per_s
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
