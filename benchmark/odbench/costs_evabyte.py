"""Operations and bytes of the EvaByte block, computed from shapes: the
parameter counts, what a slot's two rings and the pooling under way hold, and
what a decode step's attention needs over the rows it reads. As in
``costs.py``, what the mathematics requires is counted and nothing else: the
rows of a tile that lie beyond a slot's position, and pooled rows beyond the
windows a slot has ended, are time spent, never work credited.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    """-> (hidden, query heads, KV heads, a head's size)."""
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    return cfg["hidden_size"], nh, nkv, cfg["hidden_size"] // nh


def layer_param_count(cfg: dict) -> int:
    """q, k, v, o; the SwiGLU's three; two norms; adaptive_phi and adaptive_mu_k
    (a vector a KV head each)."""
    d, nh, nkv, dh = _sizes(cfg)
    f = cfg["intermediate_size"]
    return 2 * d * nh * dh + 2 * d * nkv * dh + 3 * d * f + 2 * d + 2 * nkv * dh


def param_count(cfg: dict) -> int:
    """Parameters held: the layers, the embedding, the head of
    ``num_pred_heads`` vocabularies, the final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (
        cfg["num_hidden_layers"] * layer_param_count(cfg)
        + v * d + d * v * cfg.get("num_pred_heads", 1) + d
    )


def row_bytes(cfg: dict, bytes_per_el: int = 2) -> int:
    """A K and a V row of one layer: a token's in the window's ring, a chunk's
    in the pooled ring."""
    _, _, nkv, dh = _sizes(cfg)
    return 2 * nkv * dh * bytes_per_el


def pooled_rows(cfg: dict, max_context: int) -> int:
    """Rows of a slot's pooled ring: a row per chunk of every window that
    ``max_context`` positions touch."""
    window = cfg["window_size"]
    return -(-max_context // window) * (window // cfg["chunk_size"])


def cache_bytes_per_slot(cfg: dict, max_context: int, bytes_per_el: int = 2) -> dict:
    """What a slot holds, all layers: the window's ring, the pooled ring, and
    the float32 pooling under way (2 x head + 2 values a KV head)."""
    _, _, nkv, dh = _sizes(cfg)
    layers, row = cfg["num_hidden_layers"], row_bytes(cfg, bytes_per_el)
    out = {
        "window_ring": layers * cfg["window_size"] * row,
        "pooled_ring": layers * pooled_rows(cfg, max_context) * row,
        "pooling_stats": layers * nkv * (2 * dh + 2) * 4,
    }
    out["all"] = sum(out.values())
    return out


def eva_decode_cost(cfg: dict, local_rows: float, pooled_rows_read: float,
                    bytes_per_el: int = 2):
    """-> (flops, bytes) of one decode step's attention over both rings:
    ``local_rows`` rows of the window's ring and ``pooled_rows_read`` of the
    pooled ring, each summed over slots and layers (as the program's
    ``serve_decode`` spans carry them: a slot at position p reads p % window +
    1 and p // window * (window / chunk) a layer).

    Bytes: each row read, K and V, once. FLOPs: q . k and p v, a head's size
    of MACs per query head and row each (4 x head_dim FLOP a row and head)."""
    _, nh, _, dh = _sizes(cfg)
    rows = float(local_rows) + float(pooled_rows_read)
    return 4.0 * dh * nh * rows, rows * row_bytes(cfg, bytes_per_el)
