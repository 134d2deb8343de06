"""Operations and bytes of Laguna-S-2.1's block, computed from shapes: the
parameter counts, what a slot's rings by kind hold, and what the sliding and
the full layers' attention need for the rows the program's spans count. As in
``costs.py``, what the equations require is counted and nothing else: a form
that reads a whole ring under a mask where the equations read a window's rows
spends time and is credited the window's rows, so a share can read low and
none can read over 100%.
"""

from __future__ import annotations


def layer_kinds(cfg: dict, layers: int | None = None) -> list:
    """Each layer's kind from the published keys: "dense" (a full layer over a
    dense SwiGLU), "full", "sliding"."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    ffns = cfg.get("mlp_layer_types") or ["sparse"] * n
    return ["sliding" if name == "sliding_attention" else "dense" if ffn == "dense" else "full"
            for name, ffn in zip(cfg["layer_types"][:n], ffns[:n])]


def heads(cfg: dict, kind: str) -> int:
    """A kind's query heads, from ``num_attention_heads_per_layer``."""
    per_layer = cfg["num_attention_heads_per_layer"]
    kinds = layer_kinds(cfg, len(per_layer))
    return next(h for h, k in zip(per_layer, kinds) if (k == "sliding") == (kind == "sliding"))


def held_experts(cfg: dict) -> int:
    return cfg.get("num_local_experts") or cfg["num_experts"]


def attention_param_count(cfg: dict, kind: str) -> int:
    """q, k, v, o and the gate of one kind."""
    d, dh, kv, h = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"], heads(cfg, kind)
    return 2 * d * h * dh + 2 * d * kv * dh + d * h


def layer_param_count(cfg: dict, kind: str, experts: int | None = None) -> int:
    """One layer of ``kind`` with ``experts`` routed experts (None: those held)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts = held_experts(cfg) if experts is None else experts
    attn = attention_param_count(cfg, "sliding" if kind == "sliding" else "full") + 2 * d
    if kind == "dense":
        return attn + 3 * d * cfg["intermediate_size"]
    shared = 3 * d * cfg["shared_expert_intermediate_size"]
    return attn + d * cfg["num_experts"] + experts * 3 * d * f + shared


def param_count(cfg: dict) -> int:
    """Parameters held: the layers with the experts this chip holds, the
    embedding, the untied head, the final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return sum(layer_param_count(cfg, k) for k in layer_kinds(cfg)) + 2 * v * d + d


def published_param_count(cfg: dict) -> int:
    """The uncut model by the file's ``published`` keys."""
    pub = cfg.get("published", {})
    d, v = cfg["hidden_size"], pub.get("vocab_size", cfg["vocab_size"])
    layers = pub.get("num_hidden_layers", cfg["num_hidden_layers"])
    experts = pub.get("num_local_experts", cfg["num_experts"])
    return sum(layer_param_count(cfg, k, experts) for k in layer_kinds(cfg, layers)) + 2 * v * d + d


def row_bytes(cfg: dict, bytes_per_el: int = 2) -> int:
    """A token's K and V rows in one layer's rings."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_el


def ring_bytes(cfg: dict, slots: int, rows: int, sliding_rows: int, bytes_per_el: int = 2) -> dict:
    """What the rings by kind hold at ``slots`` slots: ``rows`` rows a full
    layer, ``sliding_rows`` rows a sliding layer, K and V."""
    kinds = layer_kinds(cfg)
    full, sliding = len(kinds) - kinds.count("sliding"), kinds.count("sliding")
    out = {"full": full * slots * rows * row_bytes(cfg, bytes_per_el),
           "sliding": sliding * slots * sliding_rows * row_bytes(cfg, bytes_per_el)}
    out["all"] = sum(out.values())
    return out


def _attn_cost(cfg: dict, kind: str, pairs: float, rows_read: float, bytes_per_el: int):
    # a (query, row) pair: each head's score over the row's key and its
    # weighted sum over the row's value, 2 x 2 x head_dim a head
    return (4.0 * heads(cfg, kind) * cfg["head_dim"] * pairs,
            float(rows_read) * row_bytes(cfg, bytes_per_el))


def window_attn_cost(cfg: dict, pairs: float, rows_read: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the sliding layers' attention: ``pairs`` (query,
    row in its window) pairs and ``rows_read`` distinct rows of the windows,
    each summed over the sliding layers (a decode step: min(window, lens + 1)
    rows a slot, as many pairs; a chunk: the band's rows once and its pairs),
    by the sliding layers' heads."""
    return _attn_cost(cfg, "sliding", pairs, rows_read, bytes_per_el)


def full_attn_cost(cfg: dict, pairs: float, rows_read: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the full layers' attention: ``pairs`` (query, row
    up to its own) pairs under the causal mask and ``rows_read`` distinct live
    rows, each summed over the full layers, by the full layers' heads."""
    return _attn_cost(cfg, "full", pairs, rows_read, bytes_per_el)
