"""Operations and bytes of dots3-note-prev's block, computed from shapes: the
parameter counts, what a slot's three rings hold, and what the indexer, the
latent attention under its selection and the latent attention under a window
need for the rows the program's spans count. As in ``costs.py``, what the
equations require is counted and nothing else: an implementation that reads
every live row under a mask where the equations read the chosen rows spends
time, and is credited the chosen rows' work.
"""

from __future__ import annotations


def geometry(cfg: dict, kind: str) -> dict:
    """One kind's latent attention ("full" or "sliding"), under plain names."""
    pre = "swa_" if kind == "sliding" else ""
    g = {name: cfg[f"{pre}{key}"] for name, key in (
        ("heads", "num_attention_heads"), ("q_rank", "q_lora_rank"), ("kv_rank", "kv_lora_rank"),
        ("nope", "qk_nope_head_dim"), ("rope", "qk_rope_head_dim"), ("v", "v_head_dim"),
    )}
    g["row"] = g["kv_rank"] + g["rope"]  # a token's cached latent row, in values
    g["gated"] = cfg.get(f"{pre}attention_gate_type", "none") == "headwise"
    return g


def layer_kinds(cfg: dict, layers: int | None = None) -> list:
    """Each layer's kind from the published keys: "dense" (a full layer over
    a dense SwiGLU), "full", "sliding"."""
    dense = cfg.get("first_k_dense_replace", 0)
    names = cfg["layer_types"][: cfg["num_hidden_layers"] if layers is None else layers]
    return ["sliding" if n == "sliding_attention" else "dense" if i < dense else "full"
            for i, n in enumerate(names)]


def held_experts(cfg: dict) -> int:
    return cfg.get("num_local_experts") or cfg["n_routed_experts"]


def attention_param_count(cfg: dict, kind: str) -> int:
    """q_a, q_b, kv_a, kv_b, o, the gate and the two latent norms of one kind."""
    d, g = cfg["hidden_size"], geometry(cfg, kind)
    h = g["heads"]
    return (d * g["q_rank"] + g["q_rank"] * h * (g["nope"] + g["rope"]) + d * g["row"]
            + g["kv_rank"] * h * (g["nope"] + g["v"]) + h * g["v"] * d
            + (d * h if g["gated"] else 0) + g["q_rank"] + g["kv_rank"])


def indexer_param_count(cfg: dict) -> int:
    """Index queries from the query's latent, the key and its LayerNorm, the head weights."""
    d, hi, di = cfg["hidden_size"], cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * hi * di + d * di + d * hi + 2 * di


def layer_param_count(cfg: dict, kind: str, experts: int | None = None) -> int:
    """One layer of ``kind`` with ``experts`` routed experts (None: those held)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts = held_experts(cfg) if experts is None else experts
    attn = attention_param_count(cfg, "sliding" if kind == "sliding" else "full")
    if kind != "sliding":
        attn += indexer_param_count(cfg)
    if kind == "dense":
        return attn + 3 * d * cfg["intermediate_size"] + 2 * d
    router = d * cfg["n_routed_experts"] + cfg["n_routed_experts"]
    return attn + router + (experts + cfg["n_shared_experts"]) * 3 * d * f + 2 * d


def param_count(cfg: dict) -> int:
    """Parameters held: the layers with the experts this chip holds, the
    embedding, the untied head, the final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return sum(layer_param_count(cfg, k) for k in layer_kinds(cfg)) + 2 * v * d + d


def published_param_count(cfg: dict) -> int:
    """The uncut language model by the file's ``published`` keys."""
    pub = cfg.get("published", {})
    d, v = cfg["hidden_size"], pub.get("vocab_size", cfg["vocab_size"])
    layers = pub.get("num_hidden_layers", cfg["num_hidden_layers"])
    experts = pub.get("num_local_experts", cfg["n_routed_experts"])
    return sum(layer_param_count(cfg, k, experts) for k in layer_kinds(cfg, layers)) + 2 * v * d + d


def index_row_bytes(cfg: dict, bytes_per_el: int = 2) -> int:
    return cfg["index_head_dim"] * bytes_per_el


def latent_row_bytes(cfg: dict, kind: str, bytes_per_el: int = 2) -> int:
    return geometry(cfg, kind)["row"] * bytes_per_el


def ring_bytes(cfg: dict, slots: int, rows: int, sliding_rows: int, bytes_per_el: int = 2) -> dict:
    """What the three rings hold at ``slots`` slots: ``rows`` rows a full
    layer (latent row and index key), ``sliding_rows`` rows a sliding layer."""
    kinds = layer_kinds(cfg)
    full, sliding = len(kinds) - kinds.count("sliding"), kinds.count("sliding")
    out = {
        "full": full * slots * rows * latent_row_bytes(cfg, "full", bytes_per_el),
        "index": full * slots * rows * index_row_bytes(cfg, bytes_per_el),
        "sliding": sliding * slots * sliding_rows * latent_row_bytes(cfg, "sliding", bytes_per_el),
    }
    out["all"] = sum(out.values())
    return out


def index_cost(cfg: dict, rows_scored: float, rows_read: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the indexer's scoring for ``rows_scored`` (query,
    row) pairs over ``rows_read`` distinct index rows, each summed over the
    full layers, as the program's spans carry them. FLOPs: each pair is ``Hi``
    dot products of ``Di`` values and a weighted sum over the heads, 2 Hi Di +
    2 Hi. Bytes: each distinct index row once. The selection itself (an order
    statistic of the scores) is counted no work."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return (2.0 * hi * di + 2.0 * hi) * rows_scored, float(rows_read) * index_row_bytes(cfg, bytes_per_el)


def _absorbed_pair_flops(cfg: dict, kind: str) -> float:
    """A (query, row) pair of latent attention in the absorbed form: each head's
    score over the row's values and its weighted sum over the latent's."""
    g = geometry(cfg, kind)
    return 2.0 * g["heads"] * (g["row"] + g["kv_rank"])


def sparse_mla_cost(cfg: dict, rows_selected: float, rows_read: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the full layers' attention over the **chosen**
    rows: ``rows_selected`` (query, chosen row) pairs, summed over layers, out
    of ``rows_read`` distinct live rows. FLOPs: the absorbed form's, which
    rebuilds no key (the rebuilt form costs more wherever fewer than some 120
    queries share a row, and the chosen rows of a chunk's queries are shared by
    few). Bytes: the chosen rows' latent rows once: min(pairs, distinct rows)."""
    rows = min(float(rows_selected), float(rows_read))
    return _absorbed_pair_flops(cfg, "full") * rows_selected, rows * latent_row_bytes(cfg, "full", bytes_per_el)


def window_mla_cost(cfg: dict, pairs: float, rows_read: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the sliding layers' attention: ``pairs`` (query,
    row in its window) pairs and ``rows_read`` distinct rows of the windows,
    summed over layers: the absorbed form's FLOPs, each row's bytes once."""
    return _absorbed_pair_flops(cfg, "sliding") * pairs, float(rows_read) * latent_row_bytes(cfg, "sliding", bytes_per_el)
