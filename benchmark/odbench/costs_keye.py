"""Operations and bytes of Keye-VL-2.0's block, computed from shapes: the
parameter counts, what a slot's three rings hold, and what the indexer and the
attention under its selection need for the rows the program's spans count. As
in ``costs.py``, what the equations require is counted and nothing else: an
implementation that reads every live row under a mask where the equations read
the chosen rows spends time, and is credited the chosen rows' work.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    """-> (hidden, query heads, KV heads, a head's size, index heads, an index head's size)."""
    sa = cfg["sa_config"]
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], sa["indexer_num_heads"], sa["indexer_head_dim"])


def held_experts(cfg: dict) -> int:
    return cfg.get("num_local_experts") or cfg["num_experts"]


def layer_param_count(cfg: dict, experts: int | None = None) -> dict:
    """One layer by part: attention (q, k, v, o and the two per-head norm
    weights), the indexer (queries, key, its LayerNorm's weight and bias, head
    weights), the router, two norms, ``experts`` experts (None: those held)."""
    d, nh, nkv, dh, hi, di = _sizes(cfg)
    f = cfg["moe_intermediate_size"]
    experts = held_experts(cfg) if experts is None else experts
    out = {
        "attention": 2 * d * nh * dh + 2 * d * nkv * dh + 2 * dh,
        "indexer": d * hi * di + d * di + 2 * di + d * hi,
        "router": d * cfg["num_experts"],
        "norms": 2 * d,
        "one_expert": 3 * d * f,
    }
    out["outside_its_experts"] = sum(out[k] for k in ("attention", "indexer", "router", "norms"))
    out["all"] = out["outside_its_experts"] + experts * out["one_expert"]
    return out


def param_count(cfg: dict) -> int:
    """Parameters held: the layers with the experts this chip holds, the
    embedding, the untied head, the final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer_param_count(cfg)["all"] + 2 * v * d + d


def published_param_count(cfg: dict) -> int:
    """The uncut model by the file's ``published`` keys."""
    whole = {**cfg, **cfg.get("published", {})}
    d, v = whole["hidden_size"], whole["vocab_size"]
    layer = layer_param_count(whole, whole["num_experts"])["all"]
    return whole["num_hidden_layers"] * layer + 2 * v * d + d


def kv_row_bytes(cfg: dict, bytes_per_el: int = 2) -> int:
    """A token's K and V row of one layer."""
    _, _, nkv, dh, _, _ = _sizes(cfg)
    return 2 * nkv * dh * bytes_per_el


def index_row_bytes(cfg: dict, bytes_per_el: int = 2) -> int:
    """A token's index key of one layer."""
    return cfg["sa_config"]["indexer_head_dim"] * bytes_per_el


def ring_bytes(cfg: dict, slots: int, rows: int, bytes_per_el: int = 2) -> dict:
    """What the three rings hold at ``slots`` slots of ``rows`` rows."""
    tokens = cfg["num_hidden_layers"] * slots * rows
    out = {"kv": tokens * kv_row_bytes(cfg, bytes_per_el),
           "index": tokens * index_row_bytes(cfg, bytes_per_el)}
    out["all"] = out["kv"] + out["index"]
    return out


def index_cost(cfg: dict, rows_scored: float, rows_read: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the indexer's scoring for ``rows_scored`` (query,
    row) pairs over ``rows_read`` distinct index rows, each summed over
    layers, as the program's spans carry them (a decode step's queries are one
    a slot, so the two are equal; a chunk's 512 queries share the slot's rows).

    FLOPs: each pair is ``Hi`` dot products of ``Di`` values and a weighted
    sum over the heads: 2 Hi Di + 2 Hi. Bytes: each distinct index row once.
    The selection itself (an order statistic of the scores) is counted no
    work: whatever finds it is time spent."""
    _, _, _, _, hi, di = _sizes(cfg)
    return (2.0 * hi * di + 2.0 * hi) * rows_scored, float(rows_read) * index_row_bytes(cfg, bytes_per_el)


def sparse_attn_cost(cfg: dict, rows_selected: float, rows_read: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the attention over the **chosen** rows:
    ``rows_selected`` (query, chosen row) pairs, summed over layers, out of
    ``rows_read`` distinct live rows (a chunk's 512 queries choose among the
    same rows, so its distinct chosen rows are at most those).

    FLOPs: q . k and p v, a head's size of MACs per query head and pair each
    (4 x head_dim x heads a pair). Bytes: the chosen rows' K and V once:
    min(pairs, distinct rows) rows."""
    _, nh, _, dh, _, _ = _sizes(cfg)
    rows = min(float(rows_selected), float(rows_read))
    return 4.0 * dh * nh * rows_selected, rows * kv_row_bytes(cfg, bytes_per_el)
