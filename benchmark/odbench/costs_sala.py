"""Operations and bytes of MiniCPM-SALA's block, computed from shapes: the
parameter counts, what a slot's rings and state hold, and what the lightning
mix, the selection by blocks and the attention under it need for the tokens,
keys and rows the program's spans count. As in ``costs.py``, what the equations
require is counted and nothing else: a form that reads a whole ring under a
mask where the equations read the chosen blocks' rows spends time and is
credited the chosen rows, so a share can read low and none can read over 100%.
The projections around the lightning recurrence and around the attention (q,
k, v, the gates, the output maps) are dense matmuls and no part of any of the
three: the scopes the shares are read under hold the recurrence, the
selection and the attention alone.
"""

from __future__ import annotations


def layer_kinds(cfg: dict, layers: int | None = None) -> list:
    """Each layer's kind from ``mixer_types``: "lightning" or "sparse"."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    return ["lightning" if m == "lightning-attn" else "sparse" for m in cfg["mixer_types"][:n]]


def counts(cfg: dict, layers: int | None = None) -> tuple:
    """-> (lightning layers, sparse layers) among the leading ``layers``."""
    kinds = layer_kinds(cfg, layers)
    return kinds.count("lightning"), kinds.count("sparse")


def layer_param_count(cfg: dict, kind: str) -> int:
    """One layer of ``kind``: its attention, its SwiGLU, its two block norms."""
    d, dh, h = cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    if kind == "lightning":  # q, k, v, o, gate; two head norms; the output norm
        attn = 5 * d * h * dh + 2 * dh + h * dh
    else:  # q, o, gate; k, v; two head norms
        attn = 3 * d * h * dh + 2 * d * kv * dh + 2 * dh
    return attn + 3 * d * cfg["intermediate_size"] + 2 * d


def param_count(cfg: dict, layers: int | None = None) -> int:
    """Parameters held: the leading ``layers`` (None: those run), the
    embedding, the untied head, the final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return sum(layer_param_count(cfg, k) for k in layer_kinds(cfg, layers)) + 2 * v * d + d


def published_param_count(cfg: dict) -> int:
    """The uncut model: every layer ``mixer_types`` names."""
    return param_count(cfg, len(cfg["mixer_types"]))


def slot_bytes(cfg: dict, rows: int, bytes_per_el: int = 2) -> dict:
    """What one slot of ``rows`` rows holds: the sparse layers' K and V, their
    pooled keys (a row every ``kernel_stride``), the lightning layers' states
    (float32)."""
    lightning, sparse = counts(cfg)
    dh, kv, h = cfg["head_dim"], cfg["num_key_value_heads"], cfg["num_attention_heads"]
    stride = cfg["sparse_config"]["kernel_stride"]
    out = {
        "kv": sparse * rows * 2 * kv * dh * bytes_per_el,
        "pooled": sparse * (rows // stride) * kv * dh * bytes_per_el,
        "state": lightning * h * dh * dh * 4,
    }
    out["all"] = sum(out.values())
    return out


def lightning_cost(cfg: dict, step_tokens: float, chunk_tokens: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the lightning recurrence for ``step_tokens`` tokens
    of decode steps and ``chunk_tokens`` tokens of ONE prefill chunk, each
    summed over the lightning layers. A step's token reads and writes its
    slot's state [H, D, D] float32 once and does 4 H D D operations (decay and
    k^T v into it, q S out of it). A chunk's C tokens a layer do the pairs
    under the causal triangle (q . k and the weighted v: 4 H D a pair), q S
    and k^T v (4 H D D a token), and move q, k, v, o once and the state there
    and back."""
    lightning, _ = counts(cfg)
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    state = h * dh * dh
    flops = 4.0 * state * (step_tokens + chunk_tokens)
    nbytes = 2.0 * 4 * state * step_tokens
    if chunk_tokens:
        c = chunk_tokens / max(lightning, 1)  # the chunk's tokens in one layer
        flops += lightning * 4.0 * h * dh * c * (c + 1) / 2
        nbytes += chunk_tokens * 4 * h * dh * bytes_per_el + lightning * 2.0 * 4 * state
    return flops, nbytes


def block_select_cost(cfg: dict, pairs: float, keys: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the selection: ``pairs`` (query, pooled key it
    sees) pairs of a KV head and ``keys`` distinct pooled keys read, each
    summed over KV heads and sparse layers: 2 x (heads a group) x head_dim
    operations a pair, a key's head_dim values once."""
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return 2.0 * rep * cfg["head_dim"] * pairs, float(keys) * cfg["head_dim"] * bytes_per_el


def block_attn_cost(cfg: dict, pairs: float, rows: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the attention under the selection: ``pairs``
    (query, chosen row up to its own) pairs of a KV head and ``rows`` distinct
    chosen rows read, each summed over KV heads and sparse layers: the CHOSEN
    rows alone, a K and a V row of head_dim values once, 4 x (heads a group) x
    head_dim operations a pair."""
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return 4.0 * rep * cfg["head_dim"] * pairs, float(rows) * 2 * cfg["head_dim"] * bytes_per_el
