"""Operations and bytes of Solar-Open2's block, computed from shapes: the
parameter counts, what a slot's ring, states and tails hold, and what the kda
mixer's recurrence needs for the tokens the program's spans count. As in
``costs.py``, what the equations require is counted and nothing else: a form
that passes over the state three times where the equations read and write it
once spends time and is credited one pass, so a share can read low and none
can read over 100%. The projections, the convolution, the norms, the decay and
the gate around the recurrence are no part of it: the scope the share is read
under (``odtp_kda``) holds the recurrence alone.
"""

from __future__ import annotations

# the tokens of a block of the chunked form, the family's (arXiv 2510.26692's
# kernel): fixed here, never read from the program under test
BLOCK = 64


def layer_kinds(cfg: dict, layers: int | None = None) -> list:
    """Each layer's kind from ``gqa_layers``: "gqa" or "kda"."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    gqa = set(cfg["gqa_layers"])
    return ["gqa" if i in gqa else "kda" for i in range(n)]


def mixer_param_count(cfg: dict, kind: str) -> int:
    d, dh, h = cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"]
    if kind == "gqa":  # q, o, gate; k, v
        return 3 * d * h * dh + 2 * d * cfg["num_key_value_heads"] * dh
    lin = cfg["linear_attn_config"]
    hd, taps = lin["num_heads"] * lin["head_dim"], lin["short_conv_kernel_size"]
    low_rank = d * lin["head_dim"] + lin["head_dim"] * hd  # the decay's pair, the gate's
    # q, k, v, o; the decay's pair and dt_bias; the gate's pair and its bias; beta;
    # three convolutions; A_log; the output norm
    return (4 * d * hd + 2 * low_rank + 2 * hd + d * lin["num_heads"] + 3 * taps * hd
            + lin["num_heads"] + lin["head_dim"])


def expert_param_count(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_param_count(cfg: dict, kind: str, experts: int) -> int:
    """One layer of ``kind`` holding ``experts`` routed experts: its mixer, the
    router over all the deployment's experts with its bias, the held experts,
    the shared ones, its two block norms."""
    d, width = cfg["hidden_size"], cfg.get("num_experts", cfg["n_routed_experts"])
    return (mixer_param_count(cfg, kind) + d * width + width
            + (experts + cfg.get("n_shared_experts", 0)) * expert_param_count(cfg) + 2 * d)


def param_count(cfg: dict, layers=None, experts=None, vocab=None) -> int:
    """Parameters held: the leading ``layers`` (None: those run) with
    ``experts`` routed experts each (None: those held), ``vocab`` rows (None:
    those held) of the embedding and of the untied head, the final norm."""
    experts = cfg["n_routed_experts"] if experts is None else experts
    vocab = cfg["vocab_size"] if vocab is None else vocab
    d = cfg["hidden_size"]
    return sum(layer_param_count(cfg, k, experts) for k in layer_kinds(cfg, layers)) + 2 * vocab * d + d


def published_param_count(cfg: dict) -> int:
    """The uncut model, from the file's ``published`` block."""
    p = cfg["published"]
    return param_count(cfg, p["num_hidden_layers"], p["n_routed_experts"], p["vocab_size"])


def slot_bytes(cfg: dict, rows: int, bytes_per_el: int = 2) -> dict:
    """What one slot of ``rows`` rows holds: the gqa layers' K and V, the kda
    layers' states (float32) and their convolutions' tails."""
    kinds = layer_kinds(cfg)
    lin = cfg["linear_attn_config"]
    h, dh, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    out = {
        "kv": kinds.count("gqa") * rows * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_el,
        "state": kinds.count("kda") * h * dh * dh * 4,
        "tail": kinds.count("kda") * (taps - 1) * 3 * h * dh * bytes_per_el,
    }
    out["all"] = sum(out.values())
    return out


def kda_cost(cfg: dict, step_tokens: float, chunk_tokens: float, bytes_per_el: int = 2):
    """-> (flops, bytes) of the kda recurrence for ``step_tokens`` tokens of
    decode steps and ``chunk_tokens`` tokens of ONE prefill chunk, each summed
    over the kda layers. A step's token reads and writes its slot's state [H,
    D, D] float32 once and does 8 H D D operations (the decay, ``S'^T k``, the
    rank-one update, ``S^T q``). A chunk's C tokens a layer, in blocks of
    :data:`BLOCK`, do the pairs under a block's triangle four times (for ``A``,
    for the query scores, for the solve and for the weighted sum: 8 H D a
    pair), three products with the state a token (6 H D D), and move q, k, v,
    g and o once and the state there and back once."""
    kda = layer_kinds(cfg).count("kda")
    lin = cfg["linear_attn_config"]
    h, dh = lin["num_heads"], lin["head_dim"]
    state = h * dh * dh
    flops = 8.0 * state * step_tokens
    nbytes = 2.0 * 4 * state * step_tokens
    if chunk_tokens:
        c = chunk_tokens / max(kda, 1)  # the chunk's tokens in one layer
        blocks, rest = divmod(c, BLOCK)
        pairs = blocks * BLOCK * (BLOCK + 1) / 2 + rest * (rest + 1) / 2
        flops += kda * 8.0 * h * dh * pairs + 6.0 * state * chunk_tokens
        # q, k, v, o in the compute dtype, g in float32; the state there and back
        nbytes += chunk_tokens * h * dh * (4 * bytes_per_el + 4) + kda * 2.0 * 4 * state
    return flops, nbytes
