"""What a slot's past is, beyond the rows of one ``(k, v)`` ring, and which
feature can take it: the one table every module that handles a slot's past
asks (``LlamaConfig.traits`` says which traits a configuration has).

A feature's entry for a trait is the reason it cannot take a past of that
kind; a trait that is absent under a feature is one the feature handles.
Within a feature the traits stand in the order they are named in: of a
configuration with several, the first is the one a refusal names.
"""
from __future__ import annotations

# a ``LlamaConfig`` property each, in the order ``LlamaConfig.traits`` keeps
TRAITS = ("hybrid", "cca", "eva", "sparse", "sliding", "latent", "linear", "blocks", "kda")

# features that copy, cut, page out or restore a slot's past as the rows of
# one (k, v) ring from row 0
_ROWS = {
    "hybrid": (
        "a configuration with Mamba-2 layers ({cfg.num_mamba_layers} of "
        "{cfg.num_hidden_layers}): it treats a slot's past as cache rows, and a "
        "recurrent state cannot be cut at a row"
    ),
    "cca": (
        "a configuration with CCA (cca_time0 {cfg.cca_time0}): it treats a slot's "
        "past as cache rows, and CCA's projections read the token before through a "
        "per-slot state beside the ring, which is not rows"
    ),
    "eva": (
        "a configuration with EVA attention (attention_class 'eva', window_size "
        "{cfg.window_size}, chunk_size {cfg.chunk_size}): it handles a slot's past as "
        "the rows of one ring, and EVA keeps a window of rows that restarts beside a "
        "ring of pooled chunks and the pooling of the chunk under way, which it "
        "neither copies nor could un-pool"
    ),
    "sparse": (
        "a configuration with learned sparse attention (index_topk {cfg.index_topk}, "
        "{cfg.index_n_heads} index heads of {cfg.index_head_dim}): a token keeps an "
        "index key beside its K and V, in a ring of its own that this neither copies "
        "nor snapshots, and a query's attention reads the rows its indexer chose, "
        "which this does not compute"
    ),
    "sliding": (
        "a configuration with sliding layers: prefix reuse and the host tier copy, "
        "cut and restore a slot's past as the rows of one ring from row 0, and a "
        "sliding layer's ring wraps and keeps a window's rows"
    ),
    "latent": (
        "a configuration with latent attention (kv_lora_rank {cfg.kv_lora_rank}): it "
        "handles a slot's past as (k, v) rows of one head size, and the latent ring "
        "holds one row of {cfg.latent_row_dim} values a token, from which k and v are "
        "not rebuilt"
    ),
    "linear": (
        "a configuration with lightning linear-attention layers "
        "({cfg.num_lightning_layers} of {cfg.num_hidden_layers}): it treats a slot's past "
        "as cache rows, and a decaying state is not rows and cannot be cut at one"
    ),
    "kda": (
        "a configuration with kda linear-attention layers ({cfg.num_kda_layers} of "
        "{cfg.num_hidden_layers}): it treats a slot's past as cache rows, and a delta-rule "
        "state with its convolution's tail is not rows, cannot be cut at one and is kept at "
        "no position but the slot's last"
    ),
    "blocks": (
        "a configuration with attention under a selection by blocks (sparse_config, "
        "{cfg.block_sizes.topk} blocks of {cfg.block_sizes.block_size} rows): a KV head keeps "
        "a ring of pooled keys beside K and V that this neither copies nor rebuilds, and a "
        "query's attention reads the blocks those keys' scores chose, which this does not "
        "compute"
    ),
}

# feature -> (what a refusal calls it where the caller gives no name of its
# own, {trait: why not}). The scheduler's ``prefix_cache`` is prefix reuse and
# its ``kv_tier`` the two page features, under those names
REFUSALS = {
    "prefix_reuse": ("prefix reuse (a continued prefill over copied cache rows)", _ROWS),
    "page_out": ("the host tier's page-out", _ROWS),
    "page_in": ("the host tier's page-in", _ROWS),
    # over an index ring, latent rows and a ring that wraps it goes in whole
    # chunks from row 0 (``chunk_prefill_forward``); a lightning layer's chunk
    # enters with the slot's state and leaves the next one's, and a selection
    # by blocks pools the windows a chunk closes from the ring's own rows:
    # ``linear`` and ``blocks`` have no row here, nor has ``kda``, whose chunk
    # enters with the slot's state and its convolution's tail (``hybrid`` keeps
    # its row: a Mamba-2 layer's tail is handed to no chunk)
    "continued_prefill": (
        "the continued prefill (a prompt's chunks, the suffix behind a reused prefix)",
        {trait: _ROWS[trait] for trait in ("cca", "hybrid", "eva")},
    ),
    # the flash and ring kernels (``forward``'s ``attn_impl`` other than
    # "xla"): causal attention over every row, whatever else a slot keeps
    "attn_impl": ("attn_impl other than 'xla'", {
        "latent": (
            "a configuration with latent attention: training runs it in the rebuilt "
            "form through XLA's attention (heads of {cfg.qk_head_dim}); the flash and "
            "ring kernels have not been run at that head size"
        ),
        "sliding": (
            "a stack with sliding layers: the flash and ring kernels know a causal "
            "edge and no band (their backward neither); training runs the band in "
            "XLA's form"
        ),
        "eva": _ROWS["eva"],
        "sparse": _ROWS["sparse"],
        "linear": (
            "a configuration with lightning linear-attention layers: the kernels are causal "
            "softmax attention over every row, and training runs this stack in the XLA forms"
        ),
        "blocks": _ROWS["blocks"],
        "kda": (
            "a configuration with kda linear-attention layers: the kernels are causal "
            "softmax attention over every row, and training runs this stack in the XLA forms"
        ),
    }),
}


def refuse(cfg, feature: str, what: str | None = None) -> None:
    """Raise where ``feature`` cannot take a slot's past as ``cfg`` keeps it,
    naming ``what`` (the feature's own name where None) and the trait."""
    name, reasons = REFUSALS[feature]
    traits = cfg.traits
    for trait, reason in reasons.items():
        if trait in traits:
            raise ValueError(f"{what or name} is refused for {reason.format(cfg=cfg)}")
