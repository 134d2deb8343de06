"""Functional Llama-for-causal-LM, TPU-first.

Capability parity with the reference's use of HF ``LlamaForCausalLM``
(open_diloco/train_fsdp.py:171-174) and the size configs under
open_diloco/configs/*.json -- but designed for XLA, not translated:

- Parameters are a plain pytree (nested dicts of jax.Arrays). Per-layer
  weights are **stacked along a leading layer axis** and the decoder runs as a
  single ``lax.scan`` over layers: one compiled block regardless of depth,
  fast compiles, and clean per-layer rematerialization.
- Compute dtype (bf16) is applied at the forward boundary; master params stay
  float32 (the "bf16-mixed" of train_fsdp.py:228 without a GradScaler --
  bf16 on TPU needs no loss scaling, as the reference README itself notes).
- Attention dispatches through opendiloco_tpu.ops.attention (XLA / Pallas
  flash / ring).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import math
import os
from typing import Any, Literal, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from opendiloco_tpu.models import kda, lightning, mamba
from opendiloco_tpu.models.ring_cache import (  # noqa: F401 (re-exported)
    RingPair,
    cache_insert,
    eva_window_rows,
    init_kv_cache,
    index_chunk_insert,
    index_write_rows,
    layer_rows_insert,
    pooled_chunk_insert,
    prefix_copy,
    ring_rows,
    slot_layer_pages,
)
from opendiloco_tpu.models.traits import TRAITS, refuse
from opendiloco_tpu.ops.attention import (
    band_block,
    banded_chunk_attention,
    ring_window_rows,
    tiled_latent_attention,
    window_attention,
    decode_step_attention,
    eva_attention,
    eva_decode_step_attention,
    eva_pool,
    latent_decode_step_attention,
    causal_selection,
    chunk_selection,
    decode_selection,
    sparse_attention,
    sparse_decode_step_attention,
    tiled_sparse_attention,
    xla_attention,
    BlockSizes,
    block_decode_step_attention,
    block_selection,
    block_sparse_attention,
    causal_block_selection,
    closing_pooled_key,
    pool_pages,
    tiled_block_attention,
)
from opendiloco_tpu.ops.decode_kernels import (
    block_decode_attention,
    block_tiles_held,
    causal_prefill_attention,
    chunk_attention,
    chunk_form,
    eva_decode_attention,
    eva_prefill_attention,
    index_ring_write,
    kda_step,
    kda_step_form,
    latent_chunk_attention,
    latent_chunk_form,
    mla_decode_attention,
    paged_decode_attention,
    ring_rows_sum,
    xla_ring_rows_sum,
)


# what ``LlamaConfig.layer_types`` may name (``LlamaConfig.layer_kinds``)
_LAYER_KINDS = ("attention", "mamba", "dense", "sliding", "lightning", "kda")
# what ``LlamaConfig.rope_yarn`` holds of a published YaRN entry
_YARN_KEYS = (
    "factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "attention_factor",
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Model hyperparameters, JSON-compatible with HF llama configs
    (open_diloco/configs/config_{2m,14m,60m,150m,1b}.json)."""

    vocab_size: int = 32_000
    hidden_size: int = 1024
    intermediate_size: int = 2688
    num_hidden_layers: int = 12
    num_attention_heads: int = 16
    num_key_value_heads: Optional[int] = None
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10_000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # a fresh norm's weight is 1 + N(0, norm_init_std^2) and a norm's bias
    # N(0, norm_init_std^2): 0 leaves them at 1 and 0, as a fresh model has
    # them; a benchmark or a test that has to see every norm's weight act
    # (a trained model's are not 1) asks for a spread
    norm_init_std: float = 0.0
    # Mixture-of-Experts (beyond the reference's dense-only zoo): 0 = dense
    # FFN; > 0 = routed experts in every layer, ``num_experts_per_tok`` per
    # token and no token dropped, sharded over the "ep" mesh axis. The keys
    # are those of the published OLMoE ``config.json``
    num_experts: int = 0
    num_experts_per_tok: int = 1
    norm_topk_prob: bool = False
    router_aux_loss_coef: float = 0.01
    router_z_loss_coef: float = 0.0
    # RMSNorm over the whole q and k projections before the split into heads
    qk_norm: bool = False
    # A hybrid stack (the keys of a published ``granitemoehybrid``
    # ``config.json``): ``layer_types`` names each layer's mixer, "attention"
    # or "mamba" (None: attention everywhere); the ``mamba_*`` keys size the
    # Mamba-2 mixer (``models/mamba.py``); ``shared_intermediate_size`` > 0
    # adds a SwiGLU of that width, for every token, to the routed FFN's
    # output; the four multipliers scale the embedding, each residual
    # branch, the attention scores (None: 1/sqrt(head_dim)) and divide the
    # logits; ``position_embedding_type`` "nope" leaves q and k unrotated
    layer_types: Optional[tuple] = None
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    shared_intermediate_size: int = 0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    position_embedding_type: str = "rope"
    # One chip's share of an expert-parallel deployment: the router keeps its
    # ``num_experts`` outputs and its experts per token, the layer holds the
    # weights of ``num_local_experts`` experts from ``first_local_expert`` on
    # and computes their part of the result; a token's other experts add
    # nothing here (None: every expert is held)
    num_local_experts: Optional[int] = None
    first_local_expert: int = 0
    # Latent attention and the routed FFN of a published ``glm4_moe_lite``
    # ``config.json`` (the DeepSeek-V2/V3 block, arXiv 2405.04434):
    # ``kv_lora_rank`` > 0 makes every attention layer latent. q passes a
    # low-rank pair (``q_lora_rank``, normed between); one row of
    # ``kv_lora_rank`` normed values and ``qk_rope_head_dim`` rotated ones a
    # token is all the cache keeps, and each head's ``qk_nope_head_dim``
    # unrotated key values and ``v_head_dim`` values are rebuilt from it
    # (training, prefill) or never built (decode, absorbed into q and the
    # output). ``first_k_dense_replace`` leading layers keep a dense SwiGLU
    # of ``intermediate_size`` where the others route over experts of
    # ``moe_intermediate_size`` (0: ``intermediate_size``) beside
    # ``n_shared_experts`` shared ones of that width; ``topk_method``
    # "noaux_tc" scores by sigmoid, chooses under a bias it does not weigh
    # by (``router_bias``) and scales the weights by
    # ``routed_scaling_factor``; ``n_group``/``topk_group`` limit the choice
    # to groups of experts (1: no limit, the only value written here)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_k_dense_replace: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    topk_method: str = "greedy"
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # A head's size where it is not ``hidden_size // num_attention_heads``
    # (None: that), and the share of a head's values, from its first on,
    # that the rotation turns (the pairs are the halves of that part)
    head_dim: Optional[int] = None
    partial_rotary_factor: float = 1.0
    # The block of a published ``zaya`` ``config.json`` (ZAYA1, arXiv
    # 2511.17127; its attention is CCA, arXiv 2510.04476): ``cca_time0`` > 0
    # makes every attention layer compressed convolutional attention. q and k
    # are projected to their heads' own width, pass side by side a depthwise
    # causal convolution over ``cca_time0`` tokens and one grouped by head
    # over ``cca_time1``, are mixed with each other's means, normalised per
    # head and, k, scaled by a learned temperature; half the KV heads hold
    # the token's own values and half the token before's (``_cca_qkv``).
    # ``router_hidden_size`` > 0 makes the router an MLP of that width over a
    # down-projection of the FFN's input to which the layer before's is added
    # (``_router_features``), choosing under a selection bias beside softmax
    # scores; ``residual_scaling`` gives each sublayer a learned scale and
    # bias per channel on the stream and on the branch
    cca_time0: int = 0
    cca_time1: int = 0
    router_hidden_size: int = 0
    residual_scaling: bool = False
    # The block of a published ``evabyte`` ``config.json`` (EvaByte; its
    # attention is EVA, arXiv 2302.04542): ``attention_class`` "eva" cuts the
    # positions into windows of ``window_size`` and chunks of ``chunk_size``.
    # A query reads the rows of its own window exactly (causally, and never
    # the window before's) and, under the same softmax, one pooled key and
    # value per chunk of every earlier window: the chunk's keys under a
    # softmax of their scores against a learned vector per head
    # (``adaptive_phi``), a learned offset added (``adaptive_mu_k``), the
    # values under the same weights (``ops.attention.eva_pool``). So a slot's
    # past is two rings of two lifetimes (``ring_cache``): ``window_size`` rows
    # that restart at a window's edge, and a pooled row per ``chunk_size``
    # positions. ``num_pred_heads`` > 1 widens the head to that many
    # vocabularies side by side, head i predicting the token i + 1 ahead
    # (head 0 is the one sampled); ``norm_add_unit_offset`` scales every
    # RMSNorm by 1 + w; ``fp32_skip_add`` carries the residual stream in
    # float32 from the embedding to the final norm, the branches in the
    # compute dtype; ``fp32_logits`` accumulates the head's matmul in float32
    # (False: in the compute dtype, widened after)
    attention_class: str = "mha"
    chunk_size: int = 0
    window_size: int = 0
    num_pred_heads: int = 1
    norm_add_unit_offset: bool = False
    fp32_skip_add: bool = False
    fp32_logits: bool = False
    # Learned sparse attention, the block of a published ``KeyeVL2``
    # ``config.json`` (a Qwen3-MoE block under DeepSeek sparse attention's
    # lightning indexer; the ``index_*`` and chunk keys are its ``sa_config``'s):
    # ``index_topk`` > 0 gives every attention layer an indexer. Beside K and V
    # a token keeps one index key of ``index_head_dim`` values (a LayerNorm
    # with bias over its projection, rotated whole); a query's
    # ``index_n_heads`` index queries score every row before it, I = sum_j w_j
    # relu(q_j . k) with w a projection of the query's token, and its attention
    # reads the ``index_topk`` rows of largest score, one set for all its
    # heads (ties to the lower position). ``q_chunk_size`` is the serving
    # prefill's chunk: a prompt longer than every bucket is admitted that many
    # queries at a time over the rows before them (``chunk_prefill_forward``);
    # the published ``kv_chunk_size`` tiles its kernel's scoring, changes no
    # equation and is held by no field (``to_dict`` writes the query chunk in
    # its place, as the published file has it). ``qk_norm_per_head``: q and k pass an RMSNorm per head, its
    # weight [head_dim] shared by the heads (the Qwen3 family's; ``qk_norm`` is
    # OLMoE's, over the whole projection). ``mrope_section``: the rotation's
    # frequency pairs in three runs, each turned by its own row of positions
    # [3, B, T] (temporal, height, width); token ids have three equal rows, for
    # which this is plain RoPE
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    q_chunk_size: int = 0
    qk_norm_per_head: bool = False
    mrope_section: Optional[tuple] = None
    # Two kinds of latent attention in one stack, the block of a published
    # ``dots3_note`` ``config.json``: ``layer_types`` names each layer
    # "full_attention" or "sliding_attention" (held here as the kinds "dense" /
    # "attention" and "sliding", ``layer_kinds``). A full layer is the latent
    # attention above at the top-level sizes, and with ``index_topk`` > 0 under
    # the indexer above, whose queries then come from the query's latent
    # (``q_lora_rank`` -> ``index_n_heads`` x ``index_head_dim``) and whose
    # queries and key turn over their first ``qk_rope_head_dim`` values alone,
    # by the layer's own tables. A sliding layer is the same attention at the
    # ``swa_*`` sizes (its own head count, ranks, head sizes and rope base: a
    # latent row of ``swa_kv_lora_rank + swa_qk_rope_head_dim`` values), no
    # indexer, and query t reads rows s with 0 <= t - s < ``sliding_window_size``;
    # its ring is ``ring_cache.sliding_ring_rows`` long whatever the context
    # and wraps. ``kind_view`` gives each kind's sizes under the top-level
    # names. ``attention_gate_type`` / ``swa_attention_gate_type`` "headwise":
    # each head's output is scaled by sigmoid(x W_g), one value a head, before
    # ``o_proj``. ``apply_mla_qkv_lora_rescale``: the normed latents are scaled
    # by (hidden_size / rank)^1/2
    swa_num_attention_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10_000.0
    sliding_window_size: int = 0
    attention_gate_type: str = "none"
    swa_attention_gate_type: str = "none"
    apply_mla_qkv_lora_rescale: bool = False
    # Two kinds of grouped-query attention in one stack, the block of a
    # published ``laguna`` ``config.json``: ``layer_types`` as above, over K and
    # V rows (no latent). A sliding layer has ``swa_num_attention_heads`` query
    # heads over the same ``num_key_value_heads`` (``num_attention_heads_per_layer``),
    # its own rope base ``swa_rope_theta`` and rotated share
    # ``swa_partial_rotary_factor`` (``rope_parameters`` by kind of layer) and
    # reads under ``sliding_window_size`` a ``(k, v)`` ring of its own that
    # wraps; both kinds may carry the head-wise gate. ``rope_yarn``: the full
    # layers' YaRN (``rope_type`` "yarn": ``factor``,
    # ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    # ``attention_factor``, held as sorted pairs), under which the rotated pairs'
    # frequencies are stretched along a ramp and cos and sin carry the factor
    # (``_yarn_frequencies``); None: plain rope
    swa_partial_rotary_factor: float = 1.0
    rope_yarn: Optional[tuple] = None
    # Lightning linear attention beside grouped-query attention under a
    # selection by blocks, the block of a published ``minicpm_sala``
    # ``config.json``: ``mixer_types`` names each layer "lightning-attn" (held
    # here as the kind "lightning": ``models/lightning.py``, a decaying state
    # [heads, head_dim, head_dim] a layer and slot in place of rows, heads of
    # ``head_dim`` without grouping, rotated by ``rope_theta``) or "minicpm4"
    # (the kind "attention": ``num_key_value_heads`` KV heads, unrotated:
    # ``position_embedding_type`` "nope"). ``lightning_decays``: the lightning
    # layers' rates g, a row of ``num_attention_heads`` floats a layer (lambda
    # = exp(-g): data, float32 whatever the compute dtype; by
    # ``lightning.decay_rates`` where a file states none). ``sparse_config``:
    # the sizes of the "minicpm4" layers' selection (``ops.attention.BlockSizes``,
    # held as sorted pairs; MiniCPM4's ``sparse_config``, arXiv 2506.07900):
    # beside K and V a KV head keeps a pooled key per window of ``kernel_size``
    # rows every ``kernel_stride``, and a query reads ``topk`` blocks of
    # ``block_size`` rows chosen by the pooled keys' scores, one choice a KV
    # group; None: every row. ``attention_gate_type`` "elementwise": the
    # attention's output is scaled value by value by sigmoid(x W_g) before
    # ``o_proj`` (``attn_use_output_gate``). The family's constant scalings are
    # the fields a granite hybrid's have: ``embedding_multiplier`` (``scale_emb``),
    # ``residual_multiplier`` (``scale_depth`` / sqrt of the published depth),
    # ``logits_scaling`` (``hidden_size`` / ``dim_model_base``)
    sparse_config: Optional[tuple] = None
    lightning_decays: Optional[tuple] = None
    # Kimi-delta linear attention beside gated NoPE grouped-query attention,
    # the block of a published ``solar_open2`` ``config.json``: the layers in
    # ``gqa_layers`` are the kind "attention" (``num_key_value_heads`` KV heads,
    # ``use_gqa_gate``: ``attention_gate_type`` "elementwise"), every other the
    # kind "kda" (``models/kda.py``: a gated delta rule under a decay for every
    # key channel, made from the input; a state [heads, head_dim, head_dim]
    # float32 and the tail of a short convolution on q, k and v a layer and
    # slot in place of rows; heads of ``head_dim`` without grouping, never
    # rotated). ``kda_short_conv``: the convolution's taps
    # (``linear_attn_config.short_conv_kernel_size``); ``kda_allow_neg_eigval``:
    # beta is 2 sigmoid, so that the state's transition may have eigenvalues
    # down to -1. The decay's and the output gate's projections are low-rank
    # pairs through ``head_dim`` (``kda_use_full_proj`` false, the one form
    # written)
    kda_short_conv: int = 4
    kda_allow_neg_eigval: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_attention_heads
            )
        if self.rotary_dim < 2 or self.rotary_dim % 2:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a head of "
                f"{self.head_dim}: the rotated part is an even number of values"
            )
        if self.cca:
            if (self.cca_time0, self.cca_time1) != (2, 2) or self.kv_heads % 2:
                raise ValueError(
                    "CCA is written for two convolutions over 2 tokens each and an "
                    "even number of KV heads (half hold the token's own values, half "
                    f"the token before's); got cca_time0 {self.cca_time0}, cca_time1 "
                    f"{self.cca_time1}, {self.kv_heads} KV heads"
                )
            if (
                self.latent or self.layer_types is not None or self.qk_norm
                or self.num_attention_heads % self.kv_heads
                or self.position_embedding_type != "rope"
            ):
                raise ValueError(
                    "CCA is written for a stack of like rotated attention layers "
                    "whose query heads divide over the KV heads: no latent "
                    "attention, no layer_types, no qk_norm, no 'nope'"
                )
        if self.mrope_section is not None:
            object.__setattr__(self, "mrope_section", tuple(int(n) for n in self.mrope_section))
            if len(self.mrope_section) != 3 or sum(self.mrope_section) != self.rotary_dim // 2:
                raise ValueError(
                    f"mrope_section {self.mrope_section}: three runs of frequency pairs "
                    f"that make up the {self.rotary_dim // 2} pairs of a head"
                )
        if self.sparse:
            if not (
                self.index_n_heads > 0 and self.index_head_dim >= 2
                and self.index_head_dim % 2 == 0 and self.q_chunk_size > 0
            ):
                raise ValueError(
                    "learned sparse attention (index_topk > 0) needs index_n_heads, an "
                    "even index_head_dim and a q_chunk_size; got "
                    f"{self.index_n_heads}, {self.index_head_dim}, {self.q_chunk_size}"
                )
            if (
                self.cca or self.eva or self.hybrid
                or (self.layer_types is not None and not self.latent)
                or self.qk_norm or self.num_attention_heads % self.kv_heads
                or self.position_embedding_type != "rope"
            ):
                raise ValueError(
                    "learned sparse attention is written for a stack of like rotated "
                    "attention layers whose query heads divide over the KV heads, or for "
                    "the full layers of a latent stack: no CCA, no EVA, no Mamba-2 "
                    "layers, no qk_norm over the whole projection, no 'nope'"
                )
        if self.attention_class not in ("mha", "eva"):
            raise ValueError(
                f"attention_class {self.attention_class!r}: 'mha' (every past token "
                "a row) or 'eva' (a window of rows, pooled chunks before it)"
            )
        if self.eva:
            if not (
                self.chunk_size > 0 and self.window_size > 0
                and self.window_size % self.chunk_size == 0
            ):
                raise ValueError(
                    "EVA attention needs a chunk_size and a window_size of whole "
                    f"chunks; got chunk_size {self.chunk_size}, window_size "
                    f"{self.window_size}"
                )
            if (
                self.latent or self.cca or self.layer_types is not None or self.qk_norm
                or self.num_attention_heads % self.kv_heads
                or self.position_embedding_type != "rope"
            ):
                raise ValueError(
                    "EVA attention is written for a stack of like rotated attention "
                    "layers whose query heads divide over the KV heads: no latent "
                    "attention, no CCA, no layer_types, no qk_norm, no 'nope'"
                )
        if self.num_pred_heads < 1 or (self.num_pred_heads > 1 and self.tie_word_embeddings):
            raise ValueError(
                f"num_pred_heads {self.num_pred_heads}: at least one head, and more "
                "than one only over an untied head (the embedding has one vocabulary)"
            )
        if self.router_hidden_size and not (
            self.num_experts and self.topk_method == "greedy"
        ):
            raise ValueError(
                "router_hidden_size needs routed experts (num_experts > 0) under "
                "softmax scores (topk_method 'greedy')"
            )
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
            object.__setattr__(self, "layer_types", kinds)
            if len(kinds) != self.num_hidden_layers or set(kinds) - set(_LAYER_KINDS):
                raise ValueError(
                    f"layer_types must name {self.num_hidden_layers} layers, each one of "
                    f"{_LAYER_KINDS}; got {len(kinds)}: {sorted(set(kinds))}"
                )
            if set(kinds) & {"sliding", "dense"} and (
                "mamba" in kinds or not (self.latent or "sliding" in kinds)
            ):
                raise ValueError(
                    "layer_types 'sliding' and 'dense' are the kinds of a stack of full and "
                    "sliding attention layers (latent, kv_lora_rank > 0, or grouped-query; "
                    "no Mamba-2 layers)"
                )
        if self.rope_yarn is not None:
            scaling = dict(self.rope_yarn)
            object.__setattr__(self, "rope_yarn", tuple(sorted(scaling.items())))
            if scaling.get("rope_type", "yarn") != "yarn" or not all(
                scaling.get(key, 0) > 0 for key in _YARN_KEYS
            ) or self.latent:
                raise ValueError(
                    f"rope_yarn {scaling!r}: written for YaRN over K and V rows "
                    f"(rope_type 'yarn' and positive {_YARN_KEYS})"
                )
        if self.sliding and not self.latent:
            if (
                self.cca or self.eva or self.sparse or self.qk_norm or self.qk_norm_per_head
                or self.position_embedding_type != "rope" or self.mrope_section is not None
                or self.sliding_window_size <= 0 or self.swa_num_attention_heads <= 0
                or self.swa_num_attention_heads % self.kv_heads
                or self.num_attention_heads % self.kv_heads
                or int(self.head_dim * self.swa_partial_rotary_factor) < 2
                or int(self.head_dim * self.swa_partial_rotary_factor) % 2
            ):
                raise ValueError(
                    "sliding grouped-query layers need swa_num_attention_heads over the "
                    "same KV heads, a sliding_window_size and an even rotated part, in a "
                    "stack of plain rotated attention: no CCA, EVA, indexer, QK norm, "
                    "'nope' or sectioned rotation"
                )
        elif self.sliding:
            if not (
                self.swa_num_attention_heads and self.swa_q_lora_rank and self.swa_kv_lora_rank
                and self.swa_qk_nope_head_dim and self.swa_v_head_dim
                and self.swa_qk_rope_head_dim >= 2 and self.swa_qk_rope_head_dim % 2 == 0
                and self.sliding_window_size > 0
            ):
                raise ValueError(
                    "sliding latent layers need the swa_* sizes (heads, both ranks, the "
                    "three head sizes, an even rotated part) and a sliding_window_size"
                )
        for gate in (self.attention_gate_type, self.swa_attention_gate_type):
            if gate not in ("none", "headwise", "elementwise"):
                raise ValueError(f"attention gate {gate!r}: 'none', 'headwise' or 'elementwise'")
        if "elementwise" in (self.attention_gate_type, self.swa_attention_gate_type) and (
            self.latent or self.cca or self.eva or self.sparse or self.sliding
        ):
            raise ValueError(
                "the attention gate 'elementwise' is written for one kind of grouped-query "
                "attention over K and V rows: no latent attention, CCA, EVA, indexer or "
                "sliding layers"
            )
        if self.apply_mla_qkv_lora_rescale and not self.latent:
            raise ValueError("the latents' rescale is written for latent attention")
        if "headwise" in (self.attention_gate_type, self.swa_attention_gate_type) and (
            self.cca or self.eva or self.sparse and not self.latent
        ):
            raise ValueError(
                "the head-wise gate is written for latent attention and for plain "
                "grouped-query attention: no CCA, no EVA, no indexer over K and V rows"
            )
        if self.sparse_config is not None:
            sizes = dict(self.sparse_config)
            object.__setattr__(self, "sparse_config", tuple(sorted(sizes.items())))
            b = BlockSizes(**{key: int(sizes.get(key, 0)) for key in BlockSizes._fields})
            if set(sizes) != set(BlockSizes._fields) or min(b) < 1 or (
                b.kernel_size % b.kernel_stride or b.block_size % b.kernel_stride
                or b.window_size % b.block_size or b.window_size // b.block_size + b.init_blocks > b.topk
            ):
                raise ValueError(
                    f"sparse_config {sizes!r}: {BlockSizes._fields}, each positive; windows and "
                    "blocks of whole strides, a window_size of whole blocks, and a topk that "
                    "holds the forced blocks"
                )
        if self.linear or self.blocks:
            decays = self.lightning_decays or ()
            object.__setattr__(self, "lightning_decays", tuple(tuple(map(float, r)) for r in decays))
            if self.linear and (
                len(decays) != self.num_lightning_layers
                or any(len(r) != self.num_attention_heads for r in decays)
            ):
                raise ValueError(
                    f"lightning_decays: a row of {self.num_attention_heads} rates for each of "
                    f"the {self.num_lightning_layers} lightning layers; got {len(decays)} rows"
                )
            if (
                self.latent or self.cca or self.eva or self.sparse or self.sliding or self.hybrid
                or self.qk_norm or self.mrope_section is not None or self.rope_yarn is not None
                or self.num_attention_heads % self.kv_heads or "dense" in self.layer_kinds
                or self.attention_gate_type == "headwise" or self.residual_scaling
                or self.num_experts
            ):
                raise ValueError(
                    "lightning layers and a selection by blocks are written for a stack of "
                    "'lightning' and grouped-query 'attention' layers whose query heads divide "
                    "over the KV heads, each over a dense SwiGLU: no latent attention, CCA, EVA, "
                    "indexer, sliding or Mamba-2 layers, no routed experts, no qk_norm over the "
                    "whole projection, no head-wise gate"
                )
        if self.kda and (
            self.latent or self.cca or self.eva or self.sparse or self.sliding or self.hybrid
            or self.linear or self.blocks or self.qk_norm or self.qk_norm_per_head
            or self.mrope_section is not None or self.rope_yarn is not None
            or self.num_attention_heads % self.kv_heads or "dense" in self.layer_kinds
            or self.attention_gate_type == "headwise" or self.residual_scaling
            or self.router_hidden_size or self.kda_short_conv < 2
        ):
            raise ValueError(
                "kda layers are written for a stack of 'kda' and grouped-query 'attention' "
                "layers whose query heads divide over the KV heads, under a convolution of two "
                "taps or more: no latent attention, CCA, EVA, indexer, sliding, Mamba-2 or "
                "lightning layers, no selection by blocks, no QK norm, no head-wise gate, no "
                "router that reads the layer before"
            )
        if self.hybrid:
            if self.mamba_n_groups != 1 or not self.mamba_conv_bias or self.mamba_proj_bias:
                raise ValueError(
                    "the Mamba-2 mixer is written for mamba_n_groups 1, a conv "
                    "bias and no projection bias; got mamba_n_groups "
                    f"{self.mamba_n_groups}, mamba_conv_bias {self.mamba_conv_bias}, "
                    f"mamba_proj_bias {self.mamba_proj_bias}"
                )
            if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
                raise ValueError(
                    f"mamba_n_heads x mamba_d_head ({self.mamba_n_heads} x "
                    f"{self.mamba_d_head}) is not mamba_expand x hidden_size"
                )
        if self.position_embedding_type not in ("rope", "nope"):
            raise ValueError(
                f"position_embedding_type {self.position_embedding_type!r}: 'rope' or 'nope'"
            )
        if self.latent:
            if not (self.q_lora_rank and self.qk_nope_head_dim and self.v_head_dim):
                raise ValueError(
                    "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                    "qk_nope_head_dim and v_head_dim; got "
                    f"{self.q_lora_rank}, {self.qk_nope_head_dim}, {self.v_head_dim}"
                )
            if self.qk_rope_head_dim < 2 or self.qk_rope_head_dim % 2:
                raise ValueError(
                    f"qk_rope_head_dim {self.qk_rope_head_dim}: the rotated part "
                    "of a latent head is an even number of values"
                )
            if self.hybrid or self.qk_norm or self.position_embedding_type != "rope":
                raise ValueError(
                    "latent attention is written for a stack of rotated attention "
                    "layers: no Mamba-2 layers, no qk_norm, no 'nope'"
                )
        if self.topk_method not in ("greedy", "noaux_tc"):
            raise ValueError(f"topk_method {self.topk_method!r}: 'greedy' or 'noaux_tc'")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                "group-limited routing is not written: n_group and topk_group "
                f"must be 1; got {self.n_group}, {self.topk_group}"
            )
        if self.first_k_dense_replace and self.num_experts and not self.sliding and (
            self.layer_types is not None or self.leading_dense >= self.num_hidden_layers
        ):
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} needs a stack "
                f"of more attention layers than that ({self.num_hidden_layers}) and "
                "no layer_types"
            )
        held = self.num_local_experts
        if held is not None and not (
            self.num_experts and 0 < held and 0 <= self.first_local_expert
            and self.first_local_expert + held <= self.num_experts
        ):
            raise ValueError(
                f"experts [{self.first_local_expert}, {self.first_local_expert} + "
                f"{held}) are not among the router's {self.num_experts}"
            )

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def leading_dense(self) -> int:
        """Leading layers whose FFN is a dense SwiGLU in a routed model."""
        if self.layer_types:  # the kinds are named layer by layer
            return 0
        return self.first_k_dense_replace if self.num_experts else 0

    @property
    def layer_kinds(self) -> tuple:
        """Each layer's kind, which names its mixer and its FFN: "attention"
        (attention and the configuration's FFN), "mamba" (a Mamba-2 mixer
        and that FFN), "dense" (attention and a dense SwiGLU, the leading
        layers of a routed model)."""
        if self.layer_types:
            return self.layer_types
        k = self.leading_dense
        return ("dense",) * k + ("attention",) * (self.num_hidden_layers - k)

    @property
    def layers_by_kind(self) -> bool:
        """Are the layers' weights one stack per kind (a dict of stacks)
        and not one stack of like layers?"""
        return self.hybrid or bool(self.leading_dense) or self.sliding or self.linear or self.kda

    @property
    def kda(self) -> bool:
        """Does any layer hold a Kimi-delta mixer (and so a state and a
        convolution's tail a slot, which are not rows)?"""
        return "kda" in self.layer_kinds

    @property
    def num_kda_layers(self) -> int:
        return self.layer_kinds.count("kda")

    @property
    def linear(self) -> bool:
        """Does any layer hold a lightning linear-attention mixer (and so a
        decaying state a slot, which is not rows)?"""
        return "lightning" in self.layer_kinds

    @property
    def num_lightning_layers(self) -> int:
        return self.layer_kinds.count("lightning")

    @property
    def blocks(self) -> bool:
        """Do the attention layers read blocks chosen by pooled keys' scores
        (so a ring of pooled keys beside K and V)?"""
        return self.sparse_config is not None

    @property
    def block_sizes(self) -> BlockSizes:
        return BlockSizes(**dict(self.sparse_config))

    @property
    def sliding(self) -> bool:
        """Does any layer hold latent attention of the second geometry, under
        a window (so a second latent ring, which wraps)?"""
        return "sliding" in self.layer_kinds

    @property
    def num_sliding_layers(self) -> int:
        return self.layer_kinds.count("sliding")

    @property
    def num_full_layers(self) -> int:
        """Attention layers whose ring is as long as the context."""
        return self.num_attention_layers - self.num_sliding_layers

    @property
    def hybrid(self) -> bool:
        """Does any layer hold a Mamba-2 mixer (and so a recurrent state)?"""
        return "mamba" in self.layer_kinds

    @property
    def num_mamba_layers(self) -> int:
        return self.layer_kinds.count("mamba")

    @property
    def num_attention_layers(self) -> int:
        return (
            self.num_hidden_layers - self.num_mamba_layers - self.num_lightning_layers
            - self.num_kda_layers
        )

    @property
    def latent(self) -> bool:
        """Is the attention latent (one low-rank row a token in the cache)?"""
        return self.kv_lora_rank > 0

    @property
    def latent_row_dim(self) -> int:
        """Values of a cached latent row: the normed latent, then the
        rotated key part every head shares."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def sliding_row_dim(self) -> int:
        """Values of a sliding layer's cached latent row."""
        return self.swa_kv_lora_rank + self.swa_qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def shared_width(self) -> int:
        """Width of the SwiGLU every token passes beside the routed experts."""
        return self.shared_intermediate_size or self.n_shared_experts * self.expert_width

    @property
    def moe_counts(self) -> int:
        """Entries of a routed FFN's counts (``_routed_ffn``)."""
        return 3 if self.num_local_experts is None else 4

    @property
    def held_experts(self) -> int:
        return self.num_experts if self.num_local_experts is None else self.num_local_experts

    @property
    def rotary_dim(self) -> int:
        """Values of a head, from its first on, that the rotation turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def cca(self) -> bool:
        """Is the attention CCA (q and k through two causal convolutions, so a
        per-slot state beside the ring)?"""
        return self.cca_time0 > 0

    @property
    def eva(self) -> bool:
        """Is the attention EVA (a window of rows read exactly, every earlier
        window as pooled chunks: two rings a slot)?"""
        return self.attention_class == "eva"

    @property
    def sparse(self) -> bool:
        """Does a learned indexer choose the rows each query's attention reads
        (so an index-key ring beside K and V)?"""
        return self.index_topk > 0

    @property
    def traits(self) -> tuple:
        """What a slot's past is here beyond the rows of one (k, v) ring, as
        ``models.traits`` names it: the table of which feature can take it."""
        return tuple(trait for trait in TRAITS if getattr(self, trait))

    @property
    def eva_chunks_per_window(self) -> int:
        """Pooled rows that stand for one whole window."""
        return self.window_size // self.chunk_size

    @property
    def cca_state_dim(self) -> int:
        """Values a CCA layer keeps of a slot's last token: q and k before the
        convolutions, the same between the two, and the values the next token
        takes from this one (``_cca_qkv``)."""
        z = (self.num_attention_heads + self.kv_heads) * self.head_dim
        return 2 * z + self.kv_heads // 2 * self.head_dim

    @classmethod
    def from_json(cls, path: str) -> "LlamaConfig":
        with open(path) as f:
            raw = json.load(f)
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: dict[str, Any]) -> "LlamaConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in raw.items() if k in fields}
        if raw.get("model_type") == "olmoe":
            # the published config.json has no key for either: OLMoE always
            # normalises q and k, and its paper (arXiv 2409.02060) trains
            # with a router z-loss of 0.001
            known.setdefault("qk_norm", True)
            known.setdefault("router_z_loss_coef", 0.001)
        if known.get("layer_types") is not None:
            # a file cut in depth keeps the published pattern whole and runs
            # its leading num_hidden_layers entries
            depth = known.get("num_hidden_layers", cls.num_hidden_layers)
            known["layer_types"] = tuple(known["layer_types"])[:depth]
        if raw.get("model_type") == "granitemoehybrid":
            # the published key counts the experts; a file cut to one chip's
            # share gives the held count there and the router's width beside
            # it. The 10 weights are a softmax over the chosen logits, which
            # is the softmax over all of them renormalised over the chosen.
            # router_aux_loss_coef is no key of the catalog's config: 0
            known.setdefault("num_experts", raw.get("num_local_experts", 0))
            if known.get("num_local_experts") == known["num_experts"]:
                del known["num_local_experts"]
            known.setdefault("norm_topk_prob", True)
            known.setdefault("router_aux_loss_coef", 0.0)
        if raw.get("model_type") == "glm4_moe_lite":
            # n_routed_experts counts the experts; a file cut to one chip's
            # share gives the held count there and the router's width
            # (num_experts) beside it. The published config trains without an
            # aux loss (the selection bias balances the load): 0
            held = raw.get("n_routed_experts", 0)
            known.setdefault("num_experts", held)
            if held != known["num_experts"]:
                known.setdefault("num_local_experts", held)
            known.setdefault("router_aux_loss_coef", 0.0)
        if raw.get("model_type") == "zaya":
            # every layer is "hybrid": a CCA sublayer, then an MoE sublayer
            # (one kind, so no layer_types here); the rotation's base is the
            # "hybrid" entry of rope_parameters. The selection bias balances
            # the load and the config has no key for an aux loss: 0
            if set(raw.get("layer_types") or ()) - {"hybrid"}:
                raise ValueError(
                    "a zaya stack is written for 'hybrid' layers alone (CCA over "
                    f"the whole context); got {sorted(set(raw['layer_types']))}"
                )
            known.pop("layer_types", None)
            rope = (raw.get("rope_parameters") or {}).get("hybrid") or {}
            known.setdefault("rope_theta", rope.get("rope_theta", cls.rope_theta))
            known.setdefault("residual_scaling", True)
            known.setdefault("router_aux_loss_coef", 0.0)
            # the FFN is the routed one alone: ``intermediate_size`` is no key
            known.setdefault("intermediate_size", raw.get("moe_intermediate_size", 0))
        if raw.get("model_type") == "evabyte":
            # the published draw's scale is ``init_std``; what the block is
            # not written for is refused by name and never read past
            for key, want in (("attention_class", "eva"), ("attention_bias", False),
                              ("rope_scaling", None), ("hidden_act", "silu")):
                if raw.get(key, want) != want:
                    raise ValueError(
                        f"an evabyte stack is written for {key} {want!r}; got {raw[key]!r}"
                    )
            known.setdefault("initializer_range", raw.get("init_std", cls.initializer_range))
        if raw.get("model_type") == "KeyeVL2":
            # the language model of a published Keye-VL-2.0 config: a Qwen3-MoE
            # block (every layer routed, QK-norm per head: the family's, no key
            # states it) under ``sa_config``'s indexer. What the block is not
            # written for is refused by name and never read past. The catalog's
            # config has no key for an aux loss: 0
            sa = raw.get("sa_config") or {}
            for key, want, got in (
                ("sa_config.indexer_num_kv_heads", 1, sa.get("indexer_num_kv_heads", 1)),
                ("attention_bias", False, raw.get("attention_bias", False)),
                ("decoder_sparse_step", 1, raw.get("decoder_sparse_step", 1)),
                ("mlp_only_layers", [], list(raw.get("mlp_only_layers") or [])),
                ("use_sliding_window", False, raw.get("use_sliding_window", False)),
                ("hidden_act", "silu", raw.get("hidden_act", "silu")),
            ):
                if got != want:
                    raise ValueError(
                        f"a KeyeVL2 stack is written for {key} {want!r}; got {got!r}"
                    )
            scaling = raw.get("rope_scaling") or {}
            if scaling.get("rope_type", scaling.get("type", "default")) != "default":
                raise ValueError(
                    "a KeyeVL2 stack is written for rope_scaling of type 'default' (sectioned "
                    f"rotation, no stretching); got {scaling!r}"
                )
            known.setdefault("index_n_heads", sa.get("indexer_num_heads", 0))
            known.setdefault("index_head_dim", sa.get("indexer_head_dim", 0))
            known.setdefault("index_topk", sa.get("topk", 0))
            known.setdefault("q_chunk_size", sa.get("q_chunk_size", 0))
            if scaling.get("mrope_section") is not None:
                known.setdefault("mrope_section", tuple(scaling["mrope_section"]))
            known.setdefault("qk_norm_per_head", True)
            known.setdefault("router_aux_loss_coef", 0.0)
            # the published key repeats the experts' count; a file cut to one
            # chip's share gives the held count there, the router's width beside it
            if known.get("num_local_experts") == known.get("num_experts"):
                known.pop("num_local_experts", None)
        if raw.get("model_type") == "dots3_note":
            # the language model of a published dots3-note config: the layers'
            # kinds from ``layer_types`` and ``first_k_dense_replace`` (a leading
            # layer is a full layer over a dense SwiGLU). What the block is not
            # written for is refused by name and never read past.
            # ``n_routed_experts`` counts the experts; a file cut to one chip's
            # share gives the held count under ``num_local_experts``. No key for
            # an aux loss (the selection bias balances the load): 0
            for key, want in (("attention_bias", False), ("rope_scaling", None),
                              ("hidden_act", "silu"), ("moe_layer_freq", 1),
                              ("scoring_func", "sigmoid")):
                if raw.get(key, want) != want:
                    raise ValueError(
                        f"a dots3_note stack is written for {key} {want!r}; got {raw[key]!r}"
                    )
            depth = known.get("num_hidden_layers", cls.num_hidden_layers)
            dense = int(raw.get("first_k_dense_replace", 0))
            kinds = []
            for i, name in enumerate(tuple(raw.get("layer_types") or ())[:depth]):
                if name not in ("full_attention", "sliding_attention") or (
                    i < dense and name != "full_attention"
                ):
                    raise ValueError(
                        f"a dots3_note stack is written for 'full_attention' and "
                        f"'sliding_attention' layers, the leading dense ones full; got "
                        f"{name!r} in layer {i}"
                    )
                kinds.append(
                    "dense" if i < dense else "sliding" if name == "sliding_attention"
                    else "attention"
                )
            known["layer_types"] = tuple(kinds)
            known.setdefault("num_experts", raw.get("n_routed_experts", 0))
            if known.get("num_local_experts") == known["num_experts"]:
                known.pop("num_local_experts", None)
            known.setdefault("q_chunk_size", raw.get("q_chunk_size", 512))
            known.setdefault("router_aux_loss_coef", 0.0)
        if raw.get("model_type") == "minicpm_sala":
            known.update(_sala_keys(raw, known.get("num_hidden_layers", cls.num_hidden_layers), known))
        if raw.get("model_type") == "solar_open2":
            known.update(_solar2_keys(raw, known.get("num_hidden_layers", cls.num_hidden_layers)))
            if known.get("num_local_experts") == known["num_experts"]:
                known.pop("num_local_experts", None)
        if raw.get("model_type") == "laguna":
            known.update(_laguna_keys(raw, known.get("num_hidden_layers", cls.num_hidden_layers)))
            if known.get("num_local_experts") == known.get("num_experts"):
                known.pop("num_local_experts", None)
            known.setdefault("router_aux_loss_coef", 0.0)
        return cls(**known)

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        if d["num_key_value_heads"] is None:
            d["num_key_value_heads"] = self.num_attention_heads
        if self.head_dim == self.hidden_size // self.num_attention_heads:
            del d["head_dim"]  # derived: a reader that changes a width derives it anew
        d.update(
            architectures=["LlamaForCausalLM"],
            model_type="llama",
            hidden_act="silu",
            use_cache=False,
        )
        if self.hybrid:
            d.update(
                architectures=["GraniteMoeHybridForCausalLM"],
                model_type="granitemoehybrid",
                layer_types=list(self.layer_types),
            )
        if self.latent:
            d.update(
                architectures=["Glm4MoeLiteForCausalLM"],
                model_type="glm4_moe_lite",
                n_routed_experts=self.held_experts,
            )
        if d["rope_yarn"] is not None:
            d["rope_yarn"] = dict(self.rope_yarn)
        if self.linear or self.blocks:
            names = {"lightning": "lightning-attn", "attention": "minicpm4"}
            d.update(
                architectures=["MiniCPMSALAForCausalLM"], model_type="minicpm_sala",
                mixer_types=[names[k] for k in self.layer_kinds], qk_norm=self.qk_norm_per_head,
                attn_use_rope=False, lightning_use_rope=True, lightning_scale="1/sqrt(d)",
                lightning_nh=self.num_attention_heads, lightning_nkv=self.num_attention_heads,
                lightning_head_dim=self.head_dim, use_output_gate=True, use_output_norm=True,
                attn_use_output_gate=self.attention_gate_type == "elementwise",
                sparse_config=None if self.sparse_config is None else dict(self.sparse_config),
                lightning_decays=[list(r) for r in self.lightning_decays],
            )
            del d["layer_types"]
            return d
        if self.kda:
            d.update(
                architectures=["SolarOpen2ForCausalLM"], model_type="solar_open2",
                gqa_layers=[i for i, k in enumerate(self.layer_kinds) if k == "attention"],
                use_rope=False, use_gqa_gate=self.attention_gate_type == "elementwise",
                kda_use_full_proj=False, n_routed_experts=self.held_experts,
                linear_attn_config={
                    "short_conv_kernel_size": self.kda_short_conv, "head_dim": self.head_dim,
                    "num_heads": self.num_attention_heads, "num_kv_heads": None,
                },
            )
            del d["layer_types"]
            return d
        if self.sliding and not self.latent:
            kinds = self.layer_kinds
            heads = {"sliding": self.swa_num_attention_heads}
            full = {"rope_type": "default", "rope_theta": self.rope_theta,
                    "partial_rotary_factor": self.partial_rotary_factor}
            if self.rope_yarn is not None:
                full.update(dict(self.rope_yarn), rope_type="yarn")
            d.update(
                architectures=["LagunaForCausalLM"], model_type="laguna",
                layer_types=["sliding_attention" if k == "sliding" else "full_attention"
                             for k in kinds],
                mlp_layer_types=["dense" if k == "dense" else "sparse" for k in kinds],
                mlp_only_layers=[i for i, k in enumerate(kinds) if k == "dense"],
                num_attention_heads_per_layer=[
                    heads.get(k, self.num_attention_heads) for k in kinds],
                gating="per-head", gating_types=["per_head"] * len(kinds),
                sliding_window=self.sliding_window_size,
                moe_routed_scaling_factor=self.routed_scaling_factor,
                shared_expert_intermediate_size=self.shared_intermediate_size,
                rope_parameters={"full_attention": full, "sliding_attention": {
                    "rope_type": "default", "rope_theta": self.swa_rope_theta,
                    "partial_rotary_factor": self.swa_partial_rotary_factor}},
            )
            return d
        if self.sliding:
            names = {"sliding": "sliding_attention"}
            d.update(
                architectures=["Dots3NoteForConditionalGeneration"], model_type="dots3_note",
                n_routed_experts=self.num_experts,
                layer_types=[names.get(k, "full_attention") for k in self.layer_kinds],
                first_k_dense_replace=self.layer_kinds.count("dense"),
                rope_scaling=None, moe_layer_freq=1, scoring_func="sigmoid",
            )
            return d
        if self.cca:
            d.update(
                architectures=["ZayaForCausalLM"],
                model_type="zaya",
                layer_types=["hybrid"] * self.num_hidden_layers,
            )
        if self.eva:
            d.update(
                architectures=["EvaByteForCausalLM"], model_type="evabyte",
                init_std=self.initializer_range,
            )
        if self.sparse:
            d.update(
                architectures=["KeyeVL2ForConditionalGeneration"], model_type="KeyeVL2",
                sa_config={
                    "indexer_num_heads": self.index_n_heads,
                    "indexer_head_dim": self.index_head_dim, "indexer_num_kv_heads": 1,
                    "topk": self.index_topk, "q_chunk_size": self.q_chunk_size,
                    "kv_chunk_size": self.q_chunk_size,
                },
                rope_scaling={
                    "rope_type": "default", "type": "default",
                    **({} if self.mrope_section is None
                       else {"mrope_section": list(self.mrope_section)}),
                },
            )
        return d

    def num_params(self) -> int:
        return sum(x.size for x in jax.tree.leaves(shapes(self)))


def _sala_keys(raw: dict, depth: int, known: dict) -> dict:
    """A published ``minicpm_sala`` config's keys as ``LlamaConfig``'s, for the
    leading ``depth`` layers: the kinds from ``mixer_types`` (a file cut in
    depth keeps the published list whole: its length is the published depth,
    which the residual scaling ``scale_depth / sqrt(depth)`` and the decays'
    rule keep); the MiniCPM family's three constant scalings under the fields
    a granite hybrid's have; ``qk_norm`` as the RMSNorm per head it is in this
    family; the selection's sizes from ``sparse_config`` (MiniCPM4's, no key of
    the catalog's row: a file states them under that key) and the decays from
    ``lightning_decays`` or, where a file states none, by the family's rule.
    What the block is not written for is refused by name and never read past."""
    mixers = tuple(raw.get("mixer_types") or ())
    heads = raw.get("num_attention_heads")
    head_dim = raw.get("head_dim") or raw.get("hidden_size", 0) // max(heads or 1, 1)
    for key, want in (
        ("attention_bias", False), ("hidden_act", "silu"), ("attn_use_rope", False),
        ("lightning_use_rope", True), ("lightning_scale", "1/sqrt(d)"),
        ("lightning_nh", heads), ("lightning_nkv", heads), ("lightning_head_dim", head_dim),
        ("use_output_gate", True), ("use_output_norm", True), ("rope_scaling", None),
    ):
        if raw.get(key, want) != want:
            raise ValueError(f"a minicpm_sala stack is written for {key} {want!r}; got {raw[key]!r}")
    if len(mixers) < depth or set(mixers) - {"minicpm4", "lightning-attn"}:
        raise ValueError(
            f"a minicpm_sala stack names its {depth} layers in mixer_types, each 'minicpm4' or "
            f"'lightning-attn'; got {len(mixers)}: {sorted(set(mixers))}"
        )
    published = len(mixers)
    lightning_at = [i for i, m in enumerate(mixers[:depth]) if m == "lightning-attn"]
    keys = dict(
        layer_types=tuple("lightning" if m == "lightning-attn" else "attention" for m in mixers[:depth]),
        qk_norm=False, qk_norm_per_head=bool(raw.get("qk_norm", False)),
        position_embedding_type="nope",
        attention_gate_type="elementwise" if raw.get("attn_use_output_gate", False) else "none",
    )
    if "scale_emb" in raw:  # h_0 = scale_emb E[id]
        keys["embedding_multiplier"] = float(raw["scale_emb"])
    if "scale_depth" in raw:  # a branch enters under scale_depth / sqrt(the published depth)
        keys["residual_multiplier"] = float(raw["scale_depth"]) / math.sqrt(published)
    if "dim_model_base" in raw:  # the head reads norm(h) / (hidden_size / dim_model_base)
        keys["logits_scaling"] = raw["hidden_size"] / float(raw["dim_model_base"])
    if raw.get("sparse_config") is not None:
        keys["sparse_config"] = tuple(dict(raw["sparse_config"]).items())
    keys["lightning_decays"] = known.get("lightning_decays") or lightning.decay_rates(
        lightning_at, int(heads), published
    )
    return keys


def _solar2_keys(raw: dict, depth: int) -> dict:
    """A published ``solar_open2`` config's keys as ``LlamaConfig``'s, for the
    leading ``depth`` layers: the kinds from ``gqa_layers`` (a file cut in depth
    keeps the published list whole: the entries under the depth are read); the
    expert layer's keys are the DeepSeek-V3 / GLM-4.5 family's, whose router
    ``topk_method`` "noaux_tc" computes (no key states the scoring function);
    ``n_routed_experts`` counts the experts, and a file cut to one chip's share
    gives the held count there and the router's width (``num_experts``) beside
    it; ``use_gqa_gate`` is the elementwise gate. ``intermediate_size`` sizes
    dense layers, of which ``first_k_dense_replace`` 0 leaves none. What the
    block is not written for is refused by name and never read past."""
    linear = dict(raw.get("linear_attn_config") or {})
    heads = raw.get("num_attention_heads")
    head_dim = raw.get("head_dim") or raw.get("hidden_size", 0) // max(heads or 1, 1)
    for key, want, got in (
        ("kda_use_full_proj", False, raw.get("kda_use_full_proj", False)),
        ("use_rope", False, raw.get("use_rope", False)),
        ("first_k_dense_replace", 0, raw.get("first_k_dense_replace", 0)),
        ("linear_attn_config.num_kv_heads", None, linear.get("num_kv_heads")),
        ("linear_attn_config.num_heads", heads, linear.get("num_heads", heads)),
        ("linear_attn_config.head_dim", head_dim, linear.get("head_dim", head_dim)),
        ("hidden_act", "silu", raw.get("hidden_act", "silu")),
        ("attention_bias", False, raw.get("attention_bias", False)),
    ):
        if got != want:
            raise ValueError(f"a solar_open2 stack is written for {key} {want!r}; got {got!r}")
    gqa = set(raw.get("gqa_layers") or ())
    held = raw.get("n_routed_experts", 0)
    keys = dict(
        layer_types=tuple("attention" if i in gqa else "kda" for i in range(depth)),
        position_embedding_type="nope",
        attention_gate_type="elementwise" if raw.get("use_gqa_gate", False) else "none",
        topk_method="noaux_tc", router_aux_loss_coef=0.0,
        num_experts=raw.get("num_experts", held),
        kda_short_conv=int(linear.get("short_conv_kernel_size", 4)),
    )
    if held != keys["num_experts"]:
        keys["num_local_experts"] = raw.get("num_local_experts", held)
    return keys


def _laguna_keys(raw: dict, depth: int) -> dict:
    """A published ``laguna`` config's keys as ``LlamaConfig``'s, for the
    leading ``depth`` layers: the kinds from ``layer_types``, ``mlp_layer_types``
    / ``mlp_only_layers`` and ``num_attention_heads_per_layer`` read together and
    refused where they disagree (a dense layer is a full layer; the full layers
    have ``num_attention_heads``, the sliding ones one other count); the rope
    tables by kind from ``rope_parameters`` (YaRN over the full layers' rotated
    pairs, plain rope in the sliding ones); a gate per head in every layer.
    What the block is not written for is refused by name and never read past.
    No key names a scoring function, a selection bias or an aux loss: softmax
    scores ("greedy"), none, 0."""
    for key, want in (("attention_bias", False), ("decoder_sparse_step", 1),
                      ("moe_apply_router_weight_on_input", False),
                      ("moe_router_logit_softcapping", 0), ("hidden_act", "silu")):
        if raw.get(key, want) != want:
            raise ValueError(f"a laguna stack is written for {key} {want!r}; got {raw[key]!r}")
    gating = [raw.get("gating", "per-head"), *(raw.get("gating_types") or ())[:depth]]
    if set(gating) - {"per-head", "per_head", True}:
        raise ValueError(f"a laguna stack is written for a gate per head; got {sorted(map(str, set(gating)))}")
    names = tuple(raw.get("layer_types") or ())[:depth]
    ffns = tuple(raw.get("mlp_layer_types") or ("sparse",) * depth)[:depth]
    heads = tuple(raw.get("num_attention_heads_per_layer") or ())[:depth]
    only = sorted(i for i in raw.get("mlp_only_layers") or () if i < depth)
    if len(names) != depth or len(ffns) != depth or len(heads) != depth:
        raise ValueError(
            f"a laguna stack names {depth} layers in layer_types, mlp_layer_types and "
            f"num_attention_heads_per_layer; got {len(names)}, {len(ffns)}, {len(heads)}"
        )
    if only != [i for i, f in enumerate(ffns) if f == "dense"] or set(ffns) - {"dense", "sparse"}:
        raise ValueError(
            f"mlp_only_layers {only} and mlp_layer_types disagree on the dense layers"
        )
    kinds, swa_heads = [], set()
    for i, (name, ffn, h) in enumerate(zip(names, ffns, heads)):
        if name not in ("full_attention", "sliding_attention") or (
            ffn == "dense" and name != "full_attention"
        ):
            raise ValueError(
                "a laguna stack is written for 'full_attention' and 'sliding_attention' "
                f"layers, a dense FFN under a full one; got {name!r} over {ffn!r} in layer {i}"
            )
        if name == "sliding_attention":
            swa_heads.add(h)
        elif h != raw.get("num_attention_heads"):
            raise ValueError(
                f"layer {i} is a full layer of {h} heads in num_attention_heads_per_layer "
                f"and num_attention_heads is {raw.get('num_attention_heads')}"
            )
        kinds.append("sliding" if name == "sliding_attention" else "dense" if ffn == "dense"
                     else "attention")
    if len(swa_heads) > 1:
        raise ValueError(f"the sliding layers have one head count; got {sorted(swa_heads)}")
    rope = raw.get("rope_parameters") or {}
    full, swa = rope.get("full_attention") or {}, rope.get("sliding_attention") or {}
    if full.get("rope_type", "default") not in ("default", "yarn") or (
        swa.get("rope_type", "default") != "default"
    ):
        raise ValueError(
            "a laguna stack is written for YaRN or plain rope in the full layers and "
            f"plain rope in the sliding ones; got {full.get('rope_type')!r}, {swa.get('rope_type')!r}"
        )
    keys = dict(
        layer_types=tuple(kinds),
        rope_theta=float(full.get("rope_theta", LlamaConfig.rope_theta)),
        partial_rotary_factor=float(full.get("partial_rotary_factor", 1.0)),
        swa_rope_theta=float(swa.get("rope_theta", LlamaConfig.swa_rope_theta)),
        swa_partial_rotary_factor=float(swa.get("partial_rotary_factor", 1.0)),
        sliding_window_size=int(raw.get("sliding_window", 0)),
        swa_num_attention_heads=int(swa_heads.pop()) if swa_heads else 0,
        attention_gate_type="headwise", swa_attention_gate_type="headwise",
        routed_scaling_factor=float(raw.get("moe_routed_scaling_factor", 1.0)),
        shared_intermediate_size=int(raw.get("shared_expert_intermediate_size", 0)),
    )
    if full.get("rope_type") == "yarn":
        keys["rope_yarn"] = tuple((key, full[key]) for key in _YARN_KEYS)
    return keys


@functools.lru_cache(maxsize=None)
def kind_view(cfg: LlamaConfig, kind: str) -> LlamaConfig:
    """The configuration as the layers of ``kind`` see it: attention's geometry
    is a function of the kind. For a stack without sliding layers that is
    ``cfg`` itself. For a grouped-query stack with them, a "sliding" layer's
    view holds its head count (``swa_num_attention_heads``, over the same KV
    heads), its rope (``swa_rope_theta``, ``swa_partial_rotary_factor``, no
    YaRN) and its gate under the top-level names and keeps
    ``sliding_window_size``; a full layer's ("dense", "attention") keeps the
    top-level ones and has no window: so ``_qkv``, ``_rotate_heads``, ``_rope``,
    ``decoder_block``'s gate, ``shapes`` and ``init_params`` serve both kinds.
    For a latent stack with them, a "sliding" layer's view holds the ``swa_*``
    sizes under the top-level names (heads, ranks, head sizes, rope base, gate),
    keeps ``sliding_window_size`` and has no indexer; a full layer's ("dense",
    "attention") keeps the top-level sizes and the indexer and has no window. So
    ``_latent_qkv``, ``latent_keys_values``, ``latent_absorb`` and
    ``latent_expand`` serve both, and ``view.sparse`` / ``view.sliding_window_size``
    say what the layer's attention reads. A view names no kinds of its own
    (``layer_types`` None): it sizes one layer, not a stack."""
    if not cfg.sliding:
        return cfg
    own = dict(layer_types=None, first_k_dense_replace=0)
    if not cfg.latent:
        # grouped-query kinds: a sliding layer's head count, rope (base, rotated
        # share, no stretching) and gate under the top-level names, and its
        # window; a full layer's has no window
        if kind != "sliding":
            return dataclasses.replace(cfg, **own, sliding_window_size=0)
        return dataclasses.replace(
            cfg, **own, num_attention_heads=cfg.swa_num_attention_heads,
            rope_theta=cfg.swa_rope_theta, rope_yarn=None,
            partial_rotary_factor=cfg.swa_partial_rotary_factor,
            attention_gate_type=cfg.swa_attention_gate_type,
        )
    if kind == "sliding":
        return dataclasses.replace(
            cfg, **own,
            num_attention_heads=cfg.swa_num_attention_heads, num_key_value_heads=None,
            q_lora_rank=cfg.swa_q_lora_rank, kv_lora_rank=cfg.swa_kv_lora_rank,
            qk_nope_head_dim=cfg.swa_qk_nope_head_dim,
            qk_rope_head_dim=cfg.swa_qk_rope_head_dim, v_head_dim=cfg.swa_v_head_dim,
            rope_theta=cfg.swa_rope_theta, attention_gate_type=cfg.swa_attention_gate_type,
            index_topk=0, index_n_heads=0, index_head_dim=0,
        )
    return dataclasses.replace(cfg, **own, sliding_window_size=0)


@functools.lru_cache(maxsize=None)
def lightning_view(cfg: LlamaConfig) -> LlamaConfig:
    """The configuration as a lightning layer's projections see it: heads of
    ``head_dim`` without grouping, rotated by ``rope_theta`` (``_qkv``, ``_rope``
    serve it); a view sizes one layer, not a stack."""
    return dataclasses.replace(
        cfg, layer_types=None, num_key_value_heads=None, position_embedding_type="rope",
        sparse_config=None, lightning_decays=None, attention_gate_type="none",
    )


def latent_rescale(cfg: LlamaConfig) -> tuple[float, float]:
    """(s_q, s_kv): what the normed query and key-value latents are scaled by
    under ``apply_mla_qkv_lora_rescale``, (hidden_size / rank)^1/2 each; (1, 1)
    without it."""
    if not cfg.apply_mla_qkv_lora_rescale:
        return 1.0, 1.0
    return (cfg.hidden_size / cfg.q_lora_rank) ** 0.5, (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5


def _of_the_runs_kind(cfg: LlamaConfig, body, run, positions, rope):
    """A serving forward's ``body(carry, layer, li, view=, rope=)`` for one
    run: as it is for a stack without sliding layers (its defaults are the
    configuration and the shared tables ``rope``); else under the run's kind's
    view, and for a sliding run under that kind's own rope tables."""
    if not cfg.sliding:
        return body
    view = kind_view(cfg, run.kind)
    if run.kind == "sliding":
        rope = _rope(view, positions)
    return functools.partial(body, view=view, rope=rope)


def shapes(cfg: LlamaConfig) -> dict:
    """ShapeDtypeStructs of the parameter pytree (all float32 masters)."""
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    L, Nh, Nkv, Dh = (
        cfg.num_hidden_layers,
        cfg.num_attention_heads,
        cfg.kv_heads,
        cfg.head_dim,
    )
    f32 = jnp.float32

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, f32)

    E, Eh, Fe = cfg.num_experts, cfg.held_experts, cfg.expert_width
    dense_ffn = {"gate_proj": (D, F), "up_proj": (D, F), "down_proj": (F, D)}
    ffn = dense_ffn
    if E:
        ffn = {
            "router": (D, E),
            "gate_proj": (Eh, D, Fe),
            "up_proj": (Eh, D, Fe),
            "down_proj": (Eh, Fe, D),
        }
        if cfg.topk_method == "noaux_tc" or cfg.router_hidden_size:
            ffn["router_bias"] = (E,)
        if cfg.router_hidden_size:
            # the router as an MLP (``_router_features``): its last map keeps
            # the name a linear router has
            R = cfg.router_hidden_size
            ffn.update(
                router=(R, E), router_down=(D, R), router_down_bias=(R,),
                router_gamma=(R,), router_norm=(R,), router_fc1=(R, R),
                router_fc1_bias=(R,), router_fc2=(R, R), router_fc2_bias=(R,),
            )
    if cfg.shared_width:
        Fs = cfg.shared_width
        ffn.update(
            shared_gate_proj=(D, Fs), shared_up_proj=(D, Fs), shared_down_proj=(Fs, D)
        )
    norms = {"input_norm": (D,), "post_attn_norm": (D,)}
    if cfg.residual_scaling:
        norms.update({
            f"{sub}_{part}_{what}": (D,) for sub in ("attn", "ffn")
            for part in ("stream", "branch") for what in ("scale", "bias")
        })
    def latent_leaves(view):  # one kind's latent attention, its indexer and gate
        Rq, Rkv, H = view.q_lora_rank, view.kv_lora_rank, view.num_attention_heads
        leaves = {
            "q_a_proj": (D, Rq),
            "q_a_norm": (Rq,),
            "q_b_proj": (Rq, H * view.qk_head_dim),
            "kv_a_proj": (D, view.latent_row_dim),
            "kv_a_norm": (Rkv,),
            "kv_b_proj": (Rkv, H * (view.qk_nope_head_dim + view.v_head_dim)),
            "o_proj": (H * view.v_head_dim, D),
        }
        if view.attention_gate_type == "headwise":
            leaves["attn_gate"] = (D, H)
        if view.sparse:  # its queries come from the query's latent
            Hi, Di = view.index_n_heads, view.index_head_dim
            leaves.update(
                index_q=(Rq, Hi * Di), index_k=(D, Di), index_k_norm=(Di,),
                index_k_norm_bias=(Di,), index_w=(D, Hi),
            )
        return leaves

    def gqa_leaves(view):  # one kind's grouped-query attention and its gate
        H = view.num_attention_heads
        leaves = {
            "q_proj": (D, H * Dh),
            "k_proj": (D, Nkv * Dh),
            "v_proj": (D, Nkv * Dh),
            "o_proj": (H * Dh, D),
        }
        if view.attention_gate_type == "headwise":
            leaves["attn_gate"] = (D, H)
        if view.attention_gate_type == "elementwise":  # a value each of the output's
            leaves["attn_gate"] = (D, H * Dh)
        return leaves

    of_kind = latent_leaves if cfg.latent else gqa_leaves
    attention = of_kind(kind_view(cfg, "attention"))
    if cfg.qk_norm:
        attention.update(q_norm=(Nh * Dh,), k_norm=(Nkv * Dh,))
    if cfg.qk_norm_per_head:  # one weight a head's value, shared by the heads
        attention.update(q_norm=(Dh,), k_norm=(Dh,))
    if cfg.sparse and not cfg.latent:  # the indexer: its queries, its one key (LayerNorm with bias), its head weights
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        attention.update(
            index_q=(D, Hi * Di), index_k=(D, Di), index_k_norm=(Di,),
            index_k_norm_bias=(Di,), index_w=(D, Hi),
        )
    if cfg.eva:  # the pooling's learned query and the pooled key's offset, per head
        attention.update(adaptive_phi=(Nkv, Dh), adaptive_mu_k=(Nkv, Dh))
    if cfg.cca:
        Z = (Nh + Nkv) * Dh  # q and k side by side
        attention.update(
            v_proj=(D, Nkv // 2 * Dh), v_prev_proj=(D, Nkv // 2 * Dh),
            cca_conv0_weight=(cfg.cca_time0, Z), cca_conv0_bias=(Z,),
            cca_conv1_weight=(Nh + Nkv, cfg.cca_time1, Dh, Dh), cca_conv1_bias=(Z,),
            cca_k_temp=(Nkv,),
        )

    def stack(n, *groups):
        return {k: s(n, *v) for g in groups for k, v in g.items()}

    if cfg.hybrid:
        # one stack per kind of layer, each layer with its norms and FFN: the
        # forwards scan runs of like layers out of them (``layer_runs``)
        H, P, _, K, C = mamba.sizes(cfg)
        mixer = {
            "in_proj": (D, mamba.in_proj_width(cfg)),
            "conv_weight": (K, C),
            "conv_bias": (C,),
            "dt_bias": (H,),
            "A_log": (H,),
            "D": (H,),
            "mixer_norm": (H * P,),
            "out_proj": (H * P, D),
        }
        layers = {"mamba": stack(cfg.num_mamba_layers, norms, mixer, ffn)}
        if cfg.num_attention_layers:
            layers["attention"] = stack(cfg.num_attention_layers, norms, attention, ffn)
    elif cfg.linear:
        mixer = {
            "q_proj": (D, Nh * Dh), "k_proj": (D, Nh * Dh), "v_proj": (D, Nh * Dh),
            "out_gate": (D, Nh * Dh), "o_proj": (Nh * Dh, D), "q_norm": (Dh,), "k_norm": (Dh,),
            "out_norm": (Nh * Dh,),
        }
        layers = {"lightning": stack(cfg.num_lightning_layers, norms, mixer, ffn)}
        if cfg.num_attention_layers:
            layers["attention"] = stack(cfg.num_attention_layers, norms, attention, ffn)
    elif cfg.kda:
        mixer = {
            "q_proj": (D, Nh * Dh), "k_proj": (D, Nh * Dh), "v_proj": (D, Nh * Dh),
            "o_proj": (Nh * Dh, D), "conv_weight": (cfg.kda_short_conv, 3 * Nh * Dh),
            "f_a_proj": (D, Dh), "f_b_proj": (Dh, Nh * Dh), "dt_bias": (Nh * Dh,), "A_log": (Nh,),
            "b_proj": (D, Nh), "g_a_proj": (D, Dh), "g_b_proj": (Dh, Nh * Dh),
            "g_bias": (Nh * Dh,), "out_norm": (Dh,),
        }
        layers = {"kda": stack(cfg.num_kda_layers, norms, mixer, ffn)}
        if cfg.num_attention_layers:
            layers["attention"] = stack(cfg.num_attention_layers, norms, attention, ffn)
    elif cfg.sliding:
        kinds = cfg.layer_kinds
        sliding = of_kind(kind_view(cfg, "sliding"))
        layers = {"sliding": stack(kinds.count("sliding"), norms, sliding, ffn)}
        if "dense" in kinds:
            layers["dense"] = stack(kinds.count("dense"), norms, attention, dense_ffn)
        if "attention" in kinds:
            layers["attention"] = stack(kinds.count("attention"), norms, attention, ffn)
    elif cfg.leading_dense:
        layers = {
            "dense": stack(cfg.leading_dense, norms, attention, dense_ffn),
            "attention": stack(L - cfg.leading_dense, norms, attention, ffn),
        }
    else:
        layers = stack(L, norms, attention, ffn)
    tree = {"embed_tokens": s(V, D), "layers": layers, "final_norm": s(D)}
    if not cfg.tie_word_embeddings:
        # ``num_pred_heads`` vocabularies side by side, head-major
        tree["lm_head"] = s(D, V * cfg.num_pred_heads)
    return tree


class Run(NamedTuple):
    """Consecutive layers of one kind: layers ``start`` to ``start + count``
    of that kind's stack, whose first keeps its mixer's past at index
    ``state`` of the cache (attention) or of the recurrent state (Mamba-2)."""

    kind: str  # "attention", "mamba" or "dense" (``LlamaConfig.layer_kinds``)
    start: int
    count: int
    state: int

    @property
    def mixer(self) -> str:
        return mixer_of(self.kind)


def mixer_of(kind: str) -> str:
    """A kind of layer's mixer, which is what names its past's store: "mamba",
    "sliding" (attention under a window over a ring of its own that wraps) or
    "attention" (the "dense" kind differs from "attention" in its FFN alone)."""
    return kind if kind in ("mamba", "sliding", "lightning", "kda") else "attention"


def layer_runs(cfg: LlamaConfig) -> list[Run]:
    """The stack as runs of like layers, in order: one run for a stack of
    attention layers, 5 Mamba / 1 attention / 4 Mamba for a period of the
    granite hybrid, 1 dense / 23 attention for a routed stack behind a
    leading dense layer. Each forward scans each run."""
    runs, seen, past = [], {}, {"attention": 0, "mamba": 0, "sliding": 0, "lightning": 0, "kda": 0}
    for kind in cfg.layer_kinds:
        if runs and runs[-1].kind == kind:
            runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
        else:
            runs.append(Run(kind, seen.get(kind, 0), 1, past[mixer_of(kind)]))
        seen[kind] = seen.get(kind, 0) + 1
        past[mixer_of(kind)] += 1
    return runs


# a routed layer's expert matrices, [Eh, in, out] each in a stack [L, Eh, in, out]
EXPERT_LEAVES = ("gate_proj", "up_proj", "down_proj")


class InStack(NamedTuple):
    """Layer ``index``'s part of ``stack`` [L, ...], not cut out of it: what
    ``scan_layers`` hands a serving body for each expert matrix of a routed
    layer, and what ``_grouped_matmul`` reads in place."""

    stack: jax.Array
    index: jax.Array


def scan_layers(
    cfg: LlamaConfig, body, carry, layers: dict, run: Run, unroll: int = 1,
    experts_in_place: bool = False,
):
    """``lax.scan`` of ``body(carry, layer, li) -> (carry, ys)`` over one
    run's layers: ``layer`` the layer's weights, ``li`` its index in its
    mixer's cache or state (which is its index in its kind's stack, but
    where two kinds of layer share one cache).

    A stack of like layers is the scan's ``xs``. A hybrid's run is part of
    its kind's stack: the scan runs over the indices and the body cuts its
    layer out of the whole stack, which is what a scan does with its ``xs``
    anyway; a static slice of the stack would be a copy of the run's weights
    in every call. A dynamic one is a copy too where its reader is a custom
    call, whose operand has to be a buffer of its own and cannot be fused
    with the slice: the TPU's grouped matmul (``lax.ragged_dot``) is one, so
    a routed layer's three expert matrices, cut from their stack, are written
    out in every layer of every call. With ``experts_in_place`` (the serving
    forwards) those three leaves are left out of what is cut: the body gets
    each as ``InStack(whole stack, the layer's index in its kind's stack)``,
    and ``_grouped_matmul`` reads the layer's experts where they lie. Every
    other leaf is cut all the same (XLA fuses those slices into the dots
    that read them). Training keeps the cut for the experts too:
    differentiated, each layer's weight gradient would be of the whole
    stack's size."""
    ids = run.start + jnp.arange(run.count, dtype=jnp.int32)
    stack = layers[run.kind] if cfg.layers_by_kind else layers
    whole = {}
    if experts_in_place and "router" in stack:
        whole = {name: stack[name] for name in EXPERT_LEAVES}
        stack = {name: x for name, x in stack.items() if name not in whole}

    def in_place(layer, i):  # i: the layer's index in its kind's stack
        return {**layer, **{name: InStack(x, i) for name, x in whole.items()}}

    if not cfg.layers_by_kind:
        return jax.lax.scan(
            lambda c, xs: body(c, in_place(xs[0], xs[1]), xs[1]),
            carry, (stack, ids), unroll=unroll,
        )
    ahead = run.state - run.start  # static; 0 where a kind has a cache to itself

    def indexed(c, li):
        layer = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, li, 0, keepdims=False), stack
        )
        return body(c, in_place(layer, li), li + ahead if ahead else li)

    return jax.lax.scan(indexed, carry, ids, unroll=unroll)


def init_params(rng: jax.Array, cfg: LlamaConfig) -> dict:
    """Fresh init matching HF llama conventions: normal(0, initializer_range)
    for projections/embeddings, ones for norms (init_weights.py parity)."""
    leaves, treedef = jax.tree.flatten_with_path(shapes(cfg))
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(cfg, _leaf_name(path), key, leaf)
                  for key, (path, leaf) in zip(keys, leaves)]
    )


def init_params_leafwise(rng: jax.Array, cfg: LlamaConfig, dtype) -> dict:
    """``init_params`` cast to ``dtype``, leaf for leaf the same values, each
    leaf drawn and cast in a program of its own: the device never holds the
    float32 tree, only one leaf's draw beside what is already cast (a
    configuration whose float32 tree does not fit a chip is drawn so)."""
    leaves, treedef = jax.tree.flatten_with_path(shapes(cfg))
    keys = jax.random.split(rng, len(leaves))
    draw = jax.jit(
        lambda key, name, leaf: _init_leaf(cfg, name, key, leaf).astype(dtype),
        static_argnums=(1, 2),
    )
    return jax.tree.unflatten(
        treedef, [draw(key, _leaf_name(path), leaf) for key, (path, leaf) in zip(keys, leaves)]
    )


def _leaf_name(path) -> str:
    return path[-1].key if hasattr(path[-1], "key") else str(path[-1])


def _init_leaf(cfg: LlamaConfig, name: str, key: jax.Array, leaf) -> jax.Array:
    """One leaf's fresh draw, by its name (``init_params``)."""
    if "norm" in name and cfg.norm_add_unit_offset:
        # the norm scales by 1 + w: w about zero, and away from it, so that
        # the offset is tested
        return jax.random.normal(key, leaf.shape, leaf.dtype) * 0.02
    if "norm" in name and cfg.norm_init_std:
        # away from the values that would leave a norm's weight untested
        noise = jax.random.normal(key, leaf.shape, leaf.dtype) * cfg.norm_init_std
        return noise if name.endswith("bias") else 1.0 + noise
    if "norm" in name and name.endswith("bias"):
        return jnp.zeros(leaf.shape, leaf.dtype)
    if "norm" in name or name == "D":
        return jnp.ones(leaf.shape, leaf.dtype)
    if name in ("adaptive_phi", "adaptive_mu_k"):
        # a unit-scale vector per head, each value within +-1/sqrt(Dh): the
        # pooling's scores phi . k then spread about as k's own norm does
        noise = jnp.clip(jax.random.normal(key, leaf.shape, leaf.dtype), -1.0, 1.0)
        return noise * leaf.shape[-1] ** -0.5
    if name == "router_bias":
        # a trained router's selection bias is not zero, and zero would
        # leave the term untested: N(0, 0.1^2), beside sigmoid scores in
        # (0, 1); beside softmax scores, whose mean is 1/E, a fifth of that
        sigma = 0.1 if cfg.topk_method == "noaux_tc" else 0.2 / cfg.num_experts
        return jax.random.normal(key, leaf.shape, leaf.dtype) * sigma
    if name in ("A_log", "dt_bias", "conv_weight", "conv_bias"):
        return _init_mixer_leaf(name, key, leaf)
    if name in _ZAYA_DRAWS or (name == "router" and cfg.router_hidden_size):
        return _init_zaya_leaf(name, key, leaf)
    return jax.random.normal(key, leaf.shape, leaf.dtype) * cfg.initializer_range


def _init_mixer_leaf(name: str, key: jax.Array, leaf) -> jax.Array:
    """The Mamba-2 reference initialisation (arXiv 2405.21060's code), so
    that a fresh model's decays lie where a trained one's do: ``A`` uniform
    in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1] (``dt_bias`` its inverse
    softplus), and the depthwise conv as ``torch.nn.Conv1d`` draws it,
    uniform in +-1/sqrt(K)."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, leaf.shape, leaf.dtype, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(
            jax.random.uniform(key, leaf.shape, leaf.dtype, jnp.log(1e-3), jnp.log(1e-1))
        )
        return dt + jnp.log(-jnp.expm1(-dt))
    bound = leaf.shape[-2] ** -0.5 if name == "conv_weight" else 0.5
    return jax.random.uniform(key, leaf.shape, leaf.dtype, -bound, bound)


# the leaves of a ZAYA block that are not drawn N(0, initializer_range)
_ZAYA_DRAWS = (
    "cca_conv0_weight", "cca_conv1_weight", "cca_k_temp", "router_gamma",
    "router_fc1", "router_fc2",
    *(f"{sub}_{part}_scale" for sub in ("attn", "ffn") for part in ("stream", "branch")),
)


def _init_zaya_leaf(name: str, key: jax.Array, leaf) -> jax.Array:
    """The draws of a ZAYA block that no config key fixes, each away from the
    value that would leave its term untested: the convolutions as
    ``torch.nn.Conv1d`` draws them (uniform in +-1/sqrt(fan-in): taps for the
    depthwise one, taps x a head's values for the grouped one); the learned
    factors (k's temperature, the residual scales) 1 + N(0, 0.05^2); the
    router's carry-over ``gamma`` 0.5 + N(0, 0.1^2); the router MLP's three
    maps N(0, 2 / fan-in), so that its logits spread about as a trained
    router's do (a standard deviation of 1.2 at the published widths, the
    largest probability 0.3 on average: drawn N(0, 0.02^2) they would all but
    vanish through two GELUs and the selection bias alone would choose, one
    expert for every token)."""
    if name.startswith("cca_conv"):  # [taps, channels] or [heads, taps, in, out]
        fan_in = leaf.shape[-2] * (leaf.shape[-3] if name == "cca_conv1_weight" else 1)
        bound = fan_in**-0.5
        return jax.random.uniform(key, leaf.shape, leaf.dtype, -bound, bound)
    noise = jax.random.normal(key, leaf.shape, leaf.dtype)
    if name in ("router", "router_fc1", "router_fc2"):
        return noise * (2.0 / leaf.shape[-2]) ** 0.5
    if name == "router_gamma":
        return 0.5 + 0.1 * noise
    return 1.0 + 0.05 * noise


# the rematerialization policy accepted everywhere a `remat` argument
# appears (``_maybe_remat`` says what each value saves)
RematPolicy = Union[bool, Literal["none", "full", "dots", "dots_all"]]
# the names an attention implementation gives its output and its softmax
# statistic (``checkpoint_name``), which every checkpointing policy keeps
ATTN_RESIDUALS = ("attn_out", "attn_lse")


def _maybe_remat(block, remat: RematPolicy):
    """Apply the rematerialization policy to a per-layer block function.

    remat=False/"none": save all activations (no recompute -- fastest when
    they fit); True/"full": save the layer boundaries (reference-style full
    checkpointing) and, where the attention in the block tagged them, its
    output and log-sum-exp (``ATTN_RESIDUALS``); "dots": save matmul/MXU
    outputs too and recompute the cheap elementwise chain (norms, rope,
    silu) -- recovers most of full remat's memory while skipping the extra
    forward through the matmuls, which is where ~all the FLOPs are.

    The two names are what the attention kernels' own VJPs keep
    (ops/flash_attention._flash_fwd, both forms of ops/ring_attention):
    they are custom calls and whole rings of ppermutes, so a policy that
    drops them reruns the forward kernel in the backward only to rebuild
    them. Saved, the backward still recomputes norms, projections and the
    FFN (rotary too, where it is not inside the kernels: ``RowsAttend``),
    and the kernel runs once. The price per device and layer
    is B x T x Hq x Dh elements of the compute dtype plus B x Hq x T
    float32 (``attn_residual_bytes``): as much again as the layer's input
    where Hq x Dh is the hidden size, and about twice that in the compiled
    step's temporaries on a v5e (PERF.md section 4: the two training
    cells before and after), so a job that sat at the memory limit under
    ``remat=True`` no longer fits. Where nothing carries the names (XLA's
    attention, a Mamba-2 mixer, the latent and the EVA form) the policy
    keeps nothing and the program is the bare checkpoint's."""
    if remat in (False, None, "none"):
        return block
    names = jax.checkpoint_policies.save_only_these_names(*ATTN_RESIDUALS)
    if remat in (True, "full"):
        return jax.checkpoint(block, policy=names)
    if remat in ("dots", "dots_all"):
        # "dots_all" saves batched dots too (the XLA-attention
        # score/weighted-sum matmuls), trading more HBM for less backward
        # recompute
        dots = (
            jax.checkpoint_policies.dots_saveable
            if remat == "dots_all"
            else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
        return jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.save_from_both_policies(dots, names),
        )
    raise ValueError(f"unknown remat policy {remat!r}")


def attn_residual_bytes(cfg: LlamaConfig, batch: int, seq: int, dtype) -> int:
    """Bytes of ``ATTN_RESIDUALS`` over the stack for ``batch`` rows of
    ``seq`` tokens: a layer with attention keeps its output, rows
    [B, T, Hq * Dh] in ``dtype``, and its log-sum-exp [B, Hq, T] in float32,
    a Mamba-2 layer nothing."""
    layers = sum(mixer_of(kind) == "attention" for kind in cfg.layer_kinds)
    per_row = cfg.head_dim * jnp.dtype(dtype).itemsize + 4
    return layers * batch * seq * cfg.num_attention_heads * per_row


def _rms_norm(x: jax.Array, weight: jax.Array, eps: float, unit_offset: bool = False) -> jax.Array:
    # variance in float32 for stability (HF llama semantics); with
    # ``unit_offset`` the scale is 1 + weight (``norm_add_unit_offset``)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    scale = weight.astype(jnp.float32)
    if unit_offset:
        scale = 1.0 + scale
    return (xf * scale).astype(x.dtype)


def _block_norm(cfg: LlamaConfig, h: jax.Array, weight: jax.Array) -> jax.Array:
    """A sublayer's (or the head's) RMSNorm of the stream h, handed on in the
    weights' dtype: the compute dtype, which is h's own but under
    ``fp32_skip_add``, where the stream is float32 and its branches are not."""
    x = _rms_norm(h, weight, cfg.rms_norm_eps, cfg.norm_add_unit_offset)
    return x.astype(weight.dtype) if cfg.fp32_skip_add else x


def _yarn_frequencies(d: int, theta: float, scaling: tuple) -> tuple:
    """YaRN's (arXiv 2309.00071) d / 2 rotation frequencies as a published
    ``rope_parameters`` entry states them (``LlamaConfig.rope_yarn``), and
    the factor its cos and sin carry: pair i turns at ``f_i = theta^(-2i/d)``
    below the ramp (wavelengths that fit ``original_max_position_embeddings``
    ``beta_fast`` times and more), at ``f_i / factor`` above it (those that fit
    ``beta_slow`` times and fewer), and in between at ``f_i (1 - r_i) + f_i /
    factor r_i``, ``r_i = clip((i - low) / (high - low), 0, 1)``; ``low`` and
    ``high`` the pairs whose wavelengths fit ``beta_fast`` and ``beta_slow``
    times, rounded down and up and held to [0, d - 1]. -> ([d / 2] float32,
    ``attention_factor``)."""
    p = dict(scaling)

    def pair_of(turns):  # the pair whose wavelength fits the original context ``turns`` times
        fit = p["original_max_position_embeddings"] / (turns * 2 * math.pi)
        return d * math.log(fit) / (2 * math.log(theta))

    low = max(math.floor(pair_of(p["beta_fast"])), 0)
    high = min(math.ceil(pair_of(p["beta_slow"])), d - 1)
    high = high + 0.001 if high == low else high
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    r = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(f * (1 - r) + f / p["factor"] * r, jnp.float32), p["attention_factor"]


def _rope_tables(
    positions: jax.Array, d: int, theta: float, sections: Optional[tuple] = None,
    scaling: Optional[tuple] = None,
) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) [B, T, 1, D/2] float32 for the given positions.

    Hoisted out of the layer scan: the tables are shared by every layer's
    q and k, so the cos/sin transcendentals run once per step instead of
    2*num_layers times.

    ``positions`` [3, B, T] with ``sections`` (``mrope_section``): frequency
    pair i turns by the first row for i < sections[0], by the second for the
    next sections[1] pairs, by the third for the rest. ``scaling``: YaRN's
    frequencies in the plain ones' place, cos and sin times its factor
    (``_yarn_frequencies``; the Hugging Face reading: the tables carry it, so
    the rotated part of q and of k does and the rest of the head does not)."""
    if scaling is not None:
        inv_freq, factor = _yarn_frequencies(d, theta, scaling)
        angles = positions[..., None].astype(jnp.float32) * inv_freq
        return (jnp.cos(angles) * factor)[:, :, None, :], (jnp.sin(angles) * factor)[:, :, None, :]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [(3,) B, T, D/2]
    if positions.ndim == 3:
        row = jnp.asarray([r for r, n in enumerate(sections) for _ in range(n)], jnp.int32)
        angles = jnp.take_along_axis(angles, row[None, None, None, :], axis=0)[0]
    return jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]


def _rope_apply(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [B, T, H, D] by precomputed tables (HF half-rotation layout).

    Rotation happens in x's dtype (HF llama applies rope in the input dtype
    too): the tables are f32 but cos/sin magnitudes are <= 1, so bf16
    rotation loses no more precision than the bf16 q/k it feeds -- and the
    [B, T, H, D] elementwise chain stays off the f32 HBM budget."""
    c = cos.astype(x.dtype)
    s = sin.astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate((x1 * c - x2 * s, x2 * c + x1 * s), axis=-1)


def _rope(cfg: LlamaConfig, positions: jax.Array):
    """The layers' shared (cos, sin) tables, or (None, None) for a model
    whose attention takes no positions (``position_embedding_type`` nope)."""
    if cfg.position_embedding_type == "nope":
        return None, None
    d = cfg.qk_rope_head_dim if cfg.latent else cfg.rotary_dim
    if positions.ndim == 3 and cfg.mrope_section is None:
        raise ValueError(
            "three rows of positions [3, B, T] need a configuration with an mrope_section"
        )
    return _rope_tables(positions, d, cfg.rope_theta, cfg.mrope_section, cfg.rope_yarn)


def _index_rope(cfg: LlamaConfig, positions: jax.Array):
    """The indexer's (cos, sin): all ``index_head_dim`` values of an index
    query and key turn by the temporal row of the positions; None without an
    indexer."""
    if not cfg.sparse or cfg.latent:  # a latent layer's indexer turns by the layer's own
        return None
    if positions.ndim == 3:
        positions = positions[0]
    return _rope_tables(positions, cfg.index_head_dim, cfg.rope_theta)


def _rotate_heads(cfg: LlamaConfig, x: jax.Array, cos, sin) -> jax.Array:
    """Heads x [B, T, H, Dh] rotated by position over their first
    ``cfg.rotary_dim`` values (all of them, but under a
    ``partial_rotary_factor``), or as they are where ``cos`` is None."""
    if cos is None:
        return x
    rot = cfg.rotary_dim
    if rot == x.shape[-1]:
        return _rope_apply(x, cos, sin)
    return jnp.concatenate((_rope_apply(x[..., :rot], cos, sin), x[..., rot:]), axis=-1)


def _qkv_rows(cfg: LlamaConfig, x: jax.Array, layer: dict):
    """The attention block's projections of x [B, T, D] as the matmuls leave
    them: q [B, T, Nh * Dh], k and v [B, T, Nkv * Dh], unrotated. With
    ``cfg.qk_norm`` q and k pass an RMSNorm over their whole width (OLMoE).
    Every attention reader scales the scores by 1/sqrt(Dh); a configuration
    that states another scale (``attention_multiplier``) has the ratio put on
    q here."""
    q = x @ layer["q_proj"]
    k = x @ layer["k_proj"]
    v = x @ layer["v_proj"]
    if cfg.qk_norm:
        q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    if cfg.attention_multiplier is not None:
        q = q * jnp.asarray(cfg.attention_multiplier * cfg.head_dim**0.5, q.dtype)
    return q, k, v


def _qkv(cfg: LlamaConfig, x: jax.Array, layer: dict, cos, sin, whole_rows: bool = False):
    """``_qkv_rows`` split into heads: q [B, T, Nh, Dh] and k [B, T, Nkv, Dh]
    rotated by position (as they are where ``cos`` is None), and v; q and k
    under an RMSNorm per head first where the configuration has one
    (``qk_norm_per_head``). ``whole_rows``: the projections' rows are made
    whole before a head is split off (a decode step's lightning layers: fused
    with the norm per head, the chip's compiler wants each projection's stack
    of all layers in another order and copies it in every step)."""
    B, T, _ = x.shape
    Nh, Nkv, Dh = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    q, k, v = _qkv_rows(cfg, x, layer)
    if whole_rows:
        q, k, v = jax.lax.optimization_barrier((q, k, v))
    q, k, v = q.reshape(B, T, Nh, Dh), k.reshape(B, T, Nkv, Dh), v.reshape(B, T, Nkv, Dh)
    if cfg.qk_norm_per_head:
        q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    return _rotate_heads(cfg, q, cos, sin), _rotate_heads(cfg, k, cos, sin), v


@dataclasses.dataclass(frozen=True)
class RowsAttend:
    """The ``attend`` of an attention that takes the projections' own rows:
    ``fn(q [B, T, Nh * Dh], k, v [B, T, Nkv * Dh], head_dim=, rope=)`` -> [B,
    T, Nh * Dh], q and k unrotated and ``rope`` the kernels' tables
    (``ops.flash_attention.rope_rows``; None: no positions). Handed one,
    ``decoder_block`` splits nothing into heads between the projections and
    ``o_proj``."""

    fn: Any
    head_dim: int
    rope: Any

    def __call__(self, q, k, v):
        return self.fn(q, k, v, head_dim=self.head_dim, rope=self.rope)


def takes_rows(cfg: LlamaConfig) -> bool:
    """Whether training's attention can take this configuration's q, k, v as
    rows: they are ``_qkv``'s (no latent rows, no CCA, no EVA pooling, no
    indexer beside them) and need nothing a head at a time but the rotation
    (no ``qk_norm_per_head``)."""
    return not (cfg.latent or cfg.cca or cfg.eva or cfg.sparse or cfg.qk_norm_per_head)


def rows_attend(cfg: LlamaConfig, attn_fn, cos, sin) -> Optional[RowsAttend]:
    """``RowsAttend`` over an ``attn_fn`` marked ``takes_rows`` (the flash
    kernels' entries: what ``forward`` builds under ``attn_impl=pallas``) for
    a configuration that ``takes_rows``; None for an ``attn_fn`` over heads
    alone."""
    if not getattr(attn_fn, "takes_rows", False) or not takes_rows(cfg):
        return None
    rope = None
    if cos is not None:
        from opendiloco_tpu.ops.flash_attention import rope_rows

        rope = rope_rows(cos, sin, cfg.head_dim)
    return RowsAttend(attn_fn, cfg.head_dim, rope)


def _index_qkw(cfg: LlamaConfig, x: jax.Array, layer: dict, cos, sin, c_q=None):
    """The indexer's projections of the layer's normed input x [B, T, D] ->
    (index queries [B, T, Hi, Di], the tokens' index keys [B, T, Di], the
    queries' head weights [B, T, Hi]): queries and the one key rotated by
    position, the key under a LayerNorm with bias before it (mean and variance
    in float32). The tables say how much turns: whole where they span
    ``index_head_dim`` (``_index_rope``), the first values alone where they
    are a latent layer's own (``qk_rope_head_dim``). ``c_q`` [B, T, Rq]: the
    query's latent, from which a latent layer's index queries are projected
    (None: from x)."""
    B, T, _ = x.shape
    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    rot = 2 * cos.shape[-1]

    def turn(a):  # [B, T, H, Di]
        if rot == Di:
            return _rope_apply(a, cos, sin)
        return jnp.concatenate((_rope_apply(a[..., :rot], cos, sin), a[..., rot:]), axis=-1)

    qi = turn(((x if c_q is None else c_q) @ layer["index_q"]).reshape(B, T, Hi, Di))
    kf = (x @ layer["index_k"]).astype(jnp.float32)
    kf = kf - jnp.mean(kf, axis=-1, keepdims=True)
    kf = kf * jax.lax.rsqrt(jnp.mean(kf * kf, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    kf = kf * layer["index_k_norm"].astype(jnp.float32) + layer["index_k_norm_bias"].astype(jnp.float32)
    ki = turn(kf.astype(x.dtype)[:, :, None])[:, :, 0]
    return qi, ki, x @ layer["index_w"]


def _cca_qkv(cfg: LlamaConfig, x: jax.Array, layer: dict, cos, sin, past=None):
    """CCA's projections of x [B, T, D] (arXiv 2510.04476) -> (q [B, T, Nh,
    Dh], k and v [B, T, Nkv, Dh], tails [B, T, ``cfg.cca_state_dim``]).

    q and k are projected to their heads' own width and pass, side by side as
    z [.., (Nh + Nkv) Dh], two causal convolutions over 2 tokens: a depthwise
    one, c_t = w0[0] z_{t-1} + w0[1] z_t + b0, and one grouped by head (each
    head a Dh x Dh map a tap), d_t = W1[g, 0] c_{t-1} + W1[g, 1] c_t + b1.
    To d are added the means of the projections themselves: a query head takes
    (its own + its KV group's k) / 2, a KV head (the mean of its group's query
    heads + its own) / 2. Each head is then L2-normalised to norm sqrt(Dh), k
    further scaled by a learned temperature per KV head, and both rotated.
    The first half of the KV heads hold the token's own values, the second
    half the token before's (a projection of their own).

    So position t reads t - 1 three times over: z, c and the values. ``past``
    [B, cca_state_dim] holds the three of the token before x's first (a
    decode step's slot state); None: x starts its sequence and they are zero.
    ``tails`` is what each position would hand the next: a decode step stores
    its one, a prefill the one at the prompt's true length."""
    B, T, _ = x.shape
    Nh, Nkv, Dh = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    rep, Zq = Nh // Nkv, Nh * Dh
    z = jnp.concatenate((x @ layer["q_proj"], x @ layer["k_proj"]), axis=-1)
    u = x @ layer["v_prev_proj"]  # what the next token takes as its values
    Z = z.shape[-1]
    before = (None,) * 3 if past is None else (
        past[:, :Z], past[:, Z : 2 * Z], past[:, 2 * Z :]
    )

    def back(a, first):  # a [B, T, W] one token back; ``first`` [B, W] before a[:, 0]
        first = jnp.zeros_like(a[:, :1]) if first is None else first[:, None].astype(a.dtype)
        return jnp.concatenate((first, a[:, :-1]), axis=1)

    w0, w1 = layer["cca_conv0_weight"], layer["cca_conv1_weight"]
    c = back(z, before[0]) * w0[0] + z * w0[1] + layer["cca_conv0_bias"]
    heads = lambda a: a.reshape(B, T, Nh + Nkv, Dh)
    d = (
        jnp.einsum("bthi,hio->btho", heads(back(c, before[1])), w1[:, 0])
        + jnp.einsum("bthi,hio->btho", heads(c), w1[:, 1])
        + layer["cca_conv1_bias"].reshape(Nh + Nkv, Dh)
    ).astype(x.dtype)
    zq, zk = z[..., :Zq].reshape(B, T, Nkv, rep, Dh), z[..., Zq:].reshape(B, T, Nkv, 1, Dh)
    half = jnp.asarray(0.5, x.dtype)
    q = d[:, :, :Nh] + ((zq + zk) * half).reshape(B, T, Nh, Dh)
    k = d[:, :, Nh:] + (jnp.mean(zq, axis=3) + zk[:, :, :, 0]) * half
    # each head to norm sqrt(Dh): an RMSNorm of the head whose weight is one
    # for q and the KV head's temperature for k
    q = _rms_norm(q, jnp.ones((), x.dtype), cfg.rms_norm_eps)
    k = _rms_norm(k, layer["cca_k_temp"][:, None], cfg.rms_norm_eps)
    v = jnp.concatenate((x @ layer["v_proj"], back(u, before[2])), axis=-1)
    tails = jnp.concatenate((z, c.astype(x.dtype), u), axis=-1)
    return (
        _rotate_heads(cfg, q, cos, sin), _rotate_heads(cfg, k, cos, sin),
        v.reshape(B, T, Nkv, Dh), tails,
    )


def _latent_qkv(cfg: LlamaConfig, x: jax.Array, layer: dict, cos, sin):
    """The latent attention's projections of x [B, T, D] -> (q [B, T, Nh,
    nope + rope], each head its unrotated part then its rotated one; the
    token's latent row [B, T, kv_lora_rank + rope]: the latent under its
    RMSNorm, then the one rotated key part all heads share). The row is what
    the cache keeps; ``latent_keys_values`` rebuilds k and v from it and
    ``latent_absorb`` / ``latent_expand`` compute the same attention without
    them; and the query's latent c_q [B, T, q_lora_rank], which a layer's
    indexer reads). ``cfg`` is the layer's kind's view (``kind_view``); both
    normed latents are scaled under ``apply_mla_qkv_lora_rescale``."""
    B, T, _ = x.shape
    Nh, Dn, Dr, R = (
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.kv_lora_rank,
    )
    s_q, s_kv = latent_rescale(cfg)
    c_q = _rms_norm(x @ layer["q_a_proj"], layer["q_a_norm"], cfg.rms_norm_eps)
    if s_q != 1.0:
        c_q = c_q * jnp.asarray(s_q, c_q.dtype)
    q = (c_q @ layer["q_b_proj"]).reshape(B, T, Nh, Dn + Dr)
    q = jnp.concatenate((q[..., :Dn], _rope_apply(q[..., Dn:], cos, sin)), axis=-1)
    row = x @ layer["kv_a_proj"]  # [B, T, R + Dr]
    c_kv = _rms_norm(row[..., :R], layer["kv_a_norm"], cfg.rms_norm_eps)
    if s_kv != 1.0:
        c_kv = c_kv * jnp.asarray(s_kv, c_kv.dtype)
    k_r = _rope_apply(row[..., None, R:], cos, sin)[:, :, 0]  # one head
    return q, jnp.concatenate((c_kv, k_r), axis=-1), c_q


def _kv_b_heads(cfg: LlamaConfig, w_kvb: jax.Array) -> jax.Array:
    """``kv_b_proj`` [R, Nh * (nope + v)] as [R, Nh, nope + v]: head i's
    columns rebuild its unrotated key part, then its values."""
    return w_kvb.reshape(cfg.kv_lora_rank, cfg.num_attention_heads, -1)


def latent_keys_values(cfg: LlamaConfig, row: jax.Array, w_kvb: jax.Array):
    """Latent rows [B, T, R + rope] -> (k [B, T, Nh, nope + rope], v [B, T,
    Nh, v]): the rebuilt form, in which latent attention is multi-head
    attention (training, prefill, the reference)."""
    Dn, R = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    kv = jnp.einsum("btr,rhd->bthd", row[..., :R], _kv_b_heads(cfg, w_kvb))
    k_r = jnp.broadcast_to(
        row[:, :, None, R:], (*kv.shape[:3], cfg.qk_rope_head_dim)
    )
    return jnp.concatenate((kv[..., :Dn], k_r), axis=-1), kv[..., Dn:]


def rebuilt_attend(cfg: LlamaConfig, attn_fn):
    """The ``attend(q, rows, kv_b_proj)`` of the rebuilt form: ``attn_fn(q, k,
    v)`` over the keys and values that the rows give."""
    return lambda q, rows, w_kvb: attn_fn(q, *latent_keys_values(cfg, rows, w_kvb))


def latent_attend(cfg: LlamaConfig, attn_fn):
    """The ``attend`` of one kind of latent layer (``cfg`` its ``kind_view``)
    in the rebuilt form over a whole sequence from position 0 (training,
    evaluation, a whole-prompt prefill): ``attn_fn(q, k, v)`` over the keys and
    values that the rows give; under an indexer ``attend(q, rows, kv_b_proj,
    qi, ki, wi)`` scores and chooses first (``odtp_dsa_index``, under
    ``stop_gradient``) and attends over the chosen rows (``odtp_dsa_attn``);
    under a window each query over the rows of its last ``sliding_window_size``
    positions."""
    if cfg.sparse:
        def attend(q, rows, w_kvb, qi, ki, wi):
            with jax.named_scope("odtp_dsa_index"):
                chosen = jax.lax.stop_gradient(causal_selection(qi, wi, ki, cfg.index_topk))
            with jax.named_scope("odtp_dsa_attn"):
                return sparse_attention(q, *latent_keys_values(cfg, rows, w_kvb), chosen)

        return attend
    if cfg.sliding_window_size:
        def under_window(q, rows, w_kvb):
            with jax.named_scope("odtp_swa"):
                return window_attention(
                    q, *latent_keys_values(cfg, rows, w_kvb), cfg.sliding_window_size
                )

        return under_window
    return rebuilt_attend(cfg, attn_fn)


def kinds_attend(cfg: LlamaConfig, attn_fn):
    """The ``attend(q, k, v)`` of one kind of grouped-query layer in a stack of
    full and sliding ones (``cfg`` its ``kind_view``) over a whole sequence
    from position 0 (training, evaluation, a whole-prompt prefill): a full
    layer's is ``attn_fn`` (scope ``odtp_full_attn``), a sliding layer's each
    query over the rows of its last ``sliding_window_size`` positions in the
    banded XLA form (``odtp_swa``)."""
    if cfg.sliding_window_size:
        def under_window(q, k, v):
            with jax.named_scope("odtp_swa"):
                return window_attention(q, k, v, cfg.sliding_window_size)

        return under_window

    def over_every_row(q, k, v):
        with jax.named_scope("odtp_full_attn"):
            return attn_fn(q, k, v)

    return over_every_row


def causal_prefill_heads(cfg: LlamaConfig) -> Optional[tuple]:
    """(query heads, KV heads, the keys' head size, the values') of the
    attention that ``prefill_forward`` runs as plain causal attention over a
    whole prompt, the rebuilt latent form's among them; None where it runs none
    (EVA, an indexer, a stack with sliding layers: each has its own
    ``attend``). What ``decode_kernels.prefill_form`` is asked with."""
    if cfg.eva or cfg.sparse or cfg.sliding:
        return None
    heads = cfg.num_attention_heads
    if cfg.latent:
        return heads, heads, cfg.qk_head_dim, cfg.v_head_dim
    return heads, cfg.kv_heads, cfg.head_dim, cfg.head_dim


def latent_absorb(cfg: LlamaConfig, q: jax.Array, w_kvb: jax.Array) -> jax.Array:
    """q [S, Nh, nope + rope] -> the query against a cached latent row, [S,
    Nh, R + rope]: head i's unrotated part through W_UK_i^T (the key half of
    ``kv_b_proj``), so that q_lat . c_kv = q_nope . k_nope, and the rotated
    part as it is. The absorbed form, in which no key is rebuilt (decode)."""
    Dn = cfg.qk_nope_head_dim
    w_uk = _kv_b_heads(cfg, w_kvb)[..., :Dn]  # [R, Nh, nope]
    q_lat = jnp.einsum("shn,rhn->shr", q[..., :Dn], w_uk)
    return jnp.concatenate((q_lat.astype(q.dtype), q[..., Dn:]), axis=-1)


def latent_expand(cfg: LlamaConfig, o_lat: jax.Array, w_kvb: jax.Array) -> jax.Array:
    """The attention's weighted sum of latents [S, Nh, R] -> each head's
    values [S, Nh, v], through W_UV_i (the value half of ``kv_b_proj``): the
    sum commutes with the projection, so no value is rebuilt either."""
    w_uv = _kv_b_heads(cfg, w_kvb)[..., cfg.qk_nope_head_dim:]  # [R, Nh, v]
    return jnp.einsum("shr,rhv->shv", o_lat, w_uv).astype(o_lat.dtype)


INDEXER_LEAVES = ("index_q", "index_k", "index_k_norm", "index_k_norm_bias", "index_w")


def untrained_by_the_lm_loss(cfg: LlamaConfig) -> tuple:
    """The layer leaves that the next-token loss gives no gradient: an
    indexer's (it chooses under ``stop_gradient``; its own alignment loss is
    in no config key and is not built). A trainer says so by name."""
    return INDEXER_LEAVES if cfg.sparse else ()


def sparse_attend(cfg: LlamaConfig):
    """The ``attend(q, k, v, qi, ki, wi)`` of learned sparse attention over a
    whole sequence from position 0 (training, evaluation, a whole-prompt
    prefill): the indexer's scores and the selection (scope
    ``odtp_dsa_index``; under ``stop_gradient``: the LM loss trains no
    indexer), then attention over the chosen rows (``odtp_dsa_attn``)."""

    def attend(q, k, v, qi, ki, wi):
        with jax.named_scope("odtp_dsa_index"):
            rows = jax.lax.stop_gradient(causal_selection(qi, wi, ki, cfg.index_topk))
        with jax.named_scope("odtp_dsa_attn"):
            return sparse_attention(q, k, v, rows)

    return attend


def block_attend(cfg: LlamaConfig, attn_fn, length=None):
    """The ``attend(q, k, v)`` of attention under a selection by blocks
    (``cfg.blocks``) over a whole sequence from position 0 (training,
    evaluation, a whole-prompt prefill of ``length`` real tokens): the pooled
    keys, the scores and the choice (scope ``odtp_block_select``; under
    ``stop_gradient``, as the family trains it), then attention over the
    chosen blocks' rows (``odtp_block_attn``). A sequence of fewer than
    ``dense_len`` tokens reads every row and scores nothing (``attn_fn``)."""
    sizes = cfg.block_sizes

    def attend(q, k, v):
        t = q.shape[1]
        if t < sizes.dense_len:
            with jax.named_scope("odtp_block_attn"):
                return attn_fn(q, k, v)
        dense = jnp.broadcast_to(False if length is None else length < sizes.dense_len, (t,))
        with jax.named_scope("odtp_block_select"):
            chosen = jax.lax.stop_gradient(causal_block_selection(q, k, sizes, dense))
        with jax.named_scope("odtp_block_attn"):
            return block_sparse_attention(q, k, v, chosen, sizes.block_size)

    return attend


def lightning_mix(
    cfg: LlamaConfig, rope, li, state=None, length=None, left: Optional[list] = None,
    whole_rows: bool = False,
):
    """The ``mix(x, layer)`` of lightning layer ``li`` (traced: its index among
    the lightning layers, which names its decays) over runs of tokens x [B, T,
    D] that enter with ``state`` [B, H, D, D] (None: a sequence's start), of
    which ``length`` are real; what the run leaves goes into ``left``."""
    view = lightning_view(cfg)

    def mix(x, layer):
        q, k, v = _qkv(view, x, layer, *rope, whole_rows=whole_rows)
        with jax.named_scope("odtp_lightning"):  # the recurrence alone: the projections lie around it
            o, new = lightning.chunked(q, k, v, lightning.rates(cfg, li), state, length)
        if left is not None:
            left.append(new)
        return lightning.gated_out(cfg, o, x, layer)

    return mix


def kda_mix(cfg: LlamaConfig, state=None, tail=None, length=None, left: Optional[list] = None):
    """The ``mix(x, layer)`` of a kda layer over runs of tokens x [B, T, D] that
    enter with ``state`` [B, H, D, D] and ``tail`` [B, taps - 1, 3 H D] (None: a
    sequence's start), of which ``length`` are real; the state and the tail the
    run leaves go into ``left``."""

    def mix(x, layer):
        with jax.named_scope("odtp_kda_conv"):  # convolution, SiLU, the two norms, decay and beta
            q, k, v, g, beta, new_tail = kda.conv_inputs(cfg, x, layer, tail, length)
        with jax.named_scope("odtp_kda"):  # the recurrence alone: the projections lie around it
            o, new = kda.chunked(q, k, v, g, beta, state, length)
        if left is not None:
            left.extend((new, new_tail))
        return kda.gated_out(cfg, o, x, layer)

    return mix


def eva_attend(cfg: LlamaConfig, length=None, kept: Optional[list] = None, prefill: bool = False):
    """The ``attend(q, k, v, adaptive_phi, adaptive_mu_k)`` of EVA over a whole
    sequence from position 0 (training, prefill): the chunks pooled (scope
    ``odtp_eva``), then each query over its window's rows and the pooled rows
    of the windows before. ``length`` (traced): the sequence's true length
    under a bucket's padding, which then enters no chunk. A caller that keeps
    the pooling passes a list as ``kept``: (kbar, vbar [B, J, Kh, D], stats
    [B, J, Kh, 2 D + 2]) are appended to it. ``prefill`` (a serving prefill:
    no gradient) takes ``eva_prefill_attention``, which on the chip runs each
    window's own rows through the flash kernel and merges the pooled rows in
    (``decode_kernels.eva_prefill_form``: by the platform and the tiling)."""
    over_windows = eva_prefill_attention if prefill else eva_attention

    def attend(q, k, v, phi, mu):
        with jax.named_scope("odtp_eva"):
            kbar, vbar, stats = eva_pool(k, v, phi, mu, cfg.chunk_size, length)
        if kept is not None:
            kept.extend((kbar, vbar, stats))
        return over_windows(
            q, k, v, kbar, vbar, window=cfg.window_size, chunk=cfg.chunk_size
        )

    return attend


def _grouped_matmul(xs: jax.Array, w, sizes: jax.Array) -> jax.Array:
    """Rows ``xs`` [M, in], sorted by expert with ``sizes`` [Eh] rows each,
    through each expert's own matrix -> [M, out]; rows behind the last group
    come out zero. ``w`` is what the caller holds of the layer's experts: its
    matrices [Eh, in, out], or ``InStack`` of the stack [L, Eh, in, out] they
    lie in. Then the stack is read as L * Eh groups (a reshape of the whole
    buffer, which moves nothing) of which all but the layer's own are empty:
    an empty group gets no tile of the TPU's grouped matmul, whose weight
    window follows the group's id, so the layer's experts are read where they
    lie and no other layer's are touched. The groups with rows, their rows
    and their matrices are the same either way, and so is the product."""
    if isinstance(w, InStack):
        L, Eh = w.stack.shape[:2]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * Eh,), sizes.dtype), sizes, (w.index * Eh,)
        )
        w = w.stack.reshape(L * Eh, *w.stack.shape[2:])
    return jax.lax.ragged_dot(xs, w, sizes)


def _router_features(cfg: LlamaConfig, xf: jax.Array, layer: dict, carried):
    """What the router's last map ``layer["router"]`` reads of the tokens xf
    [N, D] -> (the features, what the next layer's router is handed): the
    tokens themselves and nothing, for a linear router. ZAYA's router (arXiv
    2511.17127; the layer holds ``router_down``) projects the tokens down to
    ``router_hidden_size``, adds the layer before's such state ``carried`` [N,
    R] under a learned factor per channel (None: this is the first layer),
    hands that sum on as it is, and passes its RMSNorm through two maps of
    that width with a GELU (erf) behind each, accumulated in float32."""
    if "router_down" not in layer:
        return xf, None
    r = jnp.dot(xf, layer["router_down"]) + layer["router_down_bias"]
    if carried is not None:
        r = r + layer["router_gamma"] * carried.reshape(r.shape)
    s = _rms_norm(r, layer["router_norm"], cfg.rms_norm_eps)
    for fc in ("router_fc1", "router_fc2"):
        s = jnp.dot(s, layer[fc], preferred_element_type=jnp.float32)
        s = jax.nn.gelu(s + layer[fc + "_bias"].astype(jnp.float32), approximate=False)
        s = s.astype(xf.dtype)
    return s, r


def _routed_ffn(
    cfg: LlamaConfig, x: jax.Array, layer: dict, live: Optional[jax.Array],
    features: Optional[jax.Array] = None, chosen: Optional[list] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routed expert FFN over x [..., D], no token dropped ->
    (out, weighted aux loss, routing counts).

    Router logits and softmax in float32; each token's k largest
    probabilities are its experts' weights, renormalised only under
    ``norm_topk_prob``. The token-expert pairs are sorted by expert, so that
    each expert's rows are contiguous and the three projections are grouped
    matmuls over them (``lax.ragged_dot``: its group dimension is the
    weights' expert dimension, which the "ep" mesh axis shards); the pairs'
    outputs go back to token order by the inverse permutation and are
    summed under their weights. The three go through ``_grouped_matmul``,
    which takes the layer's expert matrices as the caller holds them: cut
    out of their stack (training, which differentiates them, and any call
    with one layer's leaves) or in place in it (``InStack``, the serving
    forwards: ``scan_layers``).

    Aux loss (OLMoE, arXiv 2409.02060): ``router_aux_loss_coef`` times the
    load balance E * sum_e f_e P_e (f_e the share of tokens that chose e,
    over all k places; P_e the mean router probability) plus
    ``router_z_loss_coef`` times the mean squared logsumexp of the logits.

    Counts, int32 [3]: token-expert pairs, experts with at least one pair,
    the busiest expert's pairs -- of the ``live`` tokens ([N] bool; None:
    all), so padding rows of a prefill bucket and empty slots do not count.

    A layer that holds a share of the experts (``num_local_experts`` from
    ``first_local_expert`` on) routes over all of them all the same; the
    pairs of its own experts sort first, by expert, and are the groups of
    the matmuls, the other pairs lie behind the last group and are zeroed.
    The aux loss is of the whole routing. The counts are then of the held
    experts' pairs, and a fourth gives the pairs of all experts.

    ``features`` [N, R] are what the router's last map reads where that is not
    the tokens themselves (``_router_features``; ``_ffn`` computes them, with
    what the next layer is handed). A layer with a ``router_bias`` chooses its
    experts under it and weighs them without it: beside sigmoid scores
    (``noaux_tc``) or, ZAYA's, beside the softmax. A caller that wants the
    choice itself passes a list as ``chosen``: each token's experts [N, K]
    int32 are appended to it."""
    D = x.shape[-1]
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    if features is None:
        features = _router_features(cfg, xf, layer, None)[0]

    logits = jnp.dot(features, layer["router"], preferred_element_type=jnp.float32)
    if cfg.topk_method == "noaux_tc":
        # scores by sigmoid; the selection bias chooses and does not weigh
        probs = jax.nn.sigmoid(logits)  # [N, E]
        _, expert = jax.lax.top_k(probs + layer["router_bias"].astype(jnp.float32), K)
        gate = jnp.take_along_axis(probs, expert, axis=-1)
        if cfg.norm_topk_prob:
            gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
        gate = gate * cfg.routed_scaling_factor
    else:
        probs = jax.nn.softmax(logits, axis=-1)  # [N, E]
        if "router_bias" in layer:
            _, expert = jax.lax.top_k(probs + layer["router_bias"].astype(jnp.float32), K)
            gate = jnp.take_along_axis(probs, expert, axis=-1)
        else:
            gate, expert = jax.lax.top_k(probs, K)  # [N, K]; ties go to the lower index
        if cfg.norm_topk_prob:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        if cfg.routed_scaling_factor != 1.0:
            gate = gate * cfg.routed_scaling_factor

    if chosen is not None:
        chosen.append(expert.astype(jnp.int32))
    flat = expert.reshape(-1)  # pair p belongs to token p // K
    share = cfg.num_local_experts is not None
    if share:
        Eh = cfg.num_local_experts
        group = flat - cfg.first_local_expert
        held = (group >= 0) & (group < Eh)
        group = jnp.where(held, group, Eh)  # the others sort behind the groups
    else:
        Eh, group = E, flat

    def group_sizes(weights=None):  # int32, one group per held expert
        return jnp.bincount(group, weights=weights, length=Eh + share)[:Eh]

    sizes = group_sizes()
    order = jnp.argsort(group, stable=True)  # pairs by expert
    xs = xf[order // K]
    h = jax.nn.silu(
        _grouped_matmul(xs, layer["gate_proj"], sizes)
    ) * _grouped_matmul(xs, layer["up_proj"], sizes)
    ys = _grouped_matmul(h, layer["down_proj"], sizes)  # [N * K, D]
    ys = ys[jnp.argsort(order)]
    if share:
        ys = jnp.where(held[:, None], ys, 0)
    out = jnp.sum(ys.reshape(N, K, D).astype(jnp.float32) * gate[..., None], axis=1)

    chosen = jnp.bincount(flat, length=E) if share else sizes
    balance = E * jnp.sum(chosen / N * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = cfg.router_aux_loss_coef * balance + cfg.router_z_loss_coef * z

    pairs_all = jnp.int32(N * K)
    if live is not None:
        counted = jnp.repeat(live.reshape(-1), K).astype(jnp.int32)
        sizes, pairs_all = group_sizes(counted), jnp.sum(counted)
    counts = [jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes)]
    if share:
        counts.append(pairs_all)
    return out.astype(x.dtype).reshape(x.shape), aux, jnp.stack(counts).astype(jnp.int32)


def _swiglu(x, layer: dict, prefix: str = ""):
    gated = jax.nn.silu(x @ layer[prefix + "gate_proj"]) * (x @ layer[prefix + "up_proj"])
    return gated @ layer[prefix + "down_proj"]


def _ffn(
    cfg: LlamaConfig, x: jax.Array, layer: dict, live=None, features=None, chosen=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The block's feed-forward over x [..., D] -> (out, aux loss, routing
    counts): SwiGLU, or the routed experts (``features``: what their router
    reads, where that is not x; ``chosen``: a list to take each token's
    experts, ``_routed_ffn``), and beside those, where the configuration has
    one, a shared SwiGLU that every token passes: the layer's leaves say
    which it has. aux and counts are zero for a dense layer."""
    if "router" in layer:
        out, aux, counts = _routed_ffn(cfg, x, layer, live, features, chosen)
    else:  # a dense model's layer, or a routed model's leading dense one
        out, aux = _swiglu(x, layer), jnp.float32(0.0)
        counts = jnp.zeros((cfg.moe_counts,), jnp.int32)
    if "shared_gate_proj" in layer:
        out = out + _swiglu(x, layer, "shared_")
    return out, aux, counts


class BlockOut(NamedTuple):
    """What one layer leaves beside the hidden state."""

    # this layer's keys, rotated [B, T, Nkv, Dh], and values; for latent
    # attention k is the latent rows [B, T, R + rope] and v None; both None
    # for a layer without attention
    k: Optional[jax.Array]
    v: Optional[jax.Array]
    attn_out: jax.Array  # the mixer's branch after its output projection [B, T, D]
    aux: jax.Array  # the routed FFN's weighted aux loss (0 for a dense one)
    counts: jax.Array  # the routed FFN's counts, int32 [3] (``_routed_ffn``)
    # what this layer's router hands the next one's [B, T, R]; None but for a
    # router that reads the layer before (``_router_features``)
    router: Optional[jax.Array] = None
    # CCA: what each position hands the next [B, T, cca_state_dim] (``_cca_qkv``)
    tails: Optional[jax.Array] = None
    # a routed FFN's choice, each token's experts [B * T, K] int32 (None: dense)
    experts: Optional[jax.Array] = None
    # learned sparse attention: the tokens' index keys [B, T, index_head_dim]
    index_k: Optional[jax.Array] = None


def decoder_block(
    cfg: LlamaConfig,
    h: jax.Array,
    layer: dict,
    cos: Optional[jax.Array],
    sin: Optional[jax.Array],
    *,
    attend=None,
    mix=None,
    live: Optional[jax.Array] = None,
    router_in: Optional[jax.Array] = None,
    past: Optional[jax.Array] = None,
    index_rope: Optional[tuple] = None,
    mix_scope: str = "odtp_ssm",
) -> tuple[jax.Array, BlockOut]:
    """One decoder layer over h [B, T, D], the only statement of its
    skeleton: RMSNorm, the mixer, residual; RMSNorm, FFN, residual. The
    mixer is attention (q/k/v, ``attend``, o_proj) or, where the caller
    passes ``mix``, whatever that computes from the normed input (the
    Mamba-2 mixer of a hybrid stack). Training and the serving forwards
    differ in what they pass: ``attend(q, k, v)`` is the caller's attention
    over this layer's q [B, T, Nh, Dh] and new k, v (a ``RowsAttend``:
    training's flash kernels, over the projections' own rows [B, T, Nh * Dh],
    which then are split and rotated nowhere outside the kernels),
    ``mix(x, layer)`` its
    mixer over x [B, T, D] (a cache or a state either reads or writes is the
    caller's own), ``live`` the tokens a routed FFN counts. Latent attention
    enters the same way: the projection returns q and the tokens' latent
    rows, and the caller's ``attend(q, rows, kv_b_proj)`` is its attention
    over them, in the rebuilt form or the absorbed one -> [B, T, Nh, v].
    Learned sparse attention enters as attention too: beside q, k, v the
    layer's indexer projects index queries, the tokens' index keys and the
    queries' head weights (``_index_qkw``, rotated by ``index_rope``), and the
    caller's ``attend(q, k, v, qi, ki, wi)`` scores, chooses and attends over
    the chosen rows; ``BlockOut.index_k`` is what a cache keeps of them. EVA
    enters as attention too: the caller's
    ``attend(q, k, v, adaptive_phi, adaptive_mu_k)`` pools k and v by chunk
    under the layer's two vectors and attends over the query's window and the
    pooled rows before it (``ops.attention.eva_attention``, or a decode step
    over the slot's two rings). CCA enters as attention does too, behind a
    projection that reads the token before: ``past`` [B, cca_state_dim] is
    what that token left (a decode step's slot state; None at a sequence's
    start), and ``BlockOut.tails`` what each position leaves. ``router_in``
    [B, T, R] is the state of the layer before's router, for a router that
    reads it (None in the first layer), ``BlockOut.router`` this layer's. Both
    branches enter the residual under the configuration's
    ``residual_multiplier``, or, where the layer holds them, under a learned
    scale and bias per channel on the stream and on the branch."""
    B, T, _ = h.shape
    tails = router_out = features = index_k = None
    chosen: list = []

    def residual(h, branch, sub):
        if f"{sub}_stream_scale" in layer:
            stream = (h + layer[f"{sub}_stream_bias"]) * layer[f"{sub}_stream_scale"]
            return stream + (branch + layer[f"{sub}_branch_bias"]) * layer[f"{sub}_branch_scale"]
        scale = cfg.residual_multiplier
        return h + (branch if scale == 1.0 else branch * jnp.asarray(scale, h.dtype))

    # the scopes name the device work in a profiler trace (an operation's
    # op_name metadata); they change nothing that is computed
    if mix is None and cfg.latent:
        # ``cfg`` is the layer's kind's view (``kind_view``)
        with jax.named_scope("odtp_mla"):
            x = _rms_norm(h, layer["input_norm"], cfg.rms_norm_eps)
            q, k, c_q = _latent_qkv(cfg, x, layer, cos, sin)
            v = None
            pool = ()
            if cfg.sparse:  # its attend also scores and chooses: by the indexer's three
                pool = _index_qkw(cfg, x, layer, cos, sin, c_q)
                index_k = pool[1]
            o = attend(q, k, layer["kv_b_proj"], *pool)
            if "attn_gate" in layer:  # one value a head, from the layer's normed input
                with jax.named_scope("odtp_attn_gate"):
                    gate = jax.nn.sigmoid(x @ layer["attn_gate"])  # [B, T, Nh]
                    o = o.reshape(B, T, cfg.num_attention_heads, -1) * gate[..., None].astype(o.dtype)
            attn_out = o.reshape(B, T, -1) @ layer["o_proj"]
    elif mix is None and cfg.cca:
        with jax.named_scope("odtp_cca"):
            x = _rms_norm(h, layer["input_norm"], cfg.rms_norm_eps)
            q, k, v, tails = _cca_qkv(cfg, x, layer, cos, sin, past)
        with jax.named_scope("odtp_attention"):
            attn_out = attend(q, k, v).reshape(B, T, -1) @ layer["o_proj"]
    elif mix is None:
        with jax.named_scope("odtp_attention"):
            x = _block_norm(cfg, h, layer["input_norm"])
            if isinstance(attend, RowsAttend):  # rows in, rows out: no head is split off
                q, k, v = _qkv_rows(cfg, x, layer)
            else:
                q, k, v = _qkv(cfg, x, layer, cos, sin)
            # EVA's attend also pools k and v: under the layer's two vectors
            pool = (layer["adaptive_phi"], layer["adaptive_mu_k"]) if cfg.eva else ()
            if cfg.sparse:  # its attend also scores and chooses: by the indexer's three
                pool = _index_qkw(cfg, x, layer, *index_rope)
                index_k = pool[1]
            o = attend(q, k, v, *pool)
            if cfg.attention_gate_type == "elementwise":  # a value each, from the normed input
                with jax.named_scope("odtp_attn_gate"):
                    gate = jax.nn.sigmoid(x @ layer["attn_gate"])  # [B, T, Nh * Dh]
                    o = o.reshape(B, T, -1) * gate.astype(o.dtype)
            elif "attn_gate" in layer:  # one value a head, from the layer's normed input
                with jax.named_scope("odtp_attn_gate"):
                    gate = jax.nn.sigmoid(x @ layer["attn_gate"])  # [B, T, Nh]
                    o = o.reshape(B, T, cfg.num_attention_heads, -1) * gate[..., None].astype(o.dtype)
            attn_out = o.reshape(B, T, -1) @ layer["o_proj"]
    else:
        k = v = None
        with jax.named_scope(mix_scope):
            x = _rms_norm(h, layer["input_norm"], cfg.rms_norm_eps)
            attn_out = mix(x, layer)
    h = residual(h, attn_out, "attn")
    with jax.named_scope("odtp_mlp"):
        x = _block_norm(cfg, h, layer["post_attn_norm"])
        if "router_down" in layer:
            with jax.named_scope("odtp_router"):
                features, router_out = _router_features(
                    cfg, x.reshape(B * T, -1), layer, router_in
                )
                router_out = router_out.reshape(B, T, -1)
        ffn, aux, counts = _ffn(cfg, x, layer, live, features, chosen)
    return residual(h, ffn, "ffn"), BlockOut(
        k, v, attn_out, aux, counts, router_out, tails, chosen[0] if chosen else None,
        index_k,
    )


def training_block(
    cfg: LlamaConfig, attn_fn, positions: jax.Array, remat: RematPolicy,
    kind: str = "attention",
):
    """The body of training's scan over a run of ``kind`` layers
    (``scan_layers``), ``((h, r), layer, li) -> ((h, r), (mixer-output L2
    norm, moe aux loss))``, under the rematerialization policy; r is the
    router's state that one layer hands the next (``router_carry``; None
    but for a router that reads the layer before). The norm is the activation probe the reference
    attaches via forward hooks on ``self_attn`` (utils.py:43-67,
    train_fsdp.py:65)."""
    view = kind_view(cfg, kind)  # the kind's own latent geometry and rope base
    cos, sin = _rope(view, positions)
    index_rope = _index_rope(view, positions)
    mix, attend = None, attn_fn
    scope = {"lightning": "odtp_lightning_proj", "kda": "odtp_kda_proj"}.get(kind, "odtp_ssm")
    lightning_rope = _rope(lightning_view(cfg), positions) if kind == "lightning" else None
    if kind == "mamba":
        mix = lambda x, layer: mamba.ssm_chunked(cfg, x, layer)[0]
    elif kind == "lightning":  # its decays are the layer's: the mix is made in the body
        pass
    elif kind == "kda":
        mix = kda_mix(cfg)
    elif cfg.blocks:  # its own attention over the sequence; ``attn_fn`` under ``dense_len``
        attend = block_attend(cfg, attn_fn)
    elif view.latent:  # the rebuilt form: multi-head attention over k and v
        attend = latent_attend(view, attn_fn)
    elif cfg.sparse:  # its own attention over the sequence: ``attn_fn`` is not asked
        attend = sparse_attend(cfg)
    elif cfg.eva:  # its own attention over the sequence: ``attn_fn`` is not asked
        attend = eva_attend(cfg)
    elif cfg.sliding:  # grouped-query kinds: the full layers' ``attn_fn``, the band in XLA
        attend = kinds_attend(view, attn_fn)
    else:  # as rows where the configuration and ``attn_fn`` allow
        attend = rows_attend(cfg, attn_fn, cos, sin) or attend
    cfg = view

    def body(carry, layer, li=None):
        h, r = carry
        h, out = decoder_block(
            cfg, h, layer, cos, sin, attend=attend, router_in=r, index_rope=index_rope,
            mix=lightning_mix(cfg, lightning_rope, li) if kind == "lightning" else mix,
            mix_scope=scope,
        )
        with jax.named_scope("odtp_attention"):
            attn_norm = jnp.sqrt(jnp.sum(out.attn_out.astype(jnp.float32) ** 2))
        return (h, out.router), (attn_norm, out.aux)

    return _maybe_remat(body, remat)


def router_carry(cfg: LlamaConfig, h: jax.Array) -> Optional[jax.Array]:
    """What the first layer's router is handed beside h [..., D]: zeros [...,
    router_hidden_size] for a router that reads the layer before, else None
    (no member of a scan's carry)."""
    if not cfg.router_hidden_size:
        return None
    return jnp.zeros((*h.shape[:-1], cfg.router_hidden_size), h.dtype)


def _embed(cfg: LlamaConfig, cparams: dict, ids: jax.Array) -> jax.Array:
    """Token embeddings of ``ids``, under the ``embedding_multiplier``."""
    h = jnp.take(cparams["embed_tokens"], ids, axis=0)
    if cfg.embedding_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    # ``fp32_skip_add``: the stream is float32 from here to the final norm
    return h.astype(jnp.float32) if cfg.fp32_skip_add else h


def _final_norm_and_head(
    cfg: LlamaConfig, cparams: dict, h: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """-> (h under the final RMSNorm and divided by the configuration's
    ``logits_scaling``, the head [D, V]: the embedding's transpose where the
    configuration ties them)."""
    h = _block_norm(cfg, h, cparams["final_norm"])
    if cfg.logits_scaling != 1.0:  # the logits are h @ head wherever taken
        h = h / jnp.asarray(cfg.logits_scaling, h.dtype)
        if cfg.linear:
            # (of one row the chip's compiler would scale the head in h's place:
            # the whole matrix widened and rounded again, in every chunk)
            h = jax.lax.optimization_barrier(h)
    head = (
        cparams["embed_tokens"].T
        if cfg.tie_word_embeddings
        else cparams["lm_head"]
    )
    return h, head


def _head_matmul(cfg: LlamaConfig, h: jax.Array, head: jax.Array) -> jax.Array:
    """Float32 logits [..., V * num_pred_heads] (head i's vocabulary in columns
    [i V, (i + 1) V)): accumulated in float32 under ``fp32_logits``, else
    computed in h's dtype and widened."""
    if cfg.fp32_logits:
        return jnp.matmul(h, head, preferred_element_type=jnp.float32)
    return (h @ head).astype(jnp.float32)


def forward(
    params: dict,
    input_ids: jax.Array,
    cfg: LlamaConfig,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    attn_impl: str = "xla",
    remat: RematPolicy = True,
    positions: Optional[jax.Array] = None,
    return_aux: bool = False,
    return_hidden: bool = False,
    ring_mesh=None,  # the plan mesh: ring attention AND the SPMD kernel
    # wrappers key off it — a multi-device pallas caller MUST pass it (a
    # pallas operand with a sharded dim fails XLA compile otherwise)
    ring_axis: str = "sp",
    pp_mesh=None,
    pp_axis: str = "pp",
    pp_microbatches: Optional[int] = None,
    return_moe_aux: bool = False,
    batch_axes: tuple = (),
    tp_axis: Optional[str] = None,
    scan_unroll: Optional[int] = None,
):
    """input_ids [B, T] int32 -> logits [B, T, V] float32. ``positions`` [B,
    T], or [3, B, T] for a configuration whose rotation runs in sections
    (``mrope_section``: temporal, height and width rows; equal for token ids).

    return_hidden=True returns (final_hidden [B, T, D], head [D, V]) instead
    of logits -- the hook for fused lm-head losses (ops/fused_xent.py);
    with return_moe_aux=True it returns (final_hidden, head, moe_aux) so
    those losses can thread the router aux term: the mean over layers of
    the routed FFN's aux loss, already weighted by the configuration's two
    coefficients (``_routed_ffn``), to be added to the loss as it is.

    return_aux=True additionally returns activation-probe metrics
    {"attn_out_norm": [L], "lm_head_norm": scalar} (the reference's
    self_attn/lm_head hook probes, utils.py:43-67)."""
    B, T = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    cparams = jax.tree.map(lambda x: x.astype(compute_dtype), params)

    if attn_impl != "xla":
        refuse(cfg, "attn_impl", f"attn_impl={attn_impl!r}")
    if attn_impl == "xla":
        attn_fn = lambda q, k, v: xla_attention(q, k, v, causal=True)
    elif attn_impl == "pallas":
        from opendiloco_tpu.ops.flash_attention import (
            flash_attention,
            flash_attention_sharded,
        )

        if pp_mesh is None and ring_mesh is not None and ring_mesh.size > 1:
            # multi-device mesh: Mosaic kernels cannot be auto-partitioned,
            # so the kernel runs manual over the sharded activation axes
            # (flash_attention_sharded).
            attn_fn = functools.partial(
                flash_attention_sharded, mesh=ring_mesh, batch_axes=batch_axes,
                tp_axis=tp_axis, causal=True,
            )
            attn_fn.takes_rows = True  # rows [B, T, H * D] under ``head_dim=`` (``rows_attend``)
        elif pp_mesh is not None and any(
            s > 1 for a, s in pp_mesh.shape.items() if a not in (pp_axis, ring_axis)
        ):
            # pp composed with dp/fsdp/tp/ep: pipeline_hidden binds only
            # pp (and sp) manual, so those axes stay AUTO inside the
            # region and operands reach the kernel still sharded — Mosaic
            # cannot be auto-partitioned, and wrapping a shard_map here
            # would nest inside the pp-manual region, which has no jvp
            # lowering. Documented downgrade: XLA attention (fuses fine;
            # the pallas win is single-stage-measured ~+5-20%).
            attn_fn = lambda q, k, v: xla_attention(q, k, v, causal=True)
        else:
            attn_fn = functools.partial(flash_attention, causal=True)
            attn_fn.takes_rows = True  # rows [B, T, H * D] under ``head_dim=`` (``rows_attend``)
    elif attn_impl == "ring":
        from opendiloco_tpu.ops.ring_attention import ring_attention_auto

        attn_fn = lambda q, k, v: ring_attention_auto(
            q, k, v, mesh=ring_mesh, axis=ring_axis
        )
    else:
        raise ValueError(f"unknown attn_impl {attn_impl!r}")

    h = _embed(cfg, cparams, input_ids)

    if pp_mesh is not None:
        # decoder stack staged over the pp mesh axis (parallel/pipeline.py);
        # activation probes are not threaded through the pipeline
        from opendiloco_tpu.parallel.pipeline import pipeline_hidden

        h, moe_aux = pipeline_hidden(
            cparams,
            h,
            positions,
            cfg,
            pp_mesh,
            microbatches=pp_microbatches or pp_mesh.shape[pp_axis],
            attn_fn=attn_fn,
            remat=remat,
            axis=pp_axis,
            # sp+pp composition: the pipeline binds the ring axis manual
            # too, and ring attention runs directly on the local chunks
            sp_axis=ring_axis if attn_impl == "ring" else None,
        )
        attn_norms = jnp.zeros((cfg.num_hidden_layers,), jnp.float32)
    else:
        # Unroll the layer scan N-wide (N >= num layers removes the while
        # loop entirely). The trainer auto-resolves scan_unroll to FULL
        # unroll on TPU for dense stacks (measured +6.8% tok/s on the
        # HBM-bound 150m step -- cross-layer scheduling/fusion; round-5
        # live window). ODTP_SCAN_UNROLL overrides for experiments and for
        # scripts/aot_roofline.py -- cost analysis counts a while-loop body
        # ONCE, so per-layer FLOPs/bytes only become visible to the
        # compiled-HLO cost model when the stack is unrolled.
        env_unroll = os.environ.get("ODTP_SCAN_UNROLL")
        unroll = int(env_unroll) if env_unroll else (scan_unroll or 1)
        probes = []  # one scan per run of like layers (one, but for a hybrid)
        r = router_carry(cfg, h)
        for run in layer_runs(cfg):
            (h, r), probe = scan_layers(
                cfg, training_block(cfg, attn_fn, positions, remat, run.kind),
                (h, r), cparams["layers"], run, unroll=max(1, unroll),
            )
            probes.append(probe)
        attn_norms, layer_auxs = (
            probes[0] if len(probes) == 1
            else tuple(jnp.concatenate(p) for p in zip(*probes))
        )
        moe_aux = jnp.mean(layer_auxs)

    h, head = _final_norm_and_head(cfg, cparams, h)
    if return_hidden:
        # composes with return_moe_aux so fused lm-head losses can thread
        # the router aux loss (trainer._loss_fn)
        return (h, head, moe_aux) if return_moe_aux else (h, head)
    with jax.named_scope("odtp_lm_head_loss"):
        logits = _head_matmul(cfg, h, head)
    if return_aux:
        aux = {
            "attn_out_norm": attn_norms,
            "lm_head_norm": jnp.sqrt(jnp.sum(logits**2)),
            "moe_aux": moe_aux,
        }
        return logits, aux
    if return_moe_aux:
        return logits, moe_aux
    return logits


# ---------------------------------------------------------------------------
# serving: prefill / incremental decode over a slot-paged ring KV cache
# (opendiloco_tpu/serve; the cache is models/ring_cache.py). Each forward
# below drives ``decoder_block``: it builds the attention over its cache and
# scans the layers, so dense and routed-expert stacks both serve.
# ---------------------------------------------------------------------------


def _serving_boundary(params, compute_dtype):
    """What the serving forwards do first -> the weights in the compute
    dtype. A leaf that already has it emits nothing: ``ServeEngine`` binds
    its weights in the compute dtype, so its programs hold no cast, and a
    caller with a float32 tree gets the same rounding here, per call."""
    return jax.tree.map(lambda x: x.astype(compute_dtype), params)


def _logits(cfg: LlamaConfig, cparams: dict, h: jax.Array) -> jax.Array:
    """Final norm and lm head over h [..., D] -> float32 logits [..., V]."""
    h, head = _final_norm_and_head(cfg, cparams, h)
    return _head_matmul(cfg, h, head)


def _stacked(parts: list) -> Optional[jax.Array]:
    """The runs' per-layer outputs as one stack, in layer order (None where
    the layers leave none: a latent cache has no values)."""
    if not parts or parts[0] is None:
        return None
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def prefill_forward(
    params: dict,
    input_ids: jax.Array,
    length: jax.Array,
    cfg: LlamaConfig,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    decode_kernel: str = "xla",
    return_moe_counts: bool = False,
    return_expert_choices: bool = False,
):
    """Prompt prefill for serving: ids [1, P] -> (last-token logits [1, V]
    f32, per-layer K/V [La, P, Nkv, Dh] in compute dtype over the attention
    layers; for latent attention, computed here in the rebuilt form, the
    latent rows [La, P, R + rope] in K's place and None in V's); for CCA
    then the layers' state [L, cca_state_dim] as the last real token left it
    (``_cca_qkv``: a bucket's padding rows do not reach it); for a
    EVA then the prompt's chunks pooled [L, ceil(P / chunk), Nkv, Dh] (keys,
    then values; of these the ones that ended before ``length`` are final) and
    the pooling under way of the chunk that ``length`` lies in [L, Nkv, 2 Dh +
    2] float32 (``ops.attention.eva_pool``: a bucket's padding rows enter no
    chunk), and the K/V are then the rows of the prompt's last window alone
    [L, min(P, window_size), Nkv, Dh] (``ring_cache.eva_window_rows``); for learned sparse attention then the
    tokens' index keys [L, P, index_head_dim] (and at P <= index_topk every
    causal row is read: the indexer scores nothing, its keys are kept all the
    same); for a
    hybrid stack then the Mamba-2 layers' recurrent states
    [Lm, H, P, N] float32 and conv tails [Lm, K - 1, C], as the last real
    token left them; for a stack with kda layers then their states [Lk, H, D, D]
    float32 and their convolutions' tails [Lk, taps - 1, 3 H D], likewise; and with ``return_moe_counts`` last the routed FFN's
    counts over the live prompt tokens summed over layers (int32, see
    ``_routed_ffn``), and with ``return_expert_choices`` after them each
    position's experts in each layer [L, P, K] int32.

    ``length`` (traced scalar) is the true prompt length; ``input_ids``
    may be right-padded to a compile-size bucket. Padding K/V rows do land
    in the returned stack (and hence the cache) but are never attended:
    the decode mask stops at the live length and every ring write
    overwrites index ``len % T`` before index ``len`` becomes visible. A
    recurrent state has no rows to mask: the mixer itself stops at
    ``length`` (``mamba.ssm_chunked``).

    ``decode_kernel`` (the engine's, resolved: "pallas" | "xla") decides with
    the bucket's rows and the heads which form a whole prompt's plain causal
    attention takes (``decode_kernels.prefill_form``: the flash forward kernel
    or ``xla_attention``); the rebuilt latent form's goes the same way. EVA's,
    the indexer's and a window's attention are their own."""
    B, P = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
    cparams = _serving_boundary(params, compute_dtype)
    rope = _rope(kind_view(cfg, "attention"), positions)
    index_rope = _index_rope(cfg, positions)
    live = positions < length
    causal = functools.partial(causal_prefill_attention, decode_kernel=decode_kernel)

    def attention_body(carry, layer, li, view=cfg, rope=rope):  # the run's kind's
        h, r = carry
        cos, sin = rope
        attend = causal
        if view.latent:  # k and v rebuilt for the prompt; the rows are what is kept
            attend = latent_attend(view, causal)
        elif cfg.sparse:
            attend = sparse_attend(cfg)
        elif cfg.sliding:
            attend = kinds_attend(view, causal)
        elif cfg.blocks:
            attend = block_attend(cfg, causal, length)
        pooling: list = []  # EVA: the chunks pooled, and each chunk's pooling as stats
        h, out = decoder_block(
            view, h, layer, cos, sin, live=live, router_in=r, index_rope=index_rope,
            attend=eva_attend(cfg, length, pooling, prefill=True) if cfg.eva else attend,
        )
        kept = [out.k[0], None if out.v is None else out.v[0]]
        if view.sparse:
            kept.append(out.index_k[0])
        if cfg.cca:  # what the prompt's last token leaves the first decode step
            with jax.named_scope("odtp_cca"):
                kept.append(jax.lax.dynamic_index_in_dim(out.tails[0], length - 1, 0, False))
        if cfg.eva:
            # of the rows those of the prompt's last window (what a slot's ring
            # takes), and of the stats those of the chunk the prompt ends in
            with jax.named_scope("odtp_eva"):
                kept = [eva_window_rows(x, length, cfg.window_size) for x in kept]
                kbar, vbar, stats = pooling
                kept += [kbar[0], vbar[0], jax.lax.dynamic_index_in_dim(
                    stats[0], length // cfg.chunk_size, 0, False
                )]
        return (h, out.router), (*kept, (out.counts, out.experts))

    def mamba_body(carry, layer, li):
        h, r = carry
        left = []

        def mix(x, layer):
            out, state, tail = mamba.ssm_chunked(cfg, x, layer, length)
            left.extend((state[0], tail[0]))
            return out

        h, out = decoder_block(cfg, h, layer, *rope, mix=mix, live=live, router_in=r)
        return (h, out.router), (*left, (out.counts, out.experts))

    def lightning_body(carry, layer, li):
        h, r = carry
        left: list = []
        h, out = decoder_block(
            cfg, h, layer, None, None, live=live, router_in=r, mix_scope="odtp_lightning_proj",
            mix=lightning_mix(cfg, lightning_rope, li, length=length, left=left, whole_rows=True),
        )
        return (h, out.router), (left[0][0], (out.counts, out.experts))

    def kda_body(carry, layer, li):
        h, r = carry
        left: list = []
        h, out = decoder_block(
            cfg, h, layer, None, None, live=live, router_in=r, mix_scope="odtp_kda_proj",
            mix=kda_mix(cfg, length=length, left=left),
        )
        return (h, out.router), (left[0][0], left[1][0], (out.counts, out.experts))

    lightning_rope = _rope(lightning_view(cfg), positions) if cfg.linear else None
    h = _embed(cfg, cparams, input_ids)
    r = router_carry(cfg, h)
    kept = {"attention": ([], [], [], [], []), "mamba": ([], []), "sliding": ([], []),
            "lightning": ([],), "kda": ([], [])}
    counts, experts = [], []
    for run in layer_runs(cfg):
        body = {"mamba": mamba_body, "lightning": lightning_body, "kda": kda_body}.get(
            run.mixer
        ) or _of_the_runs_kind(cfg, attention_body, run, positions, rope)
        (h, r), (*left, (c, e)) = scan_layers(
            cfg, body, (h, r), cparams["layers"], run, experts_in_place=True
        )
        for parts, part in zip(kept[run.mixer], left):
            parts.append(part)
        counts.append(c)
        experts.append(e)
    h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
    logits = _logits(cfg, cparams, h_last)
    out = [logits[:, 0], *map(_stacked, kept["attention"][:2])]
    if cfg.sliding and not cfg.latent:  # the rows by kind: the full layers' pair, the sliding layers'
        out[1:3] = RingPair(*out[1:3]), RingPair(*map(_stacked, kept["sliding"]))
    elif cfg.sliding:  # the sliding layers' rows, in the values' place
        out[2] = _stacked(kept["sliding"][0])
    if cfg.cca or cfg.sparse:
        out.append(_stacked(kept["attention"][2]))
    if cfg.eva:
        out.extend(map(_stacked, kept["attention"][2:]))
    if cfg.hybrid:
        out.extend(map(_stacked, kept["mamba"]))
    if cfg.linear:  # the lightning layers' states as the last real token left them
        out.append(_stacked(kept["lightning"][0]))
    if cfg.kda:  # the kda layers' states and tails as the last real token left them
        out.extend(map(_stacked, kept["kda"]))
    if return_moe_counts:
        out.append(jnp.sum(_stacked(counts), axis=0))
    if return_expert_choices:
        out.append(_chosen(cfg, experts))
    return tuple(out)


def _chosen(cfg: LlamaConfig, experts: list) -> jax.Array:
    """The runs' expert choices as one [L, N, K] int32, in layer order: -1 in
    a layer whose FFN is not routed."""
    if not cfg.num_experts:
        raise ValueError("return_expert_choices needs routed experts (num_experts > 0)")
    shape = next(e.shape[1:] for e in experts if e is not None)
    runs = layer_runs(cfg)
    return jnp.concatenate([
        jnp.full((run.count, *shape), -1, jnp.int32) if e is None else e
        for run, e in zip(runs, experts)
    ])


def decode_forward(
    params: dict,
    tokens: jax.Array,
    lens: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cfg: LlamaConfig,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    decode_kernel: str = "xla",
    return_moe_counts: bool = False,
    return_expert_choices: bool = False,
    ssm_state: Optional[jax.Array] = None,
    conv_state: Optional[jax.Array] = None,
    cca_state: Optional[jax.Array] = None,
    eva_state: Optional[tuple] = None,
    index_cache: Optional[jax.Array] = None,
    return_row_choices: bool = False,
    pooled_cache: Optional[jax.Array] = None,
    lightning_state: Optional[jax.Array] = None,
    return_block_tiles: bool = False,
    kda_state: Optional[jax.Array] = None,
    kda_tail: Optional[jax.Array] = None,
):
    """One incremental decode step over all S slots.

    tokens [S] int32 are each slot's current input token; lens [S] int32
    are the token counts already cached (== the new token's absolute
    position); cache_{k,v} are the ring pages (``ring_cache``). Returns
    (logits [S, V] f32, new_cache_k, new_cache_v): the new K/V is written
    at ring index ``lens % T`` and attention covers the last
    ``min(lens + 1, T)`` positions. The scan over the layers *carries* the
    caches, and on the Pallas path each layer's attention call reads its
    pages from the whole cache and writes the step's row into it through
    aliased outputs: jitted with the caches donated, the step reads each
    live row once and writes one row a slot, layer and KV head into the
    buffers the engine holds, and nothing of a layer's size is sliced,
    re-laid-out or copied (pinned at the cells' shapes by
    tests/test_tpu_compile.py). Latent attention runs here in the absorbed
    form against the one latent ring ``cache_k`` (``cache_v`` is None and
    comes back None): the query goes through the key half of ``kv_b_proj``,
    meets each cached row once, and the weighted sum of latents goes through
    the value half (``ops.decode_kernels.mla_decode_attention``). The XLA path (off the TPU, and the per-call
    fallback for a shape the kernel cannot tile) scatters the row and
    slices the layer's pages, with copies where the compiler wants them.

    A hybrid stack also takes the slots' recurrent states ``ssm_state``
    [Lm, S, H, P, N] float32 and conv tails ``conv_state`` [Lm, S, K - 1, C]
    (``ring_cache.init_ssm_state``) and returns both, updated, after the
    caches; the caches then hold the attention layers alone. Each run of
    Mamba-2 layers carries the two through its scan, reads a layer's part
    and writes it back in place (jitted with both donated).

    CCA also takes ``cca_state`` [L, S, cca_state_dim]
    (``ring_cache.init_cca_state``): what each slot's last token left each
    layer's projection, which reads one token back (``_cca_qkv``). The scan
    carries it beside the caches, each layer reads its part and writes the
    step's own in its place, and it comes back after the caches.

    EVA attention takes ``eva_state``, the slots' pooled ring and pooling
    under way (``ring_cache.init_eva_state``: pool_k, pool_v, stats), and
    ``cache_{k,v}`` are then rings of one window: the step's row goes to ring
    row ``lens % window_size`` and rows [0, lens % window_size] are read, its
    chunk as pooled so far to pooled row ``lens // chunk_size``, and the
    pooled rows of the windows before are read under the same softmax
    (``ops.decode_kernels.eva_decode_attention``). The scan carries the three
    beside the caches and they come back after them; no program copies a ring.

    Learned sparse attention takes ``index_cache``, the slots' index-key ring
    (``ring_cache.init_index_cache``) beside ``cache_{k,v}``: the step's index
    row is written at ring row ``lens % T``, the slot's min(lens + 1, T) live
    index rows scored and ``index_topk`` of them chosen exactly
    (``ops.attention.decode_selection``; the ring is read as the step found it
    and the layers' keys are written behind the scan,
    ``decode_kernels.index_ring_write``), and the decode kernel writes the
    step's K and V and attends over the slot's pages under that selection
    (``paged_decode_attention``'s ``chosen``; off the TPU
    ``ops.attention.sparse_decode_step_attention``). The ring comes back after
    the caches; no program copies a ring. With ``return_row_choices`` each slot's chosen rows in each layer [L,
    S, T] bool come last of all.

    A stack of lightning layers and attention under a selection by blocks
    takes ``lightning_state`` [Ll, S, H, D, D] float32
    (``ring_cache.init_lightning_state``) and ``pooled_cache`` (the pooled-key
    ring beside K and V, ``ring_cache.init_pooled_cache``) and returns both
    after the caches. A lightning layer reads its part of the state, decays
    it, adds the step's k^T v and writes it back in place; a slot at ``lens``
    0 (it may be one whose prompt is arriving in chunks) keeps its state. An
    attention layer reads all three rings **as the step found them**: where the
    step's key closes a pooling window its pooled key is made from the ring's
    rows and the key itself and enters the scores beside the ring
    (``ops.attention.closing_pooled_key``), the chosen blocks' rows before the
    step's own are read (``decode_kernels.block_decode_attention``: tiles that
    hold no chosen block stay unread; off the TPU the gather of
    ``ops.attention.block_decode_step_attention``) and the step's own row is
    merged in under the one softmax; the layers' K, V and pooled keys are
    written behind the scan (``index_ring_write``), nothing for a slot at
    ``lens`` 0. With ``return_block_tiles`` the ring tiles that held a chosen
    block, over slots, KV heads and layers ([1] int32), come after the counts;
    with ``return_row_choices`` the chosen blocks [Ls, S, Kh, blocks] last.

    A stack with kda layers takes ``kda_state`` [Lk, S, H, D, D] float32 and
    ``kda_tail`` [Lk, taps - 1, S, 3 H D] (``ring_cache.init_kda_state``) and
    returns both after the caches. A kda layer shifts its part of the tails by
    a row and runs the one-step form on its part of the states, decayed and
    updated by the delta rule: on the Pallas path where a head's state is whole
    tiles (``decode_kernels.kda_step_form``) one kernel over the stacked
    states, a live slot's read once and written once where it lies
    (``decode_kernels.kda_step``); else ``kda.step_state``, which passes over
    the layer's states three times, and a slice update of the stack. A slot at
    ``lens`` 0 (it may be one whose prompt is arriving in chunks) keeps its
    state and tail, and its attention layers' rings are written nothing.

    With ``return_moe_counts`` the routed FFN's counts over the slots that
    hold a sequence (``lens > 0``), summed over layers, come last, and with
    ``return_expert_choices`` after them each slot's experts in each layer [L,
    S, K] int32."""
    cparams = _serving_boundary(params, compute_dtype)
    positions = lens[:, None].astype(jnp.int32)  # [S, 1]
    rope = _rope(kind_view(cfg, "attention"), positions)
    index_rope = _index_rope(cfg, positions)
    live = lens > 0
    pallas = decode_kernel == "pallas"
    step_attention = paged_decode_attention if pallas else decode_step_attention
    latent_attention = mla_decode_attention if pallas else latent_decode_step_attention
    eva_attention_step = eva_decode_attention if pallas else eva_decode_step_attention
    eva_state = None if eva_state is None else tuple(eva_state)
    # a latent stack whose prompts arrive in chunks: a slot at ``lens`` 0 may be
    # one of those, and is written nothing
    chunked = {"live_only": True} if cfg.latent and cfg.q_chunk_size else {}
    # the same for the plain ring of a stack with kda layers, whose every prompt does
    live_rows = {"live_only": True} if cfg.kda else {}
    kda_kernel = cfg.kda and kda_step_form(decode_kernel, cfg.head_dim) == "pallas"

    def attention_body(carry, layer, li, view=cfg, rope=rope):  # the run's kind's
        # the whole caches, every layer's tails, EVA's pooled ring and stats
        h, r, ck, cv, tails, eva = carry
        cos, sin = rope
        rows_chosen: list = []
        index_keys: list = []

        def over_chosen_rows(q, k, v, qi, ki, wi):
            # the slot's live index rows and the step's own key scored and
            # chosen (the index ring is read as the step found it: the keys
            # are written behind the layers), then the kernel (or its XLA
            # stand-in) over the slot's pages under that selection: it writes
            # the step's K and V
            nonlocal ck, cv
            index_keys.append(ki[:, 0])
            with jax.named_scope("odtp_dsa_index"):
                rows = decode_selection(
                    qi[:, 0], wi[:, 0], ki[:, 0], index_cache[li], lens, cfg.index_topk
                )
            if return_row_choices:
                rows_chosen.append(rows)
            with jax.named_scope("odtp_dsa_attn"):
                if pallas:
                    out, ck, cv = paged_decode_attention(
                        q[:, 0], k[:, 0], v[:, 0], ck, cv, lens, li, chosen=rows
                    )
                else:
                    out, ck, cv = sparse_decode_step_attention(
                        q[:, 0], k[:, 0], v[:, 0], rows, ck, cv, lens, li
                    )
            return out[:, None]

        def attend(q, k, v):
            nonlocal ck, cv
            out, ck, cv = step_attention(
                q[:, 0], k[:, 0], v[:, 0], ck, cv, lens, li, **live_rows
            )
            return out

        def over_the_kinds_ring(q, k, v):
            # grouped-query kinds: a sliding layer over its pair of rings, which
            # wrap, under the window; a full layer over its own. A slot at
            # ``lens`` 0 may be one whose prompt is arriving in chunks, and is
            # written nothing
            nonlocal ck, cv
            step = (q[:, 0], k[:, 0], v[:, 0])
            if view.sliding_window_size:
                with jax.named_scope("odtp_swa"):
                    out, *ring = step_attention(
                        *step, *cv, lens, li, window=view.sliding_window_size, live_only=True
                    )
                cv = RingPair(*ring)
            else:
                with jax.named_scope("odtp_full_attn"):
                    out, *ring = step_attention(*step, *ck, lens, li, live_only=True)
                ck = RingPair(*ring)
            return out

        def over_two_rings(q, k, v, phi, mu):
            nonlocal ck, cv, eva
            out, ck, cv, *eva = eva_attention_step(
                q[:, 0], k[:, 0], v[:, 0], phi, mu, ck, cv, *eva, lens, li,
                window=cfg.window_size, chunk=cfg.chunk_size,
            )
            return out

        def absorbed(q, rows, w_kvb, qi=None, ki=None, wi=None):  # no key or value is rebuilt
            nonlocal ck, cv
            q_lat = latent_absorb(view, q[:, 0], w_kvb)
            sizes = dict(scale=view.qk_head_dim**-0.5, value_dim=view.kv_lora_rank, **chunked)
            if view.sliding_window_size:  # the sliding layers' ring, which wraps
                with jax.named_scope("odtp_swa"):
                    o_lat, cv = latent_attention(
                        q_lat, rows[:, 0], cv, lens, li, window=view.sliding_window_size, **sizes
                    )
            elif view.sparse:
                # as ``over_chosen_rows``: the index ring as the step found it
                # and the step's own key, then the latent ring under the selection
                index_keys.append(ki[:, 0])
                with jax.named_scope("odtp_dsa_index"):
                    chosen = decode_selection(
                        qi[:, 0], wi[:, 0], ki[:, 0], index_cache[li], lens, view.index_topk
                    )
                if return_row_choices:
                    rows_chosen.append(chosen)
                with jax.named_scope("odtp_dsa_attn"):
                    o_lat, ck = latent_attention(
                        q_lat, rows[:, 0], ck, lens, li, chosen=chosen, **sizes
                    )
            else:
                o_lat, ck = latent_attention(q_lat, rows[:, 0], ck, lens, li, **sizes)
            return latent_expand(view, o_lat, w_kvb)

        h, out = decoder_block(
            view, h, layer, cos, sin, live=live, router_in=r, index_rope=index_rope,
            attend=absorbed if cfg.latent else over_two_rings if cfg.eva
            else over_chosen_rows if cfg.sparse else over_the_kinds_ring if cfg.sliding
            else attend,
            past=None if tails is None else tails[li],
        )
        if tails is not None:
            with jax.named_scope("odtp_cca"):  # the state's write is CCA's work
                tails = jax.lax.dynamic_update_index_in_dim(
                    tails, out.tails[:, 0].astype(tails.dtype), li, 0
                )
        eva = None if eva is None else tuple(eva)
        return (h, out.router, ck, cv, tails, eva), (
            out.counts, out.experts, rows_chosen[0] if rows_chosen else None,
            index_keys[0] if index_keys else None,
        )

    def mamba_body(carry, layer, li):
        h, r, states, tails = carry  # every Mamba-2 layer's

        def mix(x, layer):
            nonlocal states, tails
            out, state, tail = mamba.ssm_step(cfg, x[:, 0], layer, states[li], tails[li])
            states = jax.lax.dynamic_update_index_in_dim(states, state, li, 0)
            tails = jax.lax.dynamic_update_index_in_dim(tails, tail, li, 0)
            return out[:, None]

        h, out = decoder_block(cfg, h, layer, *rope, mix=mix, live=live, router_in=r)
        return (h, out.router, states, tails), (out.counts, out.experts, None, None)

    def lightning_body(carry, layer, li):
        h, r, states = carry  # every lightning layer's

        def mix(x, layer):
            nonlocal states
            q, k, v = _qkv(lightning_view(cfg), x, layer, *lightning_rope, whole_rows=True)
            with jax.named_scope("odtp_lightning"):
                o, new = lightning.step(
                    q[:, 0], k[:, 0], v[:, 0], lightning.rates(cfg, li), states[li], live
                )
                states = jax.lax.dynamic_update_index_in_dim(states, new, li, 0)
            return lightning.gated_out(cfg, o, x[:, 0], layer)[:, None]

        h, out = decoder_block(
            cfg, h, layer, None, None, mix=mix, live=live, router_in=r, mix_scope="odtp_lightning_proj"
        )
        return (h, out.router, states), (out.counts, out.experts)

    def kda_body(carry, layer, li):
        h, r, states, tails = carry  # every kda layer's

        def mix(x, layer):
            nonlocal states, tails
            *rows, tail = kda.step_inputs(cfg, x[:, 0], layer, tails[li], live)
            if kda_kernel:  # a live slot's state visited once, where it lies in the stack
                with jax.named_scope("odtp_kda"):
                    o, states = kda_step(*rows, states, li, live)
            else:
                o, state = kda.step_state(*rows, states[li], live)
                with jax.named_scope("odtp_kda"):
                    states = jax.lax.dynamic_update_index_in_dim(states, state, li, 0)
            with jax.named_scope("odtp_kda_conv"):
                tails = jax.lax.dynamic_update_index_in_dim(tails, tail, li, 0)
            return kda.gated_out(cfg, o, x[:, 0], layer)[:, None]

        h, out = decoder_block(
            cfg, h, layer, None, None, mix=mix, live=live, router_in=r, mix_scope="odtp_kda_proj"
        )
        return (h, out.router, states, tails), (out.counts, out.experts)

    def block_body(carry, layer, li):
        # the three rings as the step found them; what the step writes goes out
        h, r = carry
        wrote: list = []

        def attend(q, k, v):
            sizes = cfg.block_sizes
            q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]
            with jax.named_scope("odtp_block_select"):
                own, j, closes = closing_pooled_key(
                    cache_k, li, k1, lens, sizes, ring_rows_sum if pallas else xla_ring_rows_sum
                )
                own = own.astype(pooled_cache.dtype)
                page = pooled_cache[li]  # [S, Kh, D, Tp]
                here = (jnp.arange(page.shape[-1]) == j[:, None]) & closes[:, None]
                page = jnp.where(here[:, None, None], own[..., None], page)
                chosen = jax.vmap(
                    lambda qs, ps, at, dn: block_selection(
                        qs[None], ps, at[None], dn[None], sizes, sizes.blocks(ring_rows(cache_k))
                    )[:, 0]
                )(q1, page, lens, lens + 1 < sizes.dense_len)  # [S, Kh, blocks]
                chosen = chosen & live[:, None, None]
            with jax.named_scope("odtp_block_attn"):
                over_blocks = block_decode_attention if pallas else block_decode_step_attention
                out = over_blocks(q1, k1, v1, chosen, cache_k, cache_v, lens, li, sizes)
            at = jnp.where(closes & live, j + page.shape[-1], 0)  # ``index_ring_write``'s: 0 writes nothing
            wrote.append((k1, v1, own, at, chosen))
            return out[:, None]

        h, out = decoder_block(cfg, h, layer, None, None, live=live, router_in=r, attend=attend)
        return (h, out.router), (out.counts, out.experts, wrote[0])

    lightning_rope = _rope(lightning_view(cfg), positions) if cfg.linear else None
    h = _embed(cfg, cparams, tokens)[:, None]  # [S, 1, D]
    r = router_carry(cfg, h)
    counts, experts, rows, keys = [], [], [], []
    written: list = []  # the block layers' (k, v, pooled key, its place, chosen blocks)
    for run in layer_runs(cfg):
        if run.mixer == "lightning":
            (h, r, lightning_state), (c, e) = scan_layers(
                cfg, lightning_body, (h, r, lightning_state), cparams["layers"], run,
                experts_in_place=True,
            )
        elif run.mixer == "kda":
            (h, r, kda_state, kda_tail), (c, e) = scan_layers(
                cfg, kda_body, (h, r, kda_state, kda_tail), cparams["layers"], run,
                experts_in_place=True,
            )
        elif cfg.blocks:
            (h, r), (c, e, wrote) = scan_layers(
                cfg, block_body, (h, r), cparams["layers"], run, experts_in_place=True,
            )
            written.append(wrote)
        elif run.mixer != "mamba":
            (h, r, cache_k, cache_v, cca_state, eva_state), (c, e, chose, wrote) = scan_layers(
                cfg, _of_the_runs_kind(cfg, attention_body, run, positions, rope),
                (h, r, cache_k, cache_v, cca_state, eva_state),
                cparams["layers"], run, experts_in_place=True,
            )
            rows.append(chose)
            keys.append(wrote)
        else:
            (h, r, ssm_state, conv_state), (c, e, _, _) = scan_layers(
                cfg, mamba_body, (h, r, ssm_state, conv_state), cparams["layers"], run,
                experts_in_place=True,
            )
        counts.append(c)
        experts.append(e)
    logits = _logits(cfg, cparams, h)
    out = [logits[:, 0], cache_k, cache_v]
    if cfg.cca:
        out.append(cca_state)
    if cfg.eva:
        out.extend(eva_state)
    if cfg.sparse:  # the step's index keys, every layer's, behind the layers
        with jax.named_scope("odtp_dsa_index"):
            write = index_ring_write if pallas else index_write_rows
            out.append(write(index_cache, _stacked([k for k in keys if k is not None]), lens))
    if cfg.hybrid:
        out.extend((ssm_state, conv_state))
    block_tiles = None
    if cfg.blocks:  # the step's rows and pooled keys, every block layer's, behind the layers
        ks, vs, pooled, at, chose = (jnp.concatenate(x) for x in zip(*written))
        write = index_ring_write if pallas else index_write_rows
        merged = lambda x: x.reshape(*x.shape[:2], -1, *x.shape[4:])  # KV heads and their values as one axis
        with jax.named_scope("odtp_block_attn"):
            out[1] = write(merged(cache_k), merged(ks), lens).reshape(cache_k.shape)
            out[2] = write(merged(cache_v), merged(vs), lens).reshape(cache_v.shape)
        with jax.named_scope("odtp_block_select"):
            # a window closes at the same step in every layer: the first layer's place
            out.append(write(merged(pooled_cache), merged(pooled), at[0]).reshape(pooled_cache.shape))
            block_tiles = block_tiles_held(chose, lens, cfg.block_sizes, ring_rows(cache_k))
        if return_row_choices:
            rows = [chose]
    if cfg.linear:
        out.append(lightning_state)
    if cfg.kda:
        out.extend((kda_state, kda_tail))
    if return_moe_counts:
        out.append(jnp.sum(_stacked(counts), axis=0))
    if return_expert_choices:
        out.append(_chosen(cfg, experts))
    if return_block_tiles:
        out.append(jnp.zeros((1,), jnp.int32) if block_tiles is None else block_tiles)
    if return_row_choices:  # the layers with an indexer, in order
        out.append(_stacked([x for x in rows if x is not None]))
    return tuple(out)


_SUFFIX_TILE = 512  # ring rows a tile of a run's attention where no ``q_chunk_size`` says


def chunk_tile(cfg: LlamaConfig, rows: int) -> int:
    """Ring rows a tile of ``chunk_prefill_forward``'s attention over a ring
    of ``rows``: ``q_chunk_size`` where the configuration names it (a chunk
    that is the engine's stays out of it: the tile stays a tile), and the
    whole ring where tiles do not cut it."""
    tile = min(cfg.q_chunk_size or _SUFFIX_TILE, rows)
    if (cfg.sliding and not cfg.latent) or cfg.blocks or cfg.kda:  # the chunk is the engine's
        tile = min(_SUFFIX_TILE, rows)
    return tile if rows % tile == 0 else rows


def chunk_attn_form(cfg: LlamaConfig, chunk: int, rows: int, decode_kernel: str | None) -> str:
    """The form ``chunk_prefill_forward`` runs a chunk of ``chunk`` tokens'
    grouped-query attention in (a stack's full layers) over a ring of ``rows``
    (``decode_kernels.chunk_form`` at the configuration's heads)."""
    return chunk_form(
        chunk, cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim, rows, chunk_tile(cfg, rows),
        decode_kernel,
    )


def latent_chunk_attn_form(cfg: LlamaConfig, chunk: int, rows: int, decode_kernel: str | None) -> str:
    """The form ``chunk_prefill_forward`` runs a chunk of ``chunk`` tokens'
    latent attention in over a ring of ``rows`` that does not wrap (a latent
    stack's full layers; ``decode_kernels.latent_chunk_form`` at their heads
    and their row)."""
    view = kind_view(cfg, "attention")
    return latent_chunk_form(
        chunk, view.num_attention_heads, view.latent_row_dim, view.kv_lora_rank, rows,
        chunk_tile(cfg, rows), decode_kernel,
    )


def chunk_prefill_forward(
    params: dict,
    ids: jax.Array,
    plen: jax.Array,
    count: jax.Array,
    slot: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    index_cache: Optional[jax.Array],
    cfg: LlamaConfig,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    return_moe_counts: bool = False,
    return_row_choices: bool = False,
    pooled_cache: Optional[jax.Array] = None,
    lightning_state: Optional[jax.Array] = None,
    total=None,
    return_block_tiles: bool = False,
    decode_kernel: str = "xla",
    kda_state: Optional[jax.Array] = None,
    kda_tail: Optional[jax.Array] = None,
):
    """A run of a prompt's tokens over a slot that holds the rows before them:
    a chunk of a prompt admitted in chunks, or the suffix behind a reused
    prefix. ids [1, C] are the tokens at positions ``plen + i``, of which the
    first ``count`` are real (a last chunk, a suffix in its bucket, is padded),
    over ``slot``'s rings, which hold the prompt's rows [0, plen). -> (logits
    [1, V] float32 of the last real token, cache_k, cache_v, index_cache).

    One program for every prompt length: ``plen``, ``count`` and ``slot`` are
    traced. Layer by layer the run's own K and V rows go into the slot's
    pages at [plen, plen + count) (``ring_cache.layer_rows_insert``: a padding
    row is never written), and its C queries attend over the slot's rows a
    tile of ring rows at a time under an online softmax
    (``ops.attention.tiled_sparse_attention``): no [C, T] block of attention
    scores is held where the ring is whole tiles, and tiles past the run's
    last row are not visited. The scan carries the rings; jitted with them
    donated the rows are written in place. ``plen + count`` lies within the
    ring: a prompt fits its slot, and nothing wraps. ``decode_kernel`` (the
    engine's, resolved: "pallas" | "xla") decides with the shapes which form a
    grouped-query layer's walk over the tiles takes
    (``decode_kernels.chunk_form``: the kernel ``chunk_attention``, whose score
    tile stays in VMEM, where the XLA form's would be written to memory at
    every tile; the XLA form elsewhere, over a sliding layer's ring and without
    the argument), and likewise a latent layer's over its page
    (``decode_kernels.latent_chunk_form``: ``latent_chunk_attention``).

    Which rows a query reads: every row up to its own, or, under learned
    sparse attention (``index_cache``: the slot's index ring beside K and V,
    None elsewhere), the ``index_topk`` of them that its index queries score
    highest (``odtp_dsa_index``; the index ring is read as the run found it
    and every layer's keys are written behind the scan, ``index_chunk_insert``;
    the attention under the selection is ``odtp_dsa_attn``). There the tile is
    ``q_chunk_size``, the ring is whole chunks and a prompt goes in from row 0
    in whole chunks, so ``plen + C`` lies within the ring too. A prompt's first
    chunk, ``plen`` 0, is the whole-prompt prefill's equations over a ring
    that holds nothing of it yet; at ``plen + count <= index_topk`` every row
    is chosen.

    A stack of lightning layers and attention under a selection by blocks
    also takes ``pooled_cache`` and ``lightning_state`` (``decode_forward``) and
    ``total``, the whole prompt's length (traced; it decides ``dense_len``'s
    side for every chunk of the prompt), and returns both after the index
    ring's place. **A chunk enters with the state the chunk before left**: a
    lightning layer reads ``slot``'s state (nothing where ``plen`` is 0: a
    prompt starts from zeros, whatever the slot's last tenant left), runs the
    chunked form over the chunk's real tokens and writes the state back in
    place. An attention layer writes the chunk's K and V rows, pools the
    windows that close inside the chunk **from the ring's own rows** (a window
    may start in the chunk before), scores the slot's pooled keys, those
    among them, chooses each query's blocks and attends over the slot's pages
    a tile at a time (``ops.attention.tiled_block_attention``, or the kernel);
    the new pooled keys of all layers are written behind the scan
    (``ring_cache.pooled_chunk_insert``). The chunk is whole strides and goes in
    from row 0 in whole chunks. With ``return_block_tiles`` the tiles the
    attention visited, over layers ([1] int32), come after the counts; with
    ``return_row_choices`` the last real token's chosen blocks [Ls, Kh, blocks].

    A stack with kda layers also takes ``kda_state`` and ``kda_tail``
    (``decode_forward``) and returns both after the index ring's place. **A
    chunk enters with the state and the tail the chunk before left**: a kda
    layer reads ``slot``'s (nothing where ``plen`` is 0: a prompt starts from
    zeros, whatever the slot's last tenant left), runs the convolution over
    the tail and the chunk and the chunked form over the chunk's real tokens,
    and writes both back in place; its attention layers are the plain ones
    over the slot's K and V rows.

    With ``return_moe_counts`` the routed FFN's counts over the real tokens
    come after, and with ``return_row_choices`` then the rows the last real
    token read in each layer [L, T] bool."""
    refuse(cfg, "continued_prefill")
    if (cfg.latent or cfg.sliding) and not cfg.q_chunk_size:
        raise ValueError(
            "the continued prefill (a prompt's chunks, the suffix behind a reused prefix) is "
            "refused for a configuration with latent attention or sliding layers that states "
            "no q_chunk_size: over latent rows and over a ring that wraps it goes in whole "
            "chunks from row 0 (a prompt admitted in chunks; a serving engine lays its own "
            "chunk over a configuration that names none)"
        )
    if cfg.sparse != (index_cache is not None):
        raise ValueError("the index ring goes with learned sparse attention, and only with it")
    B, C = ids.shape
    plen, count = jnp.asarray(plen, jnp.int32), jnp.asarray(count, jnp.int32)
    positions = plen + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    cparams = _serving_boundary(params, compute_dtype)
    cos, sin = _rope(kind_view(cfg, "attention"), positions)
    index_rope = _index_rope(cfg, positions) if cfg.sparse else None
    live = jnp.arange(C)[None] < count
    T = ring_rows(cache_k)
    tile = chunk_tile(cfg, T)
    seen = jnp.arange(T)[None] <= positions[0][:, None]  # [C, T]: the rows up to a query's own
    dsa = jax.named_scope if cfg.sparse else (lambda name: contextlib.nullcontext())

    def in_kernel(q, pages):  # q [C, H, D] over pages [Kh, D, T]: ``chunk_attention`` takes them
        form = chunk_form(C, q.shape[1], pages.shape[0], q.shape[2], T, tile, decode_kernel)
        return form == "tiles-pallas"

    def over_pages(q, pages_k, pages_v, rows=None):
        # the chunk's queries over the slot's rows up to each query's own; under
        # ``rows`` [C, T], a selection, over those it holds
        if in_kernel(q, pages_k):
            return chunk_attention(
                q, pages_k, pages_v, positions[0], plen + count, tile,
                None if rows is None else rows[None],
            )[0]
        return tiled_sparse_attention(
            q, pages_k, pages_v, seen if rows is None else rows, plen + count, tile
        )

    def body(carry, layer, li):
        h, ck, cv = carry
        last_row: list = []
        own_keys: list = []

        def attend(q, k, v, qi=None, ki=None, wi=None):
            nonlocal ck, cv
            ck, cv = layer_rows_insert(
                ck, cv, li, slot, k[0], v[0], plen, count, whole_chunks=cfg.sparse
            )
            rows = None
            if cfg.sparse:
                own_keys.append(ki[0])
                with dsa("odtp_dsa_index"):
                    rows = chunk_selection(
                        qi[0], wi[0], ki[0], slot_layer_pages(ci, li, slot), plen, cfg.index_topk
                    )
            if return_row_choices:
                last_row.append(
                    jax.lax.dynamic_index_in_dim(seen if rows is None else rows, count - 1, 0, False)
                )
            with dsa("odtp_dsa_attn"):
                out = over_pages(
                    q[0], slot_layer_pages(ck, li, slot), slot_layer_pages(cv, li, slot), rows
                )
            return out[None]

        h, out = decoder_block(
            cfg, h, layer, cos, sin, live=live, attend=attend, index_rope=index_rope
        )
        return (h, ck, cv), (
            out.counts, last_row[0] if last_row else None, own_keys[0] if own_keys else None
        )

    def latent_body(carry, layer, li, view, rope):
        # one kind of latent layer over its own ring, in the absorbed form: the
        # chunk's rows go in, then its queries over the slot's page a tile at a
        # time, under the selection (a full layer: in the kernel where
        # ``latent_chunk_form`` says) or the window's rows of a ring that wraps
        # (a sliding one); no key or value is rebuilt
        h, ck, cv = carry
        last_row: list = []
        own_keys: list = []

        def attend(q, rows, w_kvb, qi=None, ki=None, wi=None):
            nonlocal ck, cv
            q_lat = latent_absorb(view, q[0], w_kvb)  # [C, Nh, Dl]
            sizes = dict(scale=view.qk_head_dim**-0.5, value_dim=view.kv_lora_rank)
            if view.sliding_window_size:
                Tw = ring_rows(cv)
                cv, _ = layer_rows_insert(
                    cv, None, li, slot, rows[0][:, None], None, jnp.mod(plen, Tw), count
                )
                with jax.named_scope("odtp_swa"):
                    o_lat = tiled_latent_attention(
                        q_lat, slot_layer_pages(cv, li, slot)[0],
                        ring_window_rows(positions[0], Tw, view.sliding_window_size),
                        Tw, tile if Tw % tile == 0 else Tw, **sizes,
                    )
                return latent_expand(view, o_lat, w_kvb)[None]
            ck, _ = layer_rows_insert(ck, None, li, slot, rows[0][:, None], None, plen, count)
            reads = seen
            if view.sparse:
                own_keys.append(ki[0])
                with jax.named_scope("odtp_dsa_index"):
                    reads = chunk_selection(
                        qi[0], wi[0], ki[0], slot_layer_pages(ci, li, slot), plen, view.index_topk
                    )
                if return_row_choices:
                    last_row.append(jax.lax.dynamic_index_in_dim(reads, count - 1, 0, False))
            form = latent_chunk_form(C, *q_lat.shape[1:], view.kv_lora_rank, T, tile, decode_kernel)
            over_page = (
                latent_chunk_attention if form == "absorbed-pallas" else tiled_latent_attention
            )
            with dsa("odtp_dsa_attn"):
                o_lat = over_page(
                    q_lat, slot_layer_pages(ck, li, slot)[0], reads, plen + count, tile, **sizes
                )
            return latent_expand(view, o_lat, w_kvb)[None]

        h, out = decoder_block(view, h, layer, *rope, live=live, attend=attend)
        return (h, ck, cv), (
            out.counts, last_row[0] if last_row else None, own_keys[0] if own_keys else None
        )

    def kinds_body(carry, layer, li, view, rope):
        # one kind of grouped-query layer over its own pair of rings: the chunk's
        # K and V rows go in (a sliding layer's at ``plen`` modulo its ring), then
        # its queries over the slot's pages: a full layer's a tile at a time up
        # to the chunk's last row, a sliding layer's over the band alone
        # (``banded_chunk_attention``; where the ring cannot be cut into its
        # blocks, every tile under the window's mask: ``swa_chunk_form``)
        h, ck, cv = carry

        def attend(q, k, v):
            nonlocal ck, cv
            if view.sliding_window_size:
                Tw, window = ring_rows(cv), view.sliding_window_size
                cv = RingPair(*layer_rows_insert(
                    *cv, li, slot, k[0], v[0], jnp.mod(plen, Tw), count
                ))
                pages = (slot_layer_pages(cv.k, li, slot), slot_layer_pages(cv.v, li, slot))
                block = band_block(C, Tw, window)
                with jax.named_scope("odtp_swa"):
                    if block:
                        return banded_chunk_attention(q[0], *pages, plen, window, block)[None]
                    return tiled_sparse_attention(
                        q[0], *pages, ring_window_rows(positions[0], Tw, window), Tw,
                        tile if Tw % tile == 0 else Tw,
                    )[None]
            ck = RingPair(*layer_rows_insert(*ck, li, slot, k[0], v[0], plen, count))
            with jax.named_scope("odtp_full_attn"):
                return over_pages(
                    q[0], slot_layer_pages(ck.k, li, slot), slot_layer_pages(ck.v, li, slot)
                )[None]

        h, out = decoder_block(view, h, layer, *rope, live=live, attend=attend)
        return (h, ck, cv), (out.counts, None, None)

    def lightning_body(carry, layer, li):
        h, states = carry  # every lightning layer's, every slot's
        H, Dh = cfg.num_attention_heads, cfg.head_dim
        where = (li, jnp.asarray(slot, jnp.int32), *(jnp.int32(0),) * 3)
        entering = jax.lax.dynamic_slice(states, where, (1, 1, H, Dh, Dh))[0]
        left: list = []
        h, out = decoder_block(
            cfg, h, layer, None, None, live=live, mix_scope="odtp_lightning_proj",
            mix=lightning_mix(
                cfg, lightning_rope, li, jnp.where(plen > 0, entering, 0.0), count, left,
                whole_rows=True,
            ),
        )
        return (h, jax.lax.dynamic_update_slice(states, left[0][None], where)), out.counts

    def kda_body(carry, layer, li):
        h, states = carry  # every kda layer's, every slot's
        zero, at = jnp.int32(0), jnp.asarray(slot, jnp.int32)
        # ``slot``'s part of this layer's state [1, H, D, D] and tail [taps - 1, 1,
        # 3 H D] (the tails hold a row of the window before the slots), zeros at a
        # prompt's start. The slot's tails are cut out before the layers and
        # written behind them, as the index ring is: cut or carried inside the
        # scan the chip's compiler keeps all the slots' tails in another order
        # there and copies them in and out, 57 MB each way at 128 slots
        where_s = (li, at, zero, zero, zero)
        state = jax.lax.dynamic_slice(states, where_s, (1, 1, *states.shape[2:]))[0]
        tail = jax.lax.dynamic_index_in_dim(slot_tails, li, 0, keepdims=False)[None]
        fresh = lambda x: jnp.where(plen > 0, x, jnp.zeros_like(x))
        left: list = []
        h, out = decoder_block(
            cfg, h, layer, None, None, live=live, mix_scope="odtp_kda_proj",
            mix=kda_mix(cfg, fresh(state), fresh(tail), count, left),
        )
        states = jax.lax.dynamic_update_slice(states, left[0][None], where_s)
        return (h, states), (out.counts, left[1][0])

    def block_body(carry, layer, li):
        h, ck, cv = carry
        sizes = cfg.block_sizes
        stride, before = sizes.kernel_stride, sizes.kernel_size // sizes.kernel_stride - 1
        kept: list = []

        def attend(q, k, v):
            nonlocal ck, cv
            ck, cv = layer_rows_insert(ck, cv, li, slot, k[0], v[0], plen, count)
            with jax.named_scope("odtp_block_select"):
                # the windows that close inside the chunk, C / stride of them
                # from window ``first`` on, pooled from the ring's own rows: a
                # slice from a whole tile of lanes before the chunk on (one cut
                # between two lanes makes the compiler re-lay the whole ring)
                first = jnp.maximum(plen // stride - before, 0)
                zero = jnp.int32(0)
                lanes = math.gcd(C, 128)
                lead = -(-before * stride // lanes) * lanes
                start = jnp.maximum(plen - lead, 0)
                rows = jax.lax.dynamic_slice(
                    ck, (li, jnp.asarray(slot, jnp.int32), zero, zero, start),
                    (1, 1, *ck.shape[2:4], C + lead),
                )[0, 0]
                own = pool_pages(rows, sizes, first * stride - start, C // stride)  # [Kh, D, C / stride]
                windows = first + jnp.arange(C // stride)
                closes = stride * windows + sizes.kernel_size - 1 < plen + count
                page = slot_layer_pages(pooled_cache, li, slot)  # [Kh, D, Tp]
                old = jax.lax.dynamic_slice_in_dim(page, first, C // stride, 2)
                new = jnp.where(closes, own.astype(page.dtype), old)
                page = jax.lax.dynamic_update_slice_in_dim(page, new, first, 2)
                chosen = block_selection(
                    q[0], page, positions[0], jnp.broadcast_to(total < sizes.dense_len, (C,)),
                    sizes, sizes.blocks(T),
                )
            with jax.named_scope("odtp_block_attn"):
                pages = (slot_layer_pages(ck, li, slot), slot_layer_pages(cv, li, slot))
                if in_kernel(q[0], pages[0]):
                    out, visited = chunk_attention(
                        q[0], *pages, positions[0], plen + count, tile, chosen, sizes.block_size
                    )
                else:
                    out, visited = tiled_block_attention(
                        q[0], *pages, chosen, positions[0], plen + count, tile, sizes.block_size
                    )
            kept.append((new, first, visited, jax.lax.dynamic_index_in_dim(chosen, count - 1, 1, False)))
            return out[None]

        h, out = decoder_block(cfg, h, layer, None, None, live=live, attend=attend)
        return (h, ck, cv), (out.counts, None, kept[0])

    ci = index_cache  # read by every layer as the run found it
    h = _embed(cfg, cparams, ids)
    counts, rows, keys = [], [], []
    new_tails: list = []  # the kda layers' [run's layers, taps - 1, 3 H D]
    if cfg.kda:
        with jax.named_scope("odtp_kda_conv"):  # ``slot``'s tails [Lk, taps - 1, 3 H D]
            where_t = (jnp.int32(0), jnp.int32(0), jnp.asarray(slot, jnp.int32), jnp.int32(0))
            slot_tails = jax.lax.dynamic_slice(
                kda_tail, where_t, (*kda_tail.shape[:2], 1, kda_tail.shape[3])
            )[:, :, 0]
    if cfg.linear or cfg.blocks:
        if cfg.blocks and (
            C % cfg.block_sizes.kernel_stride or tile % cfg.block_sizes.block_size or T <= C
        ):
            raise ValueError(
                f"a chunk of {C} tokens over a ring of {T} rows in tiles of {tile}: under a "
                "selection by blocks a chunk is whole strides, a tile whole blocks and the "
                "ring longer than a chunk"
            )
        lightning_rope = _rope(lightning_view(cfg), positions)
        total = jnp.asarray(plen + count if total is None else total, jnp.int32)
    for run in layer_runs(cfg):
        if run.mixer == "lightning":
            (h, lightning_state), c = scan_layers(
                cfg, lightning_body, (h, lightning_state), cparams["layers"], run,
                experts_in_place=True,
            )
            counts.append(c)
            continue
        if run.mixer == "kda":
            (h, kda_state), (c, tail) = scan_layers(
                cfg, kda_body, (h, kda_state), cparams["layers"], run, experts_in_place=True,
            )
            counts.append(c)
            new_tails.append(tail)
            continue
        of_kind = block_body if cfg.blocks else body
        if cfg.latent or cfg.sliding:  # each run under its kind's view and rope tables
            view = kind_view(cfg, run.kind)
            rope = _rope(view, positions) if run.kind == "sliding" else (cos, sin)
            of_kind = functools.partial(
                latent_body if cfg.latent else kinds_body, view=view, rope=rope
            )
        (h, cache_k, cache_v), (c, chose, wrote) = scan_layers(
            cfg, of_kind, (h, cache_k, cache_v), cparams["layers"], run, experts_in_place=True,
        )
        counts.append(c)
        rows.append(chose)
        keys.append(wrote)
    if cfg.sparse:
        with jax.named_scope("odtp_dsa_index"):
            wrote = _stacked([k for k in keys if k is not None])
            index_cache = index_chunk_insert(index_cache, slot, wrote, plen, count)
    h_last = jax.lax.dynamic_slice_in_dim(h, count - 1, 1, axis=1)
    out = [_logits(cfg, cparams, h_last)[:, 0], cache_k, cache_v, index_cache]
    block_tiles = None
    if cfg.blocks:  # the windows the chunk closed, every block layer's, behind the layers
        new, first, visited, last = (jnp.concatenate(x) for x in zip(*keys))
        with jax.named_scope("odtp_block_select"):
            out.append(pooled_chunk_insert(pooled_cache, slot, new, first[0]))
        block_tiles = jnp.sum(visited).astype(jnp.int32).reshape(1)
        rows = [last]
    if cfg.linear:
        out.append(lightning_state)
    if cfg.kda:  # the chunk's tails, every kda layer's, behind the layers
        with jax.named_scope("odtp_kda_conv"):
            tails = jnp.concatenate(new_tails)[:, :, None].astype(kda_tail.dtype)  # [Lk, taps - 1, 1, 3 H D]
            kda_tail = jax.lax.dynamic_update_slice(kda_tail, tails, where_t)
        out.extend((kda_state, kda_tail))
    if return_moe_counts:
        out.append(jnp.sum(_stacked(counts), axis=0))
    if return_block_tiles:
        out.append(jnp.zeros((1,), jnp.int32) if block_tiles is None else block_tiles)
    if return_row_choices:  # the layers with an indexer, in order
        out.append(_stacked([x for x in rows if x is not None]))
    return tuple(out)


def causal_lm_loss(
    logits: jax.Array, labels: jax.Array, ignore_index: int = -100, pred_heads: int = 1
) -> jax.Array:
    """Shifted next-token cross-entropy, mean over non-ignored targets
    (HF CausalLM loss semantics used by the reference drivers). With
    ``pred_heads`` > 1 the logits hold that many vocabularies side by side
    (``LlamaConfig.num_pred_heads``) and head i is held to the token i + 1
    ahead: the mean over the heads of each head's loss."""
    if pred_heads > 1:
        T = logits.shape[1]
        by_head = logits.reshape(*logits.shape[:-1], pred_heads, -1)
        return sum(
            causal_lm_loss(by_head[:, : T - i, i], labels[:, i:], ignore_index)
            for i in range(pred_heads)
        ) / pred_heads
    shift_logits = logits[:, :-1]
    shift_labels = labels[:, 1:]
    mask = shift_labels != ignore_index
    safe_labels = jnp.where(mask, shift_labels, 0)
    logp = jax.nn.log_softmax(shift_logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    total = jnp.sum(nll * mask)
    count = jnp.maximum(jnp.sum(mask), 1)
    return total / count
