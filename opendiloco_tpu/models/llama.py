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

import dataclasses
import functools
import json
import os
from typing import Any, Literal, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from opendiloco_tpu.models.ring_cache import (  # noqa: F401 (re-exported)
    cache_insert,
    init_kv_cache,
    prefix_copy,
    spec_cache_insert,
    suffix_insert,
)
from opendiloco_tpu.ops.attention import (
    decode_step_attention,
    spec_tail_attention,
    xla_attention,
)
from opendiloco_tpu.ops.decode_kernels import (
    W4_BLOCK,
    paged_decode_attention,
    spec_tail_attention_fused,
    w4_matmul,
    w4_matmul_supported,
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

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

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
        return cls(**known)

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        if d["num_key_value_heads"] is None:
            d["num_key_value_heads"] = self.num_attention_heads
        d.update(
            architectures=["LlamaForCausalLM"],
            model_type="llama",
            hidden_act="silu",
            use_cache=False,
        )
        return d

    def num_params(self) -> int:
        return sum(x.size for x in jax.tree.leaves(shapes(self)))


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

    E = cfg.num_experts
    ffn = (
        {
            "router": s(L, D, E),
            "gate_proj": s(L, E, D, F),
            "up_proj": s(L, E, D, F),
            "down_proj": s(L, E, F, D),
        }
        if E
        else {
            "gate_proj": s(L, D, F),
            "up_proj": s(L, D, F),
            "down_proj": s(L, F, D),
        }
    )
    qk_norm = (
        {"q_norm": s(L, Nh * Dh), "k_norm": s(L, Nkv * Dh)} if cfg.qk_norm else {}
    )
    tree = {
        "embed_tokens": s(V, D),
        "layers": {
            "input_norm": s(L, D),
            "post_attn_norm": s(L, D),
            "q_proj": s(L, D, Nh * Dh),
            "k_proj": s(L, D, Nkv * Dh),
            "v_proj": s(L, D, Nkv * Dh),
            "o_proj": s(L, Nh * Dh, D),
            **qk_norm,
            **ffn,
        },
        "final_norm": s(D),
    }
    if not cfg.tie_word_embeddings:
        tree["lm_head"] = s(D, V)
    return tree


def init_params(rng: jax.Array, cfg: LlamaConfig) -> dict:
    """Fresh init matching HF llama conventions: normal(0, initializer_range)
    for projections/embeddings, ones for norms (init_weights.py parity)."""
    shp = shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shp)
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, (path, leaf) in zip(keys, leaves):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if "norm" in name:
            out.append(jnp.ones(leaf.shape, leaf.dtype))
        else:
            out.append(
                jax.random.normal(key, leaf.shape, leaf.dtype) * cfg.initializer_range
            )
    return jax.tree.unflatten(treedef, out)


# the rematerialization policy accepted everywhere a `remat` argument
# appears (``_maybe_remat`` says what each value saves)
RematPolicy = Union[bool, Literal["none", "full", "dots", "dots_all"]]


def _maybe_remat(block, remat: RematPolicy):
    """Apply the rematerialization policy to a per-layer block function.

    remat=False/"none": save all activations (no recompute -- fastest when
    they fit); True/"full": save only layer boundaries (reference-style full
    checkpointing); "dots": save matmul/MXU outputs and recompute the cheap
    elementwise chain (norms, rope, silu) -- recovers most of full remat's
    memory while skipping the extra forward through the matmuls, which is
    where ~all the FLOPs are."""
    if remat in (False, None, "none"):
        return block
    if remat in (True, "full"):
        return jax.checkpoint(block)
    if remat in ("dots", "dots_all"):
        # also save the flash-attention outputs (tagged in
        # ops/flash_attention._flash_fwd): they are custom-calls, not dots,
        # so the dots policy alone would rerun the whole forward kernel
        # during backward just to rebuild its residuals. "dots_all" saves
        # batched dots too (the XLA-attention score/weighted-sum matmuls),
        # trading more HBM for less backward recompute
        dots = (
            jax.checkpoint_policies.dots_saveable
            if remat == "dots_all"
            else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
        return jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.save_from_both_policies(
                dots,
                jax.checkpoint_policies.save_only_these_names(
                    "attn_out", "attn_lse"
                ),
            ),
        )
    raise ValueError(f"unknown remat policy {remat!r}")


def _rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    # variance in float32 for stability (HF llama semantics)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    return (xf * weight.astype(jnp.float32)).astype(x.dtype)


def _rope_tables(
    positions: jax.Array, d: int, theta: float
) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) [B, T, 1, D/2] float32 for the given positions.

    Hoisted out of the layer scan: the tables are shared by every layer's
    q and k, so the cos/sin transcendentals run once per step instead of
    2*num_layers times."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, D/2]
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


def _qkv(cfg: LlamaConfig, x: jax.Array, layer: dict, cos, sin, mul):
    """The attention block's projections of x [B, T, D]: q [B, T, Nh, Dh] and
    k [B, T, Nkv, Dh] rotated by position, and v. ``mul(x, w)`` is the
    caller's weight matmul. With ``cfg.qk_norm`` q and k pass an RMSNorm
    over their whole width before they are split into heads (OLMoE)."""
    B, T, _ = x.shape
    Nh, Nkv, Dh = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    q = mul(x, layer["q_proj"])
    k = mul(x, layer["k_proj"])
    v = mul(x, layer["v_proj"]).reshape(B, T, Nkv, Dh)
    if cfg.qk_norm:
        q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    q = _rope_apply(q.reshape(B, T, Nh, Dh), cos, sin)
    k = _rope_apply(k.reshape(B, T, Nkv, Dh), cos, sin)
    return q, k, v


def _routed_ffn(
    cfg: LlamaConfig, x: jax.Array, layer: dict, live: Optional[jax.Array]
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
    summed under their weights.

    Aux loss (OLMoE, arXiv 2409.02060): ``router_aux_loss_coef`` times the
    load balance E * sum_e f_e P_e (f_e the share of tokens that chose e,
    over all k places; P_e the mean router probability) plus
    ``router_z_loss_coef`` times the mean squared logsumexp of the logits.

    Counts, int32 [3]: token-expert pairs, experts with at least one pair,
    the busiest expert's pairs -- of the ``live`` tokens ([N] bool; None:
    all), so padding rows of a prefill bucket and empty slots do not count."""
    D = x.shape[-1]
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(-1, D)
    N = xf.shape[0]

    logits = jnp.dot(xf, layer["router"], preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [N, E]
    gate, expert = jax.lax.top_k(probs, K)  # [N, K]; ties go to the lower index
    if cfg.norm_topk_prob:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    flat = expert.reshape(-1)  # pair p belongs to token p // K
    sizes = jnp.bincount(flat, length=E)  # int32, one group per expert
    order = jnp.argsort(flat, stable=True)  # pairs by expert
    xs = xf[order // K]
    h = jax.nn.silu(
        jax.lax.ragged_dot(xs, layer["gate_proj"], sizes)
    ) * jax.lax.ragged_dot(xs, layer["up_proj"], sizes)
    ys = jax.lax.ragged_dot(h, layer["down_proj"], sizes)  # [N * K, D]
    ys = ys[jnp.argsort(order)].reshape(N, K, D)
    out = jnp.sum(ys.astype(jnp.float32) * gate[..., None], axis=1)

    balance = E * jnp.sum(sizes / N * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = cfg.router_aux_loss_coef * balance + cfg.router_z_loss_coef * z

    if live is not None:
        sizes = jnp.bincount(
            flat, weights=jnp.repeat(live.reshape(-1), K).astype(jnp.int32), length=E
        )
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes)])
    return out.astype(x.dtype).reshape(x.shape), aux, counts


def _ffn(
    cfg: LlamaConfig, x: jax.Array, layer: dict, mul, live=None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The block's feed-forward over x [..., D] -> (out, aux loss, routing
    counts): SwiGLU through the caller's weight matmul ``mul(x, w)``, or the
    routed experts; aux and counts are zero for a dense model."""
    if cfg.num_experts:
        return _routed_ffn(cfg, x, layer, live)
    out = mul(
        jax.nn.silu(mul(x, layer["gate_proj"])) * mul(x, layer["up_proj"]),
        layer["down_proj"],
    )
    return out, jnp.float32(0.0), jnp.zeros((3,), jnp.int32)


class BlockOut(NamedTuple):
    """What one layer leaves beside the hidden state."""

    k: jax.Array  # this layer's keys, rotated [B, T, Nkv, Dh]
    v: jax.Array  # and values
    attn_out: jax.Array  # the attention branch after o_proj [B, T, D]
    aux: jax.Array  # the routed FFN's weighted aux loss (0 for a dense one)
    counts: jax.Array  # the routed FFN's counts, int32 [3] (``_routed_ffn``)


def decoder_block(
    cfg: LlamaConfig,
    h: jax.Array,
    layer: dict,
    cos: jax.Array,
    sin: jax.Array,
    *,
    mul,
    attend,
    live: Optional[jax.Array] = None,
) -> tuple[jax.Array, BlockOut]:
    """One decoder layer over h [B, T, D], the only statement of its
    skeleton: RMSNorm, q/k/v, attention, o_proj, residual; RMSNorm, FFN,
    residual. Training and the four serving forwards differ in what they
    pass: ``mul(x, w)`` is the caller's weight matmul, ``attend(q, k, v)``
    its attention over this layer's q [B, T, Nh, Dh] and new k, v (a cache
    it reads or writes is the caller's own), ``live`` the tokens a routed
    FFN counts."""
    B, T, _ = h.shape
    # the scopes name the device work in a profiler trace (an operation's
    # op_name metadata); they change nothing that is computed
    with jax.named_scope("odtp_attention"):
        x = _rms_norm(h, layer["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(cfg, x, layer, cos, sin, mul)
        attn_out = mul(attend(q, k, v).reshape(B, T, -1), layer["o_proj"])
        h = h + attn_out
    with jax.named_scope("odtp_mlp"):
        x = _rms_norm(h, layer["post_attn_norm"], cfg.rms_norm_eps)
        ffn, aux, counts = _ffn(cfg, x, layer, mul, live)
    return h + ffn, BlockOut(k, v, attn_out, aux, counts)


def training_block(
    cfg: LlamaConfig, attn_fn, positions: jax.Array, remat: RematPolicy
):
    """The body of training's scan over layers, ``(h, layer) -> (h, (attn-
    output L2 norm, moe aux loss))``, under the rematerialization policy.
    The norm is the activation probe the reference attaches via forward
    hooks on ``self_attn`` (utils.py:43-67, train_fsdp.py:65)."""
    cos, sin = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)

    def body(h, layer):
        h, out = decoder_block(
            cfg, h, layer, cos, sin, mul=jnp.matmul, attend=attn_fn
        )
        with jax.named_scope("odtp_attention"):
            attn_norm = jnp.sqrt(jnp.sum(out.attn_out.astype(jnp.float32) ** 2))
        return h, (attn_norm, out.aux)

    return _maybe_remat(body, remat)


def _final_norm_and_head(
    cfg: LlamaConfig, cparams: dict, h: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """-> (h under the final RMSNorm, the head [D, V]: the embedding's
    transpose where the configuration ties them)."""
    h = _rms_norm(h, cparams["final_norm"], cfg.rms_norm_eps)
    head = (
        cparams["embed_tokens"].T
        if cfg.tie_word_embeddings
        else cparams["lm_head"]
    )
    return h, head


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
    """input_ids [B, T] int32 -> logits [B, T, V] float32.

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
            mesh_ = ring_mesh
            attn_fn = lambda q, k, v: flash_attention_sharded(
                q, k, v, mesh=mesh_, batch_axes=batch_axes, tp_axis=tp_axis,
                causal=True,
            )
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
            attn_fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    elif attn_impl == "ring":
        from opendiloco_tpu.ops.ring_attention import ring_attention_auto

        attn_fn = lambda q, k, v: ring_attention_auto(
            q, k, v, mesh=ring_mesh, axis=ring_axis
        )
    else:
        raise ValueError(f"unknown attn_impl {attn_impl!r}")

    h = jnp.take(cparams["embed_tokens"], input_ids, axis=0)

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
        block = training_block(cfg, attn_fn, positions, remat)
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
        h, (attn_norms, layer_auxs) = jax.lax.scan(
            block, h, cparams["layers"], unroll=max(1, unroll)
        )
        moe_aux = jnp.mean(layer_auxs)

    h, head = _final_norm_and_head(cfg, cparams, h)
    if return_hidden:
        # composes with return_moe_aux so fused lm-head losses can thread
        # the router aux loss (trainer._loss_fn)
        return (h, head, moe_aux) if return_moe_aux else (h, head)
    with jax.named_scope("odtp_lm_head_loss"):
        logits = (h @ head).astype(jnp.float32)
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


@jax.tree_util.register_pytree_node_class
class PackedW4:
    """A matmul weight held blockwise-4-bit-packed at rest (serve
    ``weight_format=w4``): ``q`` [..., ceil(n/2)] uint8 packed nibbles and
    ``s`` [..., nblocks] uint16 fp16-bit scales per ``W4_BLOCK`` values —
    the PR 8 ``blockwise4bit`` codec geometry, applied per layer so the
    packed leaves keep the leading L axis and ride the decode layer scan.
    ``shape`` is the per-layer unpacked shape (static aux data, so scan
    reconstructs the node with it intact)."""

    def __init__(self, q, s, shape):
        self.q = q
        self.s = s
        self.shape = tuple(int(x) for x in shape)

    def tree_flatten(self):
        return (self.q, self.s), self.shape

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)


def dequant_w4(q: jax.Array, s: jax.Array, shape: tuple, dtype) -> jax.Array:
    """Unpack one layer's 4-bit weight inside the jit'd forward.

    Bit-for-bit the ``native._dequant4_numpy`` math at f32: element 2i is
    the low nibble of byte i, value = (nibble - 8) * fp16(scale) / 7."""
    n = 1
    for x in shape:
        n *= int(x)
    nib = jnp.stack([q & jnp.uint8(0x0F), q >> 4], axis=-1).reshape(-1)[:n]
    qv = nib.astype(jnp.float32) - jnp.float32(8.0)
    sf = jax.lax.bitcast_convert_type(s, jnp.float16).astype(jnp.float32)
    sf = sf / jnp.float32(7.0)
    pad = (-n) % W4_BLOCK
    qp = jnp.pad(qv, (0, pad)).reshape(-1, W4_BLOCK)
    out = (qp * sf[:, None]).reshape(-1)[:n].reshape(shape)
    return out.astype(dtype)


def _wleaf(w, dtype):
    """Materialize a weight leaf for a matmul: packed leaves dequantize
    per-block here, inside the jit (fused dequant+matmul); plain arrays
    pass through (already cast by ``_cast_serving_params``)."""
    if isinstance(w, PackedW4):
        return dequant_w4(w.q, w.s, w.shape, dtype)
    return w


def _wmul(x, w, dtype, kernel="xla"):
    """One weight-matmul site: ``x @ materialized(w)``.

    On the Pallas decode path a packed leaf routes through the fused
    dequant-matmul kernel — nibbles dequantize in-registers per tile —
    instead of materializing the full weight via ``_wleaf``. Dense
    leaves and untileable packed shapes keep the XLA contraction."""
    if (
        kernel == "pallas"
        and isinstance(w, PackedW4)
        and w4_matmul_supported(w.shape)
    ):
        lead = x.shape[:-1]
        out = w4_matmul(x.reshape(-1, x.shape[-1]), w.q, w.s, w.shape, dtype)
        return out.reshape(*lead, w.shape[1])
    return x @ _wleaf(w, dtype)


def _cast_serving_params(params, dtype):
    """The forward-boundary cast, w4-aware: packed uint8/uint16 leaves
    stay packed (their dequant targets ``dtype`` at the matmul site)."""
    return jax.tree.map(
        lambda x: x if x.dtype in (jnp.uint8, jnp.uint16) else x.astype(dtype),
        params,
    )


def _serving_boundary(params, compute_dtype, decode_kernel):
    """What the four serving forwards do first -> (the weights cast to the
    compute dtype, their weight matmul ``mul(x, w)``)."""
    cparams = _cast_serving_params(params, compute_dtype)
    mul = functools.partial(_wmul, dtype=compute_dtype, kernel=decode_kernel)
    return cparams, mul


def _logits(cfg: LlamaConfig, cparams: dict, h: jax.Array) -> jax.Array:
    """Final norm and lm head over h [..., D] -> float32 logits [..., V]."""
    h, head = _final_norm_and_head(cfg, cparams, h)
    return (h @ head).astype(jnp.float32)


def prefill_forward(
    params: dict,
    input_ids: jax.Array,
    length: jax.Array,
    cfg: LlamaConfig,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    decode_kernel: str = "xla",
    return_moe_counts: bool = False,
):
    """Prompt prefill for serving: ids [1, P] -> (last-token logits [1, V]
    f32, per-layer K/V [L, P, Nkv, Dh] in compute dtype), and with
    ``return_moe_counts`` the routed FFN's counts over the live prompt
    tokens summed over layers (int32 [3], see ``_routed_ffn``).

    ``length`` (traced scalar) is the true prompt length; ``input_ids``
    may be right-padded to a compile-size bucket. Padding K/V rows do land
    in the returned stack (and hence the cache) but are never attended:
    the decode mask stops at the live length and every ring write
    overwrites index ``len % T`` before index ``len`` becomes visible."""
    B, P = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
    cparams, mul = _serving_boundary(params, compute_dtype, decode_kernel)
    cos, sin = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    live = positions < length
    attend = lambda q, k, v: xla_attention(q, k, v, causal=True)

    def body(h, layer):
        h, out = decoder_block(
            cfg, h, layer, cos, sin, mul=mul, attend=attend, live=live
        )
        return h, (out.k[0], out.v[0], out.counts)

    h = jnp.take(cparams["embed_tokens"], input_ids, axis=0)
    h, (ks, vs, counts) = jax.lax.scan(body, h, cparams["layers"])
    h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
    logits = _logits(cfg, cparams, h_last)
    if return_moe_counts:
        return logits[:, 0], ks, vs, jnp.sum(counts, axis=0)
    return logits[:, 0], ks, vs


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
    tests/test_tpu_compile.py). The XLA path (off the TPU, and the per-call
    fallback for a shape the kernel cannot tile) scatters the row and
    slices the layer's pages, with copies where the compiler wants them.
    With ``return_moe_counts`` the routed FFN's counts over the slots that
    hold a sequence (``lens > 0``), summed over layers, come fourth."""
    cparams, mul = _serving_boundary(params, compute_dtype, decode_kernel)
    positions = lens[:, None].astype(jnp.int32)  # [S, 1]
    cos, sin = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    live = lens > 0
    step_attention = (
        paged_decode_attention
        if decode_kernel == "pallas"
        else decode_step_attention
    )

    def body(carry, xs):
        h, ck, cv = carry  # the whole caches
        layer, li = xs

        def attend(q, k, v):
            nonlocal ck, cv
            out, ck, cv = step_attention(
                q[:, 0], k[:, 0], v[:, 0], ck, cv, lens, li
            )
            return out

        h, out = decoder_block(
            cfg, h, layer, cos, sin, mul=mul, attend=attend, live=live
        )
        return (h, ck, cv), out.counts

    h = jnp.take(cparams["embed_tokens"], tokens, axis=0)[:, None]  # [S, 1, D]
    layer_ids = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
    (h, new_ck, new_cv), counts = jax.lax.scan(
        body, (h, cache_k, cache_v), (cparams["layers"], layer_ids)
    )
    logits = _logits(cfg, cparams, h)
    if return_moe_counts:
        return logits[:, 0], new_ck, new_cv, jnp.sum(counts, axis=0)
    return logits[:, 0], new_ck, new_cv


def _tail_attention(decode_kernel: str):
    """Attention of tail queries over ring pages plus the tail's own K/V."""
    return (
        spec_tail_attention_fused
        if decode_kernel == "pallas"
        else spec_tail_attention
    )


def verify_forward(
    params: dict,
    tail: jax.Array,
    lens: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cfg: LlamaConfig,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    decode_kernel: str = "xla",
):
    """Batched multi-token verify pass for self-speculative decode.

    tail [S, K] int32 are K unverified tokens per slot (the current
    token followed by the draft's proposals) at absolute positions
    ``lens + i``; cache_{k,v} hold the ring pages as of BEFORE the tail.
    Returns (logits [S, K, V] f32, tail_ks, tail_vs [L, S, K, Nkv, Dh]):
    one full-depth greedy logit row per tail position, plus the tail's
    K/V -- kept OUT of the ring here so rejected tokens need no rollback;
    the engine inserts only the accepted prefix via
    :func:`spec_cache_insert`.

    Also the continued-prefill primitive for shared-prefix KV reuse
    (S = 1, tail = the suffix tokens, lens = the reused prefix length).
    """
    S, K = tail.shape
    cparams, mul = _serving_boundary(params, compute_dtype, decode_kernel)
    positions = lens[:, None] + jnp.arange(K, dtype=jnp.int32)[None]  # [S, K]
    cos, sin = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    tail_attention = _tail_attention(decode_kernel)

    def body(h, xs):
        layer, ck, cv = xs  # one layer's pages
        attend = lambda q, k, v: tail_attention(q, ck, cv, k, v, lens)
        h, out = decoder_block(cfg, h, layer, cos, sin, mul=mul, attend=attend)
        return h, (out.k, out.v)

    h = jnp.take(cparams["embed_tokens"], tail, axis=0)  # [S, K, D]
    h, (tail_ks, tail_vs) = jax.lax.scan(
        body, h, (cparams["layers"], cache_k, cache_v)
    )
    return _logits(cfg, cparams, h), tail_ks, tail_vs


def draft_propose(
    params: dict,
    tokens: jax.Array,
    lens: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cfg: LlamaConfig,
    *,
    k_steps: int,
    draft_layers: int,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    decode_kernel: str = "xla",
):
    """Self-speculative draft: propose ``k_steps`` greedy tokens per slot
    from the first ``draft_layers`` of the SAME weights (final norm and
    lm head shared with the full stack).

    The truncated stack's K/V for the proposed tail lives in registers
    (a [Ld, S, k, Nkv, Dh] buffer threaded between token steps), never
    the ring -- the draft is a heuristic and dirties nothing; exactness
    is the verify pass's job. Returns proposals [S, k_steps] int32.
    """
    S = tokens.shape[0]
    L, Ld = cfg.num_hidden_layers, int(draft_layers)
    if not 1 <= Ld <= L:
        raise ValueError(f"draft_layers {Ld} outside [1, {L}]")
    cparams, mul = _serving_boundary(params, compute_dtype, decode_kernel)
    dlayers = jax.tree.map(lambda x: x[:Ld], cparams["layers"])
    dck, dcv = cache_k[:Ld], cache_v[:Ld]
    tail_attention = _tail_attention(decode_kernel)

    tail_shape = (Ld, S, k_steps, cfg.kv_heads, cfg.head_dim)
    tkb = jnp.zeros(tail_shape, compute_dtype)
    tvb = jnp.zeros(tail_shape, compute_dtype)
    cur = tokens
    proposals = []
    for i in range(k_steps):
        positions = (lens + jnp.int32(i))[:, None]  # [S, 1]
        cos, sin = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)

        def body(h, xs, i=i, cos=cos, sin=sin):
            layer, ck, cv, tk, tv = xs

            def attend(q, k, v):
                nonlocal tk, tv
                tk = tk.at[:, i].set(k[:, 0])
                tv = tv.at[:, i].set(v[:, 0])
                return tail_attention(q, ck, cv, tk, tv, lens, q_start=i)

            h, _ = decoder_block(cfg, h, layer, cos, sin, mul=mul, attend=attend)
            return h, (tk, tv)

        h = jnp.take(cparams["embed_tokens"], cur, axis=0)[:, None]  # [S, 1, D]
        h, (tkb, tvb) = jax.lax.scan(body, h, (dlayers, dck, dcv, tkb, tvb))
        logits = _logits(cfg, cparams, h)
        cur = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        proposals.append(cur)
    return jnp.stack(proposals, axis=1)  # [S, k_steps]


def causal_lm_loss(
    logits: jax.Array, labels: jax.Array, ignore_index: int = -100
) -> jax.Array:
    """Shifted next-token cross-entropy, mean over non-ignored targets
    (HF CausalLM loss semantics used by the reference drivers)."""
    shift_logits = logits[:, :-1]
    shift_labels = labels[:, 1:]
    mask = shift_labels != ignore_index
    safe_labels = jnp.where(mask, shift_labels, 0)
    logp = jax.nn.log_softmax(shift_logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    total = jnp.sum(nll * mask)
    count = jnp.maximum(jnp.sum(mask), 1)
    return total / count
