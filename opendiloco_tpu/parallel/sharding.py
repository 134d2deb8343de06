"""PartitionSpec rules for the Llama parameter pytree.

Replaces torch-FSDP's parameter flattening/wrapping (reference:
train_fsdp.py:239-245) with explicit NamedShardings: each leaf gets a spec
over the (dp, fsdp, sp, tp) mesh and XLA emits the all-gather /
reduce-scatter pattern that FSDP hand-implements.

Rules:
- tp shards the "model-parallel" dim: attention heads for q/k/v/o, ffn dim
  for gate/up/down, vocab for embed/lm_head (Megatron-style layout).
- fsdp shards the *other* (usually largest remaining) dim, only when
  divisible by the axis size; small vectors (norms) stay replicated.
- the leading stacked-layer axis is never sharded (it is scanned over).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from opendiloco_tpu.models.llama import LlamaConfig, shapes
from opendiloco_tpu.parallel.mesh import MeshPlan, params_sharded, optstate_sharded

# per-leaf: (tp dim index, preferred fsdp dim index) -- indices into the
# UNSTACKED shape (layer leaves get +1 when the leading L axis is present;
# expert-stacked FFN leaves get a further +1 after their expert dim).
_LAYOUT: dict[str, tuple[Optional[int], int]] = {
    "embed_tokens": (0, 1),  # [V, D]: tp on vocab, fsdp on D
    "lm_head": (1, 0),  # [D, V]
    "final_norm": (None, -1),
    "input_norm": (None, -1),
    "post_attn_norm": (None, -1),
    "q_norm": (None, -1),  # [Nh*Dh]: a norm vector, replicated
    "k_norm": (None, -1),
    "q_proj": (1, 0),  # [D, Nh*Dh]
    "k_proj": (1, 0),
    "v_proj": (1, 0),
    "o_proj": (0, 1),  # [Nh*Dh, D]
    "gate_proj": (1, 0),  # [D, F] (or [E, D, F] under MoE)
    "up_proj": (1, 0),
    "down_proj": (0, 1),  # [F, D] (or [E, F, D])
    "router": (None, 0),  # [D, E]: small, fsdp on D
    "router_bias": (None, -1),  # [E]: the selection bias, replicated
    # latent attention: the two up-projections and o_proj are per head and
    # take tp like q/k/v/o; the down-projections end in a norm over their
    # whole width (and kv_a_proj's rotated tail is shared by every head),
    # so they keep it whole: fsdp only
    "q_a_proj": (None, 0),  # [D, Rq]
    "q_a_norm": (None, -1),
    "q_b_proj": (1, 0),  # [Rq, Nh*(nope+rope)]
    "kv_a_proj": (None, 0),  # [D, R+rope]
    "kv_a_norm": (None, -1),
    "kv_b_proj": (1, 0),  # [R, Nh*(nope+v)]
    "attn_gate": (1, 0),  # [D, Nh]: a value a head, tp with the heads it scales
    # (elementwise, [D, Nh*Dh]: a value each of the output's, tp as q_proj).
    # A lightning layer's (models/lightning.py): its five projections are per
    # head and take tp like q/k/v/o; its output norm runs over all heads'
    # values under one weight vector, replicated like the norms
    "out_gate": (1, 0),  # [D, Nh*Dh]
    "out_norm": (None, -1),  # [Nh*Dh]
    # A kda layer's (models/kda.py): q/k/v/o as any attention's; its decay's
    # and its output gate's low-rank pairs go down whole (fsdp only) and up per
    # head (tp like q_proj), beta is a value a head, the gate's bias a vector;
    # conv_weight, dt_bias and A_log take the Mamba-2 mixer's rows below
    "f_a_proj": (None, 0),  # [D, Dh]
    "f_b_proj": (1, 0),  # [Dh, Nh*Dh]
    "g_a_proj": (None, 0),
    "g_b_proj": (1, 0),
    "g_bias": (None, -1),  # [Nh*Dh]
    "b_proj": (1, 0),  # [D, Nh]
    # the shared SwiGLU beside a routed FFN (granite hybrid): as a dense FFN's
    "shared_gate_proj": (1, 0),  # [D, Fs]
    "shared_up_proj": (1, 0),
    "shared_down_proj": (0, 1),  # [Fs, D]
    # the Mamba-2 mixer (models/mamba.py). Its in_proj's output is z | xBC |
    # dt side by side and its conv runs over all of xBC's channels, so tp
    # has no dim that keeps a head's parts together: fsdp only. The decay
    # vectors (dt_bias, A_log, D), conv_bias and mixer_norm are [H] or [C]
    # vectors and replicate like the norms
    "in_proj": (None, 0),  # [D, 2HP + 2N + H]
    "out_proj": (None, 1),  # [HP, D]
    "conv_weight": (None, 1),  # [K, C]
    "conv_bias": (None, -1),
    "dt_bias": (None, -1),
    "A_log": (None, -1),
    "D": (None, -1),
    "mixer_norm": (None, -1),
    # CCA (llama._cca_qkv): the second value projection as v_proj; the two
    # convolutions are small and run over q and k side by side: replicated
    "v_prev_proj": (1, 0),  # [D, Nkv/2 * Dh]
    "cca_conv0_weight": (None, -1),  # [taps, (Nh + Nkv) Dh]
    "cca_conv1_weight": (None, -1),  # [Nh + Nkv, taps, Dh, Dh]
    # ZAYA's router MLP (llama._router_features): the down-projection as a
    # linear router, the two square maps replicated
    "router_down": (None, 0),  # [D, R]
    "router_fc1": (None, -1),  # [R, R]
    "router_fc2": (None, -1),
    # the indexer of learned sparse attention (llama._index_qkw): its queries
    # are per index head and take tp like q_proj; its one key and its head
    # weights are small and end in a norm or a sum over heads: fsdp only
    "index_q": (1, 0),  # [D, Hi*Di]
    "index_k": (None, 0),  # [D, Di]
    "index_k_norm": (None, -1),
    "index_k_norm_bias": (None, -1),
    "index_w": (None, 0),  # [D, Hi]
    # EVA's pooling vectors (ops.attention.eva_pool), a head's size a head: replicated
    "adaptive_phi": (None, -1),  # [Nkv, Dh]
    "adaptive_mu_k": (None, -1),
}

# FFN leaves that gain a leading expert dim when num_experts > 0
_EXPERT_LEAVES = {"gate_proj", "up_proj", "down_proj"}


def _pp_stackable(plan: MeshPlan, shape: tuple[int, ...], stacked: bool) -> bool:
    """Can the stacked layer dim shard over the pp axis for this leaf?"""
    return bool(
        stacked
        and plan.pp_axis
        and shape[0] % plan.mesh.shape[plan.pp_axis] == 0
    )


def _leaf_spec(
    name: str,
    shape: tuple[int, ...],
    stacked: bool,
    *,
    shard_params: bool,
    plan: MeshPlan,
) -> P:
    tp_dim, fsdp_dim = _LAYOUT[name]
    ndim = len(shape)
    axes: list[Optional[str]] = [None] * ndim
    offset = 1 if stacked else 0
    if _pp_stackable(plan, shape, stacked):
        axes[0] = plan.pp_axis  # pipeline stages own layer-dim slices

    # expert-stacked FFN leaf ([L, E, ...]): expert dim shards over ep
    if name in _EXPERT_LEAVES and ndim == offset + 3:
        if plan.ep_axis and shape[offset] % plan.mesh.shape[plan.ep_axis] == 0:
            axes[offset] = plan.ep_axis
        offset += 1  # tp/fsdp indices apply past the expert dim

    if plan.tp_axis and tp_dim is not None:
        d = tp_dim + offset
        if shape[d] % plan.mesh.shape[plan.tp_axis] == 0:
            axes[d] = plan.tp_axis

    if shard_params and plan.fsdp_axis and fsdp_dim >= 0:
        fsdp_n = plan.mesh.shape[plan.fsdp_axis]
        d = fsdp_dim + offset
        if axes[d] is None and shape[d] % fsdp_n == 0:
            axes[d] = plan.fsdp_axis
        else:
            # preferred dim taken by tp or not divisible: try any other
            # non-layer dim, largest first
            cands = sorted(
                (i for i in range(offset, ndim) if axes[i] is None),
                key=lambda i: -shape[i],
            )
            for i in cands:
                if shape[i] % fsdp_n == 0:
                    axes[i] = plan.fsdp_axis
                    break
    return P(*axes)


def param_specs(cfg: LlamaConfig, plan: MeshPlan, *, for_params: bool = True) -> dict:
    """Pytree of PartitionSpecs matching ``llama.shapes(cfg)``.

    for_params=True gives the resident sharding of the parameters themselves;
    for_params=False gives the sharding used for optimizer-state leaves
    (ZeRO-2 shards opt state even when params are replicated).
    """
    shard = params_sharded(plan.strategy) if for_params else optstate_sharded(plan.strategy)
    shp = shapes(cfg)

    def one(path, leaf):
        name = path[-1].key
        stacked = any(getattr(p, "key", None) == "layers" for p in path[:-1])
        if len(leaf.shape) <= (1 + (1 if stacked else 0)):
            if _pp_stackable(plan, leaf.shape, stacked):
                return P(plan.pp_axis)  # norm vectors still split by stage
            return P()  # norm vectors: replicate
        return _leaf_spec(
            name, leaf.shape, stacked, shard_params=shard, plan=plan
        )

    return jax.tree_util.tree_map_with_path(one, shp)


def optstate_specs(opt_state_shapes, params, p_specs: dict, plan: MeshPlan) -> object:
    """Shard optimizer-state leaves like their matching parameter.

    Leaves are matched to params by array shape (Adam's mu/nu mirror the
    param tree); scalars and unmatched leaves replicate. ZeRO-2 parity:
    utils.py:141-142 (SHARD_GRAD_OP).
    """
    by_shape: dict[tuple, P] = {}
    for (path, leaf), (_, spec) in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_flatten_with_path(p_specs)[0],
    ):
        by_shape.setdefault(tuple(leaf.shape), spec)

    def one(leaf):
        return by_shape.get(tuple(leaf.shape), P())

    return jax.tree.map(one, opt_state_shapes)
