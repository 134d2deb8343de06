"""Pipeline parallelism over the "pp" mesh axis (GPipe schedule).

The reference has no pipeline parallelism (SURVEY §2.4: "No"); this is a
beyond-parity axis for models whose layer stack outgrows one chip group.
TPU-native formulation: the scan-over-layers parameter stack [L, ...] is
sharded over "pp" so each stage owns L/pp contiguous layers, and a
shard_map runs the classic fill-drain schedule -- at tick t stage r
processes microbatch (t - r), then hands its activation to stage r+1 via
``jax.lax.ppermute``. The whole schedule is a ``lax.scan`` inside jit, so
the backward pass is the reverse pipeline by autodiff (ppermute transposes
to the reverse permutation; no hand-written VJP needed).

Embedding, final norm, and the lm head stay OUTSIDE the pipeline region
(they are replicated over pp and cheap); only the decoder stack is staged.
The final hidden states are reassembled on the last stage and replicated
with a masked psum.

Memory is GPipe-shaped: all in-flight microbatch activations live until
their backward tick; per-tick blocks are rematerialized (jax.checkpoint).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from opendiloco_tpu.models.llama import LlamaConfig, RematPolicy, training_block


def pipeline_hidden(
    cparams: dict,
    h0: jax.Array,
    positions: jax.Array,
    cfg: LlamaConfig,
    mesh,
    *,
    microbatches: int,
    attn_fn,
    remat: RematPolicy = True,
    axis: str = "pp",
    sp_axis: str | None = None,
) -> jax.Array:
    """Run the decoder stack as a pp-staged pipeline.

    cparams["layers"]: stacked [L, ...] pytree (sharded over ``axis`` at the
    jit level); h0: embedded inputs [B, T, D]; returns (final hidden
    [B, T, D] (pre-final-norm), moe_aux scalar). B must divide by
    ``microbatches``. ``attn_fn`` is the per-block attention callable built
    by ``llama.forward``.

    ``sp_axis`` composes sequence parallelism with the pipeline (round 5):
    the shard_map binds BOTH axes manual — nesting ring attention's own
    shard_map inside a pp-manual region lowers in the forward but neither
    Shardy nor GSPMD can lower its jvp — so activations arrive as local
    [.., T/sp, D] chunks, every non-attention op is token-local anyway,
    and ``ring_attention_auto`` detects the already-manual axis and runs
    the ring body directly. MoE caveat: router batch statistics become
    sequence-chunk-local under sp (the mean over chunks is psum'd, same
    GPipe-style semantics as the per-microbatch stats).

    moe_aux is the router aux loss averaged over layers AND microbatches
    (psum'd across stages). With microbatches=1 it equals the unpipelined
    value exactly; with M>1 the router's batch statistics are computed per
    microbatch, so the aux is the mean of M microbatch-local values --
    the standard GPipe semantics for batch-statistic losses. 0.0 for
    dense models.
    """
    if cfg.hybrid:
        raise ValueError(
            "the pp pipeline is refused for a configuration with Mamba-2 "
            "layers: it stages one homogeneous [L, ...] stack, and a hybrid's "
            "layers are stacked per kind of mixer (llama.layer_runs)"
        )
    if cfg.linear or cfg.blocks:
        raise ValueError(
            "the pp pipeline is refused for a configuration with lightning "
            "linear-attention layers or a selection by blocks: it stages one "
            "homogeneous [L, ...] stack under the caller's attn_fn, and these layers "
            "are stacked per kind of mixer, each with an attention of its own"
        )
    if cfg.kda:
        raise ValueError(
            "the pp pipeline is refused for a configuration with kda linear-attention "
            "layers: it stages one homogeneous [L, ...] stack under the caller's attn_fn, "
            "and these layers are stacked per kind of mixer"
        )
    if cfg.eva:
        raise ValueError(
            "the pp pipeline is refused for a configuration with EVA attention "
            f"(window_size {cfg.window_size}, chunk_size {cfg.chunk_size}): a stage's "
            "attention is the caller's attn_fn over one run of rows, and EVA pools "
            "its chunks under each layer's own vectors"
        )
    if cfg.sparse:
        raise ValueError(
            "the pp pipeline is refused for a configuration with learned sparse "
            f"attention (index_topk {cfg.index_topk}): a stage's attention is the "
            "caller's attn_fn over every row before a query, and here each layer's "
            "indexer chooses the rows its attention reads"
        )
    if cfg.router_hidden_size:
        raise ValueError(
            "the pp pipeline is refused for a configuration whose router reads "
            f"the layer before (router_hidden_size {cfg.router_hidden_size}): a "
            "stage hands the next the hidden state alone, and that router's "
            "state would have to cross with it"
        )
    if cfg.layers_by_kind:
        raise ValueError(
            "the pp pipeline is refused for a configuration with a leading dense "
            f"layer before its expert layers (first_k_dense_replace "
            f"{cfg.first_k_dense_replace}): it stages one homogeneous [L, ...] "
            "stack, and these layers are stacked per kind (llama.layer_runs)"
        )
    B, T, D = h0.shape
    M = microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")

    hs = h0.reshape(M, B // M, T, D)
    mb_positions = positions.reshape(M, B // M, T)

    P = jax.sharding.PartitionSpec
    layer_specs = jax.tree.map(lambda _: P(axis), cparams["layers"])
    manual_axes = (axis,) if sp_axis is None else (axis, sp_axis)
    # with sp manual, activations/positions keep their sequence sharding
    # into the region (dim 2 of [M, B/M, T(, D)]) instead of gathering
    hs_spec = P(None, None, sp_axis, None) if sp_axis else P()
    pos_spec = P(None, None, sp_axis) if sp_axis else P()

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(layer_specs, hs_spec, pos_spec),
        out_specs=(hs_spec, P(axis)),
        axis_names=set(manual_axes),
    )
    def _pipeline(layers_local, hs, mb_positions):
        r = jax.lax.axis_index(axis)
        n = jax.lax.axis_size(axis)
        perm = [(i, i + 1) for i in range(n - 1)]  # stage r -> r+1, no wrap

        def stage(x, pos):
            block = training_block(cfg, attn_fn, pos, remat)
            (y, _), (_, layer_auxs) = jax.lax.scan(block, (x, None), layers_local)
            # keep the aux rank-1 everywhere in this region: it leaves
            # through a P(pp) out spec (see the export below)
            return y, jnp.sum(layer_auxs, keepdims=True)

        def tick(carry, t):
            cur, outs, aux = carry
            mb = jnp.clip(t - r, 0, M - 1)  # this stage's microbatch index
            # stage 0 feeds fresh microbatches; later stages consume the
            # activation handed over at the previous tick
            x = jnp.where(r == 0, hs[jnp.clip(t, 0, M - 1)], cur)
            y, aux_sum = stage(x, mb_positions[mb])
            # fill/drain ticks run on clipped garbage inputs: their router
            # aux must not count
            valid = (t - r >= 0) & (t - r <= M - 1)
            aux = aux + jnp.where(valid, aux_sum, jnp.zeros_like(aux_sum))
            out_idx = t - (n - 1)
            take = (r == n - 1) & (out_idx >= 0)
            slot = jnp.clip(out_idx, 0, M - 1)
            outs = outs.at[slot].set(
                jnp.where(take, y, outs[slot]), indices_are_sorted=True
            )
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outs, aux), None

        def to_varying(x):
            # only the axes x is not ALREADY varying over: zeros_like on the
            # sp-sharded hs inherits {V:sp}, and pcast rejects mixed states
            vma = jax.typeof(x).vma
            missing = tuple(a for a in manual_axes if a not in vma)
            return jax.lax.pcast(x, missing, to="varying") if missing else x

        cur0 = to_varying(jnp.zeros_like(hs[0]))
        outs0 = to_varying(jnp.zeros_like(hs))
        # [1]-shaped (see stage) and derived from a traced input, not a
        # hoisted constant
        aux0 = to_varying((hs[0, 0, 0, :1] * 0.0).astype(jnp.float32))
        (cur, outs, aux), _ = jax.lax.scan(
            tick, (cur0, outs0, aux0), jnp.arange(M + n - 1)
        )
        # only the last stage holds real outputs; replicate them
        outs = jax.lax.psum(
            jnp.where(r == n - 1, outs, jnp.zeros_like(outs)), axis
        )
        # each stage summed the aux of its own layers over its M valid
        # microbatch runs. Export it as a per-stage [1] slice (the P(pp)
        # out spec concatenates them to [n]) and reduce OUTSIDE the
        # region: a pp-sharded vector transposes cleanly in the MoE
        # backward, and summing the slices is the psum.
        aux = aux / (cfg.num_hidden_layers * M)
        if sp_axis is not None:
            # chunk-local router stats: mean over sequence chunks, and the
            # pp-only out_spec needs the value invariant over sp
            aux = jax.lax.psum(aux, sp_axis) / jax.lax.axis_size(sp_axis)
        return outs, aux

    outs, aux_vec = _pipeline(cparams["layers"], hs, mb_positions)
    return outs.reshape(B, T, D), jnp.sum(aux_vec)
