"""Inner-loop trainer: one jit-compiled train step over a sharded pytree.

This is the TPU-native replacement for the reference's FSDP hot loop
(open_diloco/train_fsdp.py:361-413): forward/backward per micro-batch with
gradient accumulation (``no_sync`` + loop -> a single ``lax.scan`` inside
jit), global-norm clip 1.0, AdamW with cosine/warmup schedule
(train_fsdp.py:250-260), all compiled once per shape. Collectives are
inserted by XLA from the mesh shardings -- there is no hand-written
all-reduce in the step.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from opendiloco_tpu import obs
from opendiloco_tpu.models.llama import (
    LlamaConfig,
    RematPolicy,
    attn_residual_bytes,
    causal_lm_loss,
    forward,
    init_params,
    takes_rows,
    untrained_by_the_lm_loss,
)
from opendiloco_tpu.parallel.mesh import MeshPlan
from opendiloco_tpu.parallel.sharding import optstate_specs, param_specs
from opendiloco_tpu.utils.logger import get_text_logger

log = get_text_logger(__name__)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """The optimization-relevant slice of the top-level Config."""

    lr: float = 4e-4
    weight_decay: float = 0.1
    adam_betas: tuple[float, float] = (0.9, 0.95)
    adam_eps: float = 1e-8
    warmup_steps: int = 1000
    total_steps: int = 88_000
    max_grad_norm: float = 1.0
    precision: str = "bf16-mixed"
    # "auto" resolves at trainer build: pallas on TPU meshes, xla elsewhere
    attn_impl: str = "auto"
    remat: RematPolicy = True
    # fused lm-head + cross-entropy Pallas kernel (ops/fused_xent.py):
    # avoids materializing [tokens, vocab] float32 logits in HBM.
    # None = auto (TPU dense models on, otherwise off)
    fused_loss: Optional[bool] = None
    # layer-scan unroll width. None = auto: FULL unroll on TPU for dense
    # models up to 16 layers (measured +6.8% tok/s at the 150m bench shape
    # -- the HBM-bound step gains cross-layer scheduling/fusion; round-5
    # live window), 1 elsewhere (CPU tests, MoE, deep models where the
    # unrolled program's size would eat HBM -- the 1b looped program is
    # already 8.2G). ODTP_SCAN_UNROLL overrides for experiments.
    scan_unroll: Optional[int] = None
    pp_microbatches: Optional[int] = None  # pipeline microbatches (None = pp size)
    # sp+pp fallback selector. With the DEFAULT (auto) attention, sp+pp
    # composes via ring attention running inside the pipeline's manual
    # region; setting this instead selects the activation-sharding mode
    # (full-sequence attention, the sp axis only shards activations) — a
    # real memory-scaling mode, but never an implicit one. An EXPLICIT
    # attn_impl always wins over this flag (explicit ring composes, and an
    # explicit non-ring impl under sp+pp raises unless this is set).
    allow_sp_activation_sharding: bool = False
    # fp16 dynamic loss scaling (torch GradScaler parity, train_fsdp.py:228,
    # 383-405; bf16 needs none -- the reference itself recommends bf16)
    init_loss_scale: float = 2.0**15
    scale_growth_interval: int = 2000

    @property
    def compute_dtype(self):
        if self.precision == "bf16-mixed":
            return jnp.bfloat16
        if self.precision == "fp16-mixed":
            return jnp.float16
        return jnp.float32

    @property
    def use_loss_scaling(self) -> bool:
        return self.precision == "fp16-mixed"


def make_schedule(tc: TrainerConfig) -> optax.Schedule:
    """Linear warmup then cosine decay to 0 over the remaining steps
    (HF get_cosine_schedule_with_warmup semantics used at train_fsdp.py:256-260)."""
    return optax.join_schedules(
        [
            optax.linear_schedule(0.0, tc.lr, tc.warmup_steps),
            optax.cosine_decay_schedule(tc.lr, max(1, tc.total_steps - tc.warmup_steps)),
        ],
        boundaries=[tc.warmup_steps],
    )


def make_inner_optimizer(tc: TrainerConfig) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(tc.max_grad_norm),
        optax.adamw(
            make_schedule(tc),
            b1=tc.adam_betas[0],
            b2=tc.adam_betas[1],
            eps=tc.adam_eps,
            weight_decay=tc.weight_decay,
        ),
    )


def _resolve_perf_defaults(
    tc: TrainerConfig, model_cfg: LlamaConfig, plan: MeshPlan
) -> TrainerConfig:
    """Resolve attn_impl="auto" / fused_loss=None to concrete choices.

    On TPU meshes the Pallas kernels won the on-chip sweep (v5e, llama-150m
    seq 1024: flash attention +20% tokens/sec over XLA attention, fused
    lm-head+xent a further gain on top) and become the defaults; every other
    backend (the CPU test mesh included) keeps the portable XLA paths.
    Explicit user choices pass through untouched.
    """
    if (
        tc.attn_impl != "auto"
        and tc.fused_loss is not None
        and tc.scan_unroll is not None
    ):
        return tc
    dev = plan.mesh.devices.flat[0]
    on_tpu = "tpu" in getattr(dev, "device_kind", "").lower()
    changes: dict = {}
    if tc.attn_impl == "auto":
        if getattr(plan, "sp_axis", None) is not None and not (
            tc.allow_sp_activation_sharding and getattr(plan, "pp_axis", None)
        ):
            # sequence-parallel mesh: flash/xla attention are not
            # sequence-sharded, so XLA would all-gather the full sequence
            # per device, silently defeating the sp axis -- ring attention
            # is the only impl that keeps the shards local. This includes
            # sp+pp (round 5): the pipeline binds both axes manual and the
            # ring body runs DIRECTLY on each stage's local chunks (no
            # nested shard_map -- that construction has no jvp lowering)
            changes["attn_impl"] = "ring"
        else:
            if getattr(plan, "sp_axis", None) is not None:
                # sp+pp with the explicit activation-sharding opt-in: the
                # sp axis shards activations while attention sees the full
                # sequence
                log.warning(
                    "sp+pp with allow_sp_activation_sharding: using "
                    "full-sequence %s attention; the sp axis only shards "
                    "activations",
                    "pallas" if on_tpu else "xla",
                )
            changes["attn_impl"] = "pallas" if on_tpu else "xla"
        if model_cfg.latent or model_cfg.eva or model_cfg.sparse:
            # latent attention trains in the rebuilt form through XLA's
            # attention, EVA over windows and pooled chunks, learned sparse
            # attention under its selection's mask; ``forward`` refuses each
            # the flash and ring kernels
            changes["attn_impl"] = "xla"
    if tc.scan_unroll is None:
        # full unroll measured +6.8% tok/s on the HBM-bound 150m step (v5e
        # live window, round 5: 62.0k -> 66.2k at bs24+remat=dots); gated
        # to dense stacks <= 16 layers so deep/MoE models don't trade HBM
        # for program size untested
        changes["scan_unroll"] = (
            model_cfg.num_hidden_layers
            if (
                on_tpu
                and not model_cfg.num_experts
                and model_cfg.num_hidden_layers <= 16
            )
            else 1
        )
    if tc.fused_loss is None:
        # auto-on only where the sweep measured a win: pallas attention on a
        # non-sequence-parallel mesh WITH the layer scan still looped.
        # Under the full unroll (the TPU default for dense <=16-layer
        # stacks) the round-5 chained op timings showed the fused kernel's
        # backward is ~1.6x slower than XLA's unfused path, and end-to-end
        # the unfused step measured faster at every batch (70.2k vs 68.5k
        # tok/s best; PUSH40.json) -- XLA fuses the lm-head matmul into the
        # unrolled graph itself. For looped stacks (1b, MoE, pp) the fused
        # kernel's memory saving (no [B*T, V] logits materialization)
        # still carries the win. Sequence-parallel meshes keep the
        # standard loss: the fused kernel is not sequence-sharded and
        # would gather the full [B*T, d] activations per device. (MoE
        # composes: the router aux rides return_hidden and is added after
        # the fused xent.)
        attn = changes.get("attn_impl", tc.attn_impl)
        unroll = changes.get("scan_unroll", tc.scan_unroll) or 1
        changes["fused_loss"] = (
            on_tpu
            and attn == "pallas"
            and getattr(plan, "sp_axis", None) is None
            and unroll < model_cfg.num_hidden_layers
            # the fused kernel holds one vocabulary to the next token
            and model_cfg.num_pred_heads == 1
        )
    return dataclasses.replace(tc, **changes)


class InnerTrainer:
    """Owns the optimizer, shardings, and the compiled train/eval steps.

    state pytree: {"params": f32 pytree, "opt_state": optax state, "step": i32}
    """

    def __init__(self, model_cfg: LlamaConfig, tc: TrainerConfig, plan: MeshPlan):
        # sp+pp composes as of round 5: the pipeline binds BOTH axes manual
        # and ring attention runs directly on the local sequence chunks.
        # --allow-sp-activation-sharding selects the fallback mode instead
        # (full-sequence attention, sp shards activations only); a non-ring
        # attention choice under sp+pp without that opt-in stays an error —
        # it would silently defeat the sp axis ("chosen, not discovered").
        tc = _resolve_perf_defaults(tc, model_cfg, plan)
        if (
            plan.pp_axis
            and getattr(plan, "sp_axis", None)
            and tc.attn_impl != "ring"
            and not tc.allow_sp_activation_sharding
        ):
            raise ValueError(
                f"sp+pp with attn_impl={tc.attn_impl!r} would shard "
                "activations while every device attends over the FULL "
                "sequence. Use the default/ring attention (composes with "
                "the pipeline), or opt into the activation-sharding mode "
                "with --allow-sp-activation-sharding"
            )
        self.model_cfg = model_cfg
        self.tc = tc
        self.plan = plan
        # leaves the loss built here does not reach, said by name and not
        # left as a silent zero in the gradient (an indexer's, under the LM loss)
        self.untrained_leaves = untrained_by_the_lm_loss(model_cfg)
        if self.untrained_leaves:
            log.warning(
                "the leaves %s get no gradient here: they are the indexer's, which "
                "chooses rows under stop_gradient and learns from an alignment loss "
                "(its scores against the attention's own distribution) that is not "
                "built; the LM loss trains everything else",
                ", ".join(self.untrained_leaves),
            )
        if plan.pp_axis:
            pp_n = plan.mesh.shape[plan.pp_axis]
            if model_cfg.num_hidden_layers % pp_n:
                raise ValueError(
                    f"{model_cfg.num_hidden_layers} layers cannot stage over "
                    f"pp={pp_n} (must divide evenly)"
                )
            if tc.attn_impl == "ring" and not getattr(plan, "sp_axis", None):
                raise ValueError(
                    "ring attention under pp needs a sequence-parallel axis "
                    "to ring over: add sp_size > 1 (the pipeline binds both "
                    "axes manual and the ring runs on each stage's local "
                    "chunks), or use attn_impl xla/pallas"
                )
        if plan.ep_axis:
            ep_n = plan.mesh.shape[plan.ep_axis]
            if model_cfg.num_experts == 0:
                raise ValueError(
                    f"--ep-size {ep_n} with a dense model silently replicates "
                    "work across the ep axis; use an MoE config (num_experts "
                    "> 0) or drop ep_size"
                )
            if model_cfg.num_experts % ep_n:
                raise ValueError(
                    f"{model_cfg.num_experts} experts cannot shard over "
                    f"ep={ep_n} (must divide evenly)"
                )
        self.optimizer = make_inner_optimizer(tc)
        self.schedule = make_schedule(tc)
        # post-dispatch hooks: state -> state transforms run right after
        # each train_step dispatch returns (the step itself is async on
        # device, so hook work overlaps it). The streaming outer scheduler
        # rides this to launch/land mid-phase fragment rounds without the
        # driver loop ever knowing.
        self._post_dispatch_hooks: list = []
        # what the newest build of the train step keeps of its attention
        # (``attn_residual_bytes``); the gauge ``train_attn_residual_bytes``
        self.attn_residual_bytes = 0
        # and what its attention kernels compute of a head's scores
        # (``attn_scores_plan_of``): the gauges ``train_attn_scores_*_share``
        self.attn_scores_plan = None
        # and what it hands the kernels (``attn_layout_of``): ``rows ...`` or
        # ``heads ...``; the gauge ``train_attn_rows`` (1 / 0) carries it
        self.attn_layout = None
        # how to lower the train step again at the shape it was traced at
        # last (``program_texts``), and the steps dispatched so far
        self._recipes = obs.programs.Recipes()
        self.steps_dispatched = 0
        obs.programs.register(self)

        self.p_specs = param_specs(model_cfg, plan, for_params=True)
        params_shapes = jax.eval_shape(
            functools.partial(init_params, cfg=model_cfg), jax.random.key(0)
        )
        opt_shapes = jax.eval_shape(self.optimizer.init, params_shapes)
        self.opt_specs = optstate_specs(
            opt_shapes,
            params_shapes,
            param_specs(model_cfg, plan, for_params=False),
            plan,
        )
        from jax.sharding import PartitionSpec as P

        self.state_specs = {
            "params": self.p_specs,
            "opt_state": self.opt_specs,
            "step": P(),
            "scaler": {"scale": P(), "good_steps": P()},
        }
        self.state_shardings = jax.tree.map(
            plan.sharding, self.state_specs, is_leaf=lambda x: isinstance(x, P)
        )
        self._P = P

        self._train_step = jax.jit(
            self._train_step_impl,
            donate_argnums=(0,),
            in_shardings=(self.state_shardings, plan.sharding(plan.batch_spec(3, accum=True))),
            out_shardings=(self.state_shardings, None),
        )
        self._eval_step = jax.jit(
            self._eval_step_impl,
            in_shardings=(
                self.state_shardings["params"],
                plan.sharding(plan.batch_spec(2)),
            ),
        )
        self._probe_step = jax.jit(
            self._probe_step_impl,
            in_shardings=(
                self.state_shardings["params"],
                plan.sharding(plan.batch_spec(2)),
            ),
        )

    def lower_abstract(self, global_bs: int, seq: int, accum: int = 1):
        """Lower ``_train_step`` from ShapeDtypeStructs only (no arrays
        materialized) — the one recipe the offline cost/memory analyses
        share (scripts/aot_roofline.py, scripts/mfu_sweep.py). Deviceless
        AOT targets work too: the shardings carry the topology's devices."""
        state_sds = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(self.init_state, jax.random.key(0)),
            self.state_shardings,
        )
        bsh = self.plan.sharding(self.plan.batch_spec(3, accum=True))
        if global_bs % accum:
            raise ValueError(f"global_bs {global_bs} not divisible by accum {accum}")
        batch_sds = {
            k: jax.ShapeDtypeStruct(
                (accum, global_bs // accum, seq), np.int32, sharding=bsh
            )
            for k in ("input_ids", "labels")
        }
        return self._train_step.lower(state_sds, batch_sds)

    def program_recipes(self):
        """How to lower ``train_step`` again at the shape it was traced at
        last (the one that is running), for ``obs.programs``; empty before a
        step."""
        return self._recipes

    def program_texts(self) -> dict:
        """{"train_step": the compiled text of the train step}, lowered and
        compiled again through ``lower_abstract``: ``obs.programs`` reads each
        instruction's scope, pass and opcode from it."""
        return self._recipes.texts()

    # -- state ------------------------------------------------------------

    def init_state(self, rng: jax.Array, params: Optional[dict] = None) -> dict:
        """Initialize (or adopt) params and optimizer state, sharded per plan."""
        if params is None:
            # drawn straight into the plan's shardings, so no device ever
            # holds the whole model. Partitionable threefry (the installed
            # jax's default) makes the draws independent of the mesh: the
            # same seed yields the same weights on every layout, which the
            # cross-mesh equivalence tests and DiLoCo's same-seed
            # multi-worker init contract rely on. jit of the module-level
            # function with a static cfg: trainers of one config on one
            # layout (the workers of an in-process galaxy) share the compile
            params = jax.jit(
                init_params,
                static_argnames="cfg",
                out_shardings=self.state_shardings["params"],
            )(rng, cfg=self.model_cfg)
        else:
            params = jax.device_put(params, self.state_shardings["params"])
        opt_state = jax.jit(
            self.optimizer.init, out_shardings=self.state_shardings["opt_state"]
        )(params)
        step = jax.device_put(
            jnp.zeros((), jnp.int32), self.state_shardings["step"]
        )
        # device_put with the replicated sharding: an uncommitted scalar has
        # a different aval than the train-step output and would force a
        # second full compile at step 2
        scaler = jax.device_put(
            {
                "scale": jnp.float32(
                    self.tc.init_loss_scale if self.tc.use_loss_scaling else 1.0
                ),
                "good_steps": jnp.zeros((), jnp.int32),
            },
            self.state_shardings["scaler"],
        )
        return {
            "params": params,
            "opt_state": opt_state,
            "step": step,
            "scaler": scaler,
        }

    def force_step_position(self, state: dict, step: int) -> dict:
        """Teleport the LR-schedule position to ``step``.

        Used when a late joiner adopts the swarm's epoch (reference stubs
        scheduler sync, hivemind_diloco.py:54-58; here we own the stack, so a
        joiner at outer epoch E resumes the cosine schedule at
        E*local_steps instead of re-running warmup). Rewrites ``state["step"]``
        and every integer scalar counter inside the optax state (the adamw
        schedule reads its own ``count``), keeping shardings so the jit cache
        stays warm.
        """
        state = dict(state)
        state["step"] = jax.device_put(
            jnp.asarray(step, jnp.int32), self.state_shardings["step"]
        )

        def fix(leaf, shard):
            if (
                hasattr(leaf, "dtype")
                and getattr(leaf, "ndim", None) == 0
                and jnp.issubdtype(leaf.dtype, jnp.integer)
            ):
                return jax.device_put(jnp.asarray(step, leaf.dtype), shard)
            return leaf

        state["opt_state"] = jax.tree.map(
            fix, state["opt_state"], self.state_shardings["opt_state"]
        )
        return state

    # -- steps ------------------------------------------------------------

    def _fused_lm_loss(self, hidden: jax.Array, head: jax.Array, labels: jax.Array):
        """Shifted fused lm-head+xent over final hidden states (the single
        shift/reshape site for both the plain and pipeline paths). On
        multi-device meshes the SPMD entry runs the kernel manual over the
        batch shards (Mosaic cannot be auto-partitioned); single-device
        meshes take the plain kernel."""
        from opendiloco_tpu.ops.fused_xent import fused_linear_cross_entropy_sharded

        d = hidden.shape[-1]
        return fused_linear_cross_entropy_sharded(
            hidden[:, :-1].reshape(-1, d),
            head,
            labels[:, 1:].reshape(-1),
            mesh=self.plan.mesh,
            batch_axes=self.plan.batch_axes,
            tp_axis=self.plan.tp_axis,
        )

    def _loss_fn(self, params: dict, input_ids: jax.Array, labels: jax.Array):
        """Dispatch on mesh shape only; the moe/fused branching is shared.

        pp meshes stage the decoder stack over the pp axis
        (parallel/pipeline.py) with embed / final norm / head replicated;
        non-pp meshes thread the ring-attention mesh instead. fused_loss
        composes with both (they hand back hidden states), and the MoE
        router aux rides return_moe_aux either way (through the pipeline's
        per-stage accumulators under pp)."""
        if self.plan.pp_axis:
            fwd_kwargs = dict(
                pp_mesh=self.plan.mesh,
                pp_axis=self.plan.pp_axis,
                pp_microbatches=self.tc.pp_microbatches,
                # sp+pp: forward threads the ring axis into the pipeline's
                # manual region (ring runs directly on the local chunks)
                ring_mesh=self.plan.mesh,
                ring_axis=self.plan.sp_axis or "sp",
            )
        else:
            fwd_kwargs = dict(
                ring_mesh=self.plan.mesh,
                ring_axis=self.plan.sp_axis or "sp",
            )
        # the router's aux loss (load balance and z-loss) arrives weighted
        moe = bool(self.model_cfg.num_experts)
        fwd_kwargs.update(
            batch_axes=self.plan.batch_axes,
            tp_axis=self.plan.tp_axis,
            compute_dtype=self.tc.compute_dtype,
            attn_impl=self.tc.attn_impl,
            remat=self.tc.remat,
            scan_unroll=self.tc.scan_unroll,
        )
        heads = self.model_cfg.num_pred_heads
        if self.tc.fused_loss and heads > 1:
            raise ValueError(
                f"fused_loss is refused for num_pred_heads {heads}: the fused "
                "lm-head kernel holds one vocabulary to the next token, and head i "
                "of several is held to the token i + 1 ahead (causal_lm_loss)"
            )
        if self.tc.fused_loss:
            out = forward(
                params,
                input_ids,
                self.model_cfg,
                return_hidden=True,
                return_moe_aux=moe,
                **fwd_kwargs,
            )
            # same scope name as forward() gives its logits matmul: the
            # lm head and the loss read as one piece of a traced step
            with jax.named_scope("odtp_lm_head_loss"):
                if moe:
                    hidden, head, moe_aux = out
                    return self._fused_lm_loss(hidden, head, labels) + moe_aux
                hidden, head = out
                return self._fused_lm_loss(hidden, head, labels)
        out = forward(
            params, input_ids, self.model_cfg, return_moe_aux=moe, **fwd_kwargs
        )
        with jax.named_scope("odtp_lm_head_loss"):
            if moe:
                logits, moe_aux = out
                return causal_lm_loss(logits, labels, pred_heads=heads) + moe_aux
            return causal_lm_loss(out, labels, pred_heads=heads)

    def attn_residual_bytes_of(self, global_microbatch: int, seq: int) -> int:
        """Bytes per device the step's checkpointing policy keeps of its
        attention, beside each layer's input, from a microbatch's forward
        to its backward (``llama._maybe_remat``): the kernel's output and
        log-sum-exp of every layer that has attention, cut as the mesh
        cuts the batch, the sequence (ring attention), the heads (tp,
        where it divides them) and the layers (pp, whose stage holds them
        for every tick of the schedule). 0 where the resolved
        ``attn_impl`` tags nothing (``xla``, which the latent and the EVA
        form resolve to) and where ``remat`` is off and everything is
        kept anyway."""
        tc, cfg, plan = self.tc, self.model_cfg, self.plan
        if tc.remat in (False, None, "none") or tc.attn_impl not in ("pallas", "ring"):
            return 0
        size = lambda axis: plan.mesh.shape[axis] if axis else 1
        pp, sp, tp = size(plan.pp_axis), size(plan.sp_axis), size(plan.tp_axis)
        if tc.attn_impl == "pallas" and not self._flash_over_whole_rows():
            return 0  # ``forward`` falls back to XLA's attention there
        shards = plan.data_parallel_size * pp
        if tc.attn_impl == "ring":
            shards *= sp
        if cfg.num_attention_heads % tp == 0 and cfg.num_key_value_heads % tp == 0:
            shards *= tp
        rows = global_microbatch
        if pp > 1:  # a stage keeps each tick's microbatch, fill and drain too
            m = tc.pp_microbatches or pp
            rows = rows // m * (m + pp - 1)
        return attn_residual_bytes(cfg, rows, seq, tc.compute_dtype) // shards

    def _flash_over_whole_rows(self) -> bool:
        """Whether a layer's attention is ``flash_attention`` over each
        row's whole sequence: ``attn_impl=pallas``, but where ``forward``
        falls back to XLA's attention under a composed pipeline."""
        plan = self.plan
        size = lambda axis: plan.mesh.shape[axis] if axis else 1
        pp, sp = size(plan.pp_axis), size(plan.sp_axis)
        return self.tc.attn_impl == "pallas" and not (pp > 1 and plan.mesh.size > pp * sp)

    def attn_scores_plan_of(self, seq: int):
        """What the step's attention kernels compute of a head's ``seq x
        seq`` scores (``flash_attention.CausalPlan``: the sub-tile, and the
        sub-tiles computed, masked and skipped); None where the attention
        is not the flash kernel over whole rows (XLA's, the ring's chunks)
        or the kernel does not tile ``seq``."""
        if not self._flash_over_whole_rows():
            return None
        from opendiloco_tpu.ops.flash_attention import plan_of

        return plan_of(seq, self.model_cfg.head_dim)

    def attn_layout_of(self, seq: int) -> Optional[str]:
        """What the step's attention kernels are handed between the
        projections (``flash_attention``): ``rows`` ([B, T, H * D] as the
        matmuls leave them, rotary inside the kernels) or ``heads`` ([B, T, H,
        D], where the configuration works on q and k a head at a time:
        ``llama.takes_rows``), with the query and KV heads a grid step holds
        of those a chip has; None where ``attn_scores_plan_of`` is."""
        if self.attn_scores_plan_of(seq) is None:
            return None
        from opendiloco_tpu.ops.flash_attention import heads_a_step

        cfg, plan = self.model_cfg, self.plan
        hq, hkv = cfg.num_attention_heads, cfg.kv_heads
        tp = plan.mesh.shape[plan.tp_axis] if plan.tp_axis else 1
        if hq % tp == 0 and hkv % tp == 0:  # ``flash_attention_sharded`` cuts the heads
            hq, hkv = hq // tp, hkv // tp
        held = "%d,%d of %d,%d" % (*heads_a_step(hq, hkv, cfg.head_dim), hq, hkv)
        return f"{'rows' if takes_rows(cfg) else 'heads'} heads_a_step={held}"

    def _train_step_impl(self, state: dict, batch: dict):
        """batch arrays are [accum, global_microbatch, seq]."""
        params = state["params"]
        accum, microbatch, seq = batch["input_ids"].shape
        # while the step is traced: once a compiled shape
        self._recipes.note(
            "train_step", (accum, microbatch, seq),
            functools.partial(self.lower_abstract, accum * microbatch, seq, accum),
        )
        self.attn_residual_bytes = self.attn_residual_bytes_of(microbatch, seq)
        obs.gauge("train_attn_residual_bytes", self.attn_residual_bytes)
        self.attn_scores_plan = scores = self.attn_scores_plan_of(seq)
        if scores is not None:
            obs.gauge("train_attn_scores_computed_share", scores.computed_share)
            obs.gauge("train_attn_scores_masked_share", scores.masked_share)
        self.attn_layout = layout = self.attn_layout_of(seq)
        if layout is not None:
            obs.gauge("train_attn_rows", float(layout.startswith("rows")))
        log.info(
            "train step for %d x %d x %d tokens: attn_impl=%s remat=%s "
            "train_attn_residual_bytes=%d train_attn_scores=%s "
            "train_attn_layout=%s fused_loss=%s scan_unroll=%s",
            accum, microbatch, seq, self.tc.attn_impl, self.tc.remat,
            self.attn_residual_bytes, scores or "not the flash kernel's",
            layout or "not the flash kernel's",
            self.tc.fused_loss, self.tc.scan_unroll,
        )
        scale = state["scaler"]["scale"]

        def scaled_loss(p, ids, labels):
            return self._loss_fn(p, ids, labels) * scale

        grad_fn = jax.value_and_grad(scaled_loss)

        def micro(carry, mb):
            loss_sum, grad_sum = carry
            loss, grads = grad_fn(params, mb["input_ids"], mb["labels"])
            return (
                loss_sum + loss,
                jax.tree.map(jnp.add, grad_sum, grads),
            ), None

        zero_grads = jax.tree.map(jnp.zeros_like, params)
        (loss_sum, grad_sum), _ = jax.lax.scan(micro, (0.0, zero_grads), batch)
        inv = 1.0 / (accum * scale)
        grads = jax.tree.map(lambda g: g * inv, grad_sum)
        loss = loss_sum * inv

        with jax.named_scope("odtp_optimizer_update"):
            grad_norm = optax.global_norm(grads)
            updates, opt_state = self.optimizer.update(
                grads, state["opt_state"], params
            )
            new_params = optax.apply_updates(params, updates)

        if self.tc.use_loss_scaling:
            # GradScaler semantics (found_inf_grad, utils.py:124-135): on
            # non-finite grads skip the update and halve the scale; grow 2x
            # after scale_growth_interval clean steps
            finite = jnp.isfinite(grad_norm)
            keep = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(finite, a, b), new, old
            )
            new_params = keep(new_params, params)
            opt_state = keep(opt_state, state["opt_state"])
            good = jnp.where(finite, state["scaler"]["good_steps"] + 1, 0)
            grow = finite & (good >= self.tc.scale_growth_interval)
            new_scale = jnp.where(
                finite, jnp.where(grow, scale * 2.0, scale), scale * 0.5
            )
            scaler = {
                "scale": new_scale,
                "good_steps": jnp.where(grow, 0, good),
            }
            metrics = {
                "loss": loss,
                "grad_norm": grad_norm,
                "found_inf": (~finite).astype(jnp.float32),
                "loss_scale": scale,
            }
        else:
            scaler = state["scaler"]
            metrics = {"loss": loss, "grad_norm": grad_norm}
        return (
            {
                "params": new_params,
                "opt_state": opt_state,
                "step": state["step"] + 1,
                "scaler": scaler,
            },
            metrics,
        )

    def _eval_step_impl(self, params: dict, batch: dict):
        return self._loss_fn(params, batch["input_ids"], batch["labels"])

    def _probe_step_impl(self, params: dict, batch: dict):
        """Activation-norm probes (reference register_metrics_hooks,
        utils.py:43-67): runs a forward with taps, no grads."""
        _, aux = forward(
            params,
            batch["input_ids"],
            self.model_cfg,
            compute_dtype=self.tc.compute_dtype,
            attn_impl=self.tc.attn_impl,
            remat=False,
            return_aux=True,
            ring_mesh=self.plan.mesh,
            ring_axis=self.plan.sp_axis or "sp",
            batch_axes=self.plan.batch_axes,
            tp_axis=self.plan.tp_axis,
        )
        return aux

    # -- host API ---------------------------------------------------------

    def _to_global(self, a, sharding, batch_axis: int):
        """Host array -> global device array. Single-process: the array IS
        the global batch. Multihost: each process passes its LOCAL rows
        (the dataloader shards by process) and the global array is
        assembled from per-process shards."""
        if jax.process_count() == 1:
            return jax.device_put(a, sharding)
        global_shape = list(a.shape)
        global_shape[batch_axis] *= jax.process_count()
        return jax.make_array_from_process_local_data(
            sharding, a, tuple(global_shape)
        )

    def shard_batch(self, input_ids: np.ndarray, labels: np.ndarray, accum: int) -> dict:
        """[local_bs, T] host arrays -> [accum, mb, T] device arrays
        (local_bs = global batch / process_count under multihost)."""
        gbs, seq = input_ids.shape
        assert gbs % accum == 0, (gbs, accum)
        shaped = lambda a: a.reshape(accum, gbs // accum, seq)
        sharding = self.plan.sharding(self.plan.batch_spec(3, accum=True))
        return {
            "input_ids": self._to_global(shaped(input_ids), sharding, 1),
            "labels": self._to_global(shaped(labels), sharding, 1),
        }

    def add_post_dispatch_hook(self, fn) -> None:
        """Register a ``state -> state`` callback fired after every
        ``train_step`` dispatch (on the calling thread, while the step
        itself still runs on device)."""
        self._post_dispatch_hooks.append(fn)

    def train_step(self, state: dict, batch: dict):
        tr = obs.tracer()
        self.steps_dispatched += 1
        if tr is None:
            state, metrics = self._train_step(state, batch)
        else:
            # the host's seconds in the dispatch alone (the jitted step runs
            # on asynchronously), on the profiler's clock under a capture, and
            # the per-step hook: a trace's reader divides the device seconds
            # of ``train_step``'s operations (``obs.programs``) by the
            # dispatches here (the benchmark's ``readers/scope_ms.py``; an
            # operator with ``odtp_programs.json`` and ``odtp_capture.json``)
            ids = batch["input_ids"]
            t0 = tr.now()
            state, metrics = self._train_step(state, batch)
            tr.add_span(
                "inner/dispatch", t0, tr.now(),
                step=self.steps_dispatched, tokens=int(ids.size), accum=int(ids.shape[0]),
            )
            tr.count("inner_steps")
        for hook in self._post_dispatch_hooks:
            state = hook(state)
        return state, metrics

    def eval_loss(self, params: dict, input_ids: np.ndarray, labels: np.ndarray) -> float:
        sharding = self.plan.sharding(self.plan.batch_spec(2))
        batch = {
            "input_ids": self._to_global(input_ids, sharding, 0),
            "labels": self._to_global(labels, sharding, 0),
        }
        return float(self._eval_step(params, batch))

    def probe_norms(self, params: dict, input_ids: np.ndarray) -> dict:
        sharding = self.plan.sharding(self.plan.batch_spec(2))
        batch = {
            "input_ids": self._to_global(input_ids, sharding, 0),
            "labels": self._to_global(np.zeros_like(input_ids), sharding, 0),
        }
        aux = jax.device_get(self._probe_step(params, batch))
        out = {
            f"activation_norm/layers.{i}.self_attn": float(v)
            for i, v in enumerate(aux["attn_out_norm"])
        }
        out["activation_norm/lm_head"] = float(aux["lm_head_norm"])
        return out

    def current_lr(self, step: int) -> float:
        return float(self.schedule(step))
