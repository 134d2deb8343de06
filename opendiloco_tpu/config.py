"""Configuration tree and CLI parsing.

Mirrors the reference's pydantic-based config semantics (nested dotted flags
like ``--diloco.local-steps 500`` and ``--no-x`` booleans; reference:
open_diloco/train_fsdp.py:79-129, pydantic_config fork) with a thin,
dependency-free argv parser.
"""

from __future__ import annotations

import sys
from typing import Any, Literal, Optional, Union

from pydantic import BaseModel, field_validator, model_validator
from pydantic import ConfigDict


class CkptConfig(BaseModel):
    """Checkpoint cadence/paths (reference: open_diloco/ckpt_utils.py:16-21)."""

    model_config = ConfigDict(extra="forbid")

    path: str = "outputs"
    interval: Optional[int] = None
    topk: Optional[int] = None
    # resume: True -> auto-discover latest ckpt under `path`; str -> explicit
    # checkpoint directory; None/False -> fresh start.
    resume: Optional[str | bool] = None

    @field_validator("interval", "topk", mode="before")
    @classmethod
    def _no_flag_means_none(cls, v: Any) -> Any:
        # `--no-ckpt.interval` parses to False; treat as "disabled"
        return None if v is False else v


class DilocoConfig(BaseModel):
    """Outer-loop (DiLoCo) configuration.

    Equivalent of the reference's ``HvConfig`` (open_diloco/train_fsdp.py:79-101)
    plus the DiLoCoOptimizer kwargs it forwards
    (open_diloco/hivemind_diloco.py:326-406).
    """

    model_config = ConfigDict(extra="forbid")

    outer_lr: float = 0.7
    outer_momentum: float = 0.9
    outer_nesterov: bool = True
    local_steps: int = 500

    # peer bootstrap / identity
    initial_peers: list[str] = []
    host: str = "0.0.0.0"
    port: int = 0  # 0 -> ephemeral
    world_rank: int = 0
    galaxy_size: int = 1

    # straggler / failure policy (reference: hivemind_diloco.py:285-300)
    all_reduce_strategy: Literal["wait_for_all", "no_wait"] = "wait_for_all"
    timeout_waiting_for_peers: float = 600.0
    averaging_timeout: float = 300.0
    # matchmaking window for outer-round group formation. Must cover the
    # gap between a peer REPORTING its epoch boundary and it actually
    # joining matchmaking -- which includes the device->host boundary
    # param fetch (measured ~35 s for 150m through a slow transport; scale
    # with model size). A large window costs nothing when peers are
    # prompt: the rendezvous closes the round early once every live
    # registered peer has joined (rendezvous.py). 5 s windows made two
    # staggered live 150m workers matchmake SOLO groups every round; the
    # banked paired run (LIVE_DILOCO_TCP.json) used this 60 s default.
    matchmaking_time: float = 60.0
    fail_rank_drop: bool = False  # crash if a peer drops (train_fsdp.py:93)

    # wire compression for the outer all-reduce (utils.py:83-121, plus the
    # sub-8-bit codecs: blockwise4bit = packed nibbles + fp16 block scales,
    # topk = sparse top-|x| at ODTP_TOPK_DENSITY)
    compression: Literal[
        "none", "fp16", "scaled-fp16", "uniform8bit", "quantile8bit",
        "blockwise8bit", "blockwise4bit", "topk",
    ] = "none"

    # error feedback for lossy compression: each round's encode/decode
    # residual (quantization or sparsification error) is accumulated
    # per-leaf and added to the NEXT round's pseudo-gradient before
    # encoding, so dropped signal is carried instead of lost. Residuals
    # checkpoint with the optimizer state and survive elastic dropped
    # rounds. Requires a lossy codec (compression != "none").
    error_feedback: bool = False

    # onboarding (train_fsdp.py:348-349)
    skip_load_from_peers: bool = False

    # communication backend: "loopback" (in-process, tests), "tcp" (DCN)
    backend: Literal["loopback", "tcp"] = "tcp"

    # optional periodic full state averaging (hivemind_diloco.py:634-638)
    average_state_every: int = 0  # 0 = never

    # outer averaging topology:
    #   "allreduce" - every epoch averages over the whole galaxy (reference)
    #   "gossip"    - NoLoCo (arxiv 2506.10911): every worker mixes
    #                 (master, momentum, pseudo_grad) with ONE partner per
    #                 round over a point-to-point push-pull — no global
    #                 barrier, no rendezvous round. Pairings are derived
    #                 locally from a shared epoch-keyed PRNG over the
    #                 gossiped membership (diloco/gossip.py), link-biased
    #                 when link_adapt is on; disagreement mixes away over
    #                 re-pairings. Composes with streaming_fragments
    #                 (fragment k pairs on its own clock), overlap_comm,
    #                 sub-8-bit codecs + per-partner error feedback, and
    #                 device placement.
    outer_mode: Literal["allreduce", "gossip"] = "allreduce"

    # overlap the outer all-reduce with the next inner epoch (Eager Updates
    # for Overlapped Communication in DiLoCo, arxiv 2502.12996):
    #   "none"    - blocking outer step (reference semantics)
    #   "delayed" - inner training continues; the averaged outer update is
    #               applied as a parameter delta when communication lands
    #   "eager"   - additionally applies the update estimated from the LOCAL
    #               pseudo-gradient immediately, corrected on arrival
    overlap_comm: Literal["none", "delayed", "eager"] = "none"

    # Streaming DiLoCo-style fragment sync (arxiv 2501.18512): partition
    # the parameter leaves into N size-balanced fragments and sync ONE
    # fragment per outer boundary (fragment = epoch mod N). Each fragment
    # gets outer updates every N epochs on its own staggered clock; the
    # un-synced leaves keep training locally. Peak per-boundary bandwidth
    # drops ~N-fold. 0/1 = off (reference full-sync semantics).
    streaming_fragments: int = 0

    # streaming x overlap stagger (arxiv 2502.12996 "eager updates"
    # composed with the 2501.18512 fragment schedule): with
    # streaming_fragments=N AND overlap_comm != "none", EVERY fragment
    # syncs each epoch on its own mid-phase clock -- fragment k's
    # all-reduce launches at inner step  min(H, int(k*stagger*H/N)+1)
    # and lands while the inner loop keeps training. 1.0 spreads the
    # launches evenly across the whole inner phase; smaller values
    # front-load them (0.5 packs all launches into the first half,
    # leaving more time to land before the next epoch's slot).
    stream_stagger: float = 1.0

    # where the outer data plane (master weights + Nesterov momentum) lives:
    #   "host"   - numpy master, serial host Nesterov step (reference
    #              hivemind offload_optimizer semantics)
    #   "device" - sharded device arrays; pseudo-gradient and outer apply
    #              are fused, donated jit ops at HBM bandwidth and the
    #              boundary D2H moves wire-width bytes (diloco/outer_device.py)
    #   "auto"   - device on TPU meshes, host elsewhere
    # Device placement is single-process only; multihost meshes fall back
    # to host with a warning.
    outer_placement: Literal["auto", "host", "device"] = "auto"

    # bandwidth-aware adaptive outer transport (diloco/linkstate.py):
    # capacity-proportional butterfly partitioning, BDP-derived
    # striping/chunking, straggler hedging. True forces it on for this
    # worker; False defers to the ODTP_LINK_ADAPT env switch (so a swarm
    # can be flipped without touching configs). Off = bit-identical to the
    # uniform butterfly.
    link_adapt: bool = False

    @model_validator(mode="after")
    def _streaming_constraints(self):
        if self.streaming_fragments > 1:
            if self.average_state_every:
                raise ValueError(
                    "streaming_fragments makes average_state_every "
                    "unnecessary AND destructive: masters cannot drift "
                    "(every fragment update is the same all-reduced "
                    "result on every peer), while a full master reset "
                    "would erase the un-synced fragments' local progress "
                    "without it ever forming a pseudo-gradient"
                )
        if not (0.0 < self.stream_stagger <= 1.0):
            raise ValueError(
                f"stream_stagger must be in (0, 1], got {self.stream_stagger}"
            )
        return self

    # The former _gossip_constraints validator is gone: NoLoCo gossip now
    # composes with overlap_comm, streaming_fragments, sub-8-bit codecs,
    # error feedback (per-partner residuals), and device placement. The
    # master weights ride the STATE codec (fp16 family) on the pair wire;
    # only the pseudo-gradient section uses the configured lossy codec,
    # so sub-fp16 codecs no longer touch master bytes (see MIGRATION.md).

    @model_validator(mode="after")
    def _error_feedback_constraints(self):
        if self.error_feedback and self.compression == "none":
            raise ValueError(
                "error_feedback carries the codec's encode/decode residual; "
                "with compression='none' there is none -- pick a lossy codec"
            )
        return self

    @field_validator("initial_peers", mode="before")
    @classmethod
    def _coerce_peers(cls, v: Any) -> Any:
        # reference coerces scalar -> list (train_fsdp.py:95-101);
        # comma-separated strings list multiple bootstrap peers
        if isinstance(v, str):
            return [x.strip() for x in v.split(",") if x.strip()]
        return v


class ServeConfig(BaseModel):
    """In-process serving plane (opendiloco_tpu/serve): continuous-batching
    inference over the live master weights while training runs."""

    model_config = ConfigDict(extra="forbid")

    enabled: bool = False
    host: str = "127.0.0.1"
    port: int = 0  # 0 -> ephemeral; collisions downgrade to ephemeral
    # continuous-batching geometry
    max_batch: int = 8  # decode slots (concurrent sequences)
    max_context: int = 1024  # per-slot ring KV page; longer sequences slide
    # prefill compile-size buckets (prompts pad up to the smallest fit;
    # prompts beyond the largest bucket are rejected, not truncated)
    prefill_buckets: list[int] = [64, 256, 1024]
    # the chunk a prompt is admitted in where the model admits in chunks and
    # its configuration names none (a stack with sliding layers); 0: none given
    prefill_chunk: int = 0
    max_queue: int = 1024  # backpressure: submits beyond this are rejected
    # weight hot-swap policy: check every N decode steps; swap when the
    # serving weights lag the trainer's masters by MORE than
    # max_stale_rounds outer rounds (0 = adopt every new round)
    swap_every_steps: int = 16
    max_stale_rounds: int = 0
    # shared-prefix KV reuse: prefill a common prompt prefix once and
    # ring-copy its K/V into joining slots
    prefix_cache: bool = False
    # host-memory cold KV tier: evicted slot pages park D2H between decode
    # steps so the scheduler time-slices more live sequences than the ring
    # holds; off = today's all-resident behavior, bit-identical
    kv_tier: bool = False
    # cold-page codec: "none" stores f32 (evict+restore is bit-exact),
    # "blockwise4bit" quantizes pages 8x smaller (restore error bounded,
    # test-pinned)
    kv_tier_codec: Literal["none", "blockwise4bit"] = "none"
    # host tier budget: paused pages + prefix entries it may hold at once
    kv_host_slots: int = 32

    @field_validator("prefill_buckets", mode="before")
    @classmethod
    def _coerce_buckets(cls, v: Any) -> Any:
        if isinstance(v, str):
            return [int(x) for x in v.split(",") if x.strip()]
        return v

    @model_validator(mode="after")
    def _geometry(self):
        if self.max_batch < 1:
            raise ValueError("serve.max_batch must be >= 1")
        if not self.prefill_buckets:
            raise ValueError("serve.prefill_buckets must be non-empty")
        if min(self.prefill_buckets) < 1:
            raise ValueError("serve.prefill_buckets must be positive")
        if max(self.prefill_buckets) > self.max_context:
            raise ValueError(
                "largest prefill bucket exceeds serve.max_context "
                "(a prompt must fit its slot's KV page)"
            )
        if self.kv_host_slots < 1:
            raise ValueError("serve.kv_host_slots must be >= 1")
        return self


class FleetConfig(BaseModel):
    """Serving fleet (opendiloco_tpu/fleet): N replica engines fed by
    delta pushes from the trainer's masters, behind one front-end router."""

    model_config = ConfigDict(extra="forbid")

    enabled: bool = False
    replicas: int = 2
    host: str = "127.0.0.1"
    port: int = 0  # router ingress; 0 -> ephemeral
    # run replicas inside the trainer process (tests/benches) instead of
    # as `python -m opendiloco_tpu.fleet.replica` subprocesses
    inprocess: bool = False
    # delta-push channel: per-fragment master deltas in this codec with
    # per-replica error feedback; a full state-codec keyframe every
    # keyframe_every epochs re-pins bit-exactness and onboards
    # (re)joining replicas without history replay
    codec: Literal["blockwise4bit", "topk"] = "blockwise4bit"
    fragments: int = 4
    keyframe_every: int = 8
    error_feedback: bool = True
    push_interval_s: float = 0.25
    # health bound: a replica whose serving weights lag the trainer by
    # MORE than this many outer rounds reports itself stale and the
    # router stops preferring it
    max_stale_rounds: int = 2
    # per-replica engine geometry (same semantics as ServeConfig)
    max_batch: int = 4
    max_context: int = 256
    prefill_buckets: list[int] = [32, 128]
    max_queue: int = 1024
    prefix_cache: bool = True
    # fleet prefix-cache directory: replicas advertise host-tier resident
    # prefix hashes on their health frames and the router routes matching
    # prompts to a holder, so a fleet-shared system prompt is prefilled
    # once fleet-wide. Turning it on also arms each replica's host KV
    # tier (the advertised entries must outlive slot churn).
    prefix_directory: bool = False
    # SLO-driven autoscaling (fleet/autoscaler.py): a closed control loop
    # that scales replica count against the declared SLO and replaces
    # dead replicas without operator action. `replicas` becomes the
    # initial size; the loop holds it within [min_replicas, max_replicas].
    autoscale: bool = False
    # declared SLO: worst ready-replica client p99 the loop defends
    # (0 disables the latency signal) and the per-replica queue depth
    # above which traffic is considered backlogged
    slo_p99_ms: float = 0.0
    slo_queue_depth: int = 8
    min_replicas: int = 1
    max_replicas: int = 8
    # pre-keyframed standby replicas (push channel attached, router not):
    # scale-up adopts one instantly instead of cold-booting
    warm_spares: int = 0
    # control-loop damping: seconds between scale actions, evaluation
    # cadence, and consecutive breached/clear evaluations required before
    # scaling up/down (hysteresis — up reacts faster than down)
    scale_cooldown_s: float = 5.0
    scale_eval_interval_s: float = 0.5
    scale_up_evals: int = 2
    scale_down_evals: int = 8

    @field_validator("prefill_buckets", mode="before")
    @classmethod
    def _coerce_buckets(cls, v: Any) -> Any:
        if isinstance(v, str):
            return [int(x) for x in v.split(",") if x.strip()]
        return v

    @model_validator(mode="after")
    def _geometry(self):
        if self.replicas < 1:
            raise ValueError("fleet.replicas must be >= 1")
        if self.fragments < 1:
            raise ValueError("fleet.fragments must be >= 1")
        if self.keyframe_every < 1:
            raise ValueError("fleet.keyframe_every must be >= 1")
        if self.max_stale_rounds < 0:
            raise ValueError("fleet.max_stale_rounds must be >= 0")
        if not self.prefill_buckets:
            raise ValueError("fleet.prefill_buckets must be non-empty")
        if max(self.prefill_buckets) > self.max_context:
            raise ValueError(
                "largest fleet prefill bucket exceeds fleet.max_context"
            )
        if self.min_replicas < 1:
            raise ValueError("fleet.min_replicas must be >= 1")
        if self.max_replicas < self.min_replicas:
            raise ValueError(
                "fleet.max_replicas must be >= fleet.min_replicas"
            )
        if self.warm_spares < 0:
            raise ValueError("fleet.warm_spares must be >= 0")
        if self.slo_p99_ms < 0:
            raise ValueError("fleet.slo_p99_ms must be >= 0")
        if self.slo_queue_depth < 1:
            raise ValueError("fleet.slo_queue_depth must be >= 1")
        if self.scale_up_evals < 1 or self.scale_down_evals < 1:
            raise ValueError("fleet.scale_*_evals must be >= 1")
        return self


class Config(BaseModel):
    """Top-level training config (reference: open_diloco/train_fsdp.py:104-129)."""

    model_config = ConfigDict(extra="forbid")

    # model
    # "auto" resolves per-backend at trainer build: the Pallas flash kernel
    # on TPU (measured +20% tokens/sec over XLA attention on v5e), plain XLA
    # attention elsewhere; "ring" (sequence parallel) stays opt-in
    attn_implementation: Literal["auto", "xla", "pallas", "ring"] = "auto"
    path_model: str = "configs/config_150m.json"
    # rematerialization policy: false/"none" (save everything), true/"full"
    # (reference-style per-layer checkpointing: a layer's input is kept, and
    # beside it the attention kernel's output and log-sum-exp where the
    # attention is the flash or a ring kernel, so the backward does not run
    # that kernel again: B x T x Hq x Dh in the compute dtype and
    # B x Hq x T float32 a layer and device, the gauge
    # ``train_attn_residual_bytes``), or "dots" (save MXU outputs too,
    # recompute elementwise -- near-full memory savings without the extra
    # matmul forward)
    remat: Union[bool, Literal["none", "full", "dots", "dots_all"]] = True
    # fused lm-head+xent Pallas kernel; None = auto (on for TPU dense models,
    # off elsewhere -- the kernel avoids the [tokens, vocab] f32 logits in HBM)
    fused_loss: Optional[bool] = None
    # layer-scan unroll width; None = auto (full unroll on TPU for dense
    # stacks <= 16 layers -- measured +6.8% tok/s on the HBM-bound 150m
    # step -- and 1 elsewhere)
    scan_unroll: Optional[int] = None
    # sp+pp cannot run ring attention; with this opt-in the sp axis shards
    # activations only (full-sequence attention per device). Without it the
    # combination is an error rather than a silent downgrade.
    allow_sp_activation_sharding: bool = False

    # data
    dataset_name_or_paths: str = "allenai/c4"
    dataset_streaming: bool = True
    fake_data: bool = False
    # "random" = uniform tokens (entropy-floor loss, plumbing only);
    # "ramp" = learnable consecutive-token ramps (convergence-oracle
    # stream) so loss-descent assertions on fake data are meaningful
    fake_data_mode: str = "random"
    tokenizer_name: str = "mistralai/Mistral-7B-v0.1"
    seq_length: int = 1024
    num_workers: int = 1  # host dataloading threads
    prefetch_depth: int = 2  # async H2D read-ahead batches (0 disables)

    # optimization (train_fsdp.py:250-260)
    lr: float = 4e-4
    weight_decay: float = 0.1
    adam_betas: tuple[float, float] = (0.9, 0.95)
    warmup_steps: int = 1000
    total_steps: int = 88_000
    max_grad_norm: float = 1.0
    per_device_train_batch_size: int = 32
    total_batch_size: int = 512

    # precision: bf16-mixed = bf16 compute / f32 master params (TPU default;
    # the reference itself recommends bf16 over fp16, README.md:295)
    precision: Literal["bf16-mixed", "fp16-mixed", "fp32"] = "bf16-mixed"

    # in-worker parallelism (utils.py:138-152 equivalents)
    sharding_strategy: Literal[
        "NO_SHARD", "SHARD_GRAD_OP", "FULL_SHARD", "HYBRID_SHARD", "HYBRID_SHARD_ZERO2"
    ] = "NO_SHARD"
    # mesh axis sizes; None -> infer from available devices
    dp_size: Optional[int] = None
    fsdp_size: Optional[int] = None
    tp_size: int = 1
    sp_size: int = 1  # sequence/context parallel (ring attention)
    pp_size: int = 1  # pipeline stages (GPipe schedule over the layer stack)
    ep_size: int = 1  # expert parallel (MoE expert dim over the ep axis)

    # observability
    project: str = "opendiloco_tpu"
    metric_logger_type: Literal["wandb", "dummy", "jsonl"] = "wandb"
    log_activations_steps: Optional[int] = None
    # periodic evaluation on the validation split (train_diloco_torch.py:87-110)
    eval_interval: Optional[int] = None
    eval_batches: int = 16
    # jax.profiler trace of steps [profile_start, profile_start+profile_steps)
    profile_dir: Optional[str] = None
    profile_start: int = 10
    profile_steps: int = 5

    # multi-host inner loop (one TPU slice spanning hosts):
    # jax.distributed.initialize() before any jax use (train_fsdp.py:70-72
    # NCCL-group equivalent). coordinator "host:port"; ranks from env when None
    multihost: bool = False
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    ckpt: CkptConfig = CkptConfig()
    diloco: Optional[DilocoConfig] = None  # None -> plain data-parallel mode
    # in-process serving plane; None or enabled=False -> training only
    serve: Optional[ServeConfig] = None
    # serving fleet (replica galaxy + delta-push sync + router); None or
    # enabled=False -> no fleet
    fleet: Optional[FleetConfig] = None

    @field_validator("adam_betas", mode="before")
    @classmethod
    def _coerce_betas(cls, v: Any) -> Any:
        if isinstance(v, str):
            return tuple(float(x) for x in v.split(","))
        return v


# ---------------------------------------------------------------------------
# argv parsing: nested dotted flags + --no-x booleans
# ---------------------------------------------------------------------------


def _set_nested(tree: dict, dotted: str, value: Any) -> None:
    keys = dotted.split(".")
    node = tree
    for k in keys[:-1]:
        node = node.setdefault(k, {})
        if not isinstance(node, dict):
            raise ValueError(f"flag {dotted!r} conflicts with earlier scalar flag")
    leaf = keys[-1]
    node[leaf] = value  # repeated flags: last one wins


def parse_argv(argv: Optional[list[str]] = None) -> dict:
    """Parse ``--a.b value`` / ``--no-a.b`` style flags into a nested dict.

    Semantics follow the reference's pydantic_config ``parse_argv``
    (train_fsdp.py:525): dashes in key names normalize to underscores,
    ``--no-flag`` sets False, a bare ``--flag`` followed by another flag (or
    end of argv) sets True, repeated flags keep the last value (so test
    harnesses can append overrides), and list-valued fields take
    comma-separated strings.
    """
    if argv is None:
        argv = sys.argv[1:]
    tree: dict = {}
    i = 0
    while i < len(argv):
        tok = argv[i]
        if not tok.startswith("--"):
            raise ValueError(f"unexpected positional argument {tok!r}")
        key = tok[2:]
        value: Any
        if "=" in key:
            key, value = key.split("=", 1)
            i += 1
        elif key.startswith("no-") or key.startswith("no_"):
            key, value = key[3:], False
            i += 1
        elif i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            value = True
            i += 1
        else:
            value = argv[i + 1]
            i += 2
        key = ".".join(part.replace("-", "_") for part in key.split("."))
        _set_nested(tree, key, value)
    return tree
