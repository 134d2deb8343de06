"""Main training driver: the TPU-native ``train_fsdp.py``.

End-to-end Llama pretraining with optional DiLoCo outer loop:

    python -m opendiloco_tpu.train --path-model 150m --fake-data \\
        --per-device-train-batch-size 32 --total-batch-size 512 \\
        --diloco.local-steps 500 --diloco.initial-peers HOST:PORT \\
        --diloco.world-rank 0 --diloco.galaxy-size 8 \\
        --ckpt.path outputs --ckpt.interval 500 --metric-logger-type wandb

Reference call-stack parity (train_fsdp.py:177-516): config -> mesh ->
model -> dataloader -> trainer -> (DiLoCo optimizer | plain inner loop) ->
train loop with metrics, activation probes, peer-drop handling, checkpoint
cadence + resume. What disappears on TPU: torchrun process-per-GPU (one
controller process drives the local mesh), FSDP wrapping (shardings),
GradScaler (bf16), and the post-outer-step NCCL broadcast (the outer update
is written to the sharded pytree directly).
"""

from __future__ import annotations

import math
import os
import time
from typing import Optional

import jax
import numpy as np

from opendiloco_tpu import ckpt as ckpt_lib
from opendiloco_tpu import obs
from opendiloco_tpu.config import Config, DilocoConfig, parse_argv
from opendiloco_tpu.diloco import chaos
from opendiloco_tpu.data.dataloader import get_dataloader
from opendiloco_tpu.diloco.backend import OuterBackend
from opendiloco_tpu.diloco.optimizer import DiLoCoOptimizer, PeerDropError
from opendiloco_tpu.models import hf_io
from opendiloco_tpu.models.llama import init_params
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.parallel.world import make_world
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig
from opendiloco_tpu.utils.compile_cache import enable_compile_cache
from opendiloco_tpu.utils.logger import get_logger, get_text_logger

log = get_text_logger(__name__)


def make_backend(cfg: DilocoConfig) -> OuterBackend:
    if cfg.backend == "tcp":
        from opendiloco_tpu.diloco.tcp import TcpBackend

        return TcpBackend(
            cfg.initial_peers,
            host=cfg.host if cfg.host != "0.0.0.0" else "127.0.0.1",
            port=cfg.port,
            peer_id=f"worker-{cfg.world_rank}",
            compression=cfg.compression,
            matchmaking_time=cfg.matchmaking_time,
            # config True forces adaptive transport on; False defers to the
            # ODTP_LINK_ADAPT env switch (None = backend reads env per round)
            link_adapt=cfg.link_adapt or None,
        )
    raise ValueError(
        f"backend {cfg.backend!r} has no factory (loopback backends are "
        "constructed from a shared LoopbackWorld in-process)"
    )


def make_trainer_config(config: Config) -> TrainerConfig:
    """The optimization-relevant slice of ``config``, as ``train`` uses it."""
    return TrainerConfig(
        lr=config.lr,
        weight_decay=config.weight_decay,
        adam_betas=tuple(config.adam_betas),
        warmup_steps=config.warmup_steps,
        total_steps=config.total_steps,
        max_grad_norm=config.max_grad_norm,
        precision=config.precision,
        attn_impl=config.attn_implementation,
        remat=config.remat,
        fused_loss=config.fused_loss,
        scan_unroll=config.scan_unroll,
        allow_sp_activation_sharding=config.allow_sp_activation_sharding,
    )


def _end_profile(profile_dir: str) -> None:
    """Close the ``--profile-dir`` capture and lay beside the trace it wrote
    the program's spans (with the anchor that places them on the profiler's
    clock) and what the trace's device events are: each compiled program's
    instructions by scope, pass and opcode (``odtp_programs.json``; the
    programs are lowered again here, which the compile cache answers)."""
    capture = obs.capture.stop()
    path = capture.save(os.path.join(profile_dir, "odtp_capture.json"))
    named = obs.programs.save(os.path.join(profile_dir, "odtp_programs.json"))
    log.info(
        "wrote profiler trace to %s (%d program spans in %s; the instructions "
        "of %s in odtp_programs.json%s)",
        profile_dir, len(capture.spans), path, ", ".join(named["programs"]) or "no program",
        f", missing: {named['missing']}" if named["missing"] else "",
    )


def train(
    config: Config,
    backend: Optional[OuterBackend] = None,
    devices: Optional[list] = None,
) -> dict:
    """Returns a summary dict (final step/loss, plus the outer plane's
    epoch and resolved placement under DiLoCo) for programmatic callers.
    ``devices`` restricts this worker's mesh to a subset of the local
    devices (default: all of ``jax.devices()``)."""
    world_rank = config.diloco.world_rank if config.diloco else 0
    os.environ.setdefault("DILOCO_WORLD_RANK", str(world_rank))
    _cp = chaos.plane()
    if _cp is not None:
        # scope rank-targeted faults (straggle_worker, kill_worker) to us
        _cp.set_identity(world_rank)
    _tr = obs.tracer()
    if _tr is not None:
        _tr.set_identity(worker=world_rank)
        # arm the flight recorder's crash hooks (atexit / fatal signals /
        # faulthandler) so this worker leaves a black box behind even when
        # it dies mid-round; identity must be set first so the dump file
        # is blackbox-<rank>-<pid>.json, not blackbox-<pid>-<pid>.json
        obs.blackbox.install()

    if config.multihost:
        # in-worker multi-host slice: every host of the slice runs this
        # driver; jax.distributed wires the hosts into one mesh over ICI/DCN
        jax.distributed.initialize(
            coordinator_address=config.coordinator_address,
            num_processes=config.num_processes,
            process_id=config.process_id,
        )
        log.info(
            "multihost: process %d/%d, %d local / %d global devices",
            jax.process_index(),
            jax.process_count(),
            jax.local_device_count(),
            jax.device_count(),
        )

    model_cfg, params = hf_io.get_model(config.path_model)
    plan = build_mesh(
        config.sharding_strategy,
        devices=devices,
        dp_size=config.dp_size,
        fsdp_size=config.fsdp_size,
        tp_size=config.tp_size,
        sp_size=config.sp_size,
        pp_size=config.pp_size,
        ep_size=config.ep_size,
    )
    tc = make_trainer_config(config)
    trainer = InnerTrainer(model_cfg, tc, plan)

    if config.ckpt.interval:
        ckpt_lib.check_checkpoint_path_access(config.ckpt.path, world_rank)

    # batch/accumulation accounting (train_fsdp.py:189-190)
    dp = plan.data_parallel_size
    global_micro = config.per_device_train_batch_size * dp
    accum = max(1, config.total_batch_size // global_micro)
    if config.total_batch_size % global_micro:
        raise ValueError(
            f"total_batch_size {config.total_batch_size} not divisible by "
            f"per_device_train_batch_size*dp = {global_micro}"
        )

    # under multihost every process loads only its shard of the global batch
    # (the data is already split by process_index; shard_batch assembles the
    # global array from per-process rows)
    nproc = jax.process_count()
    if config.total_batch_size % nproc:
        raise ValueError(
            f"total_batch_size {config.total_batch_size} not divisible by "
            f"process_count {nproc}"
        )
    local_batch_size = config.total_batch_size // nproc
    if local_batch_size % accum:
        raise ValueError(
            f"per-process batch {local_batch_size} not divisible by the "
            f"accumulation factor {accum} (= total_batch_size / "
            f"(per_device_train_batch_size * dp)); adjust batch sizes"
        )
    if config.eval_interval and (global_micro % nproc):
        raise ValueError(
            f"eval batch per_device_train_batch_size*dp = {global_micro} "
            f"not divisible by process_count {nproc}"
        )
    loader = get_dataloader(
        fake_data=config.fake_data,
        fake_data_mode=config.fake_data_mode,
        dataset_name_or_paths=config.dataset_name_or_paths,
        tokenizer_name=config.tokenizer_name,
        seq_length=config.seq_length,
        batch_size=local_batch_size,
        vocab_size=model_cfg.vocab_size,
        world_rank=world_rank,
        galaxy_size=config.diloco.galaxy_size if config.diloco else 1,
        streaming=config.dataset_streaming,
    )

    state = trainer.init_state(jax.random.key(42), params)

    diloco_opt: Optional[DiLoCoOptimizer] = None
    owns_backend = False
    if config.diloco is not None:
        # world-messenger split (reference train_fsdp.py:183,205-212): in a
        # multihost slice only process 0 joins the WAN fabric; the other
        # processes run the same outer loop against mesh collectives
        world = make_world(plan.mesh)
        if backend is None and world.is_messenger:
            backend = make_backend(config.diloco)
            owns_backend = True
        diloco_opt = DiLoCoOptimizer(
            trainer,
            backend,
            config.diloco,
            state,
            batch_size=config.total_batch_size,
            world=world,
        )
        log.info(
            "outer data plane: placement=%s (requested %s)",
            diloco_opt.placement,
            config.diloco.outer_placement,
        )

    # resume (ckpt_utils.py:23-45 + train_fsdp.py:313-344)
    start_step = 0
    resume, resume_dir, resume_step = ckpt_lib.get_resume_info(
        config.ckpt.resume,
        config.ckpt.path,
        diloco_rank=world_rank if config.diloco else None,
    )
    if resume:
        log.info("resuming from %s (step %d)", resume_dir, resume_step)
        state, diloco_state, loader_state, extra = ckpt_lib.load_checkpoint(
            resume_dir, state
        )
        if diloco_opt is not None and diloco_state is not None:
            diloco_opt.load_state_dict(diloco_state)
        if loader_state is not None:
            loader.load_state_dict(loader_state)
        start_step = resume_step
    elif diloco_opt is not None and not config.diloco.skip_load_from_peers:
        updated = diloco_opt.load_state_from_peers(state)
        if updated is not None:
            state = updated
            log.info("loaded state from peers (epoch %d)", diloco_opt.epoch)

    metric_logger = get_logger(
        config.metric_logger_type,
        config.project,
        config.model_dump(),
        resume=bool(resume),
    )

    # in-process serving plane: inference threads share this process (and
    # its obs registry) with the inner loop; weights hot-swap from the
    # DiLoCo master snapshots between decode steps (opendiloco_tpu/serve)
    serving = None
    if config.serve is not None and config.serve.enabled:
        from opendiloco_tpu.serve import build_serving

        serving = build_serving(
            config.serve,
            model_cfg,
            state["params"],
            diloco_opt,
            compute_dtype=tc.compute_dtype,
        )
        log.info(
            "serving plane up on %s:%d (%d slots, ctx %d)",
            config.serve.host,
            serving.port,
            config.serve.max_batch,
            config.serve.max_context,
        )

    # serving fleet: replica engines (subprocesses by default) fed by
    # delta pushes off the masters, behind one router (opendiloco_tpu/fleet)
    fleet_plane = None
    if config.fleet is not None and config.fleet.enabled:
        from opendiloco_tpu.fleet import build_fleet

        fleet_plane = build_fleet(
            config.fleet,
            model_cfg,
            state["params"],
            diloco_opt,
            compute_dtype=tc.compute_dtype,
        )
        log.info(
            "serving fleet up: router %s:%d over %d replicas (codec %s)",
            config.fleet.host,
            fleet_plane.port,
            config.fleet.replicas,
            config.fleet.codec,
        )

    eval_iter = None
    if config.eval_interval:
        eval_loader = get_dataloader(
            fake_data=config.fake_data,
            fake_data_mode=config.fake_data_mode,
            dataset_name_or_paths=config.dataset_name_or_paths,
            tokenizer_name=config.tokenizer_name,
            seq_length=config.seq_length,
            batch_size=global_micro // nproc,
            vocab_size=model_cfg.vocab_size,
            world_rank=world_rank,
            galaxy_size=config.diloco.galaxy_size if config.diloco else 1,
            split="validation",
            streaming=config.dataset_streaming,
        )
        eval_iter = iter(eval_loader)

    tokens_per_step = config.total_batch_size * config.seq_length
    # one-time MFU setup: flops/token from the banked roofline (or 6N
    # fallback); the per-step cost is a single multiply in flush(). MFU is
    # a device metric: a CPU run reports none, an accelerator whose peak
    # is not on record raises
    n_params = sum(int(x.size) for x in jax.tree.leaves(state["params"]))
    model_flops_per_token, mfu_source = obs.mfu.flops_per_token(
        config.path_model, n_params
    )
    device = plan.mesh.devices.flat[0]
    peak_flops = (
        None
        if device.platform == "cpu"
        else obs.mfu.peak_flops(device.device_kind)
    )
    n_devices = plan.mesh.size
    if _tr is not None:
        _tr.set_identity(
            model=config.path_model, mfu_source=mfu_source, n_params=n_params
        )
    summary = {"step": start_step, "loss": float("nan")}
    data_iter = iter(loader)
    prefetcher = None
    if config.prefetch_depth > 0:
        from opendiloco_tpu.data.prefetch import DevicePrefetcher

        prefetcher = DevicePrefetcher(
            data_iter,
            lambda hb: trainer.shard_batch(hb["input_ids"], hb["labels"], accum),
            depth=config.prefetch_depth,
            state_fn=loader.state_dict,
        )
    pending = None  # (real_step, device_metrics, dt, extras) of the prior step
    profiling = False

    def flush(p) -> None:
        """Materialize a step's metrics row. Deferred one step behind the
        dispatch so the float() fetch never stalls the accelerator pipeline."""
        nonlocal summary
        real_step, metrics, dt, extras = p
        loss = float(metrics["loss"])
        row = {
            "Loss": loss,
            "Perplexity": math.exp(min(loss, 30.0)),
            "step": real_step,
            "lr": trainer.current_lr(real_step),
            "effective_step": real_step
            * (config.diloco.galaxy_size if config.diloco else 1),
            "total_samples": real_step * config.total_batch_size,
            "time_taken": dt,
            "tokens_per_second": tokens_per_step / dt,
            "grad_norm": float(metrics["grad_norm"]),
        }
        if model_flops_per_token is not None and peak_flops is not None:
            row["mfu"] = obs.mfu.mfu(
                row["tokens_per_second"],
                model_flops_per_token,
                n_devices,
                peak_flops,
            )
        tr = obs.tracer()
        if tr is not None:
            tr.count("inner_tokens", tokens_per_step)
            tr.gauge("inner_loss", loss)
            tr.gauge("inner_grad_norm", row["grad_norm"])
            tr.gauge("inner_tokens_per_second", row["tokens_per_second"])
            # per-worker inner-step rate: the roll-up field odtp_top's
            # step/s column reads (async skew shows here even when batch
            # shapes differ across the galaxy and tokens/s doesn't divide)
            tr.gauge("inner_steps_per_second", 1.0 / dt if dt > 0 else 0.0)
            tr.gauge("inner_step_s", dt)
            if "mfu" in row:
                tr.gauge("inner_mfu", row["mfu"])
        if diloco_opt is not None:
            row["num_peers"] = diloco_opt.max_num_peers
            row["outer_epoch"] = diloco_opt.epoch
            # round-health fields ride along so the chaos soak can read
            # elastic rescale and aggregator re-election from the rows
            for k in ("outer_step_s", "outer_d2h_s", "outer_allreduce_s",
                      "outer_apply_s", "outer_wait_s", "pseudo_grad_norm",
                      "elastic", "expected_peers", "round_retries",
                      "hier_plan", "hier_aggregators"):
                if k in metrics:
                    row[k] = metrics[k]
        row.update(extras)
        metric_logger.log(row)
        if real_step % 10 == 0 or real_step == 1:
            log.info(
                "step %d loss %.4f lr %.2e %.0f tok/s",
                real_step,
                loss,
                row["lr"],
                row["tokens_per_second"],
            )
        summary = {"step": real_step, "loss": loss}

    try:
        for step in range(start_step, config.total_steps):
            if config.profile_dir and step == start_step + config.profile_start:
                # profiler, span tracer and request ring start together;
                # the program's spans land on the profiler's clock through
                # the capture's anchor annotation
                obs.capture.start(config.profile_dir)
                profiling = True
            if profiling and step == start_step + config.profile_start + config.profile_steps:
                _end_profile(config.profile_dir)
                profiling = False
            t0 = time.perf_counter()
            if prefetcher is not None:
                host_batch, batch = next(prefetcher)
            else:
                host_batch = next(data_iter)
                batch = trainer.shard_batch(
                    host_batch["input_ids"], host_batch["labels"], accum
                )
            data_wait_s = time.perf_counter() - t0  # ~0 when prefetch keeps up
            cp = chaos.plane()
            if cp is not None:
                d = cp.straggle_inner_s()
                if d:  # slow-host emulation, inside the measured step window
                    time.sleep(d)
            if diloco_opt is not None:
                state, metrics = diloco_opt.step(state, batch)
            else:
                state, metrics = trainer.train_step(state, batch)
            if cp is not None:
                x = cp.straggle_inner_x()
                if x > 1.0:
                    # sustained rate skew: stretch THIS step by (x-1) of
                    # its own measured duration, so the worker runs at
                    # exactly 1/x speed whatever the hardware is doing
                    time.sleep((x - 1.0) * (time.perf_counter() - t0))

            # the prior step's results are certainly ready now: flush them
            # while this step runs on device
            if pending is not None:
                flush(pending)
            real_step = step + 1
            dt = time.perf_counter() - t0
            extras: dict = {"data_wait_s": round(data_wait_s, 6)}
            if (
                config.log_activations_steps
                and real_step % config.log_activations_steps == 0
            ):
                extras.update(
                    trainer.probe_norms(state["params"], host_batch["input_ids"])
                )
            if eval_iter is not None and real_step % config.eval_interval == 0:
                eval_losses = []
                for _ in range(config.eval_batches):
                    eb = next(eval_iter)
                    eval_losses.append(
                        trainer.eval_loss(state["params"], eb["input_ids"], eb["labels"])
                    )
                extras["eval_loss"] = float(np.mean(eval_losses))
                extras["eval_perplexity"] = math.exp(min(extras["eval_loss"], 30.0))
                log.info("eval at %d: loss %.4f", real_step, extras["eval_loss"])
            pending = (real_step, metrics, dt, extras)

            if config.ckpt.interval and real_step % config.ckpt.interval == 0:
                flush(pending)
                pending = None
                if diloco_opt is not None:
                    # land any in-flight overlapped outer round so the saved
                    # master reflects every launched all-reduce
                    state = diloco_opt.flush(state)
                ckpt_lib.save_checkpoint(
                    config.ckpt.path,
                    real_step,
                    state,
                    diloco_rank=world_rank if config.diloco else None,
                    diloco_state=diloco_opt.state_dict() if diloco_opt else None,
                    dataloader_state=(
                        prefetcher.state_dict() if prefetcher else loader.state_dict()
                    ),
                    extra={"loss": summary["loss"], "step": real_step},
                )
                ckpt_lib.delete_old_checkpoints(config.ckpt.path, config.ckpt.topk)
        if pending is not None:
            flush(pending)
            pending = None
        if diloco_opt is not None:
            state = diloco_opt.flush(state)
            summary["outer_epoch"] = diloco_opt.epoch
            summary["outer_placement"] = diloco_opt.placement
    except PeerDropError:
        log.error("a DiLoCo worker dropped and fail_rank_drop is set; exiting")
        raise
    finally:
        if fleet_plane is not None:
            # pusher threads read master snapshots through diloco_opt;
            # stop them (and the replicas) before the backend goes away
            fleet_plane.stop()
        if serving is not None:
            # before the backend goes away: the batcher thread may be
            # mid-swap pulling a master snapshot through diloco_opt
            serving.stop()
        if diloco_opt is not None:
            # abnormal exits must not leave an outer round holding the
            # backend open (the comm thread is daemonized, but drop it so
            # backend.close() below isn't racing a live reduce)
            diloco_opt.drop_pending()
        if profiling:
            # a window extending past total_steps must still flush the trace;
            # never let a trace-serialization failure mask the real error or
            # skip the remaining cleanup
            try:
                _end_profile(config.profile_dir)
            except Exception:
                log.exception("failed to flush profiler trace")
        if prefetcher is not None:
            prefetcher.stop()
        loader.stop()
        metric_logger.finish()
        _tr_out = obs.tracer()
        if _tr_out is not None:
            try:
                _tr_out.flush()
            except Exception:
                log.exception("failed to flush obs trace")
            _bb = obs.blackbox.recorder()
            if _bb is not None:
                try:
                    _bb.dump(reason="train_exit")
                except Exception:
                    log.exception("failed to dump flight recorder")
        if owns_backend and backend is not None:
            backend.close()
    return summary


def main() -> None:
    enable_compile_cache()
    config = Config(**parse_argv())
    log.info("starting training: %s", config.model_dump())
    train(config)


if __name__ == "__main__":
    main()
