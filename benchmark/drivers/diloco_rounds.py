"""Training cells: whole DiLoCo rounds through the objects ``train()`` builds.

``opendiloco_tpu.train.train`` cannot be stopped at a round's edge nor timed
from outside, so this drives the same objects (``make_trainer_config``,
``InnerTrainer``, ``DiLoCoOptimizer`` on a loopback backend, one worker,
``DevicePrefetcher`` over ``shard_batch``) in a copy of its loop: step n+1 is
dispatched before step n's loss is fetched, and nothing else blocks between
the window's two ends. The window starts at a round's start and holds whole
rounds only: ``local_steps`` inner steps and their boundary.
"""

from __future__ import annotations

import math
import statistics
import time

from odbench import costs, program_obs, reference, traffic, xplane

# the program's bf16-mixed step against the float32 reference, one seeded
# micro-batch. Two bf16 paths of this model differ by 2.4e-5 in the loss and
# 1.2e-4 in the gradient norm (PR 21, Pallas vs XLA attention on the chip);
# bf16 against float32 at 32 layers measured 4.3e-5 and 5.3e-5 in the loss and
# 3.0e-3 and 2.6e-3 in the norm (PR 23, on the chip, two seeds). The
# tolerances are about ten and three times that: a step in 8-bit floats, a
# layer left out or a wrong position is far outside both.
LOSS_RTOL = 5e-4
GRAD_NORM_RTOL = 1e-2
# the window holds whole rounds, as many as fit in --seconds and at least
# this many; a --trace 1 run traces its second round (index 1); a --trace 2
# run closes its window untraced and then traces one more whole round
MIN_ROUNDS = 2
TRACED_ROUND = 1


def _config(cell):
    from opendiloco_tpu.config import Config

    mix, opts = cell.traffic, cell.options["trainer"]
    chips = cell.chips
    base = dict(
        path_model=cell.config_path,
        fake_data=True,
        fake_data_mode="ramp",
        seq_length=mix["seq_length"],
        per_device_train_batch_size=mix["global_batch"] // mix["accum"] // chips,
        total_batch_size=mix["global_batch"],
        metric_logger_type="dummy",
    )
    base.update(opts)
    return Config(**base)


def run(*, cell, devices, peak, seed, seconds, trace, t_process, compiles, report):
    import jax
    import numpy as np

    from opendiloco_tpu.config import DilocoConfig
    from opendiloco_tpu.data.prefetch import DevicePrefetcher
    from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld
    from opendiloco_tpu.models.llama import LlamaConfig
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.train import make_trainer_config
    from opendiloco_tpu.trainer import InnerTrainer

    mix = cell.traffic
    steps_per_round = int(mix["local_steps"])
    batch, seq, accum = int(mix["global_batch"]), int(mix["seq_length"]), int(mix["accum"])
    config = _config(cell)
    model_cfg = LlamaConfig.from_dict(cell.config)
    plan = build_mesh(config.sharding_strategy, devices=devices)
    trainer = InnerTrainer(model_cfg, make_trainer_config(config), plan)
    state = trainer.init_state(jax.random.key(traffic.jax_seed(seed)))
    report.line(
        "built", params=costs.param_count(cell.config), attn_impl=trainer.tc.attn_impl,
        fused_loss=bool(trainer.tc.fused_loss), scan_unroll=trainer.tc.scan_unroll,
        remat=trainer.tc.remat, strategy=config.sharding_strategy,
        setup_so_far_s=time.perf_counter() - t_process,
    )

    # -- correct: one seeded micro-batch, step function against the reference
    check = cell.options["check"]
    ids, labels = traffic.ramp_batch(
        traffic.rng_for(seed, 3), model_cfg.vocab_size, check["batch"], check["seq"]
    )
    ref_fn = jax.jit(lambda p, i, l: reference.loss_and_grad_norm(p, i, l, cell.config))
    data_sharding = plan.sharding(plan.batch_spec(2))
    ref_loss, ref_gnorm = (
        float(x) for x in ref_fn(
            state["params"], jax.device_put(ids, data_sharding),
            jax.device_put(labels, data_sharding),
        )
    )
    del ref_fn
    state, m = trainer.train_step(state, trainer.shard_batch(ids, labels, 1))
    got_loss, got_gnorm = float(m["loss"]), float(m["grad_norm"])
    loss_rel = abs(got_loss - ref_loss) / abs(ref_loss)
    gnorm_rel = abs(got_gnorm - ref_gnorm) / abs(ref_gnorm)
    check_ok = (
        math.isfinite(got_loss) and loss_rel <= LOSS_RTOL and gnorm_rel <= GRAD_NORM_RTOL
    )
    report.line(
        "check", ok=check_ok, loss=got_loss, ref_loss=ref_loss, loss_rel=loss_rel,
        grad_norm=got_gnorm, ref_grad_norm=ref_gnorm, grad_norm_rel=gnorm_rel,
        tolerance={"loss_rtol": LOSS_RTOL, "grad_norm_rtol": GRAD_NORM_RTOL},
        check_batch=[check["batch"], check["seq"]],
        setup_so_far_s=time.perf_counter() - t_process,
    )

    # -- the DiLoCo worker, as train() builds it
    (backend,) = LoopbackWorld(1).make_backends()
    opt = DiLoCoOptimizer(
        trainer, backend,
        DilocoConfig(backend="loopback", local_steps=steps_per_round,
                     skip_load_from_peers=True),
        state, batch_size=batch,
    )
    rng = traffic.rng_for(seed, 4)

    def host_batches():
        while True:
            ids, labels = traffic.ramp_batch(rng, model_cfg.vocab_size, batch, seq)
            yield {"input_ids": ids, "labels": labels}

    prefetcher = DevicePrefetcher(
        host_batches(),
        lambda hb: trainer.shard_batch(hb["input_ids"], hb["labels"], accum),
        depth=config.prefetch_depth,
    )
    annotate = jax.profiler.TraceAnnotation
    losses: list = []
    outer_rows: list = []

    def flush(pending) -> None:
        with annotate("bench/flush"):
            metrics = pending
            losses.append(float(metrics["loss"]))
            float(metrics["grad_norm"])  # train.py's flush fetches both
            if "outer_step_s" in metrics:
                outer_rows.append({
                    k: float(v) for k, v in metrics.items()
                    if k.startswith("outer_") and isinstance(v, (int, float))
                })

    def one_round(state, pending, step_dts):
        """``local_steps`` steps, the last crossing the boundary; train.py's
        loop body. -> (state, pending)"""
        for i in range(steps_per_round):
            t0 = time.perf_counter()
            with annotate("bench/next_batch"):
                _, dev_batch = next(prefetcher)
            boundary = i == steps_per_round - 1
            with annotate("bench/boundary_step" if boundary else "bench/inner_step"):
                state, metrics = opt.step(state, dev_batch)
            if pending is not None:
                flush(pending)
            pending = metrics
            if not boundary:
                step_dts.append(time.perf_counter() - t0)
        return state, pending

    try:
        # -- warm-up: one whole round runs every program of the window
        t_warm = time.perf_counter()
        state, pending = one_round(state, None, [])
        flush(pending)
        jax.block_until_ready(state["params"])
        warm_round_s = time.perf_counter() - t_warm
        losses.clear()
        outer_rows.clear()
        report.line("warm", round_s=warm_round_s, placement=opt.placement,
                    setup_so_far_s=time.perf_counter() - t_process)

        # -- the window
        trace_dir = xplane.trace_dir(cell.root, cell.name)
        before = compiles.requests
        setup_s = time.perf_counter() - t_process
        round_walls, traced_walls, step_dts, pending = [], [], [], None
        t_start = round_start = time.perf_counter()
        rounds = 0
        while True:
            tracing = trace == 1 and rounds == TRACED_ROUND
            if tracing:
                xplane.start(trace_dir)
                round_start = time.perf_counter()
                window = annotate("bench/window", pc=repr(time.perf_counter()))
                window.__enter__()
            state, pending = one_round(state, pending, step_dts)
            now = time.perf_counter()
            # the traced round carries the profiler's weight: kept apart
            (traced_walls if tracing else round_walls).append(now - round_start)
            if tracing:
                jax.block_until_ready(state["params"])
                window.__exit__(None, None, None)
                jax.profiler.stop_trace()
                now = time.perf_counter()
            round_start = now
            rounds += 1
            enough = rounds >= MIN_ROUNDS
            if enough and (now - t_start) + max(round_walls + traced_walls) > seconds:
                break
        flush(pending)
        jax.block_until_ready(state["params"])
        t_end = time.perf_counter()
        in_window = compiles.requests - before
        # the window is closed: its losses and rows are the ones that count
        win_losses, win_rows = list(losses), list(outer_rows)
        stretch = None
        if trace == 2:
            # one more whole round of the same traffic, under the program's
            # capture control (profiler, spans and counters together)
            losses.clear()
            outer_rows.clear()
            with program_obs.Stretch(trace_dir, compiles) as stretch:
                state, pending = one_round(state, None, [])
                jax.block_until_ready(state["params"])
            traced_walls.append(stretch.t1 - stretch.t0)
            flush(pending)
    finally:
        prefetcher.stop()
        opt.drop_pending()

    wall = t_end - t_start
    steps = rounds * steps_per_round
    tokens = steps * batch * seq
    failed = sum(1 for x in win_losses if not math.isfinite(x))
    rate = tokens / wall / cell.chips
    inner_s = statistics.median(step_dts)
    report.line(
        "window", rounds=rounds, steps=steps, tokens=tokens, wall_s=wall,
        round_walls_s=round_walls, traced_round_walls_s=traced_walls,
        inner_step_median_s=inner_s,
        train_tokens_per_s_per_chip=rate, loss_first=win_losses[0],
        loss_last=win_losses[-1], optimizer_outer_rows=win_rows,
        compiles_in_window=in_window, setup_s=setup_s,
        **({"traced_outer_rows": outer_rows, "compiles_in_trace": stretch.compiles,
            "traced_span_seconds": program_obs.seconds_by_name(stretch.capture, "outer/")}
           if stretch else {}),
    )
    observations = {
        "counters": {
            "local_steps": steps_per_round, "round_walls_s": round_walls,
            "inner_step_dts_s": step_dts, "tokens_per_step": batch * seq,
            "seq_length": seq, "global_batch": batch, "chips": cell.chips,
            "outer_rows": win_rows,
        },
    }
    if trace:
        observations["trace"] = (
            stretch.reduce(rehearsal=peak is None) if stretch
            else xplane.reduce(trace_dir, program_obs.spans(), rehearsal=peak is None)
        )
        observations["counters"]["traced_steps"] = steps_per_round
    return {
        "correct": check_ok and failed == 0 and len(win_losses) == steps,
        "attempted": steps,
        "failed": failed,
        "compiles_in_window": in_window,
        "compiles_in_trace": stretch.compiles if stretch else 0,
        "trace_cost": stretch.cost if stretch else {},
        "end_to_end": {"train_tokens_per_s_per_chip": rate, "setup_s": setup_s},
        "observations": observations,
    }

