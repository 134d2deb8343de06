"""Normative DiLoCo reference driver -- the algorithm with no backend machinery.

Parity with the reference's ``train_diloco_torch.py`` (the "algorithm in ~20
lines" file, train_diloco_torch.py:336-353, which SURVEY.md §3.5 designates
as the convergence oracle): N simulated workers in one process, inner AdamW
on device, outer Nesterov SGD on host, exact pseudo-gradient averaging with
plain numpy -- no rendezvous, no sockets, no elasticity. Includes the eval
loop (evaluate_model parity, train_diloco_torch.py:87-110).

    python -m opendiloco_tpu.train_diloco --path-model 2m --fake-data \\
        --num-workers 4 --local-steps 50 --total-steps 500 --eval-interval 100
"""

from __future__ import annotations

import argparse
import math
import time

import jax
import numpy as np

from opendiloco_tpu.data.dataloader import get_dataloader
from opendiloco_tpu.diloco.outer_device import DeviceOuterPlane
from opendiloco_tpu.diloco.outer_optimizer import OuterSGD
from opendiloco_tpu.models import hf_io
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig
from opendiloco_tpu.utils.compile_cache import enable_compile_cache
from opendiloco_tpu.utils.logger import get_text_logger

log = get_text_logger(__name__)


def evaluate_model(trainer: InnerTrainer, params, loader_iter, num_batches: int) -> float:
    """Mean eval loss over ``num_batches`` (train_diloco_torch.py:87-110)."""
    losses = []
    for _ in range(num_batches):
        batch = next(loader_iter)
        losses.append(trainer.eval_loss(params, batch["input_ids"], batch["labels"]))
    return float(np.mean(losses))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--path-model", default="150m")
    ap.add_argument("--fake-data", action="store_true")
    ap.add_argument("--dataset", default="allenai/c4")
    ap.add_argument("--tokenizer", default="mistralai/Mistral-7B-v0.1")
    ap.add_argument("--num-workers", type=int, default=2, help="simulated DiLoCo workers")
    ap.add_argument("--local-steps", type=int, default=50)
    ap.add_argument("--total-steps", type=int, default=500)
    ap.add_argument("--warmup-steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=64, help="per-worker batch")
    ap.add_argument("--seq-length", type=int, default=1024)
    ap.add_argument("--lr", type=float, default=4e-4)
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--outer-momentum", type=float, default=0.9)
    ap.add_argument(
        "--outer-placement",
        choices=["auto", "host", "device"],
        default="auto",
        help="where the master + outer momentum live: host numpy (reference "
        "semantics) or a device-resident plane with fused boundary ops "
        "(auto = device on TPU)",
    )
    ap.add_argument("--precision", default="bf16-mixed")
    ap.add_argument("--eval-interval", type=int, default=0)
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)
    enable_compile_cache()

    model_cfg, params = hf_io.get_model(args.path_model)
    plan = build_mesh("NO_SHARD")
    tc = TrainerConfig(
        lr=args.lr,
        warmup_steps=args.warmup_steps,
        total_steps=args.total_steps,
        precision=args.precision,
    )
    trainer = InnerTrainer(model_cfg, tc, plan)

    # all workers start from identical weights (rank-0 broadcast parity,
    # train_diloco_torch.py:253-255)
    states = [
        trainer.init_state(jax.random.key(args.seed), params)
        for _ in range(args.num_workers)
    ]
    loaders = [
        get_dataloader(
            fake_data=args.fake_data,
            dataset_name_or_paths=args.dataset,
            tokenizer_name=args.tokenizer,
            seq_length=args.seq_length,
            batch_size=args.batch_size,
            vocab_size=model_cfg.vocab_size,
            world_rank=r,
            galaxy_size=args.num_workers,
            seed=args.seed,
        )
        for r in range(args.num_workers)
    ]
    iters = [iter(l) for l in loaders]
    eval_iter = iters[0]

    # outer plane: host master copy (get_offloaded_param parity) or the
    # device-resident plane with fused boundary ops
    placement = args.outer_placement
    if placement == "auto":
        dev0 = plan.mesh.devices.flat[0]
        on_tpu = "tpu" in getattr(dev0, "device_kind", "").lower()
        placement = "device" if on_tpu else "host"
    log.info("outer data plane: placement=%s", placement)
    _, treedef = jax.tree.flatten(states[0]["params"])
    plane = None
    master: list[np.ndarray] = []
    outer = OuterSGD(args.outer_lr, args.outer_momentum, nesterov=True)
    if placement == "device":
        plane = DeviceOuterPlane(
            trainer,
            jax.tree.leaves(states[0]["params"]),
            lr=args.outer_lr,
            momentum=args.outer_momentum,
            nesterov=True,
        )
    else:
        flat0 = jax.tree.leaves(jax.device_get(states[0]["params"]))
        master = [np.array(x, np.float32) for x in flat0]

    for step in range(1, args.total_steps + 1):
        t0 = time.perf_counter()
        losses = []
        for r in range(args.num_workers):
            batch = next(iters[r])
            dev = trainer.shard_batch(batch["input_ids"], batch["labels"], accum=1)
            states[r], m = trainer.train_step(states[r], dev)
            losses.append(float(m["loss"]))
        if step % args.local_steps == 0:
            # pseudo-grad = master - worker params, averaged over workers
            # (train_diloco_torch.py:336-353: all_reduce(AVG) + outer step)
            if plane is not None:
                grads = None
                for r in range(args.num_workers):
                    g, _, _ = plane.pseudo_grad(
                        jax.tree.leaves(states[r]["params"])
                    )
                    grads = (
                        g if grads is None
                        else [a + b for a, b in zip(grads, g)]
                    )
                grads = [g / args.num_workers for g in grads]
                plane.apply_average(grads)  # fused device Nesterov step
                for r in range(args.num_workers):
                    leaves = plane.sync_params(
                        jax.tree.leaves(states[r]["params"])
                    )
                    states[r]["params"] = jax.tree.unflatten(treedef, leaves)
            else:
                grads = None
                for r in range(args.num_workers):
                    flat = [
                        np.asarray(x, np.float32)
                        for x in jax.tree.leaves(
                            jax.device_get(states[r]["params"])
                        )
                    ]
                    g = [m_ - f for m_, f in zip(master, flat)]
                    grads = (
                        g if grads is None
                        else [a + b for a, b in zip(grads, g)]
                    )
                grads = [g / args.num_workers for g in grads]
                outer.step(master, grads)
                new_params = jax.tree.unflatten(treedef, master)
                for r in range(args.num_workers):
                    states[r]["params"] = jax.device_put(
                        new_params, trainer.state_shardings["params"]
                    )
            log.info("outer step at %d (epoch %d)", step, step // args.local_steps)
        if step % 10 == 0 or step == 1:
            log.info(
                "step %d loss %.4f ppl %.1f (%.2fs)",
                step,
                np.mean(losses),
                math.exp(min(np.mean(losses), 30)),
                time.perf_counter() - t0,
            )
        if args.eval_interval and step % args.eval_interval == 0:
            eval_loss = evaluate_model(
                trainer, states[0]["params"], eval_iter, args.eval_batches
            )
            log.info("eval at %d: loss %.4f ppl %.1f", step, eval_loss, math.exp(eval_loss))

    for l in loaders:
        l.stop()


if __name__ == "__main__":
    main()
