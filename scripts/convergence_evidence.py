#!/usr/bin/env python
"""On-chip DiLoCo-vs-DDP convergence artifact (VERDICT r3 ask #7).

The reference validated its normative driver by training on real C4
(train_diloco_torch.py:204-237, 336-353); this environment has zero
network egress, so real C4 is unobtainable -- documented in PARITY.md.
This script banks the strongest artifact the box allows: on whatever
platform JAX resolves (recorded in the artifact), train 2-worker DiLoCo
(25 local steps between outer syncs)
and same-total-batch single-worker DDP from the SAME init on the SAME
deterministic sequence-pattern stream, and record both loss curves plus
a shared held-out eval. Mirrors the CPU oracle
tests/test_diloco.py::test_diloco_converges_within_band_of_ddp.

Appends/overwrites CONVERGENCE.json at the repo root, flushing
incrementally; "complete": true only lands after the final eval, so a run
that died midway is told apart from one that finished.
"""
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
_OUT = os.path.join(REPO, "CONVERGENCE.json")

N_STEPS = int(os.environ.get("ODTP_CONV_STEPS", 300))
LOCAL_STEPS = 25
BS = 16  # per DiLoCo worker; DDP runs 2*BS
SEQ = 64

# additive outer-mode arms: (streaming_fragments, DilocoConfig overrides).
# Every arm shares the data stream, init, and held-out eval with the core
# diloco-vs-ddp verdict. ``--arms`` re-runs a subset against an already
# banked complete artifact without disturbing the rest (the core verdict
# may come from a TPU run this box can't reproduce).
ARMS = {
    # one fragment per boundary, blocking (arxiv 2501.18512)
    "streaming": (2, {}),
    "gossip": (0, {"outer_mode": "gossip"}),
    # barrier-free NoLoCo pair rounds (arxiv 2506.10911) composed with
    # every feature the old gossip constraints rejected: streamed
    # fragments, eager overlap, and the 4-bit wire with per-partner
    # error-feedback residuals. The composition's curve is judged
    # against the blocking diloco one like every other arm
    "gossip_noloco": (
        2,
        {
            "outer_mode": "gossip",
            "overlap_comm": "eager",
            "compression": "blockwise4bit",
            "error_feedback": True,
        },
    ),
    # gossip_noloco under FREE-RUNNING round clocks: identical wire and
    # mixing composition, but pairs are matched by the bounded-staleness
    # scheduler (ODTP_ASYNC_STALENESS via ARM_ENV) instead of the epoch-
    # aligned key — on a healthy 2-worker galaxy every match lands at
    # distance 0, so the curve must sit at parity with gossip_noloco
    "async_noloco": (
        2,
        {
            "outer_mode": "gossip",
            "overlap_comm": "eager",
            "compression": "blockwise4bit",
            "error_feedback": True,
        },
    ),
    "overlap_delayed": (0, {"overlap_comm": "delayed"}),
    "overlap_eager": (0, {"overlap_comm": "eager"}),
    # staggered in-phase fragment all-reduce with eager first-step
    # estimates (2501.18512 x 2502.12996): the parity curve for the
    # streaming eager outer sync path, judged against the blocking
    # diloco curve banked beside it
    "streaming_eager": (2, {"overlap_comm": "eager"}),
    # sub-8-bit outer compression: the 8-bit blockwise baseline and the
    # 4-bit blockwise + error-feedback arm it is judged against (the
    # residual re-injects each round's quantization error, so the curve
    # must stay within noise of the 8-bit one)
    "compress_8bit": (0, {"compression": "blockwise8bit"}),
    "compress_4bit_ef": (
        0,
        {"compression": "blockwise4bit", "error_feedback": True},
    ),
}

# env knobs an arm needs armed for its run (set before, restored after):
# the async scheduler is env-gated, not a DilocoConfig field
ARM_ENV = {
    "async_noloco": {
        "ODTP_ASYNC_STALENESS": "2",
        # generous patience: the parity claim needs real pair mixing, and
        # a 2-worker CPU galaxy's threads can drift by a compile
        "ODTP_ASYNC_PATIENCE_S": "10.0",
    },
}


def batches(seed, vocab, n, global_bs, seq=SEQ):
    """Learnable deterministic stream: each row is a consecutive-token
    ramp from a random start (same generator as the CPU oracle)."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        starts = rng.integers(0, vocab, (global_bs, 1))
        ids = ((starts + np.arange(seq)) % vocab).astype(np.int32)
        yield ids, ids.copy()


def _flush(doc):
    tmp = _OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, _OUT)


def main(arms: str = "all"):
    import jax

    from opendiloco_tpu.config import DilocoConfig
    from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld
    from opendiloco_tpu.models.hf_io import get_model
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig
    from opendiloco_tpu.utils.device import device_stamp
    cfg, _ = get_model("2m")
    want = None
    if arms != "all":
        want = [a.strip() for a in arms.split(",") if a.strip()]
        unknown = [a for a in want if a not in ARMS]
        if unknown:
            raise SystemExit(f"unknown arms {unknown}; known: {sorted(ARMS)}")
        try:
            with open(_OUT) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = None
        if not doc or not doc.get("complete"):
            raise SystemExit(
                "--arms updates a banked artifact additively; run the full "
                "script first so the core diloco-vs-ddp verdict exists"
            )
        if doc.get("n_steps") != N_STEPS:
            raise SystemExit(
                f"banked artifact has n_steps={doc.get('n_steps')}, this run "
                f"would add {N_STEPS}-step curves — incomparable; match "
                "ODTP_CONV_STEPS to the banked run"
            )
    else:
        doc = {
            "model": "2m",
            **device_stamp(),
            "device": str(jax.devices()[0]),
            "n_steps": N_STEPS,
            "local_steps": LOCAL_STEPS,
            "batch_per_worker": BS,
            "seq": SEQ,
            "ts_start": time.time(),
            "complete": False,
        }
        _flush(doc)

    def make_trainer():
        tc = TrainerConfig(
            lr=1e-3,
            warmup_steps=10,
            total_steps=N_STEPS,
            precision="fp32",
            remat=False,
        )
        return InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))

    # --- 2-worker DiLoCo over loopback, threads like the oracle test ----
    def run_diloco_pair(streaming_fragments: int, **cfg_overrides):
        """Returns (per-worker losses, worker-0 final params, wall_s).
        ``cfg_overrides`` select the outer-mode arm (gossip / overlap-comm /
        compression); every arm shares the data stream, init, and held-out
        eval. The loopback wire roundtrips the arm's codec, so a
        compression arm's curve carries the real quantization error."""
        world = LoopbackWorld(
            2, compression=cfg_overrides.get("compression", "none")
        )
        backends = world.make_backends()
        losses = [[], []]
        params = [None, None]
        errors = []

        def worker(rank):
            try:
                trainer = make_trainer()
                state = trainer.init_state(jax.random.key(7))
                opt = DiLoCoOptimizer(
                    trainer,
                    backends[rank],
                    DilocoConfig(
                        local_steps=LOCAL_STEPS,
                        outer_nesterov=True,
                        backend="loopback",
                        timeout_waiting_for_peers=120.0,
                        averaging_timeout=300.0,
                        streaming_fragments=streaming_fragments,
                        **cfg_overrides,
                    ),
                    state,
                    batch_size=BS,
                )
                for ids, labels in batches(
                    1000 + rank, cfg.vocab_size, N_STEPS, BS
                ):
                    state, m = opt.step(
                        state, trainer.shard_batch(ids, labels, accum=1)
                    )
                    losses[rank].append(round(float(m["loss"]), 5))
                # overlapped arms may end with a round in flight; the
                # harvested params must include it
                state = opt.flush(state)
                params[rank] = jax.device_get(state["params"])
            except Exception as e:  # pragma: no cover - banked as evidence
                errors.append(f"worker {rank}: {e!r}")

        t0 = time.time()
        threads = [threading.Thread(target=worker, args=(r,)) for r in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            doc["error"] = "; ".join(errors)
            _flush(doc)
            raise SystemExit(doc["error"])
        return losses, params[0], round(time.time() - t0, 1)

    if want is None:
        diloco_l, diloco_p0, doc["diloco_wall_s"] = run_diloco_pair(0)
        doc["diloco_losses"] = diloco_l[0]
        _flush(doc)

        # --- DDP at the same total batch: both shards concatenated ------
        trainer = make_trainer()
        state = trainer.init_state(jax.random.key(7))  # same init
        ddp_losses = []
        t0 = time.time()
        for (i0, l0), (i1, l1) in zip(
            batches(1000, cfg.vocab_size, N_STEPS, BS),
            batches(1001, cfg.vocab_size, N_STEPS, BS),
        ):
            batch = trainer.shard_batch(
                np.concatenate([i0, i1]), np.concatenate([l0, l1]), accum=1
            )
            state, m = trainer.train_step(state, batch)
            ddp_losses.append(round(float(m["loss"]), 5))
        doc["ddp_wall_s"] = round(time.time() - t0, 1)
        doc["ddp_losses"] = ddp_losses
        _flush(doc)
    else:
        # additive mode: the trainer only provides the (pure, jitted)
        # eval function; the banked core curves stay untouched
        trainer = make_trainer()

    # --- shared held-out eval -------------------------------------------
    eval_ids, eval_labels = next(batches(9999, cfg.vocab_size, 1, 64))
    def held_out(params):
        return float(
            trainer.eval_loss(
                jax.device_put(params, trainer.state_shardings["params"]),
                eval_ids,
                eval_labels,
            )
        )

    if want is None:
        ev = {
            "ddp": float(
                trainer.eval_loss(state["params"], eval_ids, eval_labels)
            ),
            "diloco_w0": held_out(diloco_p0),
        }
        ev["init"] = float(np.log(cfg.vocab_size))
        ev["ratio"] = ev["diloco_w0"] / ev["ddp"] if ev["ddp"] else None
        doc["eval"] = {k: round(v, 5) for k, v in ev.items()}
        doc["ts_end"] = time.time()
        # the CORE diloco-vs-DDP verdict banks complete FIRST: a run
        # dying during an optional arm below must not cost it
        doc["complete"] = True
        _flush(doc)
        print(
            f"CONVERGENCE complete on {doc['platform']}: "
            f"ddp {ev['ddp']:.4f} diloco {ev['diloco_w0']:.4f} "
            f"(init {ev['init']:.2f})"
        )
    ev_ddp = doc["eval"]["ddp"]

    # beyond-ref outer modes, appended additively after the core artifact
    # is already complete: streaming fragment sync (arxiv 2501.18512),
    # gossip pairing (arxiv 2506.10911), overlapped communication
    # (arxiv 2502.12996), and their streaming-eager composition
    for arm in (list(ARMS) if want is None else want):
        frags, overrides = ARMS[arm]
        arm_env = ARM_ENV.get(arm, {})
        saved_env = {k: os.environ.get(k) for k in arm_env}
        os.environ.update(arm_env)
        try:
            arm_l, arm_p0, doc[f"{arm}_wall_s"] = run_diloco_pair(
                frags, **overrides
            )
        except SystemExit as e:
            # a failed additive arm must not take down the banked core
            # artifact or the remaining arms
            doc.setdefault("arm_errors", {})[arm] = str(e)
            doc.pop("error", None)
            _flush(doc)
            continue
        finally:
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if "arm_errors" in doc:  # a re-run supersedes a banked failure
            doc["arm_errors"].pop(arm, None)
            if not doc["arm_errors"]:
                del doc["arm_errors"]
        doc[f"{arm}_losses"] = arm_l[0]
        doc["eval"][f"{arm}_w0"] = round(held_out(arm_p0), 5)
        doc["eval"][f"{arm}_ratio"] = (
            round(doc["eval"][f"{arm}_w0"] / ev_ddp, 5) if ev_ddp else None
        )
        # arms may be re-banked on a different box than the core verdict
        # (a TPU run vs this CPU host); record where
        doc.setdefault("arm_platforms", {})[arm] = jax.devices()[0].platform
        doc["ts_end"] = time.time()
        _flush(doc)
        print(
            f"CONVERGENCE {arm} arm: {doc['eval'][f'{arm}_w0']:.4f} "
            f"(ratio vs ddp {doc['eval'][f'{arm}_ratio']})"
        )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--arms", default="all",
        help="comma list from: " + ",".join(ARMS) + "; 'all' runs the full "
        "core-verdict + every arm, a subset updates a banked complete "
        "artifact additively",
    )
    cli = ap.parse_args()
    main(cli.arms)
