"""Live checkpoint/resume bit-exactness oracle on the real chip.

The resume-determinism tests enforce bit-exact continuation on the CPU
mesh; this re-runs the same oracle against the real TPU: train 20 steps,
save via the Orbax path (`ckpt.save_checkpoint`), train 10 more, restore
the checkpoint, replay the same 10 batches, and require every loss to
match bit-for-bit. Writes LIVE_CKPT.json.
"""

import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
_OUT = os.path.join(_ROOT, "LIVE_CKPT.json")


def main():
    import jax

    from opendiloco_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from opendiloco_tpu.ckpt import load_checkpoint, save_checkpoint
    from opendiloco_tpu.models.hf_io import get_model
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig
    from opendiloco_tpu.utils.device import device_stamp

    cfg, _ = get_model("2m")
    tc = TrainerConfig(
        lr=1e-3, warmup_steps=5, total_steps=200, precision="bf16-mixed",
        remat="dots_all",
    )
    tr = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
    state = tr.init_state(jax.random.key(0))

    def batch(i):
        r = np.random.default_rng((7, i))
        starts = r.integers(0, cfg.vocab_size, (16, 1))
        ids = ((starts + np.arange(128)) % cfg.vocab_size).astype(np.int32)
        return tr.shard_batch(ids, ids.copy(), accum=1)

    t0 = time.time()
    for i in range(20):
        state, _ = tr.train_step(state, batch(i))
    d = save_checkpoint("/tmp/odtp-live-ckpt", 20, state)

    cont = []
    for i in range(20, 30):
        state, m = tr.train_step(state, batch(i))
        cont.append(float(m["loss"]))

    restored, _, _, _ = load_checkpoint(
        d, jax.eval_shape(tr.init_state, jax.random.key(0))
    )
    restored = jax.device_put(restored, tr.state_shardings)
    res = []
    for i in range(20, 30):
        restored, m = tr.train_step(restored, batch(i))
        res.append(float(m["loss"]))

    doc = {
        "device": jax.devices()[0].device_kind,
        **device_stamp(),
        "model": "2m",
        "remat": "dots_all",
        "steps_before_save": 20,
        "steps_after": 10,
        "continued_losses": cont,
        "resumed_losses": res,
        "bit_exact": cont == res,
        "wall_s": round(time.time() - t0, 1),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    with open(_OUT, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps({k: doc[k] for k in ("device", "bit_exact", "wall_s")}))
    if not doc["bit_exact"]:
        raise SystemExit("resume NOT bit-exact on this device")


if __name__ == "__main__":
    main()
