"""The Kimi-delta decode step's kernel alone, on the chip, at a cell's shapes.

``python3 scripts/kda_step_bench.py`` (the solar2 cell's: 3 layers x 128
slots x 64 heads of 128) checks ``decode_kernels.kda_step`` against
``models.kda.step_state``, the XLA form, there, its products at the highest
precision (``o`` and the live slots' new states to float32's rounding; a dead
slot's state and every other layer's bit for bit; dead slots first, last and
in the middle, and none live at all) and times a step over
the stack's layers, the states handed on as the engine's decode scan hands
them (donated): ``pallas_us`` and ``xla_us`` a layer, each the difference of
a program of ``--calls`` LO,HI steps, so that starting a program and waiting
for it is not in the number, with the bytes a second that is of a live
slot's state there and back.

``--heads 8,16,32``: the same check and time under each of these heads a grid
step (a sweep sets the kernel's byte budget: the program has no option for
it). ``--dead N``: N of the slots hold no sequence while timed.

TPU only: off the chip the kernel is interpreted and a time means nothing.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # runnable from anywhere without an install
    sys.path.insert(0, _ROOT)


def _inputs(rng, slots, heads, d):
    """A step's rows as ``kda.step_inputs`` hands them: unit keys, queries by
    D^-1/2, decays of the family's initialisation and stronger, beta up to 2."""
    q, k = (rng.standard_normal((slots, heads, d)).astype(np.float32) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d**0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((slots, heads, d)).astype(np.float32)
    g = -rng.uniform(1e-3, 3.0, (slots, heads, d)).astype(np.float32)
    beta = rng.uniform(0, 2, (slots, heads)).astype(np.float32)
    return q, k, v, g, beta


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--num-heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--heads", type=ints, default=[], help="sweep: heads a grid step, e.g. 8,16,32")
    ap.add_argument("--dead", type=int, default=2, help="slots that hold no sequence while timed")
    ap.add_argument("--calls", type=ints, default=[4, 16], help="LO,HI steps of the two programs")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models import kda
    from opendiloco_tpu.ops import decode_kernels as dk

    if jax.default_backend() != "tpu":
        print("kda_step_bench: no TPU here; a time off the chip means nothing", file=sys.stderr)
        return 2
    lk, s, h, d = args.layers, args.slots, args.num_heads, args.head_dim
    rng = np.random.default_rng(args.seed)
    rows = tuple(map(jnp.asarray, _inputs(rng, s, h, d)))
    fresh = jax.jit(lambda key: jax.random.normal(key, (lk, s, h, d, d), jnp.float32))

    def xla_layer(*args):
        # the XLA form and ``decode_forward``'s write of the layer's states, as the parent ran them
        *rows, states, li, live = args
        o, new = kda.step_state(*rows, states[li], live)
        return o, jax.lax.dynamic_update_index_in_dim(states, new, li, 0)

    def check() -> dict:
        worst = {"o_rel": 0.0, "state_rel": 0.0}
        patterns = {
            "all": np.ones(s, bool), "none": np.zeros(s, bool),
            "ends": np.r_[False, False, np.ones(s - 3, bool), False],
            "random": rng.random(s) < 0.6,
        }
        rel = lambda a, b: float(np.linalg.norm(a - b) / np.linalg.norm(b))
        for li, (name, live) in enumerate(patterns.items()):
            li, live = li % lk, jnp.asarray(live)
            was = np.asarray(fresh(jax.random.key(li)))
            # (a new function a call: the heads a step are read when it is traced)
            o, new = jax.jit(lambda *a: dk.kda_step(*a), donate_argnums=5)(*rows, jnp.asarray(was), jnp.int32(li), live)
            # (the XLA form's two reads are a product that the chip rounds to bfloat16 unless asked)
            with jax.default_matmul_precision("highest"):
                o2, new2 = jax.jit(xla_layer, donate_argnums=5)(*rows, jnp.asarray(was), jnp.int32(li), live)
            o, new, o2, new2, at = (np.asarray(x) for x in (o, new, o2, new2, live))
            others = [j for j in range(lk) if j != li]
            assert np.array_equal(new[others], was[others]), (name, li, "another layer's states moved")
            assert np.array_equal(new[li][~at], was[li][~at]), (name, li, "a dead slot's state moved")
            if at.any():
                worst["o_rel"] = max(worst["o_rel"], rel(o[at], o2[at]))
                worst["state_rel"] = max(worst["state_rel"], rel(new[li][at], new2[li][at]))
            del was, new, new2
        assert worst["o_rel"] < 1e-5 and worst["state_rel"] < 1e-5, worst
        return worst

    def us_a_layer(layer_fn) -> float:
        live = jnp.asarray(np.r_[np.zeros(args.dead, bool), np.ones(s - args.dead, bool)])
        took = {}
        for n in args.calls:
            def program(states, *rows, n=n):
                def body(i, carry):
                    states, total = carry
                    for li in range(lk):
                        o, states = layer_fn(*rows, states, jnp.int32(li), live)
                        total = total + o
                    return states, total
                return jax.lax.fori_loop(0, n, body, (states, jnp.zeros((s, h, d), jnp.float32)))

            run = jax.jit(program, donate_argnums=0)
            states = fresh(jax.random.key(7))
            states, total = run(states, *rows)  # compiles
            total.block_until_ready()
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                states, total = run(states, *rows)
                total.block_until_ready()
                best = min(best, time.perf_counter() - t0)
            took[n] = best
            del states
        lo, hi = args.calls
        return (took[hi] - took[lo]) / ((hi - lo) * lk) * 1e6

    moved = 2 * (s - args.dead) * h * d * d * 4  # a layer's live states, there and back
    doc = {"device": jax.devices()[0].device_kind, "shape": [lk, s, h, d, d], "dead": args.dead, "runs": []}
    xla_us = us_a_layer(xla_layer)
    doc["xla_us"] = round(xla_us, 1)
    doc["xla_gb_s"] = round(moved / xla_us / 1e3, 1)
    print(json.dumps({k: doc[k] for k in ("device", "shape", "dead", "xla_us", "xla_gb_s")}), flush=True)
    for hb in args.heads or [0]:
        if hb:
            dk._KDA_STATE_BYTES = hb * d * d * 4
        run = {"heads_a_step": dk._kda_heads(h, d), **check()}
        run["pallas_us"] = round(us_a_layer(dk.kda_step), 1)
        run["pallas_gb_s"] = round(moved / run["pallas_us"] / 1e3, 1)
        doc["runs"].append(run)
        print(json.dumps(run), flush=True)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "kda_step_bench.json"), "w") as f:
        json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
