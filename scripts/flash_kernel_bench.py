"""Device time of the three training attention kernels, each apart, on the chip.

One jitted program of ``--calls`` forward-and-backward calls of
``flash_attention`` at a cell's shapes runs under the profiler, over rows
``[B, T, H * D]`` as the train step hands them to the kernels (since PR 52;
``--rope 1``, the default: unrotated, with the rotary tables, so the kernels
rotate q and k in VMEM as they do in the step; ``--rope 0``: no tables), and
each kernel's time a call is the mean duration of its own device events
(``odtp_flash_fwd``, ``odtp_flash_dq``, ``odtp_flash_dkv``): a program's
start and the wait for it are in no event. TPU only: a number from the CPU
is no kernel time.

    python3 scripts/flash_kernel_bench.py                  # both train cells' shapes
    python3 scripts/flash_kernel_bench.py --blocks 512,512 --causal 1
    python3 scripts/flash_kernel_bench.py --sub-tiles 128,256,512,1024 --check

``--blocks`` goes through ``OPENDILOCO_TPU_FLASH_BLOCKS``; ``--sub-tiles``
replaces ``flash_attention._SUB_TILE`` for the sweep that settled it (PR 42;
a value that does not divide the block is skipped). One JSON line a case, on
stdout and in ``chiprun_out/flash_kernel_bench.jsonl``; ``rotary_in_kernel``
says whether the timed kernels rotated q and k themselves, ``heads_a_step``
the (query, KV) heads a grid step held.
"""

import argparse
import functools
import glob
import itertools
import json
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # runnable from anywhere without an install
    sys.path.insert(0, _ROOT)

KERNELS = ("odtp_flash_fwd", "odtp_flash_dq", "odtp_flash_dkv")
# name: (batch, seq, query heads, kv heads, head size)
SHAPES = {
    "train-360m-h16": (8, 2048, 15, 5, 64),
    "train-1.7b-fsdp4-h8": (4, 2048, 32, 32, 64),
    "serve-evabyte-complete": (2, 2048, 32, 32, 128),
}


def kernel_us(fn, args, calls: int) -> dict:
    """Mean device microseconds of each kernel's events over one traced run
    of ``fn(*args)``, which holds ``calls`` calls of each."""
    import jax
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))  # compile and warm up outside the trace
    log_dir = tempfile.mkdtemp(prefix="flash_kernel_bench_")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        sums = {k: [0.0, 0] for k in KERNELS}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    result = ev.name.partition(" = ")[0]  # "%jvp_odtp_flash_fwd_.1"
                    for k in KERNELS:
                        if k in result:
                            sums[k][0] += ev.duration_ns
                            sums[k][1] += 1
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    out = {}
    for k, (ns, n) in sums.items():
        if n:
            assert n == calls, f"{k}: {n} events for {calls} calls"
            out[k] = round(ns / n / 1e3, 2)
    return out


def _check(one, causal, q, k, v, rope, d, args) -> list:
    """Largest difference from ``xla_attention`` over heads rotated by the
    model's ``_rope_apply``, over the largest reference value, for the output
    (forward only) or each of dq, dk, dv; rows q, k, v [1, T, H * d]."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import _rope_apply
    from opendiloco_tpu.ops.attention import xla_attention

    def heads(x, turn):
        x = x.reshape(*x.shape[:2], -1, d)
        if rope is None or not turn:
            return x
        half = d // 2  # the tables the kernels were handed, as the model's
        return _rope_apply(x, rope.cos[:1, :, None, :half], rope.sin[:1, :, None, half:d])

    def ref_fn(q, k, v):
        out = xla_attention(heads(q, True), heads(k, True), heads(v, False), causal=causal)
        return out.reshape(q.shape)

    if args.forward_only:
        ref = (ref_fn(q, k, v),)
        got = (jax.jit(one)(q, k, v),)
    else:
        loss = lambda q, k, v: ref_fn(q, k, v).astype(jnp.float32).sum()
        ref = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        got = jax.jit(one)(q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    return [
        float(jnp.max(jnp.abs(f32(g) - f32(r))) / jnp.max(jnp.abs(f32(r))))
        for g, r in zip(got, ref)
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="train-360m-h16,train-1.7b-fsdp4-h8")
    ap.add_argument("--causal", default="1,0", help="comma list of 1 / 0")
    ap.add_argument("--blocks", default="", help="'bq,bk' or empty; ';' separates several")
    ap.add_argument("--sub-tiles", default="", help="comma list; empty: the code's own rule")
    ap.add_argument("--rope", default="1", help="comma list of 1 (rotary inside the kernels) / 0")
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--forward-only", action="store_true")
    ap.add_argument("--scale-on-scores", action="store_true",
                    help="keep the scale on the float32 scores whatever the head size")
    ap.add_argument("--check", action="store_true",
                    help="each case against xla_attention on one batch row first")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import _rope_tables
    from opendiloco_tpu.ops import flash_attention as fa

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"flash_kernel_bench: needs a TPU, found {dev.platform}")
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    sink = open(os.path.join(_ROOT, "chiprun_out", "flash_kernel_bench.jsonl"), "a")
    own_sub_tile = getattr(fa, "_SUB_TILE", None)
    if args.scale_on_scores:
        fa._scale_on_operand = lambda scale: False
    sub_tiles = [int(c) for c in args.sub_tiles.split(",") if c] or [None]

    for name in args.shapes.split(","):
        b, t, hq, hkv, d = SHAPES[name]
        keys = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(keys[0], (b, t, hq * d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, t, hkv * d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, t, hkv * d), jnp.bfloat16)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        cases = itertools.product(
            args.blocks.split(";"), (bool(int(c)) for c in args.causal.split(",")), sub_tiles,
            (bool(int(c)) for c in args.rope.split(",")),
        )
        for blocks, causal, c, rotary in cases:
            os.environ.pop("OPENDILOCO_TPU_FLASH_BLOCKS", None)
            if blocks:
                os.environ["OPENDILOCO_TPU_FLASH_BLOCKS"] = blocks
            if c is not None:
                if own_sub_tile is None or (blocks and int(blocks.split(",")[0]) % c):
                    continue
                fa._SUB_TILE = c
            rope = fa.rope_rows(*_rope_tables(positions, d, 10000.0), d) if rotary else None

            def one(q, k, v, rope=rope):
                attend = functools.partial(fa.flash_attention, head_dim=d, rope=rope, causal=causal)
                if args.forward_only:
                    return attend(q, k, v)
                loss = lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum()
                return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

            def program(q, k, v):
                # each call reads the one before, so none is folded away
                outs = []
                for _ in range(args.calls):
                    o = one(q, k, v)
                    outs.append(o)
                    first = o if args.forward_only else o[0]
                    q = q + (first[:1, :1, :1] * 0).astype(q.dtype)
                return outs

            us = kernel_us(jax.jit(program), (q, k, v), args.calls)
            if args.check:
                row_rope = rope and fa.Rope(rope.cos[:1], rope.sin[:1], rope.rot)
                us["rel_err_vs_xla"] = _check(
                    functools.partial(one, rope=row_rope), causal, q[:1], k[:1], v[:1],
                    row_rope, d, args,
                )
            row = {
                "shape": name, "qkv": [b, t, hq, hkv, d], "causal": causal,
                "blocks": blocks or "default", "sub_tile": c,
                "rotary_in_kernel": rotary, "heads_a_step": fa.heads_a_step(hq, hkv, d),
                "device_kind": dev.device_kind, "calls": args.calls, "us": us,
            }
            row["plan"] = fa.plan_of(t, d, causal)._asdict()
            line = json.dumps(row)
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()
        if own_sub_tile is not None:
            fa._SUB_TILE = own_sub_tile


if __name__ == "__main__":
    main()
