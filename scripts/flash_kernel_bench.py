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

``--serving`` times a whole prompt's causal attention as a serving prefill
runs it (PR 55): the forward kernel alone over heads ``[1, P, H, D]``, batch 1,
at each block of ``--blocks`` that divides the bucket (``bq,bk`` pairs; with
none, the block ``decode_kernels.prefill_block`` ships), beside the XLA form of
the same attention (``xla_attention``: its scores written out), each as the
device's time a call over every operation of the program:

    python3 scripts/flash_kernel_bench.py --serving --check \
        --blocks "512,512;1024,1024" [--shapes serve-olmoe-fewshot --rows 2560]

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
# name: (query heads, kv heads, head size, the cell's prefill buckets)
SERVING = {
    "serve-olmoe-fewshot": (16, 16, 128, (1536, 2048, 2560, 3072)),
    "serve-glm-flash-agent": (20, 20, 256, (768, 1280, 1792)),  # the rebuilt latent form
    "serve-granite-h-docqa": (32, 8, 128, (512, 1024, 1536, 2048)),
    "serve-zaya1-reason": (8, 2, 128, (512, 1024)),
    "serve-1.7b-chat": (32, 32, 64, (512, 768)),
    "serve-360m-batch": (15, 5, 64, (128,)),
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
        every = 0.0  # all the device's operations: what a form without a kernel is timed by
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    result = ev.name.partition(" = ")[0]  # "%jvp_odtp_flash_fwd_.1"
                    every += ev.duration_ns
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
    out["device_us_a_call"] = round(every / calls / 1e3, 2)
    return out


def serving(args, sink) -> None:
    """``--serving``: one JSON line a (cell's heads, bucket, form)."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.ops import decode_kernels as dk
    from opendiloco_tpu.ops import flash_attention as fa
    from opendiloco_tpu.ops.attention import xla_attention

    names = args.shapes.split(",") if args.shapes else list(SERVING)
    asked = [int(b.split(",")[0]) for b in args.blocks.split(";") if b]
    rows_asked = [int(r) for r in args.rows.split(",") if r]
    for name in names:
        hq, hkv, d, buckets = SERVING[name]
        for rows in rows_asked or buckets:
            keys = jax.random.split(jax.random.key(rows), 3)
            q = jax.random.normal(keys[0], (1, rows, hq, d), jnp.bfloat16)
            k = jax.random.normal(keys[1], (1, rows, hkv, d), jnp.bfloat16)
            v = jax.random.normal(keys[2], (1, rows, hkv, d), jnp.bfloat16)
            shipped = dk.prefill_block(rows)
            blocks = [b for b in dict.fromkeys(asked or [shipped]) if b and rows % b == 0]
            forms = [("xla", 0)] + [("flash", b) for b in blocks]
            ref = None
            for form, block in forms:
                if form == "xla":
                    one = lambda q, k, v: xla_attention(q, k, v, causal=True)
                else:
                    one = lambda q, k, v, b=block: fa.flash_attention_lse(
                        q, k, v, causal=True, block_q=b, block_k=b)[0]

                def program(q, k, v, one=one):
                    outs = []  # each call reads the one before through one element
                    for _ in range(args.calls):
                        o = one(q, k, v)
                        outs.append(o[0, -1, 0, :8])
                        q = q.at[0, 0, 0, 0].add(o[0, 0, 0, 0] * 0)
                    return outs

                us = kernel_us(jax.jit(program), (q, k, v), args.calls)
                row = {
                    "shape": name, "qkv": [1, rows, hq, hkv, d], "form": form, "block": block,
                    "scores_mb": round(hq * rows * rows * 4 / 2**20, 1),
                    "ships": [dk.prefill_form(rows, hq, hkv, d, d), shipped],
                    "device_kind": jax.devices()[0].device_kind, "calls": args.calls, "us": us,
                }
                if args.check:
                    got = jax.jit(one)(q, k, v).astype(jnp.float32)
                    ref = got if ref is None else ref
                    row["rel_err_vs_xla"] = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
                if form == "flash":
                    jaxpr = jax.make_jaxpr(one)(q, k, v)
                    row["equations"] = _equations(jaxpr.jaxpr)
                line = json.dumps(row)
                print(line, flush=True)
                sink.write(line + "\n")
                sink.flush()


def _equations(jaxpr) -> int:
    """The equations of a jaxpr and of every jaxpr inside it: what a program's
    trace costs a process grows with them."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += _equations(inner)
    return total


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
    ap.add_argument("--shapes", default="",
                    help="comma list; empty: both train cells' (--serving: every serve cell's)")
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
    ap.add_argument("--serving", action="store_true",
                    help="a serving prefill's causal attention, the kernel beside the XLA form")
    ap.add_argument("--rows", default="", help="--serving: comma list of buckets; empty: the cells' own")
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
    if args.serving:
        return serving(args, sink)
    own_sub_tile = getattr(fa, "_SUB_TILE", None)
    if args.scale_on_scores:
        fa._scale_on_operand = lambda scale: False
    sub_tiles = [int(c) for c in args.sub_tiles.split(",") if c] or [None]

    for name in (args.shapes or "train-360m-h16,train-1.7b-fsdp4-h8").split(","):
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
