"""On-chip Pallas kernel evidence: parity vs XLA + timings, non-interpret.

Writes KERNEL_EVIDENCE.json at the repo root -- the committed artifact VERDICT
round 2 asked for (in-tree tests run the kernels in interpret mode on CPU;
this is the real-chip record). Each section is independent and the artifact
is rewritten after every section, so a run cut short still leaves the
sections that finished.

Covers the three kernel families (ref counterpart: flash-attn is the
optional-but-benchmarked fast path in the reference's ecosystem,
/root/reference/README.md:41-47):
  - flash attention fwd + bwd (opendiloco_tpu/ops/flash_attention.py)
  - fused lm-head + cross-entropy fwd + bwd (ops/fused_xent.py)
  - ring attention per-chunk path under shard_map (ops/ring_attention.py)
"""

import functools
import json
import os
import sys
import threading
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # runnable from anywhere without an install
    sys.path.insert(0, _ROOT)

_OUT = os.path.join(_ROOT, "KERNEL_EVIDENCE.json")
_DOC = {"sections": {}, "started": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}


def _flush():
    _DOC["updated"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    with open(_OUT, "w") as f:
        json.dump(_DOC, f, indent=1, sort_keys=True)
        f.write("\n")


def _watchdog(seconds: float):
    def fire():
        _DOC["aborted"] = f"watchdog after {seconds}s (accelerator unresponsive)"
        _flush()
        os._exit(4)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def _timeit(fn, *args, iters: int = 10):
    """Median wall time in microseconds (post-warmup, device-synced).

    NOTE: each call pays the host's dispatch+sync cost, which at these
    shapes can exceed the kernel time. Kept only as the fallback when a
    section has no chained variant; prefer _timeit_chained."""
    import jax

    r = fn(*args)
    jax.block_until_ready(r)  # compile + first run
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _timeit_chained(fn, feed, args, n_short: int = 8, n_long: int = 64, reps: int = 3):
    """Per-op device time in microseconds with the host round-trip removed.

    Runs fn n times inside ONE jitted lax.fori_loop, with `feed(out, args)
    -> args` forcing a data dependence between iterations (so XLA cannot
    CSE or parallelize them away), at two chain lengths; the difference
    quotient (t_long - t_short) / (n_long - n_short) cancels the fixed
    dispatch+sync overhead that dominates single-call timings."""
    import jax
    from jax import lax

    def chained(n):
        def body(_, a):
            return feed(fn(*a), a)

        return jax.jit(lambda a: lax.fori_loop(0, n, body, a))

    times = {}
    for n in (n_short, n_long):
        c = chained(n)
        jax.block_until_ready(c(tuple(args)))  # compile + first run
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(c(tuple(args)))
            ts.append(time.perf_counter() - t0)
        times[n] = float(np.median(ts))
    per_op = (times[n_long] - times[n_short]) / (n_long - n_short)
    return float(max(per_op, 0.0) * 1e6)


def _section(name):
    def deco(fn):
        def run():
            t0 = time.time()
            try:
                _DOC["sections"][name] = {"ok": True, **fn()}
            except Exception as e:  # record the failure, keep going
                _DOC["sections"][name] = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            _DOC["sections"][name]["wall_s"] = round(time.time() - t0, 1)
            _flush()

        return run

    return deco


@_section("flash_attention")
def flash_section():
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.ops.attention import xla_attention
    from opendiloco_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    B, T, HQ, HKV, D = 2, 2048, 16, 8, 64
    if _DOC.get("smoke"):
        T = 256
    mk = lambda h, dt: jnp.asarray(rng.normal(size=(B, T, h, D)) * 0.5, dt)

    # Parity oracle, self-calibrating for real MXU hardware: on TPU an f32
    # matmul runs through the MXU's bf16 passes at default precision, so
    # plain XLA attention itself is ~1e-3 off a true-f32 result. Measure the
    # Pallas kernel AND default-precision XLA against a HIGHEST-precision
    # reference and require the kernel to be no worse than XLA (x4 slack).
    # On CPU (smoke) default precision IS f32, xla_err ~ 0, and the bound
    # reduces to the original interpret-mode 2e-3.
    q, k, v = mk(HQ, jnp.float32), mk(HKV, jnp.float32), mk(HKV, jnp.float32)
    with jax.default_matmul_precision("float32"):
        ref = jax.jit(functools.partial(xla_attention, causal=True))(q, k, v)
        ref.block_until_ready()
    xla = jax.jit(functools.partial(xla_attention, causal=True))(q, k, v)
    got = jax.jit(functools.partial(flash_attention, causal=True))(q, k, v)
    xla_fwd_err = float(jnp.max(jnp.abs(xla - ref)))
    fwd_err = float(jnp.max(jnp.abs(got - ref)))
    fwd_tol = max(2e-3, 4.0 * xla_fwd_err)
    assert fwd_err < fwd_tol, f"flash fwd parity: max|err|={fwd_err} tol={fwd_tol} (xla itself {xla_fwd_err})"

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True) ** 2)

    with jax.default_matmul_precision("float32"):
        gr = jax.jit(jax.grad(functools.partial(loss, xla_attention), argnums=(0, 1, 2)))(q, k, v)
        jax.block_until_ready(gr)
    gx = jax.jit(jax.grad(functools.partial(loss, xla_attention), argnums=(0, 1, 2)))(q, k, v)
    gg = jax.jit(jax.grad(functools.partial(loss, flash_attention), argnums=(0, 1, 2)))(q, k, v)
    xla_bwd_err = float(max(jnp.max(jnp.abs(a - b)) for a, b in zip(gr, gx)))
    bwd_err = float(max(jnp.max(jnp.abs(a - b)) for a, b in zip(gr, gg)))
    scale = float(max(jnp.max(jnp.abs(a)) for a in gr))
    bwd_tol = max(2e-2 * max(scale, 1.0), 4.0 * xla_bwd_err)
    assert bwd_err < bwd_tol, f"flash bwd parity: max|err|={bwd_err} tol={bwd_tol} scale={scale}"

    # timings in bf16 (production dtype)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    f_fwd = jax.jit(functools.partial(flash_attention, causal=True))
    x_fwd = jax.jit(functools.partial(xla_attention, causal=True))
    f_bwd = jax.jit(jax.grad(functools.partial(loss, flash_attention), argnums=(0, 1, 2)))
    x_bwd = jax.jit(jax.grad(functools.partial(loss, xla_attention), argnums=(0, 1, 2)))
    return {
        "shape": f"B{B} T{T} Hq{HQ} Hkv{HKV} D{D}",
        "fwd_max_abs_err_f32": fwd_err,
        "bwd_max_abs_err_f32": bwd_err,
        "xla_default_precision_err": {"fwd": xla_fwd_err, "bwd": xla_bwd_err},
        "bf16_us": {
            # fwd chains: feed the output back as q (same [B,T,Hq,D] shape);
            # bwd chains: nudge the inputs by 1e-6*grad -- both force a data
            # dependence so the fori_loop can't be CSE'd or overlapped
            "pallas_fwd": _timeit_chained(
                f_fwd, lambda o, a: (o, a[1], a[2]), (qb, kb, vb)
            ),
            "xla_fwd": _timeit_chained(
                x_fwd, lambda o, a: (o, a[1], a[2]), (qb, kb, vb)
            ),
            "pallas_fwd_bwd": _timeit_chained(
                f_bwd,
                lambda g, a: tuple(x + 1e-6 * gx for x, gx in zip(a, g)),
                (qb, kb, vb),
            ),
            "xla_fwd_bwd": _timeit_chained(
                x_bwd,
                lambda g, a: tuple(x + 1e-6 * gx for x, gx in zip(a, g)),
                (qb, kb, vb),
            ),
        },
        "timing_method": "chained fori_loop difference quotient (dispatch-free)",
    }


@_section("fused_xent")
def xent_section():
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.ops.fused_xent import fused_linear_cross_entropy

    rng = np.random.default_rng(1)
    N, D, V = 4096, 1024, 32000
    if _DOC.get("smoke"):
        N, D, V = 256, 256, 2048
    h32 = jnp.asarray(rng.normal(size=(N, D)) * 0.02, jnp.float32)
    w32 = jnp.asarray(rng.normal(size=(D, V)) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    labels = labels.at[:64].set(-100)  # exercise the ignore path

    def ref_nll(h, w, labels):
        mask = labels != -100
        logits = h @ w
        lp = jax.nn.log_softmax(logits, axis=-1)
        safe = jnp.where(mask, labels, 0)
        nll = -jnp.take_along_axis(lp, safe[:, None], axis=1)[:, 0] * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)

    ref = float(jax.jit(ref_nll)(h32, w32, labels))
    got = float(jax.jit(fused_linear_cross_entropy)(h32, w32, labels))
    fwd_err = abs(got - ref)
    assert fwd_err < 1e-3, f"xent fwd parity: |{got}-{ref}|={fwd_err}"

    gr = jax.jit(jax.grad(ref_nll, argnums=(0, 1)))(h32, w32, labels)
    gg = jax.jit(jax.grad(fused_linear_cross_entropy, argnums=(0, 1)))(h32, w32, labels)
    bwd_err = float(max(jnp.max(jnp.abs(a - b)) for a, b in zip(gr, gg)))
    assert bwd_err < 1e-4, f"xent bwd parity: max|err|={bwd_err}"

    hb, wb = h32.astype(jnp.bfloat16), w32.astype(jnp.bfloat16)
    f_fwd = jax.jit(fused_linear_cross_entropy)
    x_fwd = jax.jit(ref_nll)
    f_bwd = jax.jit(jax.grad(fused_linear_cross_entropy, argnums=(0, 1)))
    x_bwd = jax.jit(jax.grad(ref_nll, argnums=(0, 1)))
    return {
        "shape": f"N{N} D{D} V{V} (pad path: V=32000 -> 2048-blocks)",
        "fwd_abs_err_f32": fwd_err,
        "bwd_max_abs_err_f32": bwd_err,
        "bf16_us": {
            # fwd chains: nudge h by the scalar loss; bwd chains: nudge
            # (h, w) by their grads -- data dependence without changing
            # the op's shape or dtype
            "fused_fwd": _timeit_chained(
                f_fwd,
                lambda o, a: (a[0] + o.astype(a[0].dtype) * 1e-9, a[1], a[2]),
                (hb, wb, labels),
            ),
            "xla_fwd": _timeit_chained(
                x_fwd,
                lambda o, a: (a[0] + o.astype(a[0].dtype) * 1e-9, a[1], a[2]),
                (hb, wb, labels),
            ),
            "fused_fwd_bwd": _timeit_chained(
                f_bwd,
                lambda g, a: (a[0] + 1e-6 * g[0], a[1] + 1e-6 * g[1], a[2]),
                (hb, wb, labels),
            ),
            "xla_fwd_bwd": _timeit_chained(
                x_bwd,
                lambda g, a: (a[0] + 1e-6 * g[0], a[1] + 1e-6 * g[1], a[2]),
                (hb, wb, labels),
            ),
        },
        "timing_method": "chained fori_loop difference quotient (dispatch-free)",
    }


@_section("ring_attention")
def ring_section():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from opendiloco_tpu.ops.attention import xla_attention
    from opendiloco_tpu.ops.ring_attention import ring_attention

    # single real chip: sp=1 ring still runs the per-chunk Pallas kernels
    # on-chip through the shard_map/collective machinery
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("sp",))
    rng = np.random.default_rng(2)
    B, T, HQ, HKV, D = 2, 2048, 16, 8, 64
    if _DOC.get("smoke"):
        T = 256
    q = jnp.asarray(rng.normal(size=(B, T, HQ, D)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, HKV, D)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, HKV, D)) * 0.5, jnp.float32)

    ring = jax.jit(
        jax.shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
        )
    )
    # same self-calibrating oracle as the flash section (MXU default
    # precision makes XLA's own f32 attention ~1e-3 off true f32)
    with jax.default_matmul_precision("float32"):
        ref = jax.jit(functools.partial(xla_attention, causal=True))(q, k, v)
        ref.block_until_ready()
    xla = jax.jit(functools.partial(xla_attention, causal=True))(q, k, v)
    got = ring(q, k, v)
    xla_fwd_err = float(jnp.max(jnp.abs(xla - ref)))
    fwd_err = float(jnp.max(jnp.abs(got - ref)))
    fwd_tol = max(2e-3, 4.0 * xla_fwd_err)
    assert fwd_err < fwd_tol, f"ring fwd parity: max|err|={fwd_err} tol={fwd_tol} (xla itself {xla_fwd_err})"

    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    return {
        "shape": f"B{B} T{T} Hq{HQ} Hkv{HKV} D{D} (sp=1 on one chip)",
        "fwd_max_abs_err_f32": fwd_err,
        "xla_default_precision_err": {"fwd": xla_fwd_err},
        "bf16_us": {
            "ring_fwd": _timeit_chained(
                ring, lambda o, a: (o, a[1], a[2]), (qb, kb, vb)
            )
        },
        "timing_method": "chained fori_loop difference quotient (dispatch-free)",
    }


@_section("decode_kernels")
def decode_section():
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.ops.attention import decode_step_attention
    from opendiloco_tpu.ops.decode_kernels import paged_decode_attention
    from opendiloco_tpu.models.ring_cache import cache_shape

    rng = np.random.default_rng(3)
    S, T, Nh, Nkv, D = 8, 512, 16, 8, 64
    if _DOC.get("smoke"):
        T = 128  # one 128-row tile: the least ring the kernels take
    q1 = jnp.asarray(rng.normal(size=(S, Nh, D)) * 0.5, jnp.float32)
    # a cache of one layer in the cache module's order, and the step's row
    one_layer = cache_shape(1, S, T, Nkv, D)
    ck = jnp.asarray(rng.normal(size=one_layer) * 0.5, jnp.float32)
    cv = jnp.asarray(rng.normal(size=one_layer) * 0.5, jnp.float32)
    k1 = jnp.asarray(rng.normal(size=(S, Nkv, D)) * 0.5, jnp.float32)
    v1 = jnp.asarray(rng.normal(size=(S, Nkv, D)) * 0.5, jnp.float32)

    def xla_step(*args):
        return decode_step_attention(*args, 0)[0]

    def pallas_step(*args):
        # not donated: the call's copy of the one-layer cache is in the time
        return paged_decode_attention(*args, 0)[0]

    # ragged occupancy incl. empty slot and wrapped sliding window
    lens = jnp.asarray(
        rng.integers(0, 2 * T, S).tolist()[: S - 2] + [0, 2 * T], jnp.int32
    )
    out = {"shape": f"S{S} T{T} Hq{Nh} Hkv{Nkv} D{D}"}

    ref = jax.jit(xla_step)(q1, k1, v1, ck, cv, lens)
    got, _, _, stats = paged_decode_attention(
        q1, k1, v1, ck, cv, lens, 0, return_stats=True
    )
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < 2e-6, f"paged decode parity: max|err|={err}"
    # dense equivalent: every (slot, kv head) scoring the whole ring —
    # num_t blocks each, recovered from the wrapped slot's full count
    processed = int(np.asarray(stats).sum())
    dense = int(np.asarray(stats).size) * int(np.max(np.asarray(stats)))
    out["decode_attention"] = {
        "max_abs_err_f32": err,
        "ring_blocks_processed": processed,
        "ring_blocks_dense_equiv": dense,
        "dead_block_skip_fraction": round(1.0 - processed / max(1, dense), 4),
        "pallas_us": _timeit(jax.jit(pallas_step), q1, k1, v1, ck, cv, lens),
        "xla_us": _timeit(jax.jit(xla_step), q1, k1, v1, ck, cv, lens),
    }
    return out


def main():
    global _OUT
    import jax

    if os.environ.get("KERNEL_EVIDENCE_SMOKE"):
        # CPU logic check only: interpret-mode kernels, artifact to /tmp so
        # the committed KERNEL_EVIDENCE.json stays real-chip-only
        jax.config.update("jax_platforms", "cpu")
        import jax.experimental.pallas as pl

        orig = pl.pallas_call
        from opendiloco_tpu.ops import flash_attention as fa
        from opendiloco_tpu.ops import fused_xent as fx

        def patched(*args, **kwargs):
            kwargs["interpret"] = True
            return orig(*args, **kwargs)

        fa.pl.pallas_call = patched
        fx.pl.pallas_call = patched
        _OUT = "/tmp/kernel_evidence_smoke.json"
        _DOC["smoke"] = True

    from opendiloco_tpu.utils.compile_cache import enable_compile_cache
    from opendiloco_tpu.utils.device import device_stamp

    enable_compile_cache()
    wd = _watchdog(float(os.environ.get("KERNEL_EVIDENCE_TIMEOUT", "780")))
    _DOC["device"] = jax.devices()[0].device_kind
    _DOC.update(device_stamp())
    _flush()
    flash_section()
    xent_section()
    ring_section()
    decode_section()
    wd.cancel()
    ok = all(s.get("ok") for s in _DOC["sections"].values())
    _DOC["complete"] = bool(ok)
    _flush()
    print(json.dumps(_DOC["sections"], indent=1, sort_keys=True))
    sys.exit(0 if ok else 5)


if __name__ == "__main__":
    main()
