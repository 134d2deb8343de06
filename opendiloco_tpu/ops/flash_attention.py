"""Pallas TPU flash attention (forward + backward), causal, GQA-aware.

TPU-native replacement for the reference's optional FlashAttention-2 CUDA
kernels (README.md:41-47, train_fsdp.py:107). FlashAttention-2-style online
softmax: never materializes the [T, T] score matrix; scores and softmax
statistics accumulate in float32 on the MXU/VPU while q/k/v stream through
VMEM tiles.

Layout: grid (batch, q-head, q-block, k-block) with the k-block dimension
sequential ("arbitrary") -- K/V stream through VMEM one [block_k, d] tile
per step while the online-softmax state (m, l, acc) persists in VMEM
scratch across k-steps. Per-step VMEM is O(block_q*d + block_k*d),
independent of T, so sequence length is bounded by HBM, not VMEM. GQA is
handled in the BlockSpec index maps (q-head h reads kv-head h // rep) --
KV is never materialized at q-head width.

Causal blocks above the diagonal are skipped with pl.when, and their
BlockSpec index maps clamp to the last needed tile so the revisited block
index elides the DMA too -- a skipped step costs neither compute nor HBM
traffic, only a grid step.

Backward follows the standard FA2 recompute scheme: delta = rowsum(dO * O),
one kernel for dq (streaming k blocks), one for dk/dv (streaming q blocks,
accumulating over the rep q-heads of each kv head).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from opendiloco_tpu.ops.pallas_util import (
    NEG_INF as _NEG_INF,
    pick_block as _pick_block,
)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, num_k: int
):
    # q_ref/o_ref: [block_q, d]; k_ref/v_ref: [block_k, d] (one tile per step)
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros((block_q, 1), jnp.float32)
        acc_scr[:] = jnp.zeros((block_q, d), jnp.float32)

    # causal: tiles fully above the diagonal contribute nothing
    diag_ok = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(jnp.logical_or(not causal, diag_ok))
    def _step():
        # matmul inputs stay in bf16 (f32 inputs run the MXU at ~1/8 rate on
        # v5e); accumulation and softmax statistics are f32
        q = q_ref[:]
        k_blk = k_ref[:]
        v_blk = v_ref[:]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev, l_prev, acc = m_scr[:], l_scr[:], acc_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype),
            v_blk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_k - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = (m_scr[:] + jnp.log(l_safe)).reshape(1, block_q)


def _fwd(q, k, v, *, block_q: int, block_k: int, causal: bool, vma=None,
         interpret: bool = False):
    """q: [B, Hq, T, D]; k/v: [B, Hkv, T, D] -> (out [B, Hq, T, D], lse [B, Hq, 1, T]).

    ``vma``: varying-manual-axes annotation for the outputs, required when
    called inside a shard_map manual region (the ring-attention chunks).
    When unset it is derived from q so the kernel types correctly in ANY
    manual region (e.g. flash_attention_sharded's batch/tp shard_map).
    """
    vma = jax.typeof(q).vma if vma is None else vma
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    scale = d**-0.5
    num_k = t // block_k

    if causal:
        # clamp skipped above-diagonal steps to the last needed tile: an
        # unchanged block index re-uses the resident copy (no DMA)
        def kv_map(bi, hi, qi, ki):
            last = (qi * block_q + block_q - 1) // block_k
            return (bi, hi // rep, jnp.minimum(ki, last), 0)
    else:
        def kv_map(bi, hi, qi, ki):
            return (bi, hi // rep, ki, 0)

    grid = (b, hq, t // block_q, num_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, num_k=num_k),
        name="odtp_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec((None, None, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b, hq, 1, t), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out, lse


def flash_attention_lse(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    block_q: int = 1024, block_k: int = 1024, interpret: bool = False,
):
    """The forward kernel alone, with the softmax's log-sum-exp beside the
    output, for a caller that merges this attention with another under one
    softmax (a serving prefill; no gradient is defined): q [B, T, H, D], k and
    v [B, T, Hkv, D] -> (out [B, T, H, D], lse [B, T, H] float32, of the scaled
    scores), or None where the kernel does not tile the shape."""
    t, d = q.shape[1], q.shape[-1]
    block_q, block_k = _pick_block(t, block_q), _pick_block(t, block_k)
    if block_q == 0 or block_k == 0 or d % 8 != 0:
        return None
    out, lse = _fwd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        block_q=block_q, block_k=block_k, causal=causal, interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3), lse[:, :, 0].transpose(0, 2, 1)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, causal, num_k
):
    # q/do/dq: [block_q, d]; k/v: [block_k, d] per step; lse/delta: [1, block_q]
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros((block_q, d), jnp.float32)

    diag_ok = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(jnp.logical_or(not causal, diag_ok))
    def _step():
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:].reshape(block_q, 1)
        delta = delta_ref[:].reshape(block_q, 1)
        k_blk = k_ref[:]
        v_blk = v_ref[:]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta)).astype(k_blk.dtype)
        dq_scr[:] = dq_scr[:] + scale * jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, rep, num_q
):
    # grid point: (batch, kv-head, k-block, rep*q-block). q/do: [1, block_q, d]
    # per step; k/v/dk/dv: [block_k, d]; lse/delta: [1, block_q]
    block_k, d = k_ref.shape
    block_q = q_ref.shape[1]
    ki, step = pl.program_id(2), pl.program_id(3)
    qj = step % num_q  # q-block index within a head

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_scr[:] = jnp.zeros((block_k, d), jnp.float32)

    # causal: only q blocks at or after this k block contribute
    diag_ok = (qj * block_q + block_q - 1) >= (ki * block_k)

    @pl.when(jnp.logical_or(not causal, diag_ok))
    def _step():
        k_blk = k_ref[:]
        v_blk = v_ref[:]
        q_blk = q_ref[0]
        do_blk = do_ref[0]
        lse_blk = lse_ref[:].reshape(block_q, 1)
        delta_blk = delta_ref[:].reshape(block_q, 1)
        s = scale * jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            q_pos = qj * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_blk)
        pb = p.astype(do_blk.dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pb, do_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta_blk)).astype(q_blk.dtype)
        dk_scr[:] = dk_scr[:] + scale * jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(step == rep * num_q - 1)
    def _finish():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _delta(dout, out):
    """delta = rowsum(dO * O), f32: [B, Hq, T, D] -> [B, Hq, 1, T]."""
    b, hq, t, _ = out.shape
    return jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(b, hq, 1, t)


def _bwd(block_q, block_k, causal, res, dout):
    q, k, v, out, lse = res
    return _bwd_impl(
        q, k, v, dout, lse, _delta(dout, out),
        block_q=block_q, block_k=block_k, causal=causal,
    )


def _bwd_impl(
    q, k, v, dout, lse, delta, *, block_q, block_k, causal, grad_dtype=None,
    vma=None,
):
    """Backward kernels with delta precomputed. ``grad_dtype`` overrides the
    output dtype and ``vma`` annotates varying manual axes (both used by the
    ring-attention chunk path, which accumulates f32 inside shard_map);
    an unset vma is derived from q (see _fwd)."""
    vma = jax.typeof(q).vma if vma is None else vma
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    scale = d**-0.5
    num_k = t // block_k
    num_q = t // block_q

    if causal:
        def kv_map(bi, hi, qi, ki):
            last = (qi * block_q + block_q - 1) // block_k
            return (bi, hi // rep, jnp.minimum(ki, last), 0)
    else:
        def kv_map(bi, hi, qi, ki):
            return (bi, hi // rep, ki, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, num_k=num_k),
        name="odtp_flash_dq",
        grid=(b, hq, num_q, num_k),
        in_specs=[
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, grad_dtype or q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v, dout, lse, delta)

    # dk/dv: group q by kv head: [b, hkv, rep, t, d]; the sequential grid
    # dim walks (rep, q-block) in row-major order, streaming one q tile per
    # step while dk/dv accumulate in scratch
    q_g = q.reshape(b, hkv, rep, t, d)
    do_g = dout.reshape(b, hkv, rep, t, d)
    lse_g = lse.reshape(b, hkv, rep, 1, t)
    delta_g = delta.reshape(b, hkv, rep, 1, t)

    def _qj(ki, st):
        qj = st % num_q
        if causal:  # clamp skipped below-diagonal q tiles (DMA elision)
            qj = jnp.maximum(qj, (ki * block_k) // block_q)
        return qj

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, rep=rep, num_q=num_q
        ),
        name="odtp_flash_dkv",
        grid=(b, hkv, num_k, rep * num_q),
        in_specs=[
            pl.BlockSpec(
                (None, None, 1, block_q, d),
                lambda bi, hi, ki, st: (bi, hi, st // num_q, _qj(ki, st), 0),
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, ki, st: (bi, hi, ki, 0)
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, ki, st: (bi, hi, ki, 0)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q, d),
                lambda bi, hi, ki, st: (bi, hi, st // num_q, _qj(ki, st), 0),
            ),
            pl.BlockSpec(
                (None, None, 1, 1, block_q),
                lambda bi, hi, ki, st: (bi, hi, st // num_q, 0, _qj(ki, st)),
            ),
            pl.BlockSpec(
                (None, None, 1, 1, block_q),
                lambda bi, hi, ki, st: (bi, hi, st // num_q, 0, _qj(ki, st)),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, ki, st: (bi, hi, ki, 0)
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, ki, st: (bi, hi, ki, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, grad_dtype or k.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, grad_dtype or v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(q_g, k, v, do_g, lse_g, delta_g)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, block_q, block_k, causal):
    out, _ = _fwd(q, k, v, block_q=block_q, block_k=block_k, causal=causal)
    return out


def _flash_fwd(q, k, v, block_q, block_k, causal):
    out, lse = _fwd(q, k, v, block_q=block_q, block_k=block_k, causal=causal)
    # tag the kernel outputs so the remat policies (llama._maybe_remat) can
    # save them -- without these names the backward pass reruns the whole
    # forward kernel just to rebuild its residuals. ``out`` is tagged as
    # [B, T, H * D], the form the output projection reads: kept in the
    # kernel's layout, a head of 64 fills half of the chip's 128 lanes and
    # a saved copy takes twice its bytes
    b, h, t, d = out.shape
    kept = checkpoint_name(out.transpose(0, 2, 1, 3).reshape(b, t, h * d), "attn_out")
    out = kept.reshape(b, t, h, d).transpose(0, 2, 1, 3)
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """[B, T, H, D] attention via the Pallas kernel; falls back to XLA for
    shapes the kernel doesn't tile (T not a multiple of 128).

    Blocks default large (1024x1024, on-chip-swept): per-grid-step fixed cost
    dominates at small tiles on TPU, and VMEM per step is only O(block*d) +
    the [bq, bk] f32 score tile, so these fit VMEM comfortably."""
    b, t, hq, d = q.shape
    env = os.environ.get("OPENDILOCO_TPU_FLASH_BLOCKS")  # tuning: "bq,bk"
    if env:
        try:
            eq, ek = (int(x) for x in env.split(","))
        except ValueError:
            raise ValueError(
                f"OPENDILOCO_TPU_FLASH_BLOCKS={env!r}: expected 'block_q,block_k'"
            ) from None
        if eq % 128 or ek % 128:
            raise ValueError(
                f"OPENDILOCO_TPU_FLASH_BLOCKS={env!r}: blocks must be "
                "multiples of 128 (TPU lane tiling)"
            )
        block_q, block_k = eq, ek
    block_q = _pick_block(t, block_q)
    block_k = _pick_block(t, block_k)
    if block_q == 0 or block_k == 0 or d % 8 != 0:
        from opendiloco_tpu.ops.attention import xla_attention

        return xla_attention(q, k, v, causal=causal)
    # kernel layout is [B, H, T, D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, block_q, block_k, causal)
    return out.transpose(0, 2, 1, 3)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    batch_axes: tuple = (),
    tp_axis=None,
    causal: bool = True,
) -> jax.Array:
    """SPMD entry for multi-device meshes.

    Mosaic kernels cannot be automatically partitioned — XLA raises at
    compile the moment a pallas operand has a sharded dimension (found by
    the deviceless multichip AOT compile, round 5; a single-chip mesh
    never hits it). Attention is independent per (batch row, head), so
    the fix is a shard_map manual over exactly the axes the activations
    are sharded on: the batch axes always, and tp on the head dims when
    it divides BOTH q and kv head counts (shards then keep whole GQA
    groups, so the kernel's local group arithmetic is unchanged). A
    non-dividing tp head dim is instead replicated into the region (tp
    is in the manual set with no spec entry = all-gather), which is the
    same gather the auto partitioner would emit.

    Do NOT call inside another manual region (the pp pipeline): nested
    shard_map has no jvp lowering — there the pipeline's in_specs gather
    the batch, operands arrive replicated, and the plain kernel compiles.
    """
    if mesh is None or getattr(mesh, "size", 1) <= 1:
        return flash_attention(q, k, v, causal=causal)
    P = jax.sharding.PartitionSpec
    hq, hkv = q.shape[2], k.shape[2]
    head = None
    if tp_axis is not None and mesh.shape[tp_axis] > 1:
        n_tp = mesh.shape[tp_axis]
        if hq % n_tp == 0 and hkv % n_tp == 0:
            head = tp_axis
    spec = P(tuple(batch_axes) or None, None, head, None)
    fn = jax.shard_map(
        lambda a, b, c: flash_attention(a, b, c, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # ALL mesh axes manual: a partially-manual pallas call still goes
        # through the auto partitioner for the remaining axes and XLA
        # refuses; axes outside the spec replicate into the region (the
        # same gather auto partitioning would emit)
        axis_names=set(mesh.axis_names),
    )
    return fn(q, k, v)
