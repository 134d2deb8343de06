"""Fused lm-head + cross-entropy Pallas kernel.

The single largest HBM cost of the small-model train step is materializing
float32 logits [tokens, vocab] (e.g. 2 GB for 16k tokens x 32k vocab) just to
reduce them to one scalar. This kernel streams vocab tiles of the head
matmul through VMEM with an online log-sum-exp, so the full logits never
touch HBM; the backward pass recomputes tiles and accumulates dh and dW the
same way (FlashAttention-style recompute, applied to the classifier).

Opt-in via TrainerConfig.fused_loss; numerically equivalent to the
logits-materializing path (interpret-mode parity tests).

Shapes: h [N, D] tokens, w [D, V] head, labels [N] int32 (IGNORE=-100).
Returns per-token nll [N] float32 (0 where ignored); mean-reduction happens
in the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

IGNORE = -100


def _pick(n: int, pref: int) -> int:
    for b in (pref, pref // 2, pref // 4, 128):
        if b >= 128 and n % b == 0:
            return b
    return 0


# per-kernel VMEM budget. The default scoped window (~16 MB) fits the
# d=768 kernels but every staged tile scales with d, and at 1b's d=2048
# the dh kernel died allocating its output tile on the VMEM stack —
# caught by the deviceless AOT compile (AOT_ROOFLINE, round 5) before
# any hardware run could. v5e has 128 MB of VMEM; claim most of it (all
# three pallas_calls pass vmem_limit_bytes) and only shrink blocks when
# the estimate below still doesn't fit, so the MXU keeps wide tiles.
_VMEM_BUDGET = 100 * 1024 * 1024


def _vmem_caps(d: int) -> tuple[int, int]:
    """(token-block cap, vocab-block cap) for hidden size ``d``.

    Sized against the dw kernel, the hungriest of the three: double-
    buffered (bn, d) + (d, bv) bf16 operand tiles, f32 (d, bv) scratch
    accumulator + output tile, and f32 (bn, bv) score/dlog tiles. Caps
    halve (powers of two only, so ``min(block, cap)`` keeps divisibility
    into n/v) until that estimate fits _VMEM_BUDGET. d=768 (150m) and
    d=2048 (1b) both keep the full 1024/2048 blocks (~39 MB / ~75 MB);
    d=4096 drops the vocab block to 1024."""

    def dw_bytes(bn: int, bv: int) -> int:
        return 2 * bn * d * 2 + 2 * d * bv * 2 + 2 * d * bv * 4 + 2 * bn * bv * 4

    bn, bv = 1024, 2048
    while bv > 512 and dw_bytes(bn, bv) > _VMEM_BUDGET:
        bv //= 2
    while bn > 128 and dw_bytes(bn, bv) > _VMEM_BUDGET:
        bn //= 2
    return bn, bv


def _mask_pad(s, j: int, block_v: int, true_v: int):
    """-inf out vocab-pad columns (tile j of a padded head)."""
    gcols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(gcols < true_v, s, -1e30)


# ---------------------------------------------------------------------------
# forward: grid (token_blocks, vocab_tiles); scratch carries online stats
# ---------------------------------------------------------------------------


def _fwd_kernel(
    h_ref, w_ref, lbl_ref, nll_ref, lse_ref, m_s, l_s, tgt_s, *, block_v, true_v
):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, -1e30)
        l_s[:] = jnp.zeros_like(l_s)
        tgt_s[:] = jnp.zeros_like(tgt_s)

    # bf16 matmul inputs, f32 accumulation (f32 inputs run the MXU at ~1/8
    # rate on v5e)
    s = jax.lax.dot_general(
        h_ref[:],
        w_ref[:],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_n, block_v]
    if true_v % block_v:  # vocab padded up to tile size
        s = _mask_pad(s, j, block_v, true_v)

    m_prev = m_s[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_s[:] = l_s[:] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=1, keepdims=True
    )
    m_s[:] = m_new

    # gather the target logit if it falls inside this vocab tile
    lbl = lbl_ref[:].reshape(-1, 1)  # [block_n, 1]
    local = lbl - j * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    hit = cols == local  # at most one column matches
    tgt_s[:] = tgt_s[:] + jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _():
        lse = m_s[:] + jnp.log(l_s[:])
        mask = (lbl != IGNORE).astype(jnp.float32)
        nll_ref[:] = ((lse - tgt_s[:]) * mask).reshape(nll_ref.shape)
        lse_ref[:] = lse.reshape(lse_ref.shape)


def _fwd(h, w, labels, block_n, block_v, true_v):
    # per-token vectors travel as [1, N] rows with (1, block_n) blocks: 1-D
    # operands get a global XLA tiling tied to one block size, which breaks
    # when forward and backward kernels pick different token blocks.
    # (The SPMD wrapper's shard_map runs with check_vma=False, so no vma
    # annotations are needed on the out_shapes here.)
    n, d = h.shape
    v = w.shape[1]
    grid = (n // block_n, v // block_v)
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, true_v=true_v),
        name="odtp_fused_xent_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BUDGET,
        ),
    )(h, w, labels.reshape(1, n))
    return nll.reshape(n), lse.reshape(n)


# ---------------------------------------------------------------------------
# backward: two kernels with transposed grids -- dh accumulates over vocab
# tiles (scratch, vocab innermost), dw accumulates over token blocks
# (scratch, tokens innermost); each recomputes its dlog tile from lse
# ---------------------------------------------------------------------------


def _recompute_dlog(h_ref, w_ref, lbl_ref, lse_ref, g_ref, j, *, block_v, true_v):
    """Rebuild the softmax-xent gradient tile dlog = g * (p - onehot)
    (bf16, [block_n, block_v]) from the forward residual lse."""
    hb = h_ref[:]
    wb = w_ref[:]
    s = jax.lax.dot_general(
        hb, wb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    if true_v % block_v:  # padded vocab: pad columns contribute p = 0
        s = _mask_pad(s, j, block_v, true_v)
    p = jnp.exp(s - lse_ref[:].reshape(-1, 1))

    lbl = lbl_ref[:].reshape(-1, 1)
    local = lbl - j * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (cols == local).astype(jnp.float32)

    g = g_ref[:].reshape(-1, 1)  # upstream per-token grad, 0 where ignored
    return (g * (p - onehot)).astype(hb.dtype)


def _dh_kernel(
    h_ref, w_ref, lbl_ref, lse_ref, g_ref, dh_ref, dh_s, *, block_v, true_v
):
    # grid (token_blocks, vocab_tiles): vocab innermost, dh accumulates in
    # scratch over the consecutive j steps and flushes once per token block
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        dh_s[:] = jnp.zeros_like(dh_s)

    dlog = _recompute_dlog(
        h_ref, w_ref, lbl_ref, lse_ref, g_ref, j, block_v=block_v, true_v=true_v
    )
    dh_s[:] = dh_s[:] + jax.lax.dot_general(
        dlog, w_ref[:], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(j == nv - 1)
    def _():
        dh_ref[:] = dh_s[:].astype(dh_ref.dtype)


def _dw_kernel(
    h_ref, w_ref, lbl_ref, lse_ref, g_ref, dw_ref, dw_s, *, block_v, true_v
):
    # grid (vocab_tiles, token_blocks): tokens innermost, dw accumulates in
    # scratch over the consecutive i steps and flushes once per vocab tile.
    # (A single kernel accumulating dw into its output across token blocks
    # would revisit each dw tile on NON-consecutive grid steps, which Pallas
    # output-revisiting does not support -- the write-back clobbers.)
    j = pl.program_id(0)
    i = pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _():
        dw_s[:] = jnp.zeros_like(dw_s)

    dlog = _recompute_dlog(
        h_ref, w_ref, lbl_ref, lse_ref, g_ref, j, block_v=block_v, true_v=true_v
    )
    dw_s[:] = dw_s[:] + jax.lax.dot_general(
        h_ref[:], dlog, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(i == ni - 1)
    def _():
        dw_ref[:] = dw_s[:].astype(dw_ref.dtype)


def _bwd_impl(h, w, labels, lse, g, block_n, block_v, true_v):
    n, d = h.shape
    v = w.shape[1]
    ni, nv = n // block_n, v // block_v
    args = (h, w, labels.reshape(1, n), lse.reshape(1, n), g.reshape(1, n))
    vec_spec_i = pl.BlockSpec((1, block_n), lambda i, j: (0, i))
    vec_spec_j = pl.BlockSpec((1, block_n), lambda j, i: (0, i))
    dh = pl.pallas_call(
        functools.partial(_dh_kernel, block_v=block_v, true_v=true_v),
        name="odtp_fused_xent_dh",
        grid=(ni, nv),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            vec_spec_i,
            vec_spec_i,
            vec_spec_i,
        ],
        # dh in the input dtype (cast happens in-kernel); an f32 output
        # would double its VMEM block for no benefit
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        # both grads vary like the (batch-sharded) rows: dw is each
        # shard's partial sum; shard_map's transpose of the replicated-w
        # in_spec psums the partials outside the kernel
        out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BUDGET,
        ),
    )(*args)
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, block_v=block_v, true_v=true_v),
        name="odtp_fused_xent_dw",
        grid=(nv, ni),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
            vec_spec_j,
            vec_spec_j,
            vec_spec_j,
        ],
        out_specs=pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((d, v), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, block_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BUDGET,
        ),
    )(*args)
    return dh, dw


# ---------------------------------------------------------------------------
# public entry with custom vjp
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_nll(h, w, labels, block_n, block_v, true_v):
    nll, _ = _fwd(h, w, labels, block_n, block_v, true_v)
    return nll


def _fused_fwd(h, w, labels, block_n, block_v, true_v):
    nll, lse = _fwd(h, w, labels, block_n, block_v, true_v)
    return nll, (h, w, labels, lse)


def _fused_bwd(block_n, block_v, true_v, res, g):
    h, w, labels, lse = res
    mask = (labels != IGNORE).astype(jnp.float32)
    # the backward kernels carry the f32 accumulator scratch on top of the
    # forward's tiles; halve the token block (empirically chosen at d=768,
    # kept proportionally across sizes — a halved power-of-two cap always
    # divides the forward's pick)
    bn = min(block_n, max(128, _vmem_caps(h.shape[1])[0] // 2))
    dh, dw = _bwd_impl(h, w, labels, lse, g * mask, bn, block_v, true_v)
    return dh.astype(h.dtype), dw.astype(w.dtype), None


_fused_nll.defvjp(_fused_fwd, _fused_bwd)


def _nll_sum_count(
    h: jax.Array, w: jax.Array, labels: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(sum of nll over non-ignored labels, raw non-ignored count).

    The kernel-dispatch core shared by the mean entry point and the SPMD
    wrapper (which psums sums/counts across batch shards before dividing).
    """
    n, d = h.shape
    v = w.shape[1]
    mask = labels != IGNORE
    count = jnp.sum(mask)
    if d % 128 != 0:
        logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
        lp = jax.nn.log_softmax(logits, axis=-1)
        safe = jnp.where(mask, labels, 0)
        nll = -jnp.take_along_axis(lp, safe[:, None], axis=1)[:, 0] * mask
        return jnp.sum(nll), count
    bn_cap, bv_cap = _vmem_caps(d)
    block_n = _pick(n, bn_cap)
    if block_n == 0:
        # token count doesn't tile (e.g. the causal shift gives B*(T-1));
        # pad rows up to the next 128 multiple with IGNORE labels -- they
        # contribute 0 to nll (masked) and 0 to dh/dw (upstream grad is
        # masked before the kernel)
        n_pad = -(-n // 128) * 128
        h = jnp.pad(h, ((0, n_pad - n), (0, 0)))
        labels = jnp.pad(labels, (0, n_pad - n), constant_values=IGNORE)
        n = n_pad
        block_n = _pick(n, bn_cap)  # nonzero: n is a multiple of 128
    block_v = _pick(v, bv_cap)
    if block_v < 512:
        # pad the head to the smallest wide tile (least dead columns);
        # padded logits are masked to -inf in the kernels (a small pad
        # copy beats 128-wide MXU tiles)
        block_v = min(
            (b for b in (512, 1024, 2048) if b <= bv_cap),
            key=lambda b: -(-v // b) * b,
        )
        v_pad = -(-v // block_v) * block_v
        w_in = jnp.pad(w, ((0, 0), (0, v_pad - v)))
        nll = _fused_nll(h, w_in, labels, block_n, block_v, v)
    else:
        nll = _fused_nll(h, w, labels, block_n, block_v, v)
    return jnp.sum(nll), count


def fused_linear_cross_entropy(
    h: jax.Array, w: jax.Array, labels: jax.Array
) -> jax.Array:
    """Mean nll over non-ignored labels; h [N, D], w [D, V], labels [N].

    Vocabs that don't tile (e.g. Llama's 32000) are zero-padded up to the
    next block_v multiple and masked in-kernel, so the MXU always sees wide
    tiles instead of degrading to 128; token counts that don't tile (the
    causal shift gives B*(T-1) rows) are row-padded with IGNORE labels.
    Falls back to the materializing path only when hidden % 128 != 0.
    """
    s, c = _nll_sum_count(h, w, labels)
    return s / jnp.maximum(c, 1)


def fused_linear_cross_entropy_sharded(
    h: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    *,
    mesh,
    batch_axes: tuple = (),
    tp_axis=None,
) -> jax.Array:
    """SPMD entry for multi-device meshes.

    Mosaic kernels cannot be automatically partitioned (XLA raises at
    compile when a pallas operand has a sharded dim — found by the
    deviceless multichip AOT compile, round 5). The rows of ``h``/
    ``labels`` are sharded over the batch axes, so the kernel runs inside
    a shard_map manual over them: each shard computes its local (nll sum,
    count) and the mean is taken after a psum. ``w`` has no spec entry —
    a tp-sharded head is replicated into the region (the softmax needs
    the full vocab; this is the same gather the auto partitioner emits
    for the unfused path). tp joins the manual set only so that gather is
    explicit rather than an illegal sharded operand."""
    if mesh is None or getattr(mesh, "size", 1) <= 1 or not batch_axes:
        return fused_linear_cross_entropy(h, w, labels)
    P = jax.sharding.PartitionSpec

    def body(hh, ww, ll):
        s, c = _nll_sum_count(hh, ww, ll)
        # psum over the batch shards only: over tp the operands were
        # replicated, so (s, c) are already invariant there. The replicated
        # ww in_spec's TRANSPOSE is a psum, which is exactly the
        # cross-shard aggregation the partial dw needs.
        s = jax.lax.psum(s, tuple(batch_axes))
        c = jax.lax.psum(c, tuple(batch_axes))
        return s / jnp.maximum(c, 1)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(tuple(batch_axes), None), P(), P(tuple(batch_axes))),
        out_specs=P(),
        # ALL mesh axes manual — a partially-manual pallas call still hits
        # the auto partitioner for the remaining axes and XLA refuses; a
        # tp-sharded head replicates into the region (the softmax needs
        # the full vocab; same gather the auto partitioner emits)
        axis_names=set(mesh.axis_names),
        # the vma checker rejects kernel-internal constants mixing with
        # varying refs in interpret mode (fresh jnp.full vs varying block);
        # the cross-shard semantics here are explicit psums, so the check
        # buys nothing
        check_vma=False,
    )
    return fn(h, w, labels)
