"""Ring attention: causal attention over a sequence-parallel mesh axis.

Long-context capability the reference lacks entirely (SURVEY.md §5.7: no
SP/CP anywhere; seq_length is a scalar config, train_fsdp.py:111). On TPU it
is first-class: the sequence dim shards over the "sp" mesh axis, each device
holds one contiguous chunk of q/k/v, and K/V chunks rotate around the ring
via ``jax.lax.ppermute`` while flash-style online-softmax statistics
(m, l, acc) accumulate in float32. Peak memory per device is
O(T/sp * T/sp) per rotation step, never the full [T, T].

GQA is computed grouped: Q is viewed as [B, T, Hkv, G, D] and contracted
against the narrow K/V directly -- K/V are never materialized at q-head
width.

The backward pass is a hand-written VJP (not autodiff through the scan):
the forward saves only (q, k, v, out, lse); the backward re-rotates K/V
around the ring a second time with dK/dV accumulators travelling along, so
no rotation activations are kept live and each chunk's gradient lands back
on its owner after a full revolution. This is the standard flash-attention
backward recurrence (dS = P * (dP - rowsum(dO*O))) in ring form.

Causality falls out of global position masks: a K/V chunk from a later ring
position contributes nothing (its probabilities underflow to exp(-inf)=0),
chunks from earlier positions contribute fully, and the diagonal chunk is
triangle-masked.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from opendiloco_tpu.ops.pallas_util import pick_block as _pick_block

_NEG_INF = float(-1e30)

# mesh registry: the trainer configures this so model code can stay
# mesh-agnostic (set by InnerTrainer when attn_impl == "ring")
_RING_MESH = None
_RING_AXIS = "sp"


def configure_ring(mesh, axis: str = "sp") -> None:
    global _RING_MESH, _RING_AXIS
    _RING_MESH = mesh
    _RING_AXIS = axis


def _grouped(q: jax.Array, hkv: int) -> jax.Array:
    """[B, T, Hq, D] -> [B, T, Hkv, G, D] view for grouped-query attention."""
    b, t, hq, d = q.shape
    return q.reshape(b, t, hkv, hq // hkv, d)


def _scores(qg: jax.Array, k: jax.Array, q_pos, k_pos, *, causal) -> jax.Array:
    """Masked attention logits [B, Hkv, G, Tq, Tk] (float32).

    Matmul operands stay in the input dtype (bf16 in production -- f32
    inputs run the v5e MXU at a fraction of bf16 rate, same discipline as
    the flash kernel); accumulation is f32 via preferred_element_type.
    """
    d = qg.shape[-1]
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk",
        qg,
        k,
        preferred_element_type=jnp.float32,
    ) * (d**-0.5)
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    return s


def _block_attn(qg, k, v, q_pos, k_pos, m, l, acc, *, causal):
    """One online-softmax accumulation step (grouped heads).

    qg: [B, Tq, Hkv, G, D]; k/v: [B, Tk, Hkv, D]; positions are global.
    m/l: [B, Hkv, G, Tq, 1]; acc: [B, Hkv, G, Tq, D] (all float32).
    """
    s = _scores(qg, k, q_pos, k_pos, causal=causal)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.einsum(
        "bhgqk,bkhd->bhgqd",
        p.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _ring_vma(axis_name: str, ref) -> frozenset:
    """Varying-manual-axes set for ring internals: the ring axis plus any
    OUTER manual axes ``ref`` already varies over. Standalone sp meshes get
    {sp}; nested inside the pp pipeline's partial-manual region the inputs
    are also pp-varying, and fresh scan carriers / kernel outputs must
    carry the full type from step 0 or the scan's carry types mismatch."""
    return jax.typeof(ref).vma | {axis_name}


def _ring_forward(q, k, v, axis_name, causal):
    """-> (out [B, Tl, Hq, D], lse [B, Hkv, G, Tq, 1] float32)."""
    b, tl, hq, d = q.shape
    hkv = k.shape[2]
    qg = _grouped(q, hkv)

    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    q_pos = idx * tl + jnp.arange(tl, dtype=jnp.int32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        k_cur, v_cur, m, l, acc = carry
        src = (idx - i) % n  # whose chunk we hold at this rotation
        k_pos = src * tl + jnp.arange(tl, dtype=jnp.int32)
        m, l, acc = _block_attn(
            qg, k_cur, v_cur, q_pos, k_pos, m, l, acc, causal=causal
        )
        # rotate for the next step (result intentionally unused on the
        # final iteration -- K/V are simply back at their owners)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), None

    g = hq // hkv
    m0 = jnp.full((b, hkv, g, tl, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, tl, 1), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, tl, d), jnp.float32)
    # stats become device-varying after the first accumulation step; the scan
    # carry must have that type from the start (including any outer manual
    # axes when nested in the pp pipeline)
    m0, l0, acc0 = jax.lax.pcast(
        (m0, l0, acc0), tuple(sorted(_ring_vma(axis_name, q))), to="varying"
    )
    (_, _, m, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(n), length=n
    )

    l_safe = jnp.where(l == 0, 1.0, l)
    lse = m + jnp.log(l_safe)
    out = acc / l_safe  # [B, Hkv, G, Tq, D]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, tl, hq, d).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Must run inside shard_map with the sequence dim sharded on axis_name.

    q/k/v: local chunks [B, T_local, Hq|Hkv, D] -> out [B, T_local, Hq, D].
    """
    out, _ = _ring_forward(q, k, v, axis_name, causal)
    return out


def _ring_fwd(q, k, v, axis_name, causal):
    out, lse = _ring_forward(q, k, v, axis_name, causal)
    # tag residuals so the remat policies (llama._maybe_remat) save them --
    # otherwise the backward pass replays the whole ring forward, ppermutes
    # included
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, res, dout):
    """Flash backward in ring form: dK/dV accumulators rotate WITH their K/V
    chunks, so after a full revolution each chunk's gradient is home."""
    q, k, v, out, lse = res
    b, tl, hq, d = q.shape
    hkv = k.shape[2]
    scale = d**-0.5

    qg = _grouped(q, hkv)
    dog = _grouped(dout, hkv)
    # D_i = rowsum(dO * O): [B, Hkv, G, Tq, 1] -- elementwise, keep f32
    D = jnp.sum(
        _grouped(dout.astype(jnp.float32), hkv)
        * _grouped(out.astype(jnp.float32), hkv),
        axis=-1,
    ).transpose(0, 2, 3, 1)[..., None]

    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    q_pos = idx * tl + jnp.arange(tl, dtype=jnp.int32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        k_cur, v_cur, dk_cur, dv_cur, dq = carry
        src = (idx - i) % n
        k_pos = src * tl + jnp.arange(tl, dtype=jnp.int32)
        s = _scores(qg, k_cur, q_pos, k_pos, causal=causal)
        p = jnp.exp(s - lse)  # masked entries underflow to exactly 0
        dv_cur = dv_cur + jnp.einsum(
            "bhgqk,bqhgd->bkhd",
            p.astype(dout.dtype),
            dog,
            preferred_element_type=jnp.float32,
        )
        dp = jnp.einsum(
            "bqhgd,bkhd->bhgqk",
            dog,
            v_cur,
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - D)
        dq = dq + scale * jnp.einsum(
            "bhgqk,bkhd->bqhgd",
            ds.astype(k_cur.dtype),
            k_cur,
            preferred_element_type=jnp.float32,
        )
        dk_cur = dk_cur + scale * jnp.einsum(
            "bhgqk,bqhgd->bkhd",
            ds.astype(qg.dtype),
            qg,
            preferred_element_type=jnp.float32,
        )
        rotated = [
            jax.lax.ppermute(x, axis_name, perm)
            for x in (k_cur, v_cur, dk_cur, dv_cur)
        ]
        return (*rotated, dq), None

    dk0 = jnp.zeros((b, tl, hkv, d), jnp.float32)
    dv0 = jnp.zeros_like(dk0)
    dq0 = jnp.zeros((b, tl, hkv, hq // hkv, d), jnp.float32)
    dk0, dv0, dq0 = jax.lax.pcast(
        (dk0, dv0, dq0), tuple(sorted(_ring_vma(axis_name, q))), to="varying"
    )
    (_, _, dk, dv, dq), _ = jax.lax.scan(
        step, (k, v, dk0, dv0, dq0), jnp.arange(n), length=n
    )
    # n rotations = full revolution: dk/dv are back at their owners
    dq = dq.reshape(b, tl, hq, d).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(_ring_fwd, _ring_bwd)


# ---------------------------------------------------------------------------
# flash-chunk ring: per-rotation block math runs the Pallas flash kernels
# ---------------------------------------------------------------------------
#
# Same ring schedule as above, but each rotation step processes its K/V chunk
# with the flash-attention Pallas kernels (ops/flash_attention.py) instead of
# XLA einsums: the [Tl, Tl] score matrix never reaches HBM and the per-chunk
# softmax runs fused in VMEM. Per-chunk (out, lse) pairs merge with the
# standard log-sum-exp recurrence, which is exactly the online-softmax merge
# the einsum path carries, so results are identical up to rounding. The
# rotation schedule is causal-aware: step 0 is the diagonal chunk (causal
# flash), later steps run the unmasked kernel only when the held chunk is
# from an earlier ring position (lax.cond skips future chunks).


def _ring_flash_forward(q, k, v, axis_name, block):
    """q [B,Tl,Hq,D], k/v [B,Tl,Hkv,D] -> (out [B,Tl,Hq,D], lse [B,Hq,1,Tl]).
    The kernels take the chunks as rows [B,Tl,H*D]."""
    from opendiloco_tpu.ops.flash_attention import _fwd, _rows

    b, tl, hq, d = q.shape
    qR, kR, vR = _rows(q), _rows(k), _rows(v)
    vma = _ring_vma(axis_name, q)
    fwd = functools.partial(_fwd, d=d, block_q=block, block_k=block, vma=vma)

    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # step 0: own (diagonal) chunk, standard causal flash -- guarantees a
    # finite lse for every query row before any merge
    o, lse = fwd(qR, kR, vR, causal=True)
    o = o.astype(jnp.float32).reshape(b, tl, hq, d)

    def step(carry, i):
        k_c, v_c, o, lse = carry
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        src = (idx - i) % n  # ring position of the chunk we now hold

        def live(ops):
            kk, vv = ops
            oi, lsei = fwd(qR, kk, vv, causal=False)
            return oi.astype(jnp.float32).reshape(b, tl, hq, d), lsei

        def dead(ops):
            # future chunk: contributes nothing (lse=-inf merges to a no-op)
            return jnp.zeros_like(o), jnp.full_like(lse, _NEG_INF)

        oi, lsei = jax.lax.cond(src < idx, live, dead, (k_c, v_c))
        lse_new = jnp.logaddexp(lse, lsei)
        # weights are [B,Hq,1,Tl]; as [B,Tl,Hq,1] they scale the outputs' heads
        w = jnp.exp(lse - lse_new).transpose(0, 3, 1, 2)
        wi = jnp.exp(lsei - lse_new).transpose(0, 3, 1, 2)
        o = o * w + oi * wi
        return (k_c, v_c, o, lse_new), None

    (_, _, o, lse), _ = jax.lax.scan(step, (kR, vR, o, lse), jnp.arange(1, n))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_flash_attention(q, k, v, axis_name, block):
    """Causal ring attention with Pallas flash per-chunk kernels.

    Must run inside shard_map with the sequence dim sharded on axis_name;
    Tl must tile by ``block`` (the caller gates on this).
    """
    out, _ = _ring_flash_forward(q, k, v, axis_name, block)
    return out


def _ring_flash_fwd(q, k, v, axis_name, block):
    out, lse = _ring_flash_forward(q, k, v, axis_name, block)
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, block, res, dout):
    """Flash backward per chunk with the global lse; dK/dV accumulators
    (f32) rotate with their chunks, one extra rotation brings them home."""
    from opendiloco_tpu.ops.flash_attention import _bwd_impl, _delta, _rows

    q, k, v, out, lse = res
    d = q.shape[-1]
    qR, kR, vR, oR, doR = (_rows(x) for x in (q, k, v, out, dout))
    delta = _delta(doR, oR, d)

    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    bwd = functools.partial(
        _bwd_impl, d=d, block_q=block, block_k=block, grad_dtype=jnp.float32,
        vma=_ring_vma(axis_name, q),
    )
    dq, dk, dv = bwd(qR, kR, vR, None, doR, lse, delta, causal=True)

    def step(carry, i):
        k_c, v_c, dk, dv, dq = carry
        k_c, v_c, dk, dv = (
            jax.lax.ppermute(x, axis_name, perm) for x in (k_c, v_c, dk, dv)
        )
        src = (idx - i) % n

        def live(ops):
            kk, vv = ops
            return bwd(qR, kk, vv, None, doR, lse, delta, causal=False)

        def dead(ops):
            return jnp.zeros_like(dq), jnp.zeros_like(dk), jnp.zeros_like(dv)

        dqi, dki, dvi = jax.lax.cond(src < idx, live, dead, (k_c, v_c))
        return (k_c, v_c, dk + dki, dv + dvi, dq + dqi), None

    (_, _, dk, dv, dq), _ = jax.lax.scan(
        step, (kR, vR, dk, dv, dq), jnp.arange(1, n)
    )
    # n-1 in-scan rotations + this one = full revolution: grads are home
    dk = jax.lax.ppermute(dk, axis_name, perm)
    dv = jax.lax.ppermute(dv, axis_name, perm)
    return (
        dq.astype(q.dtype).reshape(q.shape), dk.astype(k.dtype).reshape(k.shape),
        dv.astype(v.dtype).reshape(v.shape),
    )


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _flash_chunk_block(mesh, axis: str, q, causal: bool, local: bool = False) -> int:
    """Block size for the flash-chunk ring path, or 0 for the einsum path.

    Flash chunks need: causal attention, a TPU mesh (or the
    OPENDILOCO_TPU_RING_FLASH=1 override for interpret-mode tests), a local
    chunk length that tiles by 128, and a lane-aligned head dim. ``local``:
    q is already the per-device chunk (direct-call path inside an
    already-manual region) rather than the global-view array.
    """
    if not causal:
        return 0
    env = os.environ.get("OPENDILOCO_TPU_RING_FLASH", "").strip().lower()
    if env in ("0", "false", "no", "off"):
        return 0
    if env not in ("1", "true", "yes", "on"):
        # unset (or unrecognized): the Pallas path is TPU-only
        dev = mesh.devices.flat[0]
        if "tpu" not in getattr(dev, "device_kind", "").lower():
            return 0
    n = mesh.shape[axis]
    tl = q.shape[1] // n if not local else q.shape[1]
    if q.shape[-1] % 8:
        return 0
    return _pick_block(tl, 1024)


def ring_attention_auto(
    q: jax.Array, k: jax.Array, v: jax.Array, *, mesh=None, axis: Optional[str] = None
) -> jax.Array:
    """Wrap ring_attention in a shard_map over the mesh's sp axis.

    Callable from inside the (jit-compiled) model forward: batch/head dims
    stay auto-sharded, only the sequence axis is manual. Pass the mesh
    explicitly (the trainer threads its plan.mesh through forward); the
    module registry is only a fallback for direct/experimental callers.
    """
    mesh = mesh if mesh is not None else _RING_MESH
    axis = axis or _RING_AXIS
    if mesh is None:
        raise RuntimeError(
            "ring attention needs a mesh: pass mesh= or call configure_ring(mesh)"
        )
    P = jax.sharding.PartitionSpec
    spec = P(None, axis, None, None)
    # block-size/device decisions read the CONCRETE mesh; the shard_map
    # itself must use the tracing context's mesh when we are already inside
    # another partial-manual region (the pp pipeline): there the context is
    # an AbstractMesh with the outer axes Manual, and a concrete mesh would
    # be rejected. Nesting over a disjoint manual axis set is supported --
    # this is what composes sp ring attention with pipeline stages.
    ctx = jax.sharding.get_abstract_mesh()
    inside_manual = (
        dict(zip(ctx.axis_names, ctx.axis_types)).get(axis)
        == jax.sharding.AxisType.Manual
    )
    block = _flash_chunk_block(mesh, axis, q, causal=True, local=inside_manual)
    if block:
        body = lambda q, k, v: ring_flash_attention(q, k, v, axis, block)
    else:
        # positional args: custom_vjp nondiff_argnums are position-based
        body = lambda q, k, v: ring_attention(q, k, v, axis, True)
    if inside_manual:
        # already inside a manual region over the ring axis (the sp+pp
        # pipeline binds both axes manual): q/k/v are the local chunks,
        # so run the ring body directly — a nested shard_map here would
        # lower in the forward but has no jvp lowering (Shardy rejects
        # re-binding the outer axis; GSPMD check-fails)
        return body(q, k, v)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names={axis},
    )
    return fn(q, k, v)
