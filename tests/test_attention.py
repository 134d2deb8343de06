"""Attention kernel tests: flash (interpret mode) and ring (CPU mesh)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from opendiloco_tpu.ops.attention import xla_attention


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    B, T, H, HKV, D = 2, 256, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
    return q, k, v


@pytest.fixture
def interpret_pallas(monkeypatch):
    """Run pallas kernels in interpreter mode (no TPU in CI)."""
    import jax.experimental.pallas as pl

    orig = pl.pallas_call

    def patched(*args, **kwargs):
        kwargs["interpret"] = True
        return orig(*args, **kwargs)

    from opendiloco_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa.pl, "pallas_call", patched)
    return patched


# the cases the kernels' tile walk distinguishes (flash_attention._tiles):
# (batch, seq, heads, kv heads, head size, causal, OPENDILOCO_TPU_FLASH_BLOCKS).
# The compute sub-tile is 128 rows, so a 256-row block walks 2 x 2 sub-tiles
WALK_CASES = {
    "one-diagonal-tile": (2, 256, 4, 2, 64, True, None),
    "diagonal-and-full-tiles-2x-rep3": (1, 512, 3, 1, 64, True, "256,256"),
    "diagonal-and-full-tiles-4x-rep1": (1, 1024, 2, 2, 64, True, "256,256"),
    "block-is-one-sub-tile": (1, 256, 2, 1, 64, True, "128,128"),
    "d128-scale-on-the-scores": (1, 512, 2, 1, 128, True, "256,256"),
    "full-attention": (1, 512, 2, 1, 64, False, "256,256"),
    "unequal-blocks-q-under-k": (1, 512, 2, 1, 64, True, "128,256"),
    "unequal-blocks-q-over-k": (1, 512, 2, 1, 64, True, "256,128"),
    "block-of-five-sub-tiles": (1, 1280, 1, 1, 64, True, "640,640"),  # no multiple of 512
}


@pytest.fixture(params=list(WALK_CASES))
def walk_case(request, monkeypatch):
    """-> (q, k, v, causal) of one of ``WALK_CASES``, its blocks in the
    environment."""
    b, t, h, hkv, d, causal, blocks = WALK_CASES[request.param]
    if blocks:
        monkeypatch.setenv("OPENDILOCO_TPU_FLASH_BLOCKS", blocks)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    return q, k, v, causal


def test_flash_forward_matches_xla(walk_case, interpret_pallas):
    from opendiloco_tpu.ops.flash_attention import flash_attention

    q, k, v, causal = walk_case
    ref = xla_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def _assert_grads_close(got, ref, atol):
    for a, b in zip(ref, got):
        scale = np.abs(np.asarray(a)).max()
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=atol * max(scale, 1.0)
        )


def test_flash_grads_match_xla(walk_case, interpret_pallas):
    from opendiloco_tpu.ops.flash_attention import flash_attention

    q, k, v, causal = walk_case

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=causal) ** 2)

    gr = jax.grad(functools.partial(loss, xla_attention), argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(functools.partial(loss, flash_attention), argnums=(0, 1, 2))(
        q, k, v
    )
    _assert_grads_close(gg, gr, 2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_float32_grads_as_the_ring_asks(qkv, interpret_pallas, causal):
    """``_bwd_impl(grad_dtype=float32)`` over bf16 operands, the ring's
    chunk call: float32 gradients that match the bf16 ones to their
    rounding, through the walk (the diagonal chunk) and without it."""
    from opendiloco_tpu.ops import flash_attention as fa

    d = qkv[0].shape[-1]
    qR, kR, vR = (fa._rows(x.astype(jnp.bfloat16)) for x in qkv)
    kwargs = dict(d=d, block_q=256, block_k=256, causal=causal)
    out, lse = fa._fwd(qR, kR, vR, **kwargs)
    dout = jnp.ones_like(out)
    args = (qR, kR, vR, None, dout, lse, fa._delta(dout, out, d))
    wide = fa._bwd_impl(*args, grad_dtype=jnp.float32, **kwargs)
    narrow = fa._bwd_impl(*args, **kwargs)
    for w, n in zip(wide, narrow):
        assert w.dtype == jnp.float32 and n.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(w.astype(jnp.bfloat16)), np.asarray(n))


# the kernels' own interface (PR 52): rows [B, T, H * D] as the projections
# leave them, unrotated, with the rotary tables. (query heads, KV heads, head
# size, rotated lanes): the two train cells', the 1b's groups of eight, a head
# of 128, and a head half of whose lanes turn
ROWS_CASES = {
    "15-5-64": (15, 5, 64, 64),
    "32-32-64": (32, 32, 64, 64),
    "32-4-64": (32, 4, 64, 64),
    "16-16-128": (16, 16, 128, 128),
    "4-2-64-half-rotated": (4, 2, 64, 32),
}


def _rows_case(heads, b=2, t=256):
    """-> (q, k, v rows float32, the model's (cos, sin) tables for positions
    that differ by batch row, a configuration with the case's heads)."""
    from opendiloco_tpu.models.llama import LlamaConfig, _rope_tables

    hq, hkv, d, rot = ROWS_CASES[heads]
    cfg = LlamaConfig(
        hidden_size=hq * d, num_attention_heads=hq, num_key_value_heads=hkv,
        head_dim=d, partial_rotary_factor=rot / d,
    )
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, t, h * d)), jnp.float32) for h in (hq, hkv, hkv)
    )
    positions = jnp.arange(t, dtype=jnp.int32)[None] + 7 * jnp.arange(b, dtype=jnp.int32)[:, None]
    return q, k, v, _rope_tables(positions, rot, cfg.rope_theta), cfg


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("heads", list(ROWS_CASES))
def test_flash_rows_match_xla_over_rotated_heads(interpret_pallas, monkeypatch, heads, causal):
    """Forward and the three gradients of ``flash_attention`` over rows, rotary
    inside the kernels, against ``xla_attention`` over heads that
    ``_rotate_heads`` turned: two tiles a side, so every index map of the
    rows, the statistics and the tables is walked."""
    from opendiloco_tpu.models.llama import _rotate_heads
    from opendiloco_tpu.ops import flash_attention as fa

    monkeypatch.setenv("OPENDILOCO_TPU_FLASH_BLOCKS", "128,128")
    q, k, v, (cos, sin), cfg = _rows_case(heads)
    d = cfg.head_dim
    split = lambda x: x.reshape(*x.shape[:2], -1, d)

    def ref(q, k, v):
        turned = (_rotate_heads(cfg, split(x), cos, sin) for x in (q, k))
        return xla_attention(*turned, split(v), causal=causal).reshape(q.shape)

    def got(q, k, v):
        return fa.flash_attention(
            q, k, v, head_dim=d, rope=fa.rope_rows(cos, sin, d), causal=causal
        )

    np.testing.assert_allclose(np.asarray(got(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5)
    loss = lambda fn, q, k, v: jnp.sum(fn(q, k, v) ** 2)
    gr = jax.grad(functools.partial(loss, ref), argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(functools.partial(loss, got), argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(gg, gr, 2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", ["15-5-64", "4-2-64-half-rotated"])
def test_rotate_rows_is_rotate_heads(heads, dtype):
    """Rotary's flat form on its own (what a tile meets in VMEM, in XLA):
    ``rotate_rows`` under ``rope_rows``' tables against the model's
    ``_rotate_heads``, a head's lanes all turned and half of them; in bf16 to
    the one rounding the float32 form spares."""
    from opendiloco_tpu.models.llama import _rotate_heads
    from opendiloco_tpu.ops import flash_attention as fa

    q, _, _, (cos, sin), cfg = _rows_case(heads, t=32)
    q, d = q.astype(dtype), cfg.head_dim
    rope = fa.rope_rows(cos, sin, d)
    assert rope.rot == cfg.rotary_dim and rope.cos.shape == (2, 32, fa.lanes_of(d)[1])
    ref = _rotate_heads(cfg, q.reshape(2, 32, -1, d), cos, sin).reshape(q.shape)
    got = fa.rotate_rows(q, rope, d)
    assert got.dtype == dtype
    atol = 1e-6 if dtype == jnp.float32 else 4e-2  # bf16: an ulp of |x| <= 5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=atol
    )


@pytest.mark.parametrize("heads", ["15-5-64", "16-16-128"])
def test_flash_attention_lse_contract(interpret_pallas, heads):
    """``flash_attention_lse`` (a serving prefill's): heads in, heads out, the
    log-sum-exp of the scaled scores [B, T, H] beside them; None where the
    kernel does not tile the rows."""
    from opendiloco_tpu.ops.flash_attention import flash_attention_lse

    q, k, v, _, cfg = _rows_case(heads, b=1)
    d = cfg.head_dim
    q, k, v = (x.reshape(1, 256, -1, d) for x in (q, k, v))
    out, lse = flash_attention_lse(q, k, v, block_q=128, block_k=128, interpret=True)
    assert out.shape == q.shape and lse.shape == q.shape[:3] and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(xla_attention(q, k, v, causal=True)), atol=2e-5
    )
    rep = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bqhk", q, jnp.repeat(k, rep, axis=2)) * d**-0.5
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool))[None, :, None, :], s, -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(s, axis=-1)), atol=2e-5
    )
    assert flash_attention_lse(q[:, :100], k[:, :100], v[:, :100], interpret=True) is None


def test_flash_rows_fall_back_where_the_kernel_does_not_tile():
    """T = 48 tiles by nothing: rows and tables go through ``rotate_rows`` and
    XLA's attention, and come back as rows."""
    from opendiloco_tpu.models.llama import _rotate_heads
    from opendiloco_tpu.ops import flash_attention as fa

    q, k, v, (cos, sin), cfg = _rows_case("4-2-64-half-rotated", t=48)
    d = cfg.head_dim
    split = lambda x: x.reshape(*x.shape[:2], -1, d)
    turned = (_rotate_heads(cfg, split(x), cos, sin) for x in (q, k))
    ref = xla_attention(*turned, split(v), causal=True).reshape(q.shape)
    got = fa.flash_attention(q, k, v, head_dim=d, rope=fa.rope_rows(cos, sin, d))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize(
    "heads,held",
    [((15, 5, 64), (15, 5)), ((32, 32, 64), (2, 2)), ((32, 4, 64), (16, 2)),
     ((16, 16, 128), (1, 1)), ((32, 32, 128), (1, 1)), ((4, 2, 64), (4, 2)), ((9, 3, 64), (9, 3))],
    ids=lambda x: "-".join(map(str, x)),
)
def test_heads_a_step_is_read_from_the_shapes(heads, held):
    """Whole GQA groups that fill 128-lane blocks of the K rows, else the row."""
    from opendiloco_tpu.ops.flash_attention import heads_a_step

    assert heads_a_step(*heads) == held
    gq, gkv = held
    assert heads[0] % gq == 0 and gq // gkv == heads[0] // heads[1]
    assert gkv == heads[1] or gkv * heads[2] % 128 == 0


# NaN from row ``r`` on, ``r`` a multiple of the sub-tile that lies inside a
# DMA tile: (seq, blocks, r)
SKIPPED = {"inside-the-only-tile": (256, None, 128), "inside-the-second-tile": (512, "256,256", 384)}


@pytest.mark.parametrize("case", list(SKIPPED))
def test_flash_sub_tiles_above_the_diagonal_are_not_computed(interpret_pallas, monkeypatch, case):
    """NaN in every K and V row at or after row r reaches no query row
    before r, in the output or in dq: the sub-tiles above the diagonal are
    skipped, not masked (a masked one gives p = 0 times a NaN of V, and
    dO . V^T, which poison every row of the DMA tile)."""
    from opendiloco_tpu.ops.flash_attention import flash_attention

    t, blocks, r = SKIPPED[case]
    if blocks:
        monkeypatch.setenv("OPENDILOCO_TPU_FLASH_BLOCKS", blocks)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, t, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, t, 1, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, 1, 64)), jnp.float32)
    k, v = k.at[:, r:].set(jnp.nan), v.at[:, r:].set(jnp.nan)

    out = flash_attention(q, k, v, causal=True)
    assert np.isfinite(np.asarray(out[:, :r])).all()
    assert np.isnan(np.asarray(out[:, r:])).all()  # the NaN rows were read where they count
    clean = xla_attention(q[:, :r], k[:, :r], v[:, :r], causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :r]), np.asarray(clean), atol=2e-5)

    dq = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, causal=True)[:, :r] ** 2))(q)
    assert np.isfinite(np.asarray(dq[:, :r])).all()
    ref = jax.grad(lambda q: jnp.sum(xla_attention(q, k[:, :r], v[:, :r], causal=True) ** 2))(q[:, :r])
    _assert_grads_close([dq[:, :r]], [ref], 2e-5)


# (seq, block_q, block_k, causal) -> (c, unit, computed, masked, skipped)
PLANS = {
    (2048, 1024, 1024, True): (128, (128, 128), 64 + 2 * 36, 16, 120),
    (1024, 1024, 1024, True): (128, (128, 128), 36, 8, 28),
    (4096, 1024, 1024, True): (128, (128, 128), 6 * 64 + 4 * 36, 32, 496),
    (2048, 1024, 1024, False): (0, (1024, 1024), 4, 0, 0),
    (2048, 512, 1024, True): (0, (512, 1024), 6, 4, 2),  # unequal: crossed tiles whole
    (256, 128, 128, True): (128, (128, 128), 3, 2, 1),
}


@pytest.mark.parametrize("shape", list(PLANS), ids=lambda s: "-".join(map(str, s)))
def test_causal_plan_counts(shape):
    from opendiloco_tpu.ops.flash_attention import causal_plan

    plan = causal_plan(*shape)
    assert tuple(plan[:5]) == PLANS[shape]
    t = shape[0]
    area = plan.unit[0] * plan.unit[1]
    assert plan.computed_share == plan.computed * area / t**2
    assert plan.masked_share == plan.masked * area / t**2
    assert (plan.computed + plan.skipped) * area == t**2


def test_causal_plan_of_the_training_cells(monkeypatch):
    """Seq 2,048 in 1,024-row blocks: 0.53125 of a head's scores computed
    and 0.0625 under a mask; with no walk (the sub-tile the whole block)
    the counts are the kernels' before it: 0.75 and 0.5."""
    from opendiloco_tpu.ops import flash_attention as fa

    plan = fa.plan_of(2048, 64)
    assert (plan.computed_share, plan.masked_share) == (0.53125, 0.0625)
    assert fa.plan_of(2048 + 8, 64) is None  # XLA's attention runs there
    monkeypatch.setattr(fa, "_SUB_TILE", 1024)
    plan = fa.plan_of(2048, 64)
    assert (plan.computed_share, plan.masked_share) == (0.75, 0.5)


def test_flash_fallback_small_seq(qkv):
    """T=16 doesn't tile -> transparently falls back to XLA attention."""
    from opendiloco_tpu.ops.flash_attention import flash_attention

    q, k, v = (x[:, :16] for x in qkv)
    ref = xla_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_ring_attention_matches_xla(qkv):
    """Ring attention over a 4-device sp axis == single-device attention."""
    from opendiloco_tpu.ops import ring_attention as ra

    q, k, v = qkv
    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))
    ra.configure_ring(mesh, "sp")
    try:
        ref = xla_attention(q, k, v, causal=True)
        got = jax.jit(ra.ring_attention_auto)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    finally:
        ra.configure_ring(None)


def test_ring_attention_grads(qkv):
    from opendiloco_tpu.ops import ring_attention as ra

    q, k, v = qkv
    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))
    ra.configure_ring(mesh, "sp")
    try:

        def loss_ring(q, k, v):
            return jnp.sum(ra.ring_attention_auto(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gg = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gr, gg):
            scale = np.abs(np.asarray(a)).max()
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), atol=3e-5 * max(scale, 1.0)
            )
    finally:
        ra.configure_ring(None)


def test_model_forward_with_ring(tiny_cfg):
    """End-to-end: model forward with attn_impl='ring' on an sp mesh matches
    the xla attention forward."""
    from opendiloco_tpu.models.llama import forward, init_params
    from opendiloco_tpu.ops import ring_attention as ra

    params = init_params(jax.random.key(0), tiny_cfg)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, tiny_cfg.vocab_size, (2, 128)), jnp.int32
    )
    ref = forward(params, ids, tiny_cfg, compute_dtype=jnp.float32, attn_impl="xla")

    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))
    ra.configure_ring(mesh, "sp")
    try:
        got = forward(
            params, ids, tiny_cfg, compute_dtype=jnp.float32, attn_impl="ring"
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-4)
    finally:
        ra.configure_ring(None)


def test_fused_loss_matches_standard(interpret_pallas_fused):
    """Trainer with fused_loss=True computes the same losses/trajectory."""
    from opendiloco_tpu.models.llama import LlamaConfig
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    cfg = LlamaConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    )
    rng = np.random.default_rng(0)
    ids = ((rng.integers(0, 256, (8, 1)) + np.arange(65)) % 256).astype(np.int32)

    losses = {}
    for fused in (False, True):
        tc = TrainerConfig(
            lr=1e-3, warmup_steps=2, total_steps=50, precision="fp32",
            remat=False, fused_loss=fused,
        )
        trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
        state = trainer.init_state(jax.random.key(1))
        run = []
        for _ in range(3):
            state, m = trainer.train_step(
                state, trainer.shard_batch(ids, ids.copy(), accum=1)
            )
            run.append(float(m["loss"]))
        losses[fused] = run
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5, atol=1e-6)


def _ring_out(q, k, v, n_dev):
    from opendiloco_tpu.ops import ring_attention as ra

    devices = np.asarray(jax.devices()[:n_dev]).reshape(1, 1, n_dev, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))
    return np.asarray(ra.ring_attention_auto(q, k, v, mesh=mesh, axis="sp"))


def test_ring_attention_long_seq_sweep():
    """Long-context sweep (VJP'd path is the same code): ring matches dense
    at 4k, is self-consistent across ring sizes at 8k/16k, and runs at 32k
    -- per-device working set stays O(T * T/n), never the full [T, T]."""
    rng = np.random.default_rng(2)
    B, HQ, HKV, D = 1, 2, 1, 32

    def mk(T):
        q = jnp.asarray(rng.normal(size=(B, T, HQ, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
        return q, k, v

    # exactness vs dense reference at 4k
    q, k, v = mk(4096)
    ref = np.asarray(xla_attention(q, k, v, causal=True))
    np.testing.assert_allclose(_ring_out(q, k, v, 4), ref, atol=2e-5)

    # ring-size consistency at 8k and 16k (different rotation schedules
    # must agree with each other without a dense reference in memory)
    for T in (8192, 16384):
        q, k, v = mk(T)
        a = _ring_out(q, k, v, 4)
        b = _ring_out(q, k, v, 8)
        np.testing.assert_allclose(a, b, atol=2e-5)

    # 32k smoke: runs and stays finite on an 8-way ring
    q, k, v = mk(32768)
    out = _ring_out(q, k, v, 8)
    assert np.all(np.isfinite(out))


def test_ring_attention_backward_no_repeat_gqa():
    """The grouped-GQA backward produces K/V grads at K/V head width (the
    kernel never materializes q-head-wide K/V)."""
    from opendiloco_tpu.ops import ring_attention as ra

    rng = np.random.default_rng(3)
    B, T, HQ, HKV, D = 2, 256, 8, 2, 32
    q = jnp.asarray(rng.normal(size=(B, T, HQ, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))

    def loss_ring(q, k, v):
        return jnp.sum(ra.ring_attention_auto(q, k, v, mesh=mesh, axis="sp") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    assert gg[1].shape == (B, T, HKV, D) and gg[2].shape == (B, T, HKV, D)
    for a, b in zip(gr, gg):
        scale = np.abs(np.asarray(a)).max()
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=3e-5 * max(scale, 1.0)
        )


@pytest.mark.parametrize("blocks", [None, "256,256", "128,512", "512,256"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_streaming_multiblock_parity(interpret_pallas, monkeypatch, causal, blocks):
    """T=1024 in 256-row blocks -> 4 streamed k-blocks per q-block:
    exercises the scratch carry across the sequential grid dimension (fwd +
    both bwd kernels), both causal (clamped index maps; full tiles, walked
    diagonal tiles and skipped ones in one row of the grid) and full
    attention; in one 1,024-row tile, the default (the walk alone: 8 x 8
    sub-tiles); and in unequal blocks (crossed tiles whole under the mask)."""
    from opendiloco_tpu.ops.flash_attention import flash_attention

    if blocks:
        monkeypatch.setenv("OPENDILOCO_TPU_FLASH_BLOCKS", blocks)
    rng = np.random.default_rng(5)
    B, T, H, HKV, D = 1, 1024, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)

    ref = xla_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=causal) ** 2)

    gr = jax.grad(functools.partial(loss, xla_attention), argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(functools.partial(loss, flash_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gg):
        scale = np.abs(np.asarray(a)).max()
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=3e-5 * max(scale, 1.0)
        )


def test_fused_xent_padded_vocab_parity(interpret_pallas_fused):
    """Non-tileable vocab (e.g. Llama's 32000, here 1000) pads to wide
    tiles with in-kernel masking: loss and grads match the materializing
    reference exactly."""
    from opendiloco_tpu.ops.fused_xent import fused_linear_cross_entropy

    rng = np.random.default_rng(6)
    N, D, V = 256, 128, 1000
    h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(D, V)) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    labels = labels.at[::7].set(-100)  # sprinkle ignored positions

    def ref_loss(h, w, labels):
        mask = labels != -100
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        safe = jnp.where(mask, labels, 0)
        nll = -jnp.take_along_axis(lp, safe[:, None], axis=1)[:, 0] * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)

    ref = ref_loss(h, w, labels)
    got = fused_linear_cross_entropy(h, w, labels)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)

    gr = jax.grad(ref_loss, argnums=(0, 1))(h, w, labels)
    gg = jax.grad(fused_linear_cross_entropy, argnums=(0, 1))(h, w, labels)
    for a, b in zip(gr, gg):
        scale = np.abs(np.asarray(a)).max()
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-6 * max(scale, 1.0)
        )


@pytest.mark.parametrize("n", [1024, 240])
def test_fused_xent_multiblock_and_row_pad_parity(interpret_pallas_fused, n):
    """Regression oracle for two backward-pass hazards: (a) dW accumulation
    across MULTIPLE token blocks (n=1024 -> >=2 blocks in the dw kernel;
    a single-kernel output-revisiting design silently dropped contributions
    because the revisits are non-consecutive), and (b) token counts that
    don't tile (n=240: the causal shift makes B*(T-1) rows) which must be
    padded with IGNORE labels, not silently fall back."""
    from opendiloco_tpu.ops.fused_xent import fused_linear_cross_entropy

    rng = np.random.default_rng(7)
    D, V = 128, 512
    h = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(D, V)) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, n), jnp.int32)

    def ref_loss(h, w, labels):
        mask = labels != -100
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        safe = jnp.where(mask, labels, 0)
        nll = -jnp.take_along_axis(lp, safe[:, None], axis=1)[:, 0] * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)

    np.testing.assert_allclose(
        float(fused_linear_cross_entropy(h, w, labels)),
        float(ref_loss(h, w, labels)),
        rtol=1e-6,
    )
    gr = jax.grad(ref_loss, argnums=(0, 1))(h, w, labels)
    gg = jax.grad(fused_linear_cross_entropy, argnums=(0, 1))(h, w, labels)
    for a, b in zip(gr, gg):
        scale = np.abs(np.asarray(a)).max()
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-6 * max(scale, 1.0)
        )


def test_ring_attention_bf16_inputs(qkv):
    """bf16 q/k/v (the production mixed-precision path) keep matmul operands
    bf16 for the MXU while online-softmax stats stay f32; result must track
    the xla bf16 attention within bf16 tolerance."""
    from opendiloco_tpu.ops import ring_attention as ra

    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))
    ra.configure_ring(mesh, "sp")
    try:
        ref = xla_attention(q, k, v, causal=True)
        got = jax.jit(ra.ring_attention_auto)(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )
    finally:
        ra.configure_ring(None)


def test_ring_attention_bf16_grads(qkv):
    """Gradient parity on the production bf16 path: the backward ring
    recurrence recomputes scores from bf16 operands and casts p/ds for the
    MXU; gradients must track the xla bf16 backward within bf16 tolerance."""
    from opendiloco_tpu.ops import ring_attention as ra

    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))
    ra.configure_ring(mesh, "sp")
    try:

        def loss_ring(q, k, v):
            return jnp.sum(ra.ring_attention_auto(q, k, v).astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(
                xla_attention(q, k, v, causal=True).astype(jnp.float32) ** 2
            )

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gg = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gr, gg):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            scale = np.abs(a).max()
            np.testing.assert_allclose(b, a, atol=4e-2 * max(scale, 1.0))
    finally:
        ra.configure_ring(None)


@pytest.fixture
def ring_flash_enabled(monkeypatch, interpret_pallas):
    """Force the flash-chunk ring path (interpret-mode kernels) on CPU."""
    monkeypatch.setenv("OPENDILOCO_TPU_RING_FLASH", "1")
    return interpret_pallas


def _qkv512():
    rng = np.random.default_rng(7)
    B, T, H, HKV, D = 1, 512, 4, 2, 64  # Tl=128 over 4 devices: tiles
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, HKV, D)), jnp.float32)
    return q, k, v


def test_ring_flash_chunks_match_xla(ring_flash_enabled, monkeypatch):
    """Flash-chunk ring == dense attention, and the Pallas path really ran."""
    from opendiloco_tpu.ops import flash_attention as fa
    from opendiloco_tpu.ops import ring_attention as ra

    calls = []
    orig = fa._fwd

    def counting_fwd(*a, **kw):
        calls.append(kw.get("causal"))
        return orig(*a, **kw)

    monkeypatch.setattr(fa, "_fwd", counting_fwd)

    q, k, v = _qkv512()
    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))
    ref = xla_attention(q, k, v, causal=True)
    got = ra.ring_attention_auto(q, k, v, mesh=mesh, axis="sp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    assert True in calls and False in calls  # diagonal + off-diagonal kernels


def test_ring_flash_chunks_grads_match_xla(ring_flash_enabled):
    from opendiloco_tpu.ops import ring_attention as ra

    q, k, v = _qkv512()
    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))

    def loss_ring(q, k, v):
        return jnp.sum(ra.ring_attention_auto(q, k, v, mesh=mesh, axis="sp") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gr, gg):
        scale = np.abs(np.asarray(a)).max()
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=3e-5 * max(scale, 1.0)
        )


def test_ring_flash_gate_falls_back_off_tpu(qkv):
    """Without the env override on a CPU mesh the einsum path is chosen,
    and non-tiling local chunks always fall back."""
    from opendiloco_tpu.ops import ring_attention as ra

    q, k, v = _qkv512()
    devices = np.asarray(jax.devices()[:4]).reshape(1, 1, 4, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "fsdp", "sp", "tp"))
    assert ra._flash_chunk_block(mesh, "sp", q, causal=True) == 0  # cpu

    import os

    os.environ["OPENDILOCO_TPU_RING_FLASH"] = "1"
    try:
        assert ra._flash_chunk_block(mesh, "sp", q, causal=True) == 128
        qs, _, _ = qkv  # T=256 -> Tl=64: below the 128 tile minimum
        assert ra._flash_chunk_block(mesh, "sp", qs, causal=True) == 0
        assert ra._flash_chunk_block(mesh, "sp", q, causal=False) == 0
    finally:
        del os.environ["OPENDILOCO_TPU_RING_FLASH"]


def test_sharded_kernel_wrappers_match(interpret_pallas, interpret_pallas_fused):
    """SPMD entries for multi-device meshes (round 5: Mosaic kernels cannot
    be auto-partitioned — found by the deviceless multichip AOT compile):
    flash_attention_sharded and fused_linear_cross_entropy_sharded run the
    kernels manual over the batch (and dividing tp head) axes and must
    match the unsharded math exactly."""
    from opendiloco_tpu.ops.attention import xla_attention
    from opendiloco_tpu.ops.flash_attention import flash_attention_sharded
    from opendiloco_tpu.ops.fused_xent import (
        fused_linear_cross_entropy,
        fused_linear_cross_entropy_sharded,
    )

    devices = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = jax.sharding.Mesh(devices, ("dp", "tp"))
    rng = np.random.default_rng(0)
    b, t, hq, hkv, d = 4, 128, 4, 2, 16  # tp=2 divides BOTH head counts
    q = jnp.asarray(rng.standard_normal((b, t, hq, d), dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((b, t, hkv, d), dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((b, t, hkv, d), dtype=np.float32))

    got = jax.jit(
        lambda q, k, v: flash_attention_sharded(
            q, k, v, mesh=mesh, batch_axes=("dp",), tp_axis="tp", causal=True
        )
    )(q, k, v)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    # non-dividing kv heads: the head dim replicates into the region
    k3 = jnp.asarray(rng.standard_normal((b, t, 1, d), dtype=np.float32))
    v3 = jnp.asarray(rng.standard_normal((b, t, 1, d), dtype=np.float32))
    got = jax.jit(
        lambda q, k, v: flash_attention_sharded(
            q, k, v, mesh=mesh, batch_axes=("dp",), tp_axis="tp", causal=True
        )
    )(q, k3, v3)
    ref = xla_attention(q, k3, v3, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    # fused loss: batch rows sharded, head replicated into the region,
    # mean assembled from psum'd (sum, count) — including IGNORE rows
    n, dm, vocab = 256, 128, 512
    h = jnp.asarray(rng.standard_normal((n, dm), dtype=np.float32))
    w = jnp.asarray(rng.standard_normal((dm, vocab), dtype=np.float32) * 0.05)
    labels = rng.integers(0, vocab, n).astype(np.int32)
    labels[::7] = -100
    labels = jnp.asarray(labels)
    got = jax.jit(
        lambda h, w, l: fused_linear_cross_entropy_sharded(
            h, w, l, mesh=mesh, batch_axes=("dp",), tp_axis="tp"
        )
    )(h, w, labels)
    ref = fused_linear_cross_entropy(h, w, labels)
    np.testing.assert_allclose(float(got), float(ref), atol=2e-5)


def test_sharded_fused_loss_grads_match(interpret_pallas_fused):
    """d/dh and d/dw of the SPMD fused loss equal the unsharded kernel's:
    the replicated-w in_spec's transpose must psum the per-shard partial
    dw, and dh must land back on the right rows."""
    from opendiloco_tpu.ops.fused_xent import (
        fused_linear_cross_entropy,
        fused_linear_cross_entropy_sharded,
    )

    devices = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = jax.sharding.Mesh(devices, ("dp", "tp"))
    rng = np.random.default_rng(1)
    n, dm, vocab = 256, 128, 512
    h = jnp.asarray(rng.standard_normal((n, dm), dtype=np.float32))
    w = jnp.asarray(rng.standard_normal((dm, vocab), dtype=np.float32) * 0.05)
    labels = rng.integers(0, vocab, n).astype(np.int32)
    labels[::5] = -100
    labels = jnp.asarray(labels)

    g_sh = jax.jit(
        jax.grad(
            lambda h, w: fused_linear_cross_entropy_sharded(
                h, w, labels, mesh=mesh, batch_axes=("dp",), tp_axis="tp"
            ),
            argnums=(0, 1),
        )
    )(h, w)
    g_ref = jax.jit(
        jax.grad(
            lambda h, w: fused_linear_cross_entropy(h, w, labels),
            argnums=(0, 1),
        )
    )(h, w)
    np.testing.assert_allclose(
        np.asarray(g_sh[0]), np.asarray(g_ref[0]), atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(g_sh[1]), np.asarray(g_ref[1]), atol=2e-6
    )


def test_sharded_kernels_trainer_trajectory(interpret_pallas, interpret_pallas_fused):
    """Full train-step trajectory with pallas attention + fused loss on a
    multi-device FULL_SHARD mesh (SPMD kernel wrappers engaged) equals the
    single-logical-device trajectory with the same kernels."""
    from opendiloco_tpu.models.llama import LlamaConfig
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    cfg = LlamaConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    )

    def run(plan):
        tc = TrainerConfig(
            lr=1e-3, warmup_steps=2, total_steps=20, precision="fp32",
            remat=False, attn_impl="pallas", fused_loss=True,
        )
        trainer = InnerTrainer(cfg, tc, plan)
        state = trainer.init_state(jax.random.key(5))
        losses = []
        rng = np.random.default_rng(7)
        for _ in range(3):
            ids = rng.integers(0, 256, (8, 128)).astype(np.int32)
            batch = trainer.shard_batch(ids, ids.copy(), accum=1)
            state, m = trainer.train_step(state, batch)
            losses.append(float(m["loss"]))
        return losses

    ref = run(build_mesh("NO_SHARD", devices=jax.devices()[:1]))
    got = run(build_mesh("FULL_SHARD", devices=jax.devices()[:4]))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=5e-5)
