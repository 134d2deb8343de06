"""The main path's Pallas kernels, compiled for the chip without the chip.

Interpret-mode parity (test_attention, test_decode_kernels) cannot see what
the TPU's own compiler refuses: a slice Mosaic cannot lay out, a scalar
store to VMEM, a tile that outgrows VMEM. libtpu is installed here and
compiles for a *described* v5e (``jax.experimental.topologies``), so every
kernel variant the code can call is lowered AND compiled at published
widths in bf16, and must come out as a ``tpu_custom_call`` — the kernel,
not its XLA stand-in. A compile that passes is not a chip run; it only
means the chip run will not die at its first compile.

Whole-step compiles (a minute each) stay in the builder's rehearsal.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from opendiloco_tpu.ops.decode_kernels import (
    paged_decode_attention,
    spec_tail_attention_fused,
    w4_matmul,
)
from opendiloco_tpu.ops.flash_attention import flash_attention
from opendiloco_tpu.ops.fused_xent import fused_linear_cross_entropy

BF16 = jnp.bfloat16
SEQ = 1024
# (query heads, kv heads, head_dim) of the configs the repo ships
HEADS = {"150m": (16, 16, 64), "1b": (32, 4, 64)}
# (hidden, intermediate): the gate and down projections PackedW4 holds
FFN = {"150m": (1024, 2688), "1b": (2048, 5632)}


@pytest.fixture(scope="module")
def chip():
    """A described (not attached) v5e chip, with the persistent compile
    cache off around the module: a deviceless executable can be written to
    the cache but not read back, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    # another process of this sandbox may hold libtpu's lock file
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"v5e topology cannot be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def compiled_text(chip, fn, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("model", list(HEADS))
def test_flash_attention_fwd_bwd(chip, model):
    hq, hkv, d = HEADS[model]

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    text = compiled_text(
        chip,
        jax.grad(loss, argnums=(0, 1, 2)),
        ((2, SEQ, hq, d), BF16),
        ((2, SEQ, hkv, d), BF16),
        ((2, SEQ, hkv, d), BF16),
    )
    # forward, dq and dk/dv kernels are all in the program
    assert text.count("tpu_custom_call") >= 3


def test_fused_xent_fwd_bwd(chip):
    n, d, v = 4096, 2048, 32000  # the 1b lm-head, where fused_loss is auto-on
    text = compiled_text(
        chip,
        jax.value_and_grad(fused_linear_cross_entropy, argnums=(0, 1)),
        ((n, d), BF16),
        ((d, v), BF16),
        ((n,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("return_stats", [False, True])
@pytest.mark.parametrize("model", list(HEADS))
def test_paged_decode_attention(chip, model, return_stats):
    hq, hkv, d = HEADS[model]
    s = 8
    text = compiled_text(
        chip,
        lambda q, k, v, lens: paged_decode_attention(
            q, k, v, lens, interpret=False, return_stats=return_stats
        ),
        ((s, hq, d), BF16),
        ((s, SEQ, hkv, d), BF16),
        ((s, SEQ, hkv, d), BF16),
        ((s,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def _spec_text(chip, model, slots, kq, return_stats=False):
    hq, hkv, d = HEADS[model]
    return compiled_text(
        chip,
        lambda q, ck, cv, tk, tv, lens: spec_tail_attention_fused(
            q, ck, cv, tk, tv, lens, interpret=False, return_stats=return_stats
        ),
        ((slots, kq, hq, d), BF16),
        ((slots, SEQ, hkv, d), BF16),
        ((slots, SEQ, hkv, d), BF16),
        ((slots, kq, hkv, d), BF16),
        ((slots, kq, hkv, d), BF16),
        ((slots,), jnp.int32),
    )


@pytest.mark.parametrize("return_stats", [False, True])
@pytest.mark.parametrize("model", list(HEADS))
def test_spec_verify_tail(chip, model, return_stats):
    """The speculative tail (current token + 4 drafts) over 8 slots: bf16 at
    head_dim 64 is what Mosaic refused before the per-head slice moved to a
    leading dim."""
    text = _spec_text(chip, model, slots=8, kq=5, return_stats=return_stats)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "model,kq,kernel",
    [
        ("150m", 1024, True),
        ("1b", 512, True),
        # a whole GQA group's 8 x 1024 rows outgrow VMEM: the shape rule
        # hands this one to XLA instead of failing at compile
        ("1b", 1024, False),
    ],
)
def test_spec_continued_prefill(chip, model, kq, kernel):
    """The same kernel as the prefix-cache continued prefill calls it: one
    slot, the tail a whole suffix bucket."""
    text = _spec_text(chip, model, slots=1, kq=kq)
    assert ("tpu_custom_call" in text) == kernel


@pytest.mark.parametrize("rows", [8, 512])
@pytest.mark.parametrize("proj", ["gate", "down"])
@pytest.mark.parametrize("model", list(FFN))
def test_w4_matmul(chip, model, proj, rows):
    hidden, inter = FFN[model]
    k, n = (hidden, inter) if proj == "gate" else (inter, hidden)
    text = compiled_text(
        chip,
        lambda x, q, s: w4_matmul(x, q, s, (k, n), BF16, interpret=False),
        ((rows, k), BF16),
        ((k * n // 2,), jnp.uint8),
        ((-(-k * n // 4096),), jnp.uint16),
    )
    assert "tpu_custom_call" in text

