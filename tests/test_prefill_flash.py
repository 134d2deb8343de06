"""A whole-prompt prefill's causal attention through the flash forward kernel
(PR 55): which form a bucket takes (``decode_kernels.prefill_form``, from the
rows, the heads and the resolved decode kernel alone), the block the kernel
runs in (``prefill_block``), ``prefill_forward`` with the kernel interpreted
against the XLA form of the same prefill for each family of stack that reaches
the plain causal attention, and what the engine reports of it."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_glm_flash
import test_granite_hybrid
import test_olmoe
import test_zaya
from opendiloco_tpu import obs
from opendiloco_tpu.models.llama import (
    LlamaConfig,
    causal_prefill_heads,
    init_params,
    prefill_forward,
)
from opendiloco_tpu.ops import decode_kernels
from opendiloco_tpu.ops.decode_kernels import prefill_block, prefill_form
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")

# the benchmark's serve cells (and ``serve-1.7b-chat``, which is held back):
# (query heads, KV heads, the keys' head size, the values'), each bucket's form
# on the chip
CELLS = {
    "serve-360m-batch": ((15, 5, 64, 64), {32: "xla", 128: "xla"}),
    "serve-1.7b-chat": (
        (32, 32, 64, 64), {64: "xla", 128: "xla", 256: "xla", 512: "xla", 768: "xla"}),
    "serve-olmoe-fewshot": (
        (16, 16, 128, 128), {1536: "flash", 2048: "flash", 2560: "flash", 3072: "flash"}),
    "serve-granite-h-docqa": (
        (32, 8, 128, 128), {512: "xla", 1024: "flash", 1536: "flash", 2048: "flash"}),
    "serve-glm-flash-agent": ((20, 20, 256, 256), {768: "xla", 1280: "flash", 1792: "flash"}),
    "serve-zaya1-reason": ((8, 2, 128, 128), {512: "xla", 1024: "xla"}),
}
# the cells whose prefill runs no plain causal attention: EVA's windows, an
# indexer's selection, sliding layers (every prompt in chunks)
OWN_ATTEND = {
    "serve-evabyte-complete": "evabyte-6.5b",
    "serve-keye-videoqa": "keye-vl-2.0-30b-a3b",
    "serve-dots3-notes": "dots3-note-prev",
}
CONFIGS = {
    "serve-360m-batch": "smollm2-360m", "serve-1.7b-chat": "smollm2-1.7b",
    "serve-olmoe-fewshot": "olmoe-1b-7b", "serve-granite-h-docqa": "granite-4.0-h-small",
    "serve-glm-flash-agent": "glm-4.7-flash", "serve-zaya1-reason": "zaya1-8b",
}


def _bench_cfg(config: str) -> LlamaConfig:
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        return LlamaConfig.from_dict(json.load(f))


def _buckets(cell: str) -> list:
    with open(os.path.join(BENCH, "workloads", f"{cell}.json")) as f:
        return json.load(f)["engine"]["prefill_buckets"]


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_cells_buckets_take_the_form_the_chip_gives_them(cell):
    """From the cell's own files: the heads the form is asked with are the
    configuration's, the buckets the cell's, and each takes the form stated."""
    heads, forms = CELLS[cell]
    assert causal_prefill_heads(_bench_cfg(CONFIGS[cell])) == heads
    assert sorted(forms) == sorted(_buckets(cell))
    assert {b: prefill_form(b, *heads, "pallas") for b in forms} == forms
    # the scores XLA would write out: the line lies between 72 and 125 MB
    for bucket, form in forms.items():
        assert (heads[0] * bucket * bucket * 4 > 100e6) == (form == "flash")
    # off the TPU nothing changes unless the kernel is asked for, interpreted
    assert {prefill_form(b, *heads) for b in forms} == {"xla"}
    assert {prefill_form(b, *heads, "xla") for b in forms} == {"xla"}


@pytest.mark.parametrize("cell", list(OWN_ATTEND))
def test_a_prefill_with_its_own_attend_is_asked_nothing(cell):
    assert causal_prefill_heads(_bench_cfg(OWN_ATTEND[cell])) is None


@pytest.mark.parametrize("rows,heads,form", [
    (2048, (20, 20, 20, 16), "xla"),  # the agent cell's rehearsal: 12 + 8 against 16
    (2048, (16, 16, 192, 128), "xla"),  # keys' heads wider than the values'
    (2048, (16, 16, 100, 100), "xla"),  # a head size the kernel does not take
    (2048, (16, 5, 128, 128), "xla"),  # query heads no KV head count divides
    (1000, (16, 16, 128, 128), "xla"),  # no multiple of 128 divides the rows
    (384, (256, 256, 128, 128), "xla"),  # under the floor, whatever the scores weigh
    (768, (32, 32, 64, 64), "xla"),  # 72 MB of scores: XLA keeps them fused, and is faster
    (1280, (20, 20, 256, 256), "flash"),  # 125 MB
    (512, (96, 96, 128, 128), "flash"),  # 96 MiB at the floor: where the line is
    (512, (95, 95, 128, 128), "xla"),
    (1024, (32, 8, 128, 128), "flash"),
])
def test_the_form_is_a_function_of_what_the_call_sees(rows, heads, form):
    assert prefill_form(rows, *heads, "pallas") == form


def test_the_form_follows_the_platform_as_the_decode_kernel_does(monkeypatch):
    heads = (16, 16, 128, 128)
    assert prefill_form(2048, *heads, None) == "xla"  # nothing passed, off the TPU
    monkeypatch.setenv("ODTP_DECODE_KERNEL", "pallas")  # a name nothing reads (PR 58)
    assert prefill_form(2048, *heads, None) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert prefill_form(2048, *heads, None) == "flash"
    assert prefill_form(2048, *heads, "xla") == "xla"


@pytest.mark.parametrize("rows,block", [
    (768, 768), (1280, 640), (1792, 896),  # the agent cell's: no 512 divides them
    (1536, 512), (2560, 512), (512, 512), (1024, 512), (2048, 512), (3072, 512),
    (640, 640), (896, 896), (1152, 384), (128, 128), (4096, 512),
])
def test_the_block_a_bucket_runs_in(rows, block):
    assert prefill_block(rows) == block
    assert rows % block == 0 and block % 128 == 0 and block <= 1024


# ---------------------------------------------------------------------------
# prefill_forward, the kernel interpreted, against the XLA form
# ---------------------------------------------------------------------------

ROWS = 512  # the floor: the smallest bucket that takes the kernel


def _dense():
    raw = dict(hidden_size=64, intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=128, max_position_embeddings=1024)
    cfg = LlamaConfig.from_dict(raw)
    return cfg, init_params(jax.random.key(0), cfg)


def _olmoe():
    raw = {**test_olmoe.published(2), "max_position_embeddings": 1024}
    cfg = LlamaConfig.from_dict(raw)
    params = init_params(jax.random.key(0), cfg)
    keys = iter(jax.random.split(jax.random.key(100), 4))
    for name in ("input_norm", "post_attn_norm", "q_norm", "k_norm"):  # QK-norm away from 1
        shape = params["layers"][name].shape
        params["layers"][name] = 1.0 + 0.3 * jax.random.normal(next(keys), shape)
    return cfg, params


FAMILIES = {
    "gqa": _dense,
    "olmoe-qk-norm": _olmoe,
    # latent attention with keys' and values' heads of one size: 8 + 8 against 16
    "latent": lambda: test_glm_flash.model(
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16, max_position_embeddings=1024)[1:],
    "cca": lambda: test_zaya.model(max_position_embeddings=1024)[1:],
    "hybrid": lambda: test_granite_hybrid.model(max_position_embeddings=1024)[1:],
}


def _prefill(cfg, params, ids, length, kernel, dtype):
    fn = jax.jit(lambda p, i, n: prefill_forward(
        p, i, n, cfg, compute_dtype=dtype, decode_kernel=kernel,
        return_moe_counts=bool(cfg.num_experts)))
    return fn, fn(params, ids, jnp.int32(length))


def _rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / max(np.sum(want**2), 1e-30)))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_prefill_through_the_kernel_equals_the_xla_form(family, monkeypatch):
    """A right-padded bucket of 512 rows in float32: logits, the rows kept for
    the cache and every state the prompt leaves agree to rounding, a routed
    FFN's counts exactly; the kernel is in the one program and not the other."""
    monkeypatch.setattr(decode_kernels, "_PREFILL_SCORE_BYTES", 0)
    cfg, params = FAMILIES[family]()
    heads = causal_prefill_heads(cfg)
    assert prefill_form(ROWS, *heads, "pallas") == "flash"
    ids = jnp.asarray(np.random.default_rng(3).integers(3, cfg.vocab_size, (1, ROWS)), jnp.int32)
    length = 389
    xla, want = _prefill(cfg, params, ids, length, "xla", jnp.float32)
    flash, got = _prefill(cfg, params, ids, length, "pallas", jnp.float32)
    texts = [str(jax.make_jaxpr(f)(params, ids, jnp.int32(length))) for f in (xla, flash)]
    assert ["odtp_flash_fwd" in t for t in texts] == [False, True]
    assert len(got) == len(want)
    for a, b in zip(got, want):
        if a is None:
            assert b is None
        elif jnp.issubdtype(a.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            assert _rel_l2(a, b) < 2e-5, (a.shape, _rel_l2(a, b))


def test_prefill_through_the_kernel_in_bfloat16(monkeypatch):
    """The serving precision: the kernel rounds the unnormalised probabilities
    and divides at the end where XLA divides and then rounds."""
    monkeypatch.setattr(decode_kernels, "_PREFILL_SCORE_BYTES", 0)
    cfg, params = _dense()
    ids = jnp.asarray(np.random.default_rng(4).integers(3, cfg.vocab_size, (1, ROWS)), jnp.int32)
    _, want = _prefill(cfg, params, ids, ROWS, "xla", jnp.bfloat16)
    _, got = _prefill(cfg, params, ids, ROWS, "pallas", jnp.bfloat16)
    assert 0 < _rel_l2(got[0], want[0]) < 3e-2
    assert _rel_l2(got[1], want[1]) < 3e-2 and _rel_l2(got[2], want[2]) < 3e-2


def test_unequal_latent_heads_keep_the_xla_form_to_the_letter(monkeypatch):
    """The agent cell's rehearsal configuration (keys 12 + 8, values 16): the
    kernel asked for, the program is the XLA form's."""
    monkeypatch.setattr(decode_kernels, "_PREFILL_SCORE_BYTES", 0)
    _, cfg, params = test_glm_flash.model(max_position_embeddings=1024)
    assert causal_prefill_heads(cfg) == (4, 4, 20, 16)
    ids = jnp.zeros((1, ROWS), jnp.int32)
    texts = [
        jax.jit(lambda p, i, n, k=k: prefill_forward(p, i, n, cfg, decode_kernel=k))
        .lower(params, ids, jnp.int32(7)).as_text()
        for k in ("xla", "pallas")
    ]
    assert texts[0] == texts[1]


# ---------------------------------------------------------------------------
# the engine says which form a bucket took
# ---------------------------------------------------------------------------


def test_the_engine_reports_each_buckets_form_and_counts_the_admissions(monkeypatch):
    monkeypatch.setattr(decode_kernels, "_PREFILL_SCORE_BYTES", 0)
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "128")
    cfg, params = _dense()
    make = lambda kernel: ServeEngine(
        cfg, params, num_slots=2, max_context=640, prefill_buckets=(128, 512),
        compute_dtype=jnp.float32, decode_kernel=kernel,
    )
    engine, plain = make("pallas"), make("xla")
    assert engine.prefill_forms == {128: "xla", 512: "flash"}
    assert plain.prefill_forms == {128: "xla", 512: "xla"}
    rng = np.random.default_rng(5)
    long, short = (rng.integers(3, cfg.vocab_size, n).tolist() for n in (300, 100))
    obs.capture.start()
    try:
        first = [engine.admit(0, long)[0], engine.admit(1, short)[0]]
    finally:
        cap = obs.capture.stop()
    assert first == [plain.admit(0, long)[0], plain.admit(1, short)[0]]
    assert engine.prefill_flash_admissions == 1 and plain.prefill_flash_admissions == 0
    assert cap.counters["serve_prefill_flash"] == 1 and cap.counters["serve_prefill_xla"] == 1
    stats = ContinuousBatcher(engine).stats()["prefill"]
    assert stats == {"forms": {"128": "xla", "512": "flash"}, "flash_admissions": 1}


def test_an_engine_whose_prefill_has_its_own_attend_reports_no_form():
    import test_keye

    _, cfg, params = test_keye.model()
    engine = ServeEngine(cfg, params, num_slots=2, max_context=32, prefill_buckets=(8,),
                         compute_dtype=jnp.float32, decode_kernel="xla")
    assert engine.prefill_forms == {}
