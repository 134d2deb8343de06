"""Inner-trainer tests on the virtual 8-device CPU mesh.

Strategy-equivalence is the key oracle: DDP / ZeRO-2 / ZeRO-3 / hybrid are
*layouts* of the same computation, so loss trajectories must match bitwise-ish
across strategies (the TPU analogue of the reference's FSDP-strategy menu,
open_diloco/utils.py:138-152).
"""

import jax
import numpy as np
import pytest

from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig


def make_batch(rng, vocab, global_bs=16, seq=32, accum=2):
    # memorizable data (arithmetic sequences mod vocab) so loss can drop
    starts = rng.integers(0, vocab, (global_bs, 1))
    ids = ((starts + np.arange(seq)) % vocab).astype(np.int32)
    return ids, ids.copy()


def run_steps(tiny_cfg, strategy, n_steps=4, seed=0, **mesh_kwargs):
    tc = TrainerConfig(
        lr=1e-3, warmup_steps=2, total_steps=100, precision="fp32", remat=False
    )
    plan = build_mesh(strategy, **mesh_kwargs)
    trainer = InnerTrainer(tiny_cfg, tc, plan)
    state = trainer.init_state(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(n_steps):
        ids, labels = make_batch(rng, tiny_cfg.vocab_size)
        batch = trainer.shard_batch(ids, labels, accum=2)
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    return np.array(losses), state, trainer


def test_loss_decreases(tiny_cfg):
    losses, state, _ = run_steps(tiny_cfg, "NO_SHARD", n_steps=8)
    assert np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert int(state["step"]) == 8


@pytest.mark.parametrize(
    "strategy,kwargs",
    [
        ("FULL_SHARD", {}),
        ("SHARD_GRAD_OP", {}),
        ("HYBRID_SHARD", {"fsdp_size": 4}),
        ("HYBRID_SHARD_ZERO2", {"fsdp_size": 2}),
    ],
)
def test_strategy_equivalence(tiny_cfg, strategy, kwargs):
    """Every sharding strategy computes the same optimization trajectory."""
    ref, _, _ = run_steps(tiny_cfg, "NO_SHARD")
    got, state, trainer = run_steps(tiny_cfg, strategy, **kwargs)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_params_actually_sharded(tiny_cfg):
    _, state, trainer = run_steps(tiny_cfg, "FULL_SHARD", n_steps=1)
    embed = state["params"]["embed_tokens"]
    n_dev = len(jax.devices())
    assert len(embed.sharding.device_set) == n_dev
    # each shard holds 1/n of the rows
    shard = embed.addressable_shards[0]
    assert shard.data.shape[0] * n_dev == embed.shape[0] or shard.data.shape[
        1
    ] * n_dev == embed.shape[1]


def test_zero2_params_replicated_optstate_sharded(tiny_cfg):
    _, state, trainer = run_steps(tiny_cfg, "SHARD_GRAD_OP", n_steps=1)
    embed = state["params"]["embed_tokens"]
    assert embed.sharding.is_fully_replicated
    mu_embed = state["opt_state"][1][0].mu["embed_tokens"]
    assert not mu_embed.sharding.is_fully_replicated


def test_lr_schedule(tiny_cfg):
    tc = TrainerConfig(lr=4e-4, warmup_steps=10, total_steps=100)
    from opendiloco_tpu.trainer import make_schedule

    sched = make_schedule(tc)
    assert float(sched(0)) == 0.0
    np.testing.assert_allclose(float(sched(10)), 4e-4, rtol=1e-6)
    assert float(sched(99)) < 1e-5
    # monotone decay after warmup
    vals = [float(sched(s)) for s in range(10, 100, 10)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))


def test_train_step_with_ring_attention(tiny_cfg):
    """Full train step with sequence parallelism (sp=4) matches NO_SHARD xla."""
    ref, _, _ = run_steps(tiny_cfg, "NO_SHARD")
    tc = TrainerConfig(
        lr=1e-3, warmup_steps=2, total_steps=100, precision="fp32", remat=False,
        attn_impl="ring",
    )
    plan = build_mesh("NO_SHARD", sp_size=4)
    trainer = InnerTrainer(tiny_cfg, tc, plan)
    state = trainer.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(4):
        ids, labels = make_batch(rng, tiny_cfg.vocab_size)
        batch = trainer.shard_batch(ids, labels, accum=2)
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    np.testing.assert_allclose(np.array(losses), ref, rtol=2e-4, atol=2e-4)


def test_fp16_loss_scaling_trains(tiny_cfg):
    tc = TrainerConfig(
        lr=1e-3, warmup_steps=2, total_steps=100, precision="fp16-mixed",
        remat=False, init_loss_scale=2.0**10, scale_growth_interval=4,
    )
    plan = build_mesh("NO_SHARD")
    trainer = InnerTrainer(tiny_cfg, tc, plan)
    state = trainer.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    losses, scales = [], []
    for _ in range(6):
        ids, labels = make_batch(rng, tiny_cfg.vocab_size)
        state, m = trainer.train_step(state, trainer.shard_batch(ids, labels, accum=1))
        losses.append(float(m["loss"]))
        scales.append(float(m["loss_scale"]))
        assert float(m["found_inf"]) == 0.0
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert scales[-1] == 2.0**11  # grew once after 4 clean steps


def test_fp16_overflow_skips_step_and_halves_scale(tiny_cfg):
    tc = TrainerConfig(
        lr=1e-3, warmup_steps=2, total_steps=100, precision="fp16-mixed",
        remat=False, init_loss_scale=1e38,
    )
    plan = build_mesh("NO_SHARD")
    trainer = InnerTrainer(tiny_cfg, tc, plan)
    state = trainer.init_state(jax.random.key(0))
    before = jax.device_get(state["params"]["final_norm"])
    rng = np.random.default_rng(0)
    ids, labels = make_batch(rng, tiny_cfg.vocab_size)
    state, m = trainer.train_step(state, trainer.shard_batch(ids, labels, accum=1))
    assert float(m["found_inf"]) == 1.0
    np.testing.assert_array_equal(
        jax.device_get(state["params"]["final_norm"]), before
    )  # update skipped
    assert float(jax.device_get(state["scaler"]["scale"])) == pytest.approx(0.5e38)


def test_tensor_parallel_equivalence(tiny_cfg):
    """tp=2 (and tp=2 x fsdp=2) compute the same trajectory as NO_SHARD."""
    ref, _, _ = run_steps(tiny_cfg, "NO_SHARD")
    got_tp, _, _ = run_steps(tiny_cfg, "NO_SHARD", tp_size=2)
    np.testing.assert_allclose(got_tp, ref, rtol=1e-5, atol=1e-5)
    got_mix, _, _ = run_steps(tiny_cfg, "FULL_SHARD", tp_size=2)
    np.testing.assert_allclose(got_mix, ref, rtol=1e-5, atol=1e-5)


def test_mesh_validation_errors():
    from opendiloco_tpu.parallel.mesh import build_mesh

    with pytest.raises(ValueError, match="unknown sharding strategy"):
        build_mesh("ZERO_INFINITY")
    with pytest.raises(ValueError, match="not divisible"):
        build_mesh("NO_SHARD", tp_size=3)  # 8 devices % 3 != 0
    # explicit sizes that don't multiply out
    with pytest.raises(ValueError):
        build_mesh("HYBRID_SHARD", dp_size=3, fsdp_size=3)


def test_mesh_shapes_per_strategy():
    from opendiloco_tpu.parallel.mesh import build_mesh

    assert build_mesh("NO_SHARD").mesh.shape == {"pp": 1, "dp": 8, "fsdp": 1, "ep": 1, "sp": 1, "tp": 1}
    assert build_mesh("FULL_SHARD").mesh.shape == {"pp": 1, "dp": 1, "fsdp": 8, "ep": 1, "sp": 1, "tp": 1}
    plan = build_mesh("HYBRID_SHARD", fsdp_size=4)
    assert plan.mesh.shape == {"pp": 1, "dp": 2, "fsdp": 4, "ep": 1, "sp": 1, "tp": 1}
    assert plan.data_parallel_size == 8
    plan = build_mesh("NO_SHARD", sp_size=2, tp_size=2)
    assert plan.mesh.shape == {"pp": 1, "dp": 2, "fsdp": 1, "ep": 1, "sp": 2, "tp": 2}
    assert plan.data_parallel_size == 2


def test_auto_perf_defaults_resolve_to_xla_off_tpu(tiny_cfg):
    # "auto"/None must resolve against the mesh's device kind: on the CPU
    # test mesh that means the portable XLA attention and no fused loss
    # (on TPU meshes the same defaults pick pallas, with fused only for
    # looped stacks; sweep-measured)
    import dataclasses

    trainer = InnerTrainer(tiny_cfg, TrainerConfig(), build_mesh("NO_SHARD"))
    assert trainer.tc.attn_impl == "xla"
    assert trainer.tc.fused_loss is False

    # explicit choices pass through untouched
    tc = TrainerConfig(attn_impl="xla", fused_loss=True)
    trainer = InnerTrainer(tiny_cfg, tc, build_mesh("NO_SHARD"))
    assert trainer.tc.fused_loss is True

    # off-TPU auto keeps fused off for MoE too (same sweep-measured rule)
    moe_cfg = dataclasses.replace(tiny_cfg, num_experts=2)
    trainer = InnerTrainer(moe_cfg, TrainerConfig(), build_mesh("NO_SHARD"))
    assert trainer.tc.fused_loss is False


def test_auto_perf_defaults_on_tpu_device_kind(tiny_cfg):
    # drive the resolver with a faked TPU device kind: dense stacks get
    # pallas with the loss UNFUSED (the full unroll lets XLA fuse the
    # lm-head itself; round-5 sweep: unfused 70.2k vs fused 68.5k tok/s),
    # looped stacks (MoE/deep) get pallas + fused; ring attention keeps
    # the standard loss
    import dataclasses
    from types import SimpleNamespace

    from opendiloco_tpu.trainer import _resolve_perf_defaults

    real_plan = build_mesh("NO_SHARD")
    dev = SimpleNamespace(device_kind="TPU v5 lite")
    devices = SimpleNamespace(flat=[dev])
    plan = SimpleNamespace(mesh=SimpleNamespace(devices=devices), sp_axis=None)

    tc = _resolve_perf_defaults(TrainerConfig(), tiny_cfg, plan)
    # dense <=16 layers: fully unrolled, so the fused kernel loses to
    # XLA's own lm-head fusion -- auto resolves fused OFF
    assert tc.attn_impl == "pallas" and tc.fused_loss is False
    assert tc.scan_unroll == tiny_cfg.num_hidden_layers

    # deep dense stack (>16 layers): looped scan keeps fused auto-ON
    deep_cfg = dataclasses.replace(tiny_cfg, num_hidden_layers=22)
    tc = _resolve_perf_defaults(TrainerConfig(), deep_cfg, plan)
    assert tc.attn_impl == "pallas" and tc.fused_loss is True

    tc = _resolve_perf_defaults(TrainerConfig(attn_impl="ring"), tiny_cfg, plan)
    assert tc.fused_loss is False

    # explicit xla attention: fused measured slower than unfused there
    tc = _resolve_perf_defaults(TrainerConfig(attn_impl="xla"), tiny_cfg, plan)
    assert tc.fused_loss is False

    # sequence-parallel mesh: full-sequence attention impls would gather
    # the whole sequence per device -> auto must pick ring; the fused
    # kernel is likewise not sequence-sharded -> off
    sp_plan = SimpleNamespace(mesh=plan.mesh, sp_axis="sp", pp_axis=None)
    tc = _resolve_perf_defaults(TrainerConfig(), tiny_cfg, sp_plan)
    assert tc.attn_impl == "ring" and tc.fused_loss is False

    # sp+pp composes (round 5): auto resolves to ring, which runs directly
    # on each pipeline stage's local sequence chunks
    sppp_plan = SimpleNamespace(mesh=plan.mesh, sp_axis="sp", pp_axis="pp")
    tc = _resolve_perf_defaults(TrainerConfig(), tiny_cfg, sppp_plan)
    assert tc.attn_impl == "ring" and tc.fused_loss is False

    # the explicit activation-sharding opt-in selects the fallback mode:
    # full-sequence attention, sp shards activations only
    tc = _resolve_perf_defaults(
        TrainerConfig(allow_sp_activation_sharding=True), tiny_cfg, sppp_plan
    )
    assert tc.attn_impl == "pallas" and tc.fused_loss is False

    # MoE composes with the fused kernel (the router aux rides
    # return_hidden): looped scan, so fused auto-ON
    moe_cfg = dataclasses.replace(tiny_cfg, num_experts=2)
    tc = _resolve_perf_defaults(TrainerConfig(), moe_cfg, plan)
    assert tc.attn_impl == "pallas" and tc.fused_loss is True

    # a real plan's mesh exposes the same .devices.flat[0] protocol
    assert hasattr(real_plan.mesh.devices.flat[0], "device_kind")


def test_scan_unroll_auto_resolution(tiny_cfg):
    # CPU auto -> 1 (unroll is a TPU bandwidth lever, measured on-chip)
    trainer = InnerTrainer(tiny_cfg, TrainerConfig(), build_mesh("NO_SHARD"))
    assert trainer.tc.scan_unroll == 1

    import dataclasses
    from types import SimpleNamespace

    from opendiloco_tpu.trainer import _resolve_perf_defaults

    dev = SimpleNamespace(device_kind="TPU v5 lite")
    plan = SimpleNamespace(
        mesh=SimpleNamespace(devices=SimpleNamespace(flat=[dev])), sp_axis=None
    )
    # TPU dense <= 16 layers: FULL unroll (round-5 live window: +6.8% tok/s)
    tc = _resolve_perf_defaults(TrainerConfig(), tiny_cfg, plan)
    assert tc.scan_unroll == tiny_cfg.num_hidden_layers
    # MoE and deep stacks keep the looped scan
    moe_cfg = dataclasses.replace(tiny_cfg, num_experts=2)
    assert _resolve_perf_defaults(TrainerConfig(), moe_cfg, plan).scan_unroll == 1
    deep_cfg = dataclasses.replace(tiny_cfg, num_hidden_layers=22)
    assert _resolve_perf_defaults(TrainerConfig(), deep_cfg, plan).scan_unroll == 1
    # explicit value passes through
    tc = _resolve_perf_defaults(TrainerConfig(scan_unroll=4), tiny_cfg, plan)
    assert tc.scan_unroll == 4


def test_scan_unroll_preserves_trajectory(tiny_cfg):
    # lax.scan unroll is a scheduling knob, not a math change: the unrolled
    # trajectory must equal the looped one bit-for-bit (fp32, CPU)
    def run(unroll):
        tc = TrainerConfig(
            lr=1e-3, warmup_steps=2, total_steps=100, precision="fp32",
            remat=False, scan_unroll=unroll,
        )
        trainer = InnerTrainer(tiny_cfg, tc, build_mesh("NO_SHARD"))
        state = trainer.init_state(jax.random.key(0))
        rng = np.random.default_rng(0)
        losses = []
        for _ in range(3):
            ids, labels = make_batch(rng, tiny_cfg.vocab_size)
            state, m = trainer.train_step(state, trainer.shard_batch(ids, labels, accum=2))
            losses.append(float(m["loss"]))
        return losses

    np.testing.assert_array_equal(run(1), run(4))


# what full remat keeps of the attention kernel, per device and step, in the
# two training cells of the benchmark (ISSUE 41): the cell's configuration,
# strategy, devices, global batch -> layers x (out + lse) bytes a device
ATTN_RESIDUALS_KEPT = {
    # 32 x (8 x 2048 x 15 x 64 bf16 + 8 x 15 x 2048 float32)
    "train-360m-h16": ("smollm2-360m", "NO_SHARD", 1, 8, 1_038_090_240),
    # 24 x (4 x 2048 x 32 x 64 bf16 + 4 x 32 x 2048 float32)
    "train-1.7b-fsdp4-h8": ("smollm2-1.7b", "FULL_SHARD", 4, 16, 830_472_192),
}


@pytest.mark.parametrize("cell", list(ATTN_RESIDUALS_KEPT))
@pytest.mark.parametrize(
    "remat,attn_impl,keeps",
    [(True, "pallas", True), ("dots", "pallas", True), (True, "xla", False), (False, "pallas", False)],
)
def test_attn_residual_bytes_of_the_training_cells(cell, remat, attn_impl, keeps):
    import json
    import pathlib

    from opendiloco_tpu.models.llama import LlamaConfig

    config, strategy, n, batch, kept = ATTN_RESIDUALS_KEPT[cell]
    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = LlamaConfig.from_dict(json.loads((root / "benchmark/configs" / f"{config}.json").read_text()))
    tc = TrainerConfig(precision="bf16-mixed", remat=remat, attn_impl=attn_impl)
    trainer = InnerTrainer(cfg, tc, build_mesh(strategy, devices=jax.devices()[:n]))
    assert trainer.attn_residual_bytes_of(batch, 2048) == (kept if keeps else 0)


@pytest.mark.parametrize("cell", list(ATTN_RESIDUALS_KEPT))
@pytest.mark.parametrize("attn_impl", ["pallas", "xla", "ring"])
def test_attn_scores_plan_of_the_training_cells(cell, attn_impl):
    """Seq 2,048 through the flash kernel's 1,024-row blocks: 136 of a
    head's 256 sub-tiles of 128 x 128 computed, 16 under a mask, 120
    skipped; no plan where the attention is not that kernel's."""
    import json
    import pathlib

    from opendiloco_tpu.models.llama import LlamaConfig

    config, strategy, n, _, _ = ATTN_RESIDUALS_KEPT[cell]
    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = LlamaConfig.from_dict(json.loads((root / "benchmark/configs" / f"{config}.json").read_text()))
    tc = TrainerConfig(precision="bf16-mixed", remat=True, attn_impl=attn_impl)
    trainer = InnerTrainer(cfg, tc, build_mesh(strategy, devices=jax.devices()[:n]))
    plan = trainer.attn_scores_plan_of(2048)
    if attn_impl != "pallas":
        assert plan is None
        return
    assert plan[:5] == (128, (128, 128), 136, 16, 120)
    assert (plan.computed_share, plan.masked_share) == (0.53125, 0.0625)


@pytest.mark.parametrize("cell", list(ATTN_RESIDUALS_KEPT))
@pytest.mark.parametrize("attn_impl", ["pallas", "xla", "ring"])
def test_attn_layout_of_the_training_cells(cell, attn_impl):
    """What the flash kernels are handed in the two train cells: rows, the
    360M's whole (15 and 5 heads of 64 fill no 128-lane block), the 1.7B's two
    heads a grid step; nothing where the attention is not that kernel's, or
    where it does not tile the sequence; heads under ``qk_norm_per_head``."""
    import dataclasses
    import json
    import pathlib

    from opendiloco_tpu.models.llama import LlamaConfig

    config, strategy, n, _, _ = ATTN_RESIDUALS_KEPT[cell]
    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = LlamaConfig.from_dict(json.loads((root / "benchmark/configs" / f"{config}.json").read_text()))
    tc = TrainerConfig(precision="bf16-mixed", remat=True, attn_impl=attn_impl)
    trainer = InnerTrainer(cfg, tc, build_mesh(strategy, devices=jax.devices()[:n]))
    if attn_impl != "pallas":
        assert trainer.attn_layout_of(2048) is None
        return
    held = {"smollm2-360m": "15,5 of 15,5", "smollm2-1.7b": "2,2 of 32,32"}[config]
    assert trainer.attn_layout_of(2048) == f"rows heads_a_step={held}"
    assert trainer.attn_layout_of(100) is None
    per_head = dataclasses.replace(cfg, qk_norm_per_head=True)
    trainer = InnerTrainer(per_head, tc, build_mesh(strategy, devices=jax.devices()[:n]))
    assert trainer.attn_layout_of(2048) == f"heads heads_a_step={held}"


def test_building_the_step_sets_the_attention_gauge(tiny_cfg, monkeypatch, caplog):
    """Tracing the train step (which is when it is built) leaves what the
    policy keeps for attention on the trainer, in the gauge and on the log
    line beside ``remat``."""
    from opendiloco_tpu import obs, trainer as trainer_module

    monkeypatch.setenv("ODTP_OBS", "test")
    obs.reset()
    trainer_module.log.addHandler(caplog.handler)  # the text logger does not propagate
    try:
        tc = TrainerConfig(precision="bf16-mixed", remat=True, attn_impl="pallas")
        trainer = InnerTrainer(tiny_cfg, tc, build_mesh("FULL_SHARD", devices=jax.devices()[:2]))
        assert trainer.attn_residual_bytes == 0
        jax.make_jaxpr(trainer._train_step_impl)(
            jax.eval_shape(trainer.init_state, jax.random.key(0)),
            {k: jax.ShapeDtypeStruct((2, 4, 128), np.int32) for k in ("input_ids", "labels")},
        )
        # 2 layers x 2 of 4 rows x 128 tokens x 4 heads x (16 bf16 + one float32)
        kept = 2 * 2 * 128 * 4 * (16 * 2 + 4)
        assert trainer.attn_residual_bytes == kept
        assert obs.tracer().gauges()[("train_attn_residual_bytes", ())] == kept
        assert f"remat=True train_attn_residual_bytes={kept}" in caplog.text
        # and what the kernels compute of a head's 128 x 128 scores: one
        # sub-tile, on the diagonal
        assert trainer.attn_scores_plan[:5] == (128, (128, 128), 1, 1, 0)
        assert obs.tracer().gauges()[("train_attn_scores_computed_share", ())] == 1.0
        assert obs.tracer().gauges()[("train_attn_scores_masked_share", ())] == 1.0
        assert "train_attn_scores=computed_share=1.00000 masked_share=1.00000 sub_tile=128" in caplog.text
        # and that they take the projections' rows, its four query heads and two KV heads a grid step
        assert trainer.attn_layout == "rows heads_a_step=4,2 of 4,2"
        assert obs.tracer().gauges()[("train_attn_rows", ())] == 1.0
        assert "train_attn_layout=rows heads_a_step=4,2 of 4,2 fused_loss" in caplog.text
    finally:
        trainer_module.log.removeHandler(caplog.handler)
        obs.reset()
