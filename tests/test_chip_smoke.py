"""chip_smoke.py rehearsed without the chip.

(a) the script itself refuses a machine without a TPU, quickly and without
a result line; (b) its phase functions run at ``2m``, seq 64, on CPU
devices -- the one-chip phases on one device, the ``--chips 4`` phases on
four virtual devices -- so wrong arguments, meshes and control flow are
caught before chip time is spent on them; (c) the compile-cache helper.

What only the chip can show (Pallas in the program, state on the device,
the tolerances at published width) is asserted by chip_smoke.main() there.
"""

import os
import subprocess
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from opendiloco_tpu.utils import compile_cache  # noqa: E402

MODEL, SEQ = "2m", 64


def test_refuses_a_machine_without_a_tpu():
    t0 = time.monotonic()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert r.stdout.strip() == ""  # no '"ok": true', no result of any kind
    assert "not a TPU" in r.stderr
    assert time.monotonic() - t0 < 60


def test_train_serve_phase_on_one_cpu_device():
    facts = chip_smoke.phase_train_serve(
        MODEL, SEQ, jax.devices()[:1], seed=0,
        batch_sizes=(4,), local_steps=3, slots=4, buckets=(8, 32),
        prompt_lens=(5, 20), new_tokens=12,
    )
    # what resolves differently on the chip is reported, not assumed
    assert facts["attn_impl"] == "xla" and facts["outer_placement"] == "host"
    assert facts["serve"]["platform"] == "cpu"
    assert facts["serve"]["decode_kernel"] == "xla"
    assert facts["outer_epoch"] == 2 and len(facts["losses"]) == 6
    assert len(facts["serve"]["requests"]) == 4
    assert len(facts["serve"]["weight_epochs"]) >= 2


def test_compare_train_step_phase_on_one_cpu_device():
    facts = chip_smoke.phase_compare_train_step(MODEL, SEQ, jax.devices()[:1], seed=0)
    assert facts["loss_rel_diff"] <= chip_smoke.TRAIN_LOSS_RTOL


def test_compare_decode_kernels_phase_on_one_cpu_device():
    facts = chip_smoke.phase_compare_decode_kernels(
        MODEL, SEQ, jax.devices()[:1], seed=0, slots=4
    )
    for dtype, tol in chip_smoke.LOGITS_REL_L2.items():
        assert facts[dtype]["decode"]["rel_l2"] <= tol


def test_compare_engines_phase_on_one_cpu_device():
    facts = chip_smoke.phase_compare_engines(
        MODEL, SEQ, jax.devices()[:1], seed=0, buckets=(8, 32), new_tokens=12
    )
    for dtype in ("float32", "bfloat16"):
        assert facts[dtype]["prefix_hits"] > 0
    assert facts["float32"]["prefix_identical_to_plain"]
    assert jax.config.jax_default_matmul_precision is None  # restored


def test_sharded_phase_on_four_cpu_devices():
    devices = jax.devices()[:4]
    facts = chip_smoke.phase_sharded(
        MODEL, MODEL, SEQ, devices, seed=0, batch=8, steps=3,
        big_batch=8, big_accum=2, big_local_steps=2, big_steps=3,
    )
    assert facts["twin"]["max_rel_diff"] <= chip_smoke.SHARDED_LOSS_RTOL
    assert sorted(facts["big"]["state_bytes_per_device"]) == [d.id for d in devices]
    assert facts["big"]["outer_epoch"] == 1


def test_galaxy_phase_on_four_cpu_devices():
    devices = jax.devices()[:4]
    facts = chip_smoke.phase_galaxy(MODEL, SEQ, devices, seed=0, batch=4)
    assert [w["device"] for w in facts["workers"]] == [d.id for d in devices]
    assert all(w["num_peers"] == [4, 4] for w in facts["workers"])
    assert len({tuple(w["master_hashes"]) for w in facts["workers"]}) == 1


@pytest.fixture
def restored_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_helper_leaves_a_set_variable_alone(monkeypatch, restored_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # set nothing in code


def test_cache_helper_defaults_to_the_checkout(monkeypatch, restored_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
