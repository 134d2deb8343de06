"""Test env: force a virtual 8-device CPU platform before jax initializes.

Multi-chip hardware is not available in CI; sharding correctness is tested on
a CPU mesh (mirrors the reference's loopback-swarm strategy,
tests/test_diloco_hivemind.py:42-50 -- multi-node simulated locally).

The session compiles a tiny program once: one persistent compilation cache
for the run, in a directory of the system's temporary one that is made empty
when the run starts, shared by the workers of an xdist run (the controller
makes it and hands each worker its path), and removed when the run ends. Every
engine and closure a test builds is a new function to ``jax.jit`` and the same
program to the cache. Never a directory that outlives the run: the time must
not depend on what an earlier run left. A worker that meets an entry another
is still writing warns and compiles, which is jax's own fallback.
"""

import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest

# the chip-compile files' helper and fixtures (their ``pytest_plugins``): its
# asserts are rewritten as a test file's are
pytest.register_assert_rewrite("described_chip")


def pytest_configure(config):
    import jax

    worker = getattr(config, "workerinput", None)
    if worker is None:  # a plain run, or an xdist run's controller
        path = config._odtp_compile_cache = tempfile.mkdtemp(prefix="odtp-tests-jax-cache-")
    else:
        path = worker["odtp_compile_cache"]
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    node.workerinput["odtp_compile_cache"] = node.config._odtp_compile_cache


def pytest_unconfigure(config):
    made = getattr(config, "_odtp_compile_cache", None)
    if made:
        shutil.rmtree(made, ignore_errors=True)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def tiny_cfg():
    from opendiloco_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
    )


@pytest.fixture
def interpret_pallas_fused(monkeypatch):
    """Interpret-mode pallas for the fused-xent module (shared by attention
    and pipeline tests)."""
    import jax.experimental.pallas as pl

    from opendiloco_tpu.ops import fused_xent

    orig = pl.pallas_call

    def patched(*args, **kwargs):
        kwargs["interpret"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fused_xent.pl, "pallas_call", patched)
    return patched
