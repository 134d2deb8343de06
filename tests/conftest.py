"""Test env: force a virtual 8-device CPU platform before jax initializes.

Multi-chip hardware is not available in CI; sharding correctness is tested on
a CPU mesh (mirrors the reference's loopback-swarm strategy,
tests/test_diloco_hivemind.py:42-50 -- multi-node simulated locally).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest


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


import pytest as _pytest


@_pytest.fixture
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
