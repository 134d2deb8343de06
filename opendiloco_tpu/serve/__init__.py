"""Serving plane: continuous-batching inference off the live master weights.

The north-star system trains with DiLoCo while "serving heavy traffic"
from the same deployment; this package is that leg. A jitted engine runs
prefill + incremental decode over a slot-paged ring KV cache
(models/llama.py decode mode), a scheduler thread admits/retires
requests between decode steps (continuous batching), and weights
hot-swap from the outer plane's master snapshots — DiLoCo-fresh serving
(arXiv 2311.08105) with a ``max_stale_rounds`` bound, no request dropped
across a swap.

Wiring: ``build_serving(serve_cfg, model_cfg, params, diloco_opt)``
returns a started :class:`ServingPlane`; ``train.py`` calls it when
``config.serve.enabled`` so training and serving share one process (and
one obs registry / Prometheus endpoint).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax.numpy as jnp

from opendiloco_tpu.serve.engine import ServeEngine  # noqa: F401
from opendiloco_tpu.serve.kvcache import (  # noqa: F401
    HostKVTier,
    SlotAllocator,
    pick_bucket,
)
from opendiloco_tpu.serve.scheduler import ContinuousBatcher, Request  # noqa: F401
from opendiloco_tpu.serve.server import ServeServer  # noqa: F401

__all__ = [
    "ContinuousBatcher",
    "HostKVTier",
    "Request",
    "ServeEngine",
    "ServeServer",
    "ServingPlane",
    "SlotAllocator",
    "build_serving",
    "pick_bucket",
]


@dataclasses.dataclass
class ServingPlane:
    """The three live pieces, with one-call teardown (train.py finally)."""

    engine: ServeEngine
    batcher: ContinuousBatcher
    server: Optional[ServeServer]

    @property
    def port(self) -> Optional[int]:
        return None if self.server is None else self.server.port

    def stop(self) -> None:
        if self.server is not None:
            self.server.stop()
        self.batcher.stop()


def build_serving(
    serve_cfg,
    model_cfg,
    params,
    diloco_opt=None,
    *,
    compute_dtype=jnp.bfloat16,
    start_server: bool = True,
) -> ServingPlane:
    """Assemble engine + batcher (+ socket front-end) from a
    ``config.ServeConfig``. ``diloco_opt`` supplies the hot-swap source
    (``master_snapshot_wire`` / ``epoch``); None serves static weights."""
    import jax

    # host roundtrip decouples the engine from the trainer's mesh: live
    # train-state leaves may be sharded/committed, and the engine's jits
    # run single-device with their own fresh buffers
    params = jax.device_get(params)
    snapshot_fn = epoch_fn = None
    epoch = 0
    if diloco_opt is not None:
        snapshot_fn = diloco_opt.master_snapshot_wire
        epoch_fn = lambda: diloco_opt.epoch
        epoch = diloco_opt.epoch
    engine = ServeEngine(
        model_cfg,
        params,
        num_slots=serve_cfg.max_batch,
        max_context=serve_cfg.max_context,
        prefill_buckets=serve_cfg.prefill_buckets,
        compute_dtype=compute_dtype,
        epoch=epoch,
        snapshot_fn=snapshot_fn,
        epoch_fn=epoch_fn,
        max_stale_rounds=serve_cfg.max_stale_rounds,
        prefill_chunk=serve_cfg.prefill_chunk,
    )
    env_tier = os.environ.get("ODTP_KV_TIER")
    kv_tier_on = bool(int(env_tier)) if env_tier else serve_cfg.kv_tier
    kv_tier = None
    if kv_tier_on:
        kv_tier = HostKVTier(
            host_slots=int(
                os.environ.get("ODTP_KV_HOST_SLOTS")
                or serve_cfg.kv_host_slots
            ),
            codec=(
                os.environ.get("ODTP_KV_TIER_CODEC")
                or serve_cfg.kv_tier_codec
            ),
        )
    batcher = ContinuousBatcher(
        engine,
        max_queue=serve_cfg.max_queue,
        swap_every_steps=serve_cfg.swap_every_steps,
        prefix_cache=serve_cfg.prefix_cache,
        kv_tier=kv_tier,
    ).start()
    server = None
    if start_server:
        server = ServeServer(
            batcher, host=serve_cfg.host, port=serve_cfg.port
        )
    return ServingPlane(engine=engine, batcher=batcher, server=server)
