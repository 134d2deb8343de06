"""Replica runner: one serving engine fed by the fleet push channel.

A replica is ``ServeEngine + ContinuousBatcher + ServeServer`` plus a
push listener. The listener applies keyframe/delta frames
(:func:`fleet.publisher.apply_frame`) into a host-side flat f32 shadow
under a lock, and the engine adopts fully-applied epochs through its
normal ``snapshot_fn``/``maybe_swap`` path between decode steps — so
weight rebinds stay on the scheduler thread exactly like single-process
serving, and a half-pushed fragment set is never visible to decode.

Staleness has two levels here:

- the engine's ``epoch_fn`` tracks the *mailbox* (last fully-applied
  push), so ``maybe_swap`` adopts new weights eagerly;
- the replica's own :meth:`staleness` tracks the *trainer* epoch (pings
  advance it even when weight pushes stall) against
  ``max_stale_rounds`` — the health bound the router and overseer see.

Run in-process (tests, ``fleet.inprocess``) or as a subprocess::

    python -m opendiloco_tpu.fleet.replica --spec spec.json

which prints one ready line of JSON (``replica_id``, bound
``serve_port``/``push_port``, ``pid``, and the ``platform`` /
``device_kind`` JAX gave it) on stdout and serves until killed. The
subprocess takes whatever platform its environment resolves to — a rig
that means CPU says ``JAX_PLATFORMS=cpu``. Replica death is the router's problem, not ours: SIGKILL simply
stops the sockets answering.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import socket
import threading
from typing import Optional

from opendiloco_tpu import obs
from opendiloco_tpu.fleet.publisher import FleetFrameError, apply_frame
from opendiloco_tpu.fleet.wire import FleetWireError, recv_frame, send_frame

log = logging.getLogger(__name__)


class Replica:
    def __init__(
        self,
        replica_id: str,
        model_cfg,
        *,
        num_slots: int = 4,
        max_context: int = 128,
        prefill_buckets=(16, 64),
        max_queue: int = 1024,
        max_stale_rounds: int = 2,
        host: str = "127.0.0.1",
        serve_port: int = 0,
        push_port: int = 0,
        prefix_cache: bool = True,
        kv_tier: bool = False,
        kv_tier_codec: str = "none",
        kv_host_slots: int = 32,
        compute_dtype=None,
        seed: int = 0,
        start_push_server: bool = True,
    ):
        import jax
        import jax.numpy as jnp

        from opendiloco_tpu.models.llama import init_params
        from opendiloco_tpu.serve.engine import ServeEngine
        from opendiloco_tpu.serve.kvcache import HostKVTier
        from opendiloco_tpu.serve.scheduler import ContinuousBatcher
        from opendiloco_tpu.serve.server import ServeServer, bind_with_fallback

        self.replica_id = str(replica_id)
        self.max_stale_rounds = int(max_stale_rounds)
        self.trainer_epoch = 0
        self._lock = threading.Lock()
        # mailbox: last fully-applied push (flat f32 leaves). The engine
        # pulls it between decode steps; weights stay random until the
        # first keyframe lands (ready() gates the router/bench on that).
        self._leaves: Optional[list] = None
        self._epoch = -1
        params = init_params(jax.random.PRNGKey(seed), model_cfg)
        self._shapes = [tuple(x.shape) for x in jax.tree.leaves(params)]
        self.engine = ServeEngine(
            model_cfg,
            params,
            num_slots=num_slots,
            max_context=max_context,
            prefill_buckets=prefill_buckets,
            compute_dtype=compute_dtype or jnp.float32,
            epoch=-1,
            snapshot_fn=self._pull,
            epoch_fn=lambda: self._epoch,
            max_stale_rounds=0,  # adopt every fully-applied push eagerly
        )
        self.batcher = ContinuousBatcher(
            engine=self.engine,
            max_queue=max_queue,
            prefix_cache=prefix_cache,
            kv_tier=(
                HostKVTier(
                    host_slots=int(kv_host_slots), codec=str(kv_tier_codec)
                )
                if kv_tier
                else None
            ),
        ).start()
        # explicit ports mean a respawn at a known address: retry the
        # bind while the dying predecessor's listener tears down instead
        # of falling back to an ephemeral port nobody dials
        bind_retry_s = 3.0 if serve_port else 0.0
        self.server = ServeServer(
            self.batcher,
            host=host,
            port=serve_port,
            identity=self._identity,
            bind_retry_s=bind_retry_s,
        )
        tr = obs.tracer()
        if tr is not None:
            tr.set_identity(worker=self.replica_id, role="fleet-replica")
        rt = obs.reqtrace.ring()
        if rt is not None:
            rt.set_identity(self.replica_id)
        self._stop = threading.Event()
        self._push_sock: Optional[socket.socket] = None
        self.push_port = 0
        if start_push_server:
            self._push_sock = bind_with_fallback(
                host, push_port, "fleet-push",
                retry_s=3.0 if push_port else 0.0,
            )
            self._push_sock.listen(8)
            self.push_port = self._push_sock.getsockname()[1]
            threading.Thread(
                target=self._push_accept,
                name=f"odtp-fleet-push-{self.replica_id}",
                daemon=True,
            ).start()

    # -- weight state --------------------------------------------------------

    def _pull(self) -> tuple[int, list, str]:
        """Engine snapshot_fn: the mailbox as raw-f32 install_wire blobs.
        Copies under the lock so a concurrent push never mutates bytes
        mid-install."""
        with self._lock:
            if self._leaves is None:
                return self._epoch, [], "none"
            blobs = [
                (lf.tobytes(), {}, shape)
                for lf, shape in zip(self._leaves, self._shapes)
            ]
            return self._epoch, blobs, "none"

    def apply(self, meta: dict, payload: bytes) -> int:
        """Apply one weight/ping frame; returns the mailbox epoch."""
        kind = meta.get("kind")
        with self._lock:
            if kind == "ping":
                self.trainer_epoch = max(
                    self.trainer_epoch, int(meta.get("tepoch", 0))
                )
                return self._epoch
            if kind == "delta" and int(meta["base_epoch"]) != self._epoch:
                raise FleetFrameError(
                    f"delta base epoch {meta['base_epoch']} != replica "
                    f"epoch {self._epoch} (need a keyframe)"
                )
            leaves, epoch = apply_frame(self._leaves, meta, payload)
            self._leaves = leaves
            # every frame is self-contained (a keyframe, or one staggered
            # fragment's whole delta), so the mailbox epoch advances per
            # frame and the engine never sees a half-applied push
            self._epoch = epoch
            self.trainer_epoch = max(
                self.trainer_epoch, int(meta.get("tepoch", epoch))
            )
            obs.count("fleet_frames_applied", kind=kind)
            return self._epoch

    # -- health --------------------------------------------------------------

    def ready(self) -> bool:
        return self.engine.weights_epoch >= 0

    def staleness(self) -> int:
        """Outer rounds the SERVING weights lag the trainer (pings keep
        the trainer epoch moving even when weight pushes stall)."""
        return max(0, self.trainer_epoch - self.engine.weights_epoch)

    def stale(self) -> bool:
        return self.staleness() > self.max_stale_rounds

    def _identity(self) -> dict:
        return {
            "worker": self.replica_id,
            "replica": self.replica_id,
            "trainer_epoch": self.trainer_epoch,
            "staleness": self.staleness(),
            "max_stale_rounds": self.max_stale_rounds,
            "ready": self.ready(),
            "stale": self.stale(),
        }

    def status(self) -> dict:
        return {
            **self._identity(),
            "weights_epoch": self.engine.weights_epoch,
            "mailbox_epoch": self._epoch,
            "serve_port": self.server.port,
            "push_port": self.push_port,
            "free_slots": self.batcher.slots.num_free,
            "completed": self.batcher.completed,
        }

    def health(self) -> dict:
        """Load/health vector the autoscaler steers on (queue depth,
        occupancy, p99, staleness). Rides every push-channel reply, so
        the manager's view refreshes at the push cadence even when the
        obs plane is unarmed."""
        out = {
            **self.batcher.health(),
            "staleness": self.staleness(),
            "stale": self.stale(),
            "ready": self.ready(),
        }
        # prefix-cache directory advertisement: host-tier resident prefix
        # hashes at the current weights epoch. A NEW dict key on the
        # health frame — old routers/managers ignore unknown keys, so
        # mixed fleets interoperate (pinned by tests/test_fleet interop)
        prefixes = self.batcher.resident_prefixes()
        if prefixes:
            out["prefixes"] = prefixes
        return out

    def rollup(self) -> Optional[dict]:
        """Overseer health vector for this replica (None when obs is
        unarmed) — the manager merges it into the trainer's matrix."""
        ov = obs.overseer.plane()
        if ov is None:
            return None
        h = self.batcher.health()
        return ov.rollup(
            role="fleet-replica",
            replica=self.replica_id,
            staleness=self.staleness(),
            weights_epoch=self.engine.weights_epoch,
            stale=self.stale(),
            queue_depth=h["queue_depth"],
            occupancy=h["occupancy"],
            p99_ms=h["p99_ms"],
            # cold-tier load (absent when the tier is off): odtp_top's
            # tier% column keys on this
            **(
                {"tier_occupancy": h["tier_occupancy"]}
                if "tier_occupancy" in h
                else {}
            ),
        )

    # -- push channel --------------------------------------------------------

    def _push_accept(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._push_sock.accept()
            except OSError:
                return
            threading.Thread(
                target=self._push_serve, args=(conn,), daemon=True
            ).start()

    def _push_serve(self, conn: socket.socket) -> None:
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            while not self._stop.is_set():
                try:
                    kind, meta, payload = recv_frame(conn)
                except (FleetWireError, OSError, ValueError):
                    return
                try:
                    if kind == "hello":
                        reply = {
                            "replica": self.replica_id,
                            "epoch": self._epoch,
                            "weights_epoch": self.engine.weights_epoch,
                        }
                    elif kind == "reqtrace":
                        # request-trace pull: snapshot of this replica's
                        # ring (odtp_top --requests, obs_report merge).
                        # Empty when the plane is unarmed; old peers that
                        # predate the frame kind answer "error", which
                        # callers treat as "no reqtrace plane".
                        rt = obs.reqtrace.ring()
                        reply = {
                            "replica": self.replica_id,
                            "reqtrace": (
                                rt.snapshot(
                                    recent=int(meta.get("recent", 32))
                                )
                                if rt is not None
                                else None
                            ),
                        }
                    else:
                        epoch = self.apply(meta, payload)
                        reply = {
                            "replica": self.replica_id,
                            "epoch": epoch,
                            "weights_epoch": self.engine.weights_epoch,
                            "staleness": self.staleness(),
                            "stale": self.stale(),
                            "ready": self.ready(),
                            "free_slots": self.batcher.slots.num_free,
                            "health": self.health(),
                        }
                        vec = self.rollup()
                        if vec is not None:
                            reply["rollup"] = vec
                    send_frame(conn, "ok", reply)
                except FleetFrameError as e:
                    send_frame(conn, "error", {"error": str(e)})
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def stop(self) -> None:
        self._stop.set()
        if self._push_sock is not None:
            try:
                self._push_sock.close()
            except OSError:
                pass
        self.server.stop()
        self.batcher.stop()


# -- subprocess entry ---------------------------------------------------------


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spec", required=True, help="JSON replica spec file")
    args = ap.parse_args(argv)
    from opendiloco_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    with open(args.spec) as f:
        spec = json.load(f)

    from opendiloco_tpu.models.llama import LlamaConfig

    model_cfg = LlamaConfig.from_dict(spec["model"])
    serve = spec.get("serve", {})
    replica = Replica(
        spec["replica_id"],
        model_cfg,
        num_slots=int(serve.get("num_slots", 4)),
        max_context=int(serve.get("max_context", 128)),
        prefill_buckets=tuple(serve.get("prefill_buckets", (16, 64))),
        max_queue=int(serve.get("max_queue", 1024)),
        prefix_cache=bool(serve.get("prefix_cache", True)),
        kv_tier=bool(serve.get("kv_tier", False)),
        kv_tier_codec=str(serve.get("kv_tier_codec", "none")),
        kv_host_slots=int(serve.get("kv_host_slots", 32)),
        max_stale_rounds=int(spec.get("max_stale_rounds", 2)),
        host=spec.get("host", "127.0.0.1"),
        serve_port=int(spec.get("serve_port", 0)),
        push_port=int(spec.get("push_port", 0)),
        seed=int(spec.get("seed", 0)),
    )
    print(
        json.dumps(
            {
                "replica_id": replica.replica_id,
                "serve_port": replica.server.port,
                "push_port": replica.push_port,
                "pid": os.getpid(),
                # where this replica really runs: the platform JAX gave
                # it, never assumed by the parent
                "platform": replica.engine.device.platform,
                "device_kind": replica.engine.device.device_kind,
            }
        ),
        flush=True,
    )
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    replica.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
