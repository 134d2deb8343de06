"""Serving-fleet tests: delta-push weight sync, replica runner, router.

Oracles:
- delta-push is a compression of the push CHANNEL, never of the replica
  state contract: a replica following keyframe + staggered-fragment
  delta frames holds weights bit-identical to the publisher's shadow at
  EVERY epoch, and bit-identical to a from-scratch keyframe install at
  every keyframe boundary — for both sub-8-bit codecs, with and without
  error feedback
- a keyframe wholesale-replaces state, so late-join onboarding equals a
  from-scratch install by construction (and the test pins it)
- the staggered schedule keeps per-epoch delta bytes at a small fraction
  of the fp16 full-snapshot equivalent (the bench gates <= 1/4; the
  schedule lands ~1/(4*n_frag))
- staleness is bounded and *observable*: when weight pushes stall but
  pings keep arriving, the replica's reported staleness crosses
  ``max_stale_rounds`` and /healthz flips ``stale`` — serving never
  silently drifts arbitrarily far behind the trainer
- replica death is the router's non-event: an abrupt connection drop
  (what SIGKILL looks like from the other end) re-dispatches the
  in-flight request and the client still gets one answer — zero drops
  (the bench's chaos leg SIGKILLs a real subprocess; here fake backends
  keep it fast)
- prefix affinity routes a repeated system prompt to the replica whose
  KV cache is warm, unless that replica is clearly busier
- a client disconnect mid-generation retires the slot instead of
  decoding into a dead socket, and replica identity rides /healthz
"""
import json
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

from opendiloco_tpu.config import FleetConfig
from opendiloco_tpu.fleet.publisher import (
    DeltaPublisher,
    FleetFrameError,
    apply_frame,
)
from opendiloco_tpu.fleet.router import FleetRouter
from opendiloco_tpu.fleet.wire import FleetWireError, recv_frame, send_frame

# ---------------------------------------------------------------------------
# publisher <-> apply_frame: bit-exact delta round trip (numpy only)
# ---------------------------------------------------------------------------


def _masters(rng, shapes=((512,), (33, 7), (900,))):
    return [rng.standard_normal(s).astype(np.float32) for s in shapes]


def _walk(masters, rng, scale=0.01):
    for m in masters:
        m += rng.standard_normal(m.shape).astype(np.float32) * scale


@pytest.mark.parametrize("codec", ["blockwise4bit", "topk"])
@pytest.mark.parametrize("ef", [True, False])
def test_delta_roundtrip_bit_exact(codec, ef):
    """A follower applying the publisher's frames is bit-identical to the
    publisher's shadow at every epoch — keyframes AND staggered deltas,
    both codecs, with and without error feedback."""
    rng = np.random.default_rng(0)
    masters = _masters(rng)
    epoch = [0]
    pub = DeltaPublisher(
        lambda: (epoch[0], masters),
        codec=codec,
        fragments=2,
        keyframe_every=4,
        error_feedback=ef,
    )
    leaves = None
    kinds = []
    for e in range(10):
        epoch[0] = e
        if e:
            _walk(masters, rng)
        frames = pub.frames("r0")
        assert len(frames) == 1  # one keyframe or one staggered fragment
        for meta, payload in frames:
            kinds.append(meta["kind"])
            leaves, got_epoch = apply_frame(leaves, meta, payload)
            assert got_epoch == e
        shadow = pub._channels["r0"].shadow
        for a, b in zip(leaves, shadow):
            np.testing.assert_array_equal(a, b)
        assert pub.frames("r0") == []  # already current -> nothing to ship
    # keyframe cadence: fresh at 0, then every keyframe_every epochs
    assert [k == "keyframe" for k in kinds] == [
        e % 4 == 0 for e in range(10)
    ]


@pytest.mark.parametrize("codec", ["blockwise4bit", "topk"])
def test_keyframe_boundary_matches_fresh_install(codec):
    """At every keyframe boundary a long-time delta follower and a
    replica onboarding from scratch hold byte-identical weights — the
    acceptance bar for late-join/rejoin."""
    rng = np.random.default_rng(1)
    masters = _masters(rng)
    epoch = [0]
    pub = DeltaPublisher(
        lambda: (epoch[0], masters), codec=codec, fragments=3, keyframe_every=3
    )
    follower = None
    for e in range(9):
        epoch[0] = e
        if e:
            _walk(masters, rng)
        for meta, payload in pub.frames("old"):
            follower, _ = apply_frame(follower, meta, payload)
        if e % 3 == 0:
            fresh_id = f"fresh{e}"
            frames = pub.frames(fresh_id)
            assert [m["kind"] for m, _ in frames] == ["keyframe"]
            fresh, fe = apply_frame(None, *frames[0])
            assert fe == e
            for a, b in zip(follower, fresh):
                np.testing.assert_array_equal(a, b)


def test_delta_bytes_within_snapshot_budget():
    """Per-epoch delta push cost stays at a small fraction of the fp16
    full-snapshot equivalent (the SERVE_FLEET_BENCH gate is <= 1/4; the
    staggered schedule lands ~1/(4*n_frag))."""
    rng = np.random.default_rng(2)
    masters = _masters(rng, shapes=((4096,), (512, 8), (9000,)))
    epoch = [0]
    pub = DeltaPublisher(
        lambda: (epoch[0], masters), codec="blockwise4bit", fragments=4,
        keyframe_every=64,  # measure deltas, not keyframes
    )
    for e in range(9):
        epoch[0] = e
        if e:
            _walk(masters, rng)
        pub.frames("r0")  # byte accounting happens at encode time
    st = pub.stats()["replicas"]["r0"]
    assert st["delta_frames"] == 8 and st["keyframe_frames"] == 1
    per_epoch = st["delta_bytes"] / st["delta_frames"]
    assert per_epoch <= pub.fp16_snapshot_bytes / 4


def test_delta_before_keyframe_rejected():
    with pytest.raises(FleetFrameError):
        apply_frame(None, {"kind": "delta", "codec": "topk", "epoch": 1,
                           "leaves": []}, b"")
    with pytest.raises(FleetFrameError):
        apply_frame([], {"kind": "ping"}, b"")


def test_publisher_reset_rekeyframes():
    """reset() forgets the shadow (replica restarted): the next push is a
    keyframe regardless of cadence — the hello-handshake re-onboarding
    path the manager drives."""
    rng = np.random.default_rng(3)
    masters = _masters(rng)
    epoch = [0]
    pub = DeltaPublisher(
        lambda: (epoch[0], masters), fragments=2, keyframe_every=100
    )
    assert pub.frames("r0")[0][0]["kind"] == "keyframe"
    epoch[0] = 1
    _walk(masters, rng)
    assert pub.frames("r0")[0][0]["kind"] == "delta"
    assert pub.channel_epoch("r0") == 1
    pub.reset("r0")
    assert pub.channel_epoch("r0") == -1
    assert pub.frames("r0")[0][0]["kind"] == "keyframe"


def test_keyframe_every_env_override(monkeypatch):
    monkeypatch.setenv("ODTP_FLEET_KEYFRAME_EVERY", "2")
    pub = DeltaPublisher(lambda: (0, []), keyframe_every=8)
    assert pub.keyframe_every == 2


# ---------------------------------------------------------------------------
# wire frames
# ---------------------------------------------------------------------------


def test_fleet_wire_roundtrip_and_bad_magic():
    a, b = socket.socketpair()
    try:
        payload = bytes(range(256)) * 3
        send_frame(a, "delta", {"kind": "delta", "epoch": 7}, payload)
        kind, meta, got = recv_frame(b, timeout=5.0)
        assert kind == "delta" and meta["epoch"] == 7 and got == payload
        a.sendall(b"JUNKJUNKJUNK")
        with pytest.raises(FleetWireError):
            recv_frame(b, timeout=5.0)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


def test_fleet_config_validation():
    cfg = FleetConfig(enabled=True, replicas=3, prefill_buckets="8,32")
    assert cfg.prefill_buckets == [8, 32]
    with pytest.raises(ValueError):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError):
        FleetConfig(prefill_buckets=[512], max_context=256)
    with pytest.raises(ValueError):
        FleetConfig(codec="fp97")


# ---------------------------------------------------------------------------
# router over fake replicas (jax-free): re-dispatch, rejoin, affinity
# ---------------------------------------------------------------------------


class FakeReplica:
    """A thread-backed stand-in for a serving replica: answers JSONL
    generate lines and HTTP /healthz on one port, like ServeServer. Can
    die abruptly on its first request (what SIGKILL looks like from the
    router's side of the socket) or report itself stale."""

    def __init__(self, rid, *, port=0, die_on_request=False, stale=False):
        self.rid = rid
        self.die_on_request = die_on_request
        self.stale = stale
        self.served = 0
        self._stop = threading.Event()
        self._conns = set()
        self._sock = socket.socket()
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", port))
        self._sock.listen(16)
        self.port = self._sock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,), daemon=True).start()

    def _serve(self, conn):
        self._conns.add(conn)
        try:
            buf = conn.recv(65536)
            if not buf:
                return
            if buf[:4] in (b"GET ", b"HEAD"):
                body = (json.dumps({
                    "ok": True, "ready": True, "stale": self.stale,
                }) + "\n").encode()
                conn.sendall(
                    (f"HTTP/1.0 200 OK\r\nContent-Length: {len(body)}"
                     "\r\n\r\n").encode() + body
                )
                return
            while True:
                while b"\n" in buf:
                    line, _, buf = buf.partition(b"\n")
                    if self.die_on_request:
                        self.kill()  # vanish mid-request, reply never sent
                        return
                    payload = json.loads(line.decode())
                    out = {"tokens": [1, 2, 3], "replica": self.rid}
                    if payload.get("id") is not None:
                        out["id"] = payload["id"]
                    self.served += 1
                    conn.sendall((json.dumps(out) + "\n").encode())
                chunk = conn.recv(65536)
                if not chunk:
                    return
                buf += chunk
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def kill(self):
        """SIGKILL as seen from the other end: listener AND every live
        connection drop at once."""
        self._stop.set()
        for s in [self._sock, *list(self._conns)]:
            try:
                s.close()
            except OSError:
                pass


def test_router_redispatch_drops_nothing_on_replica_death():
    """The first backend dies mid-request (abrupt close, no reply): the
    router marks it dead, re-dispatches, and every client request still
    gets exactly one answer — zero drops."""
    a = FakeReplica("a", die_on_request=True)
    b = FakeReplica("b")
    router = FleetRouter(port=0, probe_interval_s=30.0, request_timeout=10.0)
    try:
        router.add_replica("a", "127.0.0.1", a.port)
        router.add_replica("b", "127.0.0.1", b.port)
        outs = [
            router.dispatch({"prompt": [1, 2, 3], "max_new_tokens": 3, "id": i})
            for i in range(6)
        ]
        assert all(o.get("tokens") == [1, 2, 3] for o in outs)
        assert [o["id"] for o in outs] == list(range(6))
        st = router.stats()
        assert st["deaths"] == 1 and st["redispatches"] >= 1
        assert st["replicas"]["a"]["dead"] and not st["replicas"]["b"]["dead"]
        assert b.served == 6
    finally:
        router.stop()
        a.kill()
        b.kill()


def test_router_probe_revives_rejoined_replica():
    """A dead backend that comes back on the same port resumes taking
    traffic with no registration call — the health probe notices."""
    a = FakeReplica("a")
    router = FleetRouter(port=0, probe_interval_s=0.1, request_timeout=5.0)
    try:
        router.add_replica("a", "127.0.0.1", a.port)
        assert router.dispatch({"prompt": [1], "max_new_tokens": 1})["tokens"]
        port = a.port
        a.kill()
        out = router.dispatch({"prompt": [1], "max_new_tokens": 1})
        assert "error" in out  # every replica dead -> honest failure
        assert router.stats()["replicas"]["a"]["dead"]
        # "respawned" replica, same address (retry while the kernel
        # releases the old connections' hold on the port)
        deadline = time.monotonic() + 10
        while True:
            try:
                a = FakeReplica("a", port=port)
                break
            except OSError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.1)
        deadline = time.monotonic() + 10
        out = {"error": "never revived"}
        while time.monotonic() < deadline:
            out = router.dispatch({"prompt": [1], "max_new_tokens": 1})
            if "tokens" in out:
                break
            time.sleep(0.05)
        assert out.get("tokens") == [1, 2, 3]
        assert not router.stats()["replicas"]["a"]["dead"]
    finally:
        router.stop()
        a.kill()


def test_router_prefers_fresh_over_stale():
    """A replica self-reporting stale (pushes stalled past its bound)
    only takes traffic when nothing fresh is alive."""
    a = FakeReplica("a", stale=True)
    b = FakeReplica("b")
    router = FleetRouter(port=0, probe_interval_s=0.1, request_timeout=5.0)
    try:
        router.add_replica("a", "127.0.0.1", a.port)
        router.add_replica("b", "127.0.0.1", b.port)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.stats()["replicas"]["a"]["stale"]:
                break
            time.sleep(0.05)
        assert router.stats()["replicas"]["a"]["stale"]
        for _ in range(4):
            assert router.dispatch({"prompt": [1]}).get("tokens")
        assert b.served == 4 and a.served == 0
        b.kill()  # stale beats dead: the fallback still answers
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.stats()["replicas"]["b"]["dead"]:
                break
            time.sleep(0.05)
        assert router.dispatch({"prompt": [1]}).get("tokens")
        assert a.served >= 1
    finally:
        router.stop()
        a.kill()
        b.kill()


def test_router_prefix_affinity():
    """A request sharing a long prompt prefix with a replica's recent
    traffic routes there (warm KV), unless that replica is clearly
    busier than the least-loaded one."""
    router = FleetRouter(port=0, probe_interval_s=30.0)
    try:
        router.add_replica("a", "127.0.0.1", 1)  # never dialed: _pick only
        router.add_replica("b", "127.0.0.1", 2)
        warm = router._backends["b"]
        cold = router._backends["a"]
        sysp = list(range(100, 120))
        warm.recent.append(sysp + [7, 8])

        # shared 20-token prefix -> affinity wins over least-loaded
        warm.inflight = 1  # slightly busier, within the slack
        assert router._pick(sysp + [40, 41], set()) is warm
        # short prompt -> plain least-loaded
        assert router._pick([1, 2], set()) is cold
        # unrelated long prompt -> least-loaded
        assert router._pick(list(range(500, 520)), set()) is cold
        # warm replica clearly busier -> affinity yields
        warm.inflight = cold.inflight + router.affinity_max_extra_inflight + 1
        assert router._pick(sysp + [40, 41], set()) is cold
    finally:
        router.stop()


def test_router_http_frontend_health_and_stats():
    a = FakeReplica("a")
    router = FleetRouter(port=0, probe_interval_s=30.0, request_timeout=5.0)
    try:
        router.add_replica("a", "127.0.0.1", a.port)
        body = json.dumps({"prompt": [5, 6], "max_new_tokens": 2}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/generate", data=body
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["tokens"] == [1, 2, 3]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/healthz", timeout=10
        ) as r:
            health = json.loads(r.read())
        assert health["ok"] and health["live"] == 1
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/stats", timeout=10
        ) as r:
            stats = json.loads(r.read())
        assert stats["replicas"]["a"]["dispatched"] == 1
    finally:
        router.stop()
        a.kill()


# ---------------------------------------------------------------------------
# replica + manager end to end (jax)
# ---------------------------------------------------------------------------


def test_build_fleet_refuses_subprocess_replicas_beside_a_tpu(
    tiny_cfg, monkeypatch
):
    """A chip belongs to one process: a trainer that holds a TPU must not
    quietly get CPU replicas (nor children that hang reaching the chip)."""
    import jax

    from opendiloco_tpu.config import FleetConfig
    from opendiloco_tpu.fleet import build_fleet

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="chip belongs to one process"):
        build_fleet(FleetConfig(enabled=True, replicas=1), tiny_cfg, params=None)


ENGINE_GEOM = dict(num_slots=4, max_context=64, prefill_buckets=(8, 16, 32))


def test_fleet_end_to_end_inprocess(tiny_cfg):
    """Publisher -> manager push channel -> in-process replica -> router:
    the replica onboards from a keyframe, follows staggered delta pushes
    epoch by epoch, serves through the router, and when pushes stall
    (pings only) its reported staleness crosses max_stale_rounds and
    /healthz flips stale — the acceptance staleness bound."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.fleet import FleetManager
    from opendiloco_tpu.fleet.replica import Replica
    from opendiloco_tpu.models.llama import init_params

    params = init_params(jax.random.PRNGKey(1), tiny_cfg)
    masters = [np.array(x, np.float32) for x in jax.tree.leaves(params)]
    epoch = [0]
    pub = DeltaPublisher(
        lambda: (epoch[0], masters), codec="blockwise4bit", fragments=4,
        keyframe_every=8,
    )
    router = FleetRouter(port=0, probe_interval_s=0.2, request_timeout=60.0)
    mgr = FleetManager(pub, router, push_interval_s=0.05)
    rep = Replica("r0", tiny_cfg, max_stale_rounds=2, max_queue=64,
                  **ENGINE_GEOM)

    def wait(pred, t=60.0):
        deadline = time.monotonic() + t
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.02)
        return False

    try:
        mgr.attach("r0", "127.0.0.1", rep.server.port, "127.0.0.1",
                   rep.push_port)
        assert wait(rep.ready), "replica never onboarded from a keyframe"
        assert rep.engine.weights_epoch == 0

        # engine weights == decoded keyframe == publisher shadow, to the bit
        # at the dtype the engine holds them in
        held = rep.engine.compute_dtype
        with rep._lock:
            mailbox = [lf.copy() for lf in rep._leaves]
        for got, want in zip(
            jax.tree.leaves(rep.engine.params), mailbox
        ):
            assert got.dtype == held
            np.testing.assert_array_equal(
                np.asarray(got, np.float32).reshape(-1),
                np.asarray(jnp.asarray(want).astype(held), np.float32),
            )

        # follow staggered deltas for five outer epochs
        rng = np.random.default_rng(9)
        for e in range(1, 6):
            _walk(masters, rng)
            epoch[0] = e
            assert wait(lambda: rep._epoch == e), f"mailbox stuck before {e}"
        assert wait(lambda: rep.engine.weights_epoch == 5)
        assert rep.staleness() == 0 and not rep.stale()

        # one request through the router front end
        out = router.dispatch({"prompt": [1, 2, 3, 4], "max_new_tokens": 4})
        assert len(out["tokens"]) == 4 and "error" not in out
        assert out["epoch"] == 5  # served by the freshest weights

        # stall weight pushes: detach the manager (which also deregisters
        # the replica from the router), re-register the replica as a
        # bare backend, and keep pinging. The trainer epoch keeps moving,
        # the weights don't -> staleness crosses the bound and health
        # reports it, including through the router's probe.
        mgr.stop()
        router.add_replica("r0", "127.0.0.1", rep.server.port)
        conn = socket.create_connection(("127.0.0.1", rep.push_port),
                                        timeout=10)
        for te in range(6, 12):
            send_frame(conn, "ping", {"kind": "ping", "tepoch": te})
            kind, rmeta, _ = recv_frame(conn, timeout=10.0)
            assert kind == "ok"
        conn.close()
        assert rep.staleness() == 6 and rep.stale()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{rep.server.port}/healthz", timeout=10
        ) as r:
            health = json.loads(r.read())
        assert health["stale"] is True and health["staleness"] == 6
        assert health["replica"] == "r0"
        # a replica names the platform it really runs on
        assert health["platform"] == "cpu" and health["decode_kernel"] == "xla"
        assert wait(lambda: router.stats()["replicas"]["r0"]["stale"], 10)
    finally:
        mgr.stop()
        router.stop()
        rep.stop()


# ---------------------------------------------------------------------------
# serve satellites: disconnect retires the slot, identity on /healthz
# ---------------------------------------------------------------------------


def test_disconnect_mid_generation_retires_slot(tiny_cfg):
    """A client that hangs up mid-generation cancels its request: the
    scheduler frees the slot instead of decoding the remaining tokens
    into a dead socket, and later requests are unaffected."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import init_params
    from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine, ServeServer

    params = init_params(jax.random.PRNGKey(0), tiny_cfg)
    engine = ServeEngine(
        tiny_cfg, params, compute_dtype=jnp.float32, **ENGINE_GEOM
    )
    batcher = ContinuousBatcher(engine, max_queue=64).start()
    srv = ServeServer(batcher, port=0)
    try:
        conn = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        conn.sendall(
            (json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 48}) + "\n")
            .encode()
        )
        conn.close()  # hang up while the request is queued or decoding
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if batcher.cancelled >= 1:
                break
            time.sleep(0.02)
        assert batcher.cancelled == 1
        assert batcher.stats()["cancelled"] == 1
        # the slot came back and serving continues normally
        r = batcher.submit([4, 5, 6], max_new_tokens=3)
        assert r.wait(60) and r.error is None
        assert batcher.slots.num_active == 0
    finally:
        srv.stop()
        batcher.stop()


def test_server_identity_on_health_and_stats(tiny_cfg):
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import init_params
    from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine, ServeServer

    params = init_params(jax.random.PRNGKey(0), tiny_cfg)
    engine = ServeEngine(
        tiny_cfg, params, compute_dtype=jnp.float32, **ENGINE_GEOM
    )
    batcher = ContinuousBatcher(engine).start()
    srv = ServeServer(
        batcher, port=0,
        identity=lambda: {"worker": "r7", "staleness": 1, "stale": False},
    )
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=10
        ) as r:
            health = json.loads(r.read())
        assert health["worker"] == "r7" and health["staleness"] == 1
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/stats", timeout=10
        ) as r:
            stats = json.loads(r.read())
        assert stats["identity"]["worker"] == "r7"
        assert "staleness" in stats  # scheduler-level staleness, satellite a
    finally:
        srv.stop()
        batcher.stop()


# ---------------------------------------------------------------------------
# admission control + probe pacing (PR 17)
# ---------------------------------------------------------------------------


class HangingReplica:
    """Accepts generate lines but never answers: requests pile up
    in-flight until :meth:`kill` drops every connection at once — the
    worst-case shape of a replica dying with multiple dispatches live."""

    def __init__(self):
        self.arrived = threading.Semaphore(0)
        self._stop = threading.Event()
        self._conns = set()
        self._sock = socket.socket()
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(16)
        self.port = self._sock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            self._conns.add(conn)
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True
            ).start()

    def _serve(self, conn):
        try:
            if conn.recv(65536):
                self.arrived.release()
            self._stop.wait()
        except OSError:
            pass

    def kill(self):
        self._stop.set()
        for s in [self._sock, *list(self._conns)]:
            try:
                s.close()
            except OSError:
                pass


def test_router_mark_dead_under_concurrent_dispatch():
    """Two threads are in-flight on the same replica when it dies: both
    must re-dispatch (zero drops), and the death is retired exactly once
    — no double-counting, no double watchdog trip."""
    hang = HangingReplica()
    good = FakeReplica("b")
    router = FleetRouter(port=0, probe_interval_s=30.0, request_timeout=10.0)
    try:
        router.add_replica("a", "127.0.0.1", hang.port)
        outs = [None, None]

        def go(i):
            outs[i] = router.dispatch(
                {"prompt": [1, 2, 3], "max_new_tokens": 3, "id": i}
            )

        threads = [
            threading.Thread(target=go, args=(i,), daemon=True)
            for i in range(2)
        ]
        for t in threads:
            t.start()
        # both requests are live on the doomed replica before it dies
        assert hang.arrived.acquire(timeout=5.0)
        assert hang.arrived.acquire(timeout=5.0)
        router.add_replica("b", "127.0.0.1", good.port)
        hang.kill()
        for t in threads:
            t.join(timeout=10.0)
        assert all(o is not None and o.get("tokens") == [1, 2, 3]
                   for o in outs)
        assert sorted(o["id"] for o in outs) == [0, 1]
        st = router.stats()
        assert st["deaths"] == 1  # idempotent retire under the race
        assert st["redispatches"] == 2
        assert st["replicas"]["a"]["dead"]
        assert good.served == 2
    finally:
        router.stop()
        hang.kill()
        good.kill()


def test_mark_dead_idempotent_many_threads():
    """_mark_dead from N racing threads counts one death."""
    router = FleetRouter(port=0, probe_interval_s=30.0)
    try:
        router.add_replica("a", "127.0.0.1", 1)
        b = router._backends["a"]
        threads = [
            threading.Thread(target=router._mark_dead, args=(b,))
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert router.stats()["deaths"] == 1
    finally:
        router.stop()


def test_router_sheds_unmeetable_deadline():
    """A request whose budget is provably below the fastest observed
    dispatch is answered 'shed' at the edge — never queued to die."""
    rep = FakeReplica("a")
    router = FleetRouter(port=0, probe_interval_s=30.0, request_timeout=10.0)
    try:
        router.add_replica("a", "127.0.0.1", rep.port)
        # warm the latency floor with successful dispatches
        for i in range(3):
            out = router.dispatch({"prompt": [1, 2, 3], "id": i})
            assert out.get("tokens") == [1, 2, 3]
        assert router._latency_floor_s() is not None
        out = router.dispatch(
            {"prompt": [1, 2, 3], "deadline_ms": 0, "id": 99}
        )
        assert out["error"] == "shed"
        assert out["reason"] == "deadline unmeetable"
        assert out["retry_after_s"] > 0 and out["id"] == 99
        # a generous deadline sails through, with the remaining budget
        # forwarded to the replica
        out = router.dispatch({"prompt": [1, 2, 3], "deadline_ms": 60000})
        assert out.get("tokens") == [1, 2, 3]
        assert router.stats()["shed"] == 1
    finally:
        router.stop()
        rep.kill()


def test_router_http_shed_is_503_with_retry_after():
    rep = FakeReplica("a")
    router = FleetRouter(port=0, probe_interval_s=30.0, request_timeout=10.0)
    try:
        router.add_replica("a", "127.0.0.1", rep.port)
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/generate",
            data=json.dumps(
                {"prompt": [1, 2, 3], "deadline_ms": 0}
            ).encode(),
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503
        assert float(ei.value.headers["Retry-After"]) > 0
        body = json.loads(ei.value.read())
        assert body["error"] == "shed"
    finally:
        router.stop()
        rep.kill()


def test_probe_backoff_doubles_jitters_and_snaps_back():
    """Dead-backend probes back off exponentially to the cap with ±25%
    jitter (no thundering herd on mass revive) and snap back to the base
    interval the moment the replica answers."""
    router = FleetRouter(port=0, probe_interval_s=1.0)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()  # nothing listens here now
    try:
        router.add_replica("a", "127.0.0.1", port)
        b = router._backends["a"]
        assert b.probe_backoff == 1.0  # alive: base interval
        cap = router.probe_backoff_cap_s
        seen = []
        for _ in range(6):
            t0 = time.monotonic()
            router._probe(b)  # connection refused -> dead
            router._reschedule_probe(b)
            seen.append(b.probe_backoff)
            lo, hi = 0.75 * b.probe_backoff, 1.25 * b.probe_backoff
            delay = b.probe_at - t0
            assert lo - 0.05 <= delay <= hi + 0.05
        assert seen == [2.0, 4.0, 8.0, cap, cap, cap]
        # replica comes back on the same port: contact snaps the pace back
        rep = FakeReplica("a", port=port)
        try:
            router._probe(b)
            router._reschedule_probe(b)
            assert not b.dead and b.probe_backoff == 1.0
        finally:
            rep.kill()
    finally:
        router.stop()
