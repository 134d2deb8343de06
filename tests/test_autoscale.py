"""FleetAutoscaler decision-logic units: fake manager/router, no jax.

The control loop's contract is about *restraint* as much as action —
hysteresis before growing, reluctance before shrinking, cooldown
between actions, replacement outside the cooldown, spares preferred
over cold boots. Each test drives ``evaluate()`` directly (no thread)
so every tick is deterministic.
"""
from __future__ import annotations

import threading
import types

import pytest

from opendiloco_tpu.fleet.autoscaler import FleetAutoscaler


class FakeRouter:
    """Just the surface the autoscaler touches: registered replicas with
    dead/inflight/dispatched, plus add/remove."""

    def __init__(self):
        self.replicas: dict = {}
        self.lock = threading.Lock()

    def add_replica(self, rid, host, port):
        with self.lock:
            self.replicas[rid] = {
                "host": host, "port": port, "dead": False, "stale": False,
                "ready": True, "inflight": 0, "dispatched": 0,
            }

    def remove_replica(self, rid):
        with self.lock:
            self.replicas.pop(rid, None)

    def dead_replicas(self):
        with self.lock:
            return [r for r, b in self.replicas.items() if b["dead"]]

    def stats(self):
        with self.lock:
            return {"replicas": {r: dict(b) for r, b in self.replicas.items()}}


class FakeManager:
    def __init__(self, router):
        self.router = router
        self._spares: set = set()
        self._ready: set = set()
        self._addrs: dict = {}
        self.health: dict = {}
        self.detached: list = []

    def attach(self, rid, serve_host, serve_port, push_host, push_port,
               router_register=True):
        self._addrs[rid] = (serve_host, serve_port)
        if router_register:
            self.router.add_replica(rid, serve_host, serve_port)
        else:
            self._spares.add(rid)

    def detach(self, rid):
        self.detached.append(rid)
        self._spares.discard(rid)
        self._addrs.pop(rid, None)
        self.health.pop(rid, None)
        self.router.remove_replica(rid)

    def spares(self):
        return sorted(self._spares)

    def spare_ready(self, rid):
        return rid in self._spares and rid in self._ready

    def promote(self, rid):
        if rid not in self._spares:
            return False
        self._spares.discard(rid)
        self.router.add_replica(rid, *self._addrs[rid])
        return True

    def demote(self, rid):
        if rid in self._spares or rid not in self._addrs:
            return False
        self._spares.add(rid)
        self.router.remove_replica(rid)
        return True

    def health_matrix(self):
        return {rid: dict(h) for rid, h in self.health.items()}


@pytest.fixture()
def fleet():
    router = FakeRouter()
    manager = FakeManager(router)
    boots: list = []

    def boot(rid, register):
        boots.append((rid, register))
        manager.attach(rid, "127.0.0.1", 9000 + len(boots), "127.0.0.1", 0,
                       router_register=register)
        if not register:
            manager._ready.add(rid)  # spares keyframe instantly in the fake

    def retire(rid):
        manager.detach(rid)

    def scaler(**kw):
        kw.setdefault("slo_p99_ms", 100.0)
        kw.setdefault("slo_queue_depth", 8)
        kw.setdefault("min_replicas", 1)
        kw.setdefault("max_replicas", 4)
        kw.setdefault("cooldown_s", 0.0)
        kw.setdefault("up_evals", 1)
        kw.setdefault("down_evals", 1)
        kw.setdefault("boot_fn", boot)
        kw.setdefault("retire_fn", retire)
        return FleetAutoscaler(manager, router, **kw)

    class F:
        pass

    f = F()
    f.router, f.manager, f.boots, f.scaler = router, manager, boots, scaler
    return f


def _load(f, rid, p99_ms=10.0, depth=0):
    f.manager.health[rid] = {
        "queue_depth": depth, "occupancy": 0.5, "p99_ms": p99_ms,
    }


def _until(pred, t=5.0):
    """Cold boots and spare boots land on background threads; poll."""
    import time as _t

    deadline = _t.monotonic() + t
    while _t.monotonic() < deadline:
        if pred():
            return True
        _t.sleep(0.01)
    return pred()


def test_scale_up_needs_consecutive_breaches(fleet):
    """One breach tick is noise; up_evals consecutive breaches scale."""
    fleet.manager.attach("r0", "h", 1, "h", 2)
    a = fleet.scaler(up_evals=3)
    _load(fleet, "r0", p99_ms=500.0)
    assert a.evaluate() == [] and a.evaluate() == []
    made = a.evaluate()
    assert [d["action"] for d in made] == ["scale_up"]
    assert made[0]["mode"] == "cold_boot"
    assert _until(lambda: len(fleet.router.replicas) == 2)
    # a breach-free tick resets the streak
    _load(fleet, "r0", p99_ms=10.0, depth=0)
    a2 = fleet.scaler(up_evals=2)
    _load(fleet, "r0", p99_ms=500.0)
    a2.evaluate()
    _load(fleet, "r0", p99_ms=10.0)
    a2.evaluate()
    _load(fleet, "r0", p99_ms=500.0)
    assert a2.evaluate() == []  # streak restarted, not resumed


def test_queue_depth_alone_breaches(fleet):
    """The SLO is an OR: deep queues scale even with no p99 signal."""
    fleet.manager.attach("r0", "h", 1, "h", 2)
    a = fleet.scaler(slo_p99_ms=0.0)
    _load(fleet, "r0", p99_ms=None, depth=50)
    assert [d["action"] for d in a.evaluate()] == ["scale_up"]


def test_cooldown_spaces_actions(fleet, monkeypatch):
    # the autoscaler's clock, injected: it measures the cooldown from
    # ``time.monotonic()``, whose zero is about the host's boot, so on a
    # machine up for less than ``cooldown_s`` the wall clock itself put the
    # FIRST action inside the window and this test failed for an hour
    from opendiloco_tpu.fleet import autoscaler

    clock = [10 * 3600.0]
    monkeypatch.setattr(
        autoscaler, "time", types.SimpleNamespace(monotonic=lambda: clock[0])
    )
    fleet.manager.attach("r0", "h", 1, "h", 2)
    a = fleet.scaler(cooldown_s=3600.0)
    _load(fleet, "r0", p99_ms=500.0)
    assert [d["action"] for d in a.evaluate()] == ["scale_up"]
    assert _until(lambda: len(fleet.router.replicas) == 2)
    for _ in range(5):  # still breaching, but inside the cooldown window
        clock[0] += 600.0
        assert a.evaluate() == []
    clock[0] += 600.0  # 3600 s after the first action: the next one is due
    assert [d["action"] for d in a.evaluate()] == ["scale_up"]


def test_max_replicas_bounds_growth(fleet):
    fleet.manager.attach("r0", "h", 1, "h", 2)
    a = fleet.scaler(max_replicas=2)
    _load(fleet, "r0", p99_ms=500.0)
    a.evaluate()
    assert _until(lambda: len(fleet.router.replicas) == 2)
    assert a.evaluate() == [] and len(fleet.router.replicas) == 2


def test_spare_promotion_preferred_over_cold_boot(fleet):
    fleet.manager.attach("r0", "h", 1, "h", 2)
    fleet.manager.attach("s1", "h", 3, "h", 4, router_register=False)
    fleet.manager._ready.add("s1")
    a = fleet.scaler()
    _load(fleet, "r0", p99_ms=500.0)
    made = a.evaluate()
    up = [d for d in made if d["action"] == "scale_up"]
    assert up and up[0]["mode"] == "spare_promotion"
    assert up[0]["replica"] == "s1"
    assert "s1" in fleet.router.replicas and fleet.manager.spares() == []


def test_unready_spare_not_promoted(fleet):
    """A spare whose keyframe hasn't landed would serve random weights —
    scale-up must cold-boot around it."""
    fleet.manager.attach("r0", "h", 1, "h", 2)
    fleet.manager.attach("s1", "h", 3, "h", 4, router_register=False)
    a = fleet.scaler(warm_spares=1)
    _load(fleet, "r0", p99_ms=500.0)
    made = a.evaluate()
    up = [d for d in made if d["action"] == "scale_up"]
    assert up and up[0]["mode"] == "cold_boot"
    assert "s1" not in fleet.router.replicas


def test_scale_down_demotes_to_spare_pool(fleet):
    for i in range(3):
        fleet.manager.attach(f"r{i}", "h", i, "h", 10 + i)
        _load(fleet, f"r{i}", p99_ms=5.0, depth=0)
    a = fleet.scaler(warm_spares=1, down_evals=2)
    first = a.evaluate()  # reluctance: only the spare pool fills this tick
    assert [d["action"] for d in first] == ["boot_spare"]
    made = a.evaluate()
    down = [d for d in made if d["action"] == "scale_down"]
    assert down and down[0]["mode"] == "demote_to_spare"
    assert len(fleet.router.replicas) == 2
    assert down[0]["replica"] in fleet.manager.spares()


def test_scale_down_retires_when_spares_full(fleet):
    for i in range(2):
        fleet.manager.attach(f"r{i}", "h", i, "h", 10 + i)
        _load(fleet, f"r{i}", p99_ms=5.0, depth=0)
    a = fleet.scaler(warm_spares=0)
    made = a.evaluate()
    down = [d for d in made if d["action"] == "scale_down"]
    assert down and down[0]["mode"] == "retire"
    assert fleet.manager.detached == [down[0]["replica"]]


def test_min_replicas_floors_shrink(fleet):
    fleet.manager.attach("r0", "h", 1, "h", 2)
    _load(fleet, "r0", p99_ms=5.0, depth=0)
    a = fleet.scaler(min_replicas=1)
    for _ in range(5):
        assert a.evaluate() == []
    assert len(fleet.router.replicas) == 1


def test_dead_replica_replaced_outside_cooldown(fleet):
    """SIGKILL recovery is not a scaling decision: the corpse is retired
    and capacity restored even mid-cooldown, with zero operator action."""
    fleet.manager.attach("r0", "h", 1, "h", 2)
    fleet.manager.attach("r1", "h", 3, "h", 4)
    fleet.manager.attach("s1", "h", 5, "h", 6, router_register=False)
    fleet.manager._ready.add("s1")
    a = fleet.scaler(cooldown_s=3600.0)
    a._last_scale = __import__("time").monotonic()  # cooldown just started
    fleet.router.replicas["r0"]["dead"] = True
    made = a.evaluate()
    rep = [d for d in made if d["action"] == "replace"]
    assert rep and rep[0]["dead"] == "r0"
    assert rep[0]["mode"] == "spare_promotion" and rep[0]["replica"] == "s1"
    assert "r0" in fleet.manager.detached
    assert set(fleet.router.replicas) == {"r1", "s1"}


def test_spare_pool_replenished(fleet):
    import time as _t

    fleet.manager.attach("r0", "h", 1, "h", 2)
    _load(fleet, "r0", p99_ms=50.0, depth=0)
    a = fleet.scaler(warm_spares=2, down_evals=99)
    made = a.evaluate()
    assert [d["action"] for d in made] == ["boot_spare", "boot_spare"]
    deadline = _t.monotonic() + 5.0  # boots land on background threads
    while len(fleet.manager.spares()) < 2 and _t.monotonic() < deadline:
        _t.sleep(0.01)
    assert len(fleet.manager.spares()) == 2
    assert all(not reg for _, reg in fleet.boots)
    assert a.evaluate() == []  # pool full: no more boots


def test_hot_replica_is_a_breach_even_with_idle_siblings(fleet):
    """Worst-replica aggregation: dispatch imbalance must not hide
    behind a healthy mean."""
    fleet.manager.attach("r0", "h", 1, "h", 2)
    fleet.manager.attach("r1", "h", 3, "h", 4)
    _load(fleet, "r0", p99_ms=1.0, depth=0)
    _load(fleet, "r1", p99_ms=999.0, depth=0)
    a = fleet.scaler()
    assert [d["action"] for d in a.evaluate()] == ["scale_up"]


def test_env_overrides(fleet, monkeypatch):
    monkeypatch.setenv("ODTP_FLEET_SLO_P99_MS", "250")
    monkeypatch.setenv("ODTP_FLEET_WARM_SPARES", "3")
    monkeypatch.setenv("ODTP_FLEET_SCALE_COOLDOWN_S", "7.5")
    a = fleet.scaler(slo_p99_ms=100.0, warm_spares=0, cooldown_s=0.0)
    assert a.slo_p99_ms == 250.0
    assert a.warm_spares == 3
    assert a.cooldown_s == 7.5


def test_decision_log_carries_evidence(fleet):
    """Decisions must be auditable: action, trigger load, and the tick
    they happened on (the bench banks this log as its artifact)."""
    fleet.manager.attach("r0", "h", 1, "h", 2)
    a = fleet.scaler()
    _load(fleet, "r0", p99_ms=500.0, depth=11)
    a.evaluate()
    d = list(a.decisions)[-1]
    assert d["action"] == "scale_up"
    assert d["p99_ms"] == 500.0 and d["queue_depth"] == 11
    assert d["tick"] == 1
    st = a.status()
    assert st["decisions"] and st["active"] == sorted(fleet.router.replicas)


def test_loop_thread_runs_and_stops(fleet):
    fleet.manager.attach("r0", "h", 1, "h", 2)
    a = fleet.scaler(eval_interval_s=0.01)
    a.start()
    try:
        deadline = __import__("time").monotonic() + 5.0
        while a.ticks < 3 and __import__("time").monotonic() < deadline:
            __import__("time").sleep(0.01)
        assert a.ticks >= 3
    finally:
        a.stop()
    t = a.ticks
    __import__("time").sleep(0.05)
    assert a.ticks == t  # loop actually stopped
