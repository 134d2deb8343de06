"""Unified tracing + metrics plane.

Covers the ISSUE-mandated guarantees:
- span nesting records parent links; the tracer is thread-safe under
  concurrent spans/counters from many threads;
- every obs path is zero-cost when ODTP_OBS is unset: tracer() is None,
  span() is an inert singleton, no allocations accrue, no port is bound;
- the Chrome trace export is a valid trace_event document (and merges
  multi-worker JSONL files with clock alignment);
- the Prometheus endpoint emits lint-clean 0.0.4 text exposition over
  the existing per-worker control port;
- a 4-worker loopback outer round with the plane armed yields a merged
  trace containing every stage for every worker;
- the logger satellites: row normalization shared across backends, the
  JSONL logger round-trips, DummyLogger.finish() is atomic.
"""

import json
import os
import pickle
import re
import threading
import tracemalloc

import numpy as np
import pytest

from opendiloco_tpu import obs
from opendiloco_tpu.diloco.loopback import LoopbackWorld
from opendiloco_tpu.obs import export, mfu
from opendiloco_tpu.utils.logger import (
    DummyLogger,
    JsonlLogger,
    get_logger,
    normalize_row,
    read_jsonl,
)


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Every test starts and ends with the obs plane disarmed."""
    for var in ("ODTP_OBS", "ODTP_OBS_DIR", "ODTP_OBS_PROM_PORT",
                "ODTP_OBS_EVENTS_CAP"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    yield
    obs.reset()


def _arm(monkeypatch, **extra):
    monkeypatch.setenv("ODTP_OBS", "test")
    for k, v in extra.items():
        monkeypatch.setenv(k, str(v))
    return obs.tracer()


# -- span API -----------------------------------------------------------------


def test_span_nesting_records_parent(monkeypatch):
    tr = _arm(monkeypatch)
    with tr.span("outer/step", epoch=1):
        with tr.span("outer/encode"):
            pass
    names = {e["name"]: e for e in tr.events}
    assert names["outer/encode"]["args"]["parent"] == "outer/step"
    assert "parent" not in names["outer/step"]["args"]
    assert names["outer/step"]["args"]["epoch"] == 1
    # spans are ph=X with microsecond ts/dur
    assert all(e["ph"] == "X" and e["dur"] >= 0.0 for e in tr.events)


def test_add_span_and_instant(monkeypatch):
    tr = _arm(monkeypatch)
    t0 = tr.now()
    t1 = tr.now()
    tr.add_span("outer/rendezvous", t0, t1, round="grads-epoch-0", group=4)
    tr.instant("outer/round", round="grads-epoch-0", group_size=4)
    kinds = sorted(e["ph"] for e in tr.events)
    assert kinds == ["X", "i"]
    assert tr.events[0]["args"]["group"] == 4


def test_thread_safety(monkeypatch):
    tr = _arm(monkeypatch)
    n_threads, n_iter = 8, 200

    def work(i):
        for k in range(n_iter):
            with tr.span(f"t{i}/span", k=k):
                tr.count("ops", worker=i)
            tr.gauge("depth", k, worker=i)

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # one "X" span event + one "C" counter-track event per gauge() call
    assert len(tr.events) == 2 * n_threads * n_iter
    counters = tr.counters()
    for i in range(n_threads):
        assert counters[("ops", (("worker", i),))] == n_iter


def test_events_cap_drops_not_grows(monkeypatch):
    tr = _arm(monkeypatch, ODTP_OBS_EVENTS_CAP=10)
    for i in range(25):
        tr.instant("tick", i=i)
    assert len(tr.events) == 10
    assert tr.dropped == 15


def test_stage_times_accumulates_across_threads():
    st = obs.StageTimes()
    fn = st.timed("encode", lambda x: x + 1)
    threads = [
        threading.Thread(target=lambda: [fn(1) for _ in range(50)])
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert st.totals["encode"] > 0.0


# -- zero-cost when disabled --------------------------------------------------


def test_disabled_tracer_is_none_and_span_is_singleton():
    assert obs.tracer() is None
    assert not obs.enabled()
    assert obs.span("x") is obs.span("y")  # the inert singleton
    with obs.span("anything", k=1):
        pass  # no-op
    obs.count("n")
    obs.gauge("g", 1.0)
    assert obs.tracer() is None


def test_disabled_paths_do_not_allocate():
    # warm every code path first so imports/caches don't count
    for _ in range(10):
        with obs.span("warm"):
            pass
        obs.count("warm")
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(1000):
        with obs.span("hot/loop", k=1):
            pass
        obs.count("hot", n=2)
        obs.gauge("hot_g", 3.0)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(
        d.size_diff for d in after.compare_to(before, "filename")
        if d.size_diff > 0
    )
    # transient frames aside, the disabled plane must retain ~nothing
    assert grown < 16 * 1024


def test_no_prom_port_bound_when_disabled(monkeypatch):
    # PROM_PORT alone must not arm anything: no tracer, no socket
    monkeypatch.setenv("ODTP_OBS_PROM_PORT", "0")
    obs.reset()
    assert obs.tracer() is None


# -- exporters ----------------------------------------------------------------


def test_chrome_trace_valid_and_merges_clocks(monkeypatch, tmp_path):
    tr = _arm(monkeypatch, ODTP_OBS_DIR=str(tmp_path))
    tr.set_identity(worker=0)
    with tr.span("outer/step", epoch=0):
        pass
    p0 = tr.flush()
    assert p0 and os.path.exists(p0)
    events, meta = export.load_jsonl(p0)
    assert meta["origin_wall"] > 0
    # fake a second worker whose clock started 1s later
    meta2 = dict(meta, origin_wall=meta["origin_wall"] + 1.0)
    trace = export.chrome_trace([(0, events, meta), (1, events, meta2)])
    doc = json.loads(json.dumps(trace))  # must be pure-JSON serializable
    assert isinstance(doc["traceEvents"], list)
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("process_name") == 2
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {0, 1}
    w0 = next(e for e in spans if e["pid"] == 0)
    w1 = next(e for e in spans if e["pid"] == 1)
    assert w1["ts"] - w0["ts"] == pytest.approx(1e6, rel=1e-3)
    for e in spans:
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}


_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+=\"([^\"\\]|\\.)*\""
    r"(,[a-zA-Z0-9_]+=\"([^\"\\]|\\.)*\")*\})? -?[0-9.e+-]+(e[+-][0-9]+)?)$"
)


def test_prometheus_text_lints(monkeypatch):
    tr = _arm(monkeypatch)
    tr.count("outer_rounds")
    tr.count("rdv_rpcs", msg="join")
    tr.gauge("outer_group_size", 8)
    tr.gauge("weird name!", 1.5, label_x='quo"te')
    text = export.prometheus_text(tr)
    assert text.endswith("\n")
    for line in text.rstrip("\n").splitlines():
        assert _PROM_LINE.match(line), f"unlintable line: {line!r}"
    assert "odtp_outer_rounds" in text
    assert 'msg="join"' in text
    assert "odtp_obs_events_total" in text
    # disabled plane renders empty (the control-port frame returns no body)
    assert export.prometheus_text(None) == ""


def test_prom_endpoint_serves_over_http(monkeypatch):
    import urllib.request

    tr = _arm(monkeypatch, ODTP_OBS_PROM_PORT=0)
    assert tr.prom is not None and tr.prom.port > 0
    tr.count("outer_rounds", 3)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{tr.prom.port}/metrics", timeout=5
    ).read().decode()
    assert "odtp_outer_rounds 3.0" in body


# -- MFU ----------------------------------------------------------------------


def test_mfu_from_roofline_and_fallback():
    per_tok, source = mfu.flops_per_token("1b", n_params=1_000_000_000)
    assert source == "roofline"
    assert per_tok and per_tok > 1e9
    peak = mfu.peak_flops("TPU v5 lite")
    assert peak == pytest.approx(1.97e14)
    # a device that is not in the peaks table is an error, not a default
    with pytest.raises(ValueError, match="no bf16 peak"):
        mfu.peak_flops("cpu")
    # unknown model falls back to 6N
    per_tok2, source2 = mfu.flops_per_token("nosuch", n_params=1000)
    assert source2 == "analytic_6n"
    assert per_tok2 == 6000
    u = mfu.mfu(1e5, per_tok, n_devices=8, peak_flops_per_device=peak)
    assert 0.0 < u < 1.0


# -- end-to-end: 4-worker loopback round --------------------------------------


def test_loopback_round_merged_trace_has_every_stage(monkeypatch, tmp_path):
    tr = _arm(monkeypatch, ODTP_OBS_DIR=str(tmp_path))
    world = LoopbackWorld(4)
    backends = world.make_backends()
    data = [np.ones((8,), np.float32)]
    results = {}

    def run(b):
        out, n = b.all_reduce(data, timeout=30.0, tag="grads", epoch=0)
        results[b.peer_id] = (out, n)

    threads = [threading.Thread(target=run, args=(b,)) for b in backends]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(n == 4 for _, n in results.values())

    by_worker: dict[str, set] = {}
    for e in tr.events:
        w = e["args"].get("worker")
        if w is not None:
            by_worker.setdefault(w, set()).add(e["name"])
    assert set(by_worker) == {b.peer_id for b in backends}
    for w, names in by_worker.items():
        assert {"outer/encode", "outer/reduce_wait", "outer/adopt",
                "outer/round"} <= names, f"{w} missing stages: {names}"
    # every worker's round record merges on the same round id
    rounds = {
        e["args"]["round"] for e in tr.events if e["name"] == "outer/round"
    }
    assert rounds == {"grads-epoch-0"}
    # and the single-process Chrome view of it is well-formed
    doc = export.tracer_chrome_trace(tr)
    assert any(e["ph"] == "i" for e in doc["traceEvents"])


# -- logger satellites --------------------------------------------------------


def test_normalize_row_coerces_and_flattens():
    row = normalize_row({
        "Loss": np.float32(1.5),
        "step": 3,
        "flag": True,
        "nested": {"a": np.int64(2), "b": {"c": 1.0}},
        "arr0d": np.array(2.5),
        "weird": object(),
    })
    assert row["Loss"] == 1.5 and isinstance(row["Loss"], float)
    assert row["step"] == 3 and isinstance(row["step"], int)
    assert row["flag"] is True
    assert row["nested/a"] == 2.0
    assert row["nested/b/c"] == 1.0
    assert row["arr0d"] == 2.5
    assert isinstance(row["weird"], str)
    json.dumps(row)  # the whole row must be JSON-typed


def test_jsonl_logger_roundtrip(tmp_path):
    path = str(tmp_path / "rows.jsonl")
    lg = get_logger("jsonl", path, config={})
    assert isinstance(lg, JsonlLogger)
    lg.log({"Loss": np.float32(2.0), "step": 1})
    lg.log({"Loss": 1.0, "step": 2})
    lg.finish()
    # a trailing partial line (killed writer) is skipped, not fatal
    with open(path, "a") as f:
        f.write('{"Loss": 0.5, "st')
    rows = read_jsonl(path)
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[0]["Loss"] == 2.0


def test_dummy_logger_finish_is_atomic(tmp_path, monkeypatch):
    path = str(tmp_path / "spy.pkl")
    lg = DummyLogger(path, config={})
    lg.log({"Loss": np.float32(1.0)})
    # a crash mid-finish must never truncate an existing artifact: finish
    # writes a tmp file then os.replace()s it into place
    replaced = {}
    real_replace = os.replace

    def spy_replace(src, dst):
        replaced["src"], replaced["dst"] = src, dst
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", spy_replace)
    lg.finish()
    assert replaced["dst"] == path
    assert replaced["src"].startswith(path + ".tmp.")
    with open(path, "rb") as f:
        assert pickle.load(f) == [{"Loss": 1.0}]
    assert not os.path.exists(replaced["src"])


def test_unknown_logger_type_rejected():
    with pytest.raises(ValueError):
        get_logger("nosuch", "/tmp/x", config={})
