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


# -- the capture control (obs/capture.py) ---------------------------------------


def _named(cap, name):
    return [s for s in cap.spans if s["name"] == name]


def test_capture_records_between_start_and_stop_and_nothing_after():
    assert obs.tracer() is None
    with obs.span("before"):
        pass
    obs.capture.start()
    assert obs.tracer() is not None
    with obs.span("inside", k=3):
        pass
    obs.count("things", 2, kind="a")
    cap = obs.capture.stop()
    assert obs.tracer() is None and obs.reqtrace.ring() is None
    with obs.span("after"):
        pass
    assert [s["name"] for s in cap.spans] == ["inside"]
    (span,) = _named(cap, "inside")
    assert span["args"]["k"] == 3
    assert cap.anchor_pc <= span["t0"] <= span["t1"] <= cap.t_stop
    assert cap.counters == {"things{kind=a}": 2.0}
    with pytest.raises(RuntimeError):
        obs.capture.stop()


def test_capture_refuses_a_second_start():
    obs.capture.start()
    try:
        with pytest.raises(RuntimeError):
            obs.capture.start()
    finally:
        obs.capture.stop()


def test_capture_honours_the_ring_cap():
    obs.capture.start(ring_cap=3)
    ring = obs.reqtrace.ring()
    assert ring.cap == 3
    for _ in range(5):
        ctx = ring.mint()
        ring.span(ctx["id"], "queue", 0.0, 0.001)
        ring.finish(ctx["id"])
    cap = obs.capture.stop()
    assert len(cap.requests) == 3 and ring.evicted == 2
    assert all(t["spans"][0]["stage"] == "queue" for t in cap.requests)


def test_capture_uses_the_tracer_odtp_obs_armed_and_leaves_it_armed(monkeypatch):
    tr = _arm(monkeypatch, ODTP_REQTRACE_CAP=7)
    ring = obs.reqtrace.ring()
    with obs.span("operator"):
        pass
    obs.capture.start(ring_cap=50)
    assert obs.tracer() is tr and obs.reqtrace.ring() is ring and ring.cap == 50
    with obs.span("captured"):
        pass
    cap = obs.capture.stop()
    assert [s["name"] for s in cap.spans] == ["captured"]
    # the operator's plane is as it was: same tracer, still armed, its cap back
    assert obs.tracer() is tr and obs.reqtrace.ring() is ring and ring.cap == 7
    assert [e["name"] for e in tr.events] == ["operator", "captured"]


def test_capture_profile_holds_the_anchor_annotation(tmp_path):
    import glob

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    obs.capture.start(str(tmp_path))
    with obs.span("work"):
        jnp.arange(8).sum().block_until_ready()
    cap = obs.capture.stop()
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    anchors = [
        ev for plane in ProfileData.from_file(pb).planes for line in plane.lines
        for ev in line.events if ev.name == obs.capture.ANCHOR
    ]
    assert len(anchors) == 1
    assert float(dict(anchors[0].stats)["pc"]) == cap.anchor_pc
    # one subtraction places a span on the profiler's clock
    (span,) = _named(cap, "work")
    on_trace_ns = anchors[0].start_ns + (span["t0"] - cap.anchor_pc) * 1e9
    assert on_trace_ns > anchors[0].start_ns
    saved = json.load(open(cap.save(str(tmp_path / "odtp_capture.json"))))
    assert saved["anchor_pc"] == cap.anchor_pc and saved["spans"][0]["name"] == "work"


def _diloco_worker(tiny_cfg, placement, local_steps=2):
    """``placement`` "device-sharded": the device plane under FULL_SHARD over
    four devices, whose fetch assembles shards on the host."""
    import jax

    from opendiloco_tpu.config import DilocoConfig
    from opendiloco_tpu.diloco import DiLoCoOptimizer
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    tc = TrainerConfig(lr=1e-3, warmup_steps=2, total_steps=200, precision="fp32",
                       remat=False)
    if placement == "device-sharded":
        placement, plan = "device", build_mesh("FULL_SHARD", devices=jax.devices()[:4])
    else:
        plan = build_mesh("NO_SHARD", devices=jax.devices()[:1])
    trainer = InnerTrainer(tiny_cfg, tc, plan)
    state = trainer.init_state(jax.random.key(0))
    (backend,) = LoopbackWorld(1).make_backends()
    opt = DiLoCoOptimizer(
        trainer, backend,
        DilocoConfig(local_steps=local_steps, backend="loopback",
                     outer_placement=placement, skip_load_from_peers=True),
        state, 8,
    )
    rng = np.random.default_rng(0)

    def one_round(state):
        """``local_steps`` steps, the last over the boundary -> (state, row)"""
        for _ in range(local_steps):
            ids = ((rng.integers(0, tiny_cfg.vocab_size, (8, 1)) + np.arange(16))
                   % tiny_cfg.vocab_size).astype(np.int32)
            state, m = opt.step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
        jax.block_until_ready(state["params"])
        return state, m

    return state, one_round


@pytest.mark.parametrize("placement", ["device", "host", "device-sharded"])
def test_arming_the_capture_compiles_nothing(tiny_cfg, placement):
    """One boundary with the capture off, ``start``, one boundary with it on:
    not one program goes to the compiler in between (the pseudo-gradient's
    norm no longer follows the tracer into a static argument)."""
    import jax

    compiles = []

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    state, one_round = _diloco_worker(tiny_cfg, placement)
    state, row_off = one_round(state)
    state, _ = one_round(state)  # the momentum buffers' first armed step
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        compiles.clear()
        obs.capture.start()
        state, row_on = one_round(state)
        cap = obs.capture.stop()
        between = len(compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert between == 0
    assert {"outer/d2h", "outer/allreduce", "outer/apply", "outer/step"} <= {
        s["name"] for s in cap.spans}
    if placement != "host":
        # carried in every run now, traced or not
        assert row_off["pseudo_grad_norm"] > 0 and row_on["pseudo_grad_norm"] > 0
        assert _named(cap, "outer/h2d")
    if placement == "device-sharded":
        # the first round allocated the host arrays; a traced one writes
        # into them again
        assert row_off["outer_d2h_new_bytes"] > 0 == row_on["outer_d2h_new_bytes"]


@pytest.mark.parametrize("placement", ["device", "host", "device-sharded"])
def test_boundary_row_splits_the_step(tiny_cfg, placement):
    import jax

    state, one_round = _diloco_worker(tiny_cfg, placement)
    obs.capture.start()
    state, row = one_round(state)
    cap = obs.capture.stop()
    assert min(row["outer_d2h_s"], row["outer_apply_s"]) > 0
    (step,) = _named(cap, "outer/step")
    d2h = _named(cap, "outer/d2h")
    # the d2h spans are the fetch's own interval, recorded in the fetch thread
    assert d2h[-1]["t1"] - d2h[0]["t0"] == pytest.approx(row["outer_d2h_s"], abs=1e-9)
    assert all(s["tid"] != step["tid"] for s in d2h)
    assert step["t0"] <= d2h[0]["t0"] and d2h[-1]["t1"] <= step["t1"]
    if placement == "host":
        # one stage after another: the parts add up to less than the step
        parts = row["outer_d2h_s"] + row["outer_allreduce_s"] + row["outer_apply_s"]
        assert parts <= row["outer_step_s"] and len(d2h) == 1
        assert "new_bytes" not in d2h[0]["args"] and "outer_d2h_new_bytes" not in row
        assert "outer_pieces" not in row
        return
    # the device boundary's three stages run side by side, a piece at a time:
    # one span a piece and stage, the fetch's tiling its interval
    n = row["outer_pieces"]
    assert n > 1 and row["outer_h2d_s"] > 0
    reduces, puts = _named(cap, "outer/allreduce"), _named(cap, "outer/h2d")
    (apply_,) = _named(cap, "outer/apply")
    for spans in (d2h, reduces, puts):
        assert [s["args"]["piece"] for s in spans] == list(range(n))
        assert all(s["args"]["bytes"] > 0 for s in spans)
    assert all(a["t1"] == b["t0"] for a, b in zip(d2h, d2h[1:]))
    model_bytes = sum(x.nbytes for x in jax.tree.leaves(state["params"]))
    for spans in (d2h, reduces, puts):
        assert sum(s["args"]["bytes"] for s in spans) == model_bytes
    for got, reduced, put in zip(d2h, reduces, puts):
        # a piece is reduced once it has landed and put once it is reduced
        assert got["t1"] <= reduced["t0"] and reduced["t1"] <= put["t0"]
    assert row["outer_allreduce_s"] == pytest.approx(
        sum(s["t1"] - s["t0"] for s in reduces), abs=1e-9)
    assert row["outer_h2d_s"] == pytest.approx(puts[-1]["t1"] - puts[0]["t0"], abs=1e-3)
    # the apply starts with the last piece's average in hand
    assert reduces[-1]["t1"] <= apply_["t0"] and puts[-1]["t1"] <= apply_["t1"]
    if placement == "device-sharded":
        # what the fetch assembled on the host, all of it new in a first round
        assert 0 < row["outer_d2h_new_bytes"] <= model_bytes
    else:  # nothing to assemble: jax.device_get, and nothing to report
        assert "outer_d2h_new_bytes" not in row


def test_reduce_wait_is_waiting_and_reduce_is_the_mean():
    world = LoopbackWorld(3)
    backends = world.make_backends()
    data = [np.ones((64,), np.float32)]
    obs.capture.start()
    threads = [
        threading.Thread(
            target=lambda b=b: b.all_reduce(data, timeout=30.0, tag="g", epoch=0))
        for b in backends
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    cap = obs.capture.stop()
    (reduce_,) = _named(cap, "outer/reduce")  # one peer publishes the mean
    assert reduce_["args"]["group"] == 3
    waits = _named(cap, "outer/reduce_wait")
    assert len(waits) == 3
    (own,) = [w for w in waits if w["args"]["worker"] == reduce_["args"]["worker"]]
    assert own["t1"] == reduce_["t0"]  # the publisher's wait ends where its sum starts
    for w in waits:  # and no wait is charged for the computation
        if w is not own:
            assert w["t1"] >= reduce_["t1"] or w["t0"] >= reduce_["t0"]


def _tiny_engine(tiny_cfg, **kw):
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import init_params
    from opendiloco_tpu.serve import ServeEngine

    return ServeEngine(
        tiny_cfg, init_params(jax.random.key(1), tiny_cfg), num_slots=4, max_context=64,
        prefill_buckets=(8, 16), compute_dtype=jnp.float32, **kw,
    )


def test_serve_decode_span_carries_the_live_rows(tiny_cfg):
    engine = _tiny_engine(tiny_cfg)
    tokens, lens = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for slot, prompt in ((0, [5, 6, 7]), (2, [9, 8, 7, 6, 5])):
        tokens[slot], _ = engine.admit(slot, prompt)
        lens[slot] = len(prompt)
    engine.decode_step(tokens, lens)  # untraced: no span, same program
    obs.capture.start()
    engine.decode_step(tokens, lens + (lens > 0))
    cap = obs.capture.stop()
    (span,) = _named(cap, "serve_decode")
    assert span["args"] == {"rows": 4 + 6, "slots": 2}
    assert not _named(cap, "serve_prefill")  # admitted before the capture


def test_loop_seconds_hold_the_stage_seconds(tiny_cfg):
    from opendiloco_tpu.serve import ContinuousBatcher

    engine = _tiny_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine).start()
    try:
        for r in [batcher.submit([3, 4, 5], max_new_tokens=4)]:
            assert r.wait(120) and r.error is None  # compiles fall in here
        loop0, stage0 = batcher.loop_seconds, sum(engine.stage_seconds.values())
        its0, steps0 = batcher.loop_iterations, batcher.decode_steps
        obs.capture.start()
        reqs = [batcher.submit([7, 8, 9, i + 1], max_new_tokens=6) for i in range(6)]
        for r in reqs:
            assert r.wait(120) and r.error is None
    finally:
        batcher.stop()  # joins the loop: its last iteration is counted whole
        cap = obs.capture.stop()
    loop_s = batcher.loop_seconds - loop0
    stage_s = sum(engine.stage_seconds.values()) - stage0
    steps = batcher.decode_steps - steps0
    assert steps > 0 and batcher.loop_iterations - its0 >= steps
    assert loop_s >= stage_s > 0
    assert batcher.stats()["loop_iterations"] == batcher.loop_iterations
    # one span per working iteration, and each decode step inside one of them
    iterations = _named(cap, "serve_iteration")
    assert iterations and all(s["args"]["admitted"] or s["args"]["stepped"]
                              for s in iterations)
    for d in _named(cap, "serve_decode"):
        assert any(i["t0"] <= d["t0"] and d["t1"] <= i["t1"] for i in iterations)


# -- the serving iteration from inside: phases of a call, phases of the loop ----

_PHASE_SPANS = ("serve_args", "serve_dispatch", "serve_fetch")
# spans_since rebuilds a stamp from microseconds after the tracer's origin
_STAMP_EPS = 1e-6


def _phases_total(engine):
    return {
        stage: (dict(engine.phase_seconds[stage]), engine.phase_calls[stage])
        for stage in ("prefill", "decode")
    }


def _admit_two_and_step(engine, steps=3):
    tokens, lens = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for slot, prompt in ((0, [5, 6, 7]), (2, [9, 8, 7, 6, 5])):
        tokens[slot], _ = engine.admit(slot, prompt)
        lens[slot] = len(prompt)
    for _ in range(steps):
        nxt, _ = engine.decode_step(tokens, lens)
        tokens, lens = np.where(lens > 0, nxt, 0).astype(np.int32), lens + (lens > 0)


@pytest.mark.parametrize("parent, stage, calls", [
    ("serve_prefill", "prefill", 2), ("serve_decode", "decode", 3),
])
def test_engine_call_is_tiled_by_its_three_phases(tiny_cfg, parent, stage, calls):
    engine = _tiny_engine(tiny_cfg)
    _admit_two_and_step(engine, steps=1)  # compiles fall in here, untraced
    obs.capture.start()
    _admit_two_and_step(engine)
    cap = obs.capture.stop()
    parents = _named(cap, parent)
    assert len(parents) == calls
    children = [s for s in cap.spans
                if s["name"] in _PHASE_SPANS and s["args"] == {"stage": stage}]
    assert len(children) == 3 * calls
    for p in parents:
        inside = sorted(
            (c for c in children if p["t0"] - _STAMP_EPS <= c["t0"] < p["t1"]),
            key=lambda c: c["t0"],
        )
        assert tuple(c["name"] for c in inside) == _PHASE_SPANS
        assert inside[0]["t0"] == pytest.approx(p["t0"], abs=_STAMP_EPS)
        for a, b in zip(inside, inside[1:]):
            assert b["t0"] == pytest.approx(a["t1"], abs=_STAMP_EPS)
        assert inside[-1]["t1"] <= p["t1"] + _STAMP_EPS
    # the parents' attributes are what they were: no ``stage`` among them
    assert all("stage" not in p["args"] for p in parents)


def test_phase_counters_grow_by_the_spans_sums_and_counts(tiny_cfg):
    engine = _tiny_engine(tiny_cfg)
    _admit_two_and_step(engine, steps=1)
    before = _phases_total(engine)
    obs.capture.start()
    _admit_two_and_step(engine)
    cap = obs.capture.stop()
    after = _phases_total(engine)
    for stage, calls in (("prefill", 2), ("decode", 3)):
        assert after[stage][1] - before[stage][1] == calls
        for phase in ("args", "dispatch", "fetch"):
            spans = [s for s in _named(cap, "serve_" + phase) if s["args"]["stage"] == stage]
            grown = after[stage][0][phase] - before[stage][0][phase]
            assert grown > 0
            assert sum(s["t1"] - s["t0"] for s in spans) == pytest.approx(
                grown, abs=2 * calls * _STAMP_EPS
            )
    # the three are inside the stage's wall, which ends after the counting
    for stage in ("prefill", "decode"):
        assert sum(engine.phase_seconds[stage].values()) <= engine.stage_seconds[stage]


def test_phase_counters_grow_with_no_tracer_armed(tiny_cfg):
    engine = _tiny_engine(tiny_cfg)
    assert obs.tracer() is None
    assert engine.phase_calls == {"prefill": 0, "decode": 0}
    _admit_two_and_step(engine)
    assert obs.tracer() is None
    assert engine.phase_calls == {"prefill": 2, "decode": 3}
    for stage in ("prefill", "decode"):
        assert set(engine.phase_seconds[stage]) == {"args", "dispatch", "fetch"}
        assert all(v > 0 for v in engine.phase_seconds[stage].values())


def test_loop_phases_lie_on_either_side_of_the_step(tiny_cfg):
    from opendiloco_tpu.serve import ContinuousBatcher

    engine = _tiny_engine(tiny_cfg)
    # the capture spans the batcher's whole life (compiles and all), so that
    # the counters and the spans hold the same steps with no race at an edge
    obs.capture.start()
    batcher = ContinuousBatcher(engine).start()
    try:
        reqs = [batcher.submit([7, 8, 9, i + 1], max_new_tokens=6) for i in range(6)]
        for r in reqs:
            assert r.wait(120) and r.error is None
    finally:
        batcher.stop()  # joins the loop: its last step is counted whole
        cap = obs.capture.stop()
    steps, iterations = _named(cap, "serve_decode"), _named(cap, "serve_iteration")
    batches, emits = _named(cap, "serve_batch"), _named(cap, "serve_emit")
    # a step is emitted by the call that read it; a batch is assembled for
    # every call, the one that only enqueues a busy period's first step too
    assert steps and len(emits) == len(steps) == batcher.decode_steps <= len(batches)
    for d, e in zip(*(sorted(spans, key=lambda s: s["t0"]) for spans in (steps, emits))):
        assert e["t0"] == pytest.approx(d["t1"], abs=_STAMP_EPS)
    calls = [s["t0"] for s in steps] + [
        s["t0"] for s in _named(cap, "serve_args") if s["args"] == {"stage": "decode"}
    ]
    for b in batches:  # ends where the engine's call starts
        assert min(abs(b["t1"] - t0) for t0 in calls) <= _STAMP_EPS
    for span in batches + emits:
        assert span["args"] == {}
        assert any(_inside(span, i) for i in iterations)
    for name, total in (("serve_batch", batcher.batch_seconds),
                        ("serve_emit", batcher.emit_seconds)):
        assert total > 0
        assert sum(s["t1"] - s["t0"] for s in _named(cap, name)) == pytest.approx(
            total, abs=2 * len(batches) * _STAMP_EPS
        )
    assert batcher.batch_seconds + batcher.emit_seconds < batcher.loop_seconds
    # five new names and no sixth
    assert {s["name"] for s in cap.spans if s["name"].startswith("serve_")} == {
        "serve_prefill", "serve_decode", "serve_iteration", "serve_batch", "serve_emit",
        *_PHASE_SPANS,
    }


def test_stats_carry_the_phases(tiny_cfg):
    from opendiloco_tpu.serve import ContinuousBatcher

    engine = _tiny_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine).start()
    try:
        r = batcher.submit([3, 4, 5], max_new_tokens=4)
        assert r.wait(120) and r.error is None
    finally:
        batcher.stop()
    stats = batcher.stats()
    assert stats["phase_calls"] == {"prefill": 1, "decode": 3}
    assert stats["phase_seconds"]["decode"] == {
        k: round(v, 6) for k, v in engine.phase_seconds["decode"].items()
    }
    assert set(stats["phase_seconds"]["prefill"]) == {"args", "dispatch", "fetch"}
    # always on: no tracer was armed while these steps ran
    assert stats["batch_seconds"] == round(batcher.batch_seconds, 6) > 0
    assert stats["emit_seconds"] == round(batcher.emit_seconds, 6) > 0
    assert "loop_seconds" in stats and "stages_s" in stats
    json.dumps(stats)  # what GET /stats serves


# -- an admission that does not block (ISSUE 38): its spans, where its read falls --


def _inside(child, parent):
    return (parent["t0"] - _STAMP_EPS <= child["t0"]
            and child["t1"] <= parent["t1"] + _STAMP_EPS)


def _stage(cap, name, stage):
    return sorted((s for s in _named(cap, name) if s["args"] == {"stage": stage}),
                  key=lambda s: s["t0"])


@pytest.mark.parametrize("kind, attribute", [
    ("dense", None), ("routed", "moe_pairs"), ("hybrid", "ssm_tokens"),
    ("latent", "latent_rows"), ("cca", "cca_tokens"),
])
def test_a_deferred_admissions_span_starts_at_its_enqueue(tiny_cfg, kind, attribute):
    import test_serve_deferred_admit as deferred
    from opendiloco_tpu.serve import ContinuousBatcher

    engine = deferred._engine(*deferred.KINDS[kind](tiny_cfg))
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5], [3, 4, 5, 6, 7, 8, 9]]  # told apart by length
    obs.capture.start()
    batcher = ContinuousBatcher(engine)
    reqs = [batcher.submit(p, max_new_tokens=4) for p in prompts]
    batcher.start()  # three in the queue: one iteration admits them all
    try:
        for r in reqs:
            assert r.wait(300) and r.error is None
    finally:
        batcher.stop()
        cap = obs.capture.stop()
    prefills = sorted(_named(cap, "serve_prefill"), key=lambda s: s["t0"])
    assert [p["args"]["tokens"] for p in prefills] == [3, 5, 7]
    assert attribute is None or all(p["args"][attribute] > 0 for p in prefills)
    phases = {name: _stage(cap, name, "prefill") for name in _PHASE_SPANS}
    steps = sorted(_named(cap, "serve_decode"), key=lambda s: s["t0"])
    step_phases = {name: _stage(cap, name, "decode") for name in _PHASE_SPANS}
    # all three were enqueued, and the step behind them, before anything was
    # read; that call read nothing and has no span
    assert prefills[-1]["t1"] <= step_phases["serve_args"][0]["t0"] + _STAMP_EPS
    assert step_phases["serve_dispatch"][0]["t1"] <= steps[0]["t0"] + _STAMP_EPS
    # the call after it enqueued the second step and then read the first,
    # behind the admissions the first was fed
    read_end = step_phases["serve_dispatch"][1]["t1"]
    own_read = step_phases["serve_fetch"][0]
    for p, args, dispatch, fetch, req in zip(prefills, *phases.values(), reqs):
        # the span is the enqueue: it starts there and its two phases tile it
        assert args["t0"] == pytest.approx(p["t0"], abs=_STAMP_EPS)
        assert dispatch["t0"] == pytest.approx(args["t1"], abs=_STAMP_EPS)
        assert dispatch["t1"] == pytest.approx(p["t1"], abs=_STAMP_EPS)
        # its read is a wait inside the call that reads the step it fed, after
        # that call's dispatch, each where the one before it ended, and before
        # the step's own read
        assert _inside(fetch, steps[0])
        assert fetch["t0"] >= read_end - _STAMP_EPS
        assert fetch["t1"] <= own_read["t0"] + _STAMP_EPS
        # the first token's stamp is the instant that read returned
        assert req.t_first == pytest.approx(fetch["t1"], abs=_STAMP_EPS)
        assert dispatch["t1"] <= req.t_first <= own_read["t1"]
        read_end = fetch["t1"]
    assert _inside(own_read, steps[0])
    iterations = sorted(_named(cap, "serve_iteration"), key=lambda s: s["t0"])
    assert all(any(_inside(s, i) for i in iterations) for s in prefills + steps)
    # the busy period's first iteration enqueues and reads nothing
    assert all(_inside(s, iterations[0]) for s in
               (*prefills, step_phases["serve_args"][0], step_phases["serve_dispatch"][0]))
    assert _inside(steps[0], iterations[1])
    assert len(steps) == 3 == batcher.decode_steps and engine.steps_ahead == 2


def test_a_step_aheads_span_holds_one_steps_dispatch_and_the_read_of_the_step_before(tiny_cfg):
    """ISSUE 48: the ``serve_decode`` span of an iteration holds the ``args``
    and ``dispatch`` of the step it enqueues and the ``fetch`` of the step it
    reads, in its ``serve_iteration``; ``stage_seconds["decode"]`` covers them
    and no admission's read; its attributes are the step's that was read."""
    from opendiloco_tpu.serve import ContinuousBatcher

    engine = _tiny_engine(tiny_cfg)
    lengths = [3, 4, 6, 6, 5, 4]  # two end a step apart, and the queue takes their slots
    obs.capture.start()
    batcher = ContinuousBatcher(engine)
    reqs = [batcher.submit([7, 8, 9, i + 1][: 2 + i % 3], max_new_tokens=n)
            for i, n in enumerate(lengths)]
    batcher.start()
    try:
        for r in reqs:
            assert r.wait(300) and r.error is None
    finally:
        batcher.stop()
        cap = obs.capture.stop()
    steps = sorted(_named(cap, "serve_decode"), key=lambda s: s["t0"])
    iterations = _named(cap, "serve_iteration")
    phases = {name: _stage(cap, name, "decode") for name in _PHASE_SPANS}
    assert len(steps) == batcher.decode_steps == len(phases["serve_fetch"])
    assert engine.steps_ahead == len(steps) - 1 == len(phases["serve_args"]) - 1
    for d in steps:
        (iteration,) = [i for i in iterations if _inside(d, i)]
        assert [s for s in steps if _inside(s, iteration)] == [d]  # one a working iteration
        inside = sorted((c for name in _PHASE_SPANS for c in phases[name] if _inside(c, d)),
                        key=lambda c: c["t0"])
        names = tuple(c["name"] for c in inside)
        # the last call of the busy period enqueues nothing
        assert names == _PHASE_SPANS or (d is steps[-1] and names == ("serve_fetch",))
        assert inside[0]["t0"] == pytest.approx(d["t0"], abs=_STAMP_EPS)
        # they tile the call's front, but for the admissions read before the
        # step's own tokens
        fed = [r for r in _stage(cap, "serve_fetch", "prefill") if _inside(r, d)]
        for a, b in zip(inside, inside[1:]):
            if fed and b["name"] == "serve_fetch":
                assert a["t1"] <= fed[0]["t0"] + _STAMP_EPS and fed[-1]["t1"] <= b["t0"] + _STAMP_EPS
            else:
                assert b["t0"] == pytest.approx(a["t1"], abs=_STAMP_EPS)
    # the attributes are the step's whose tokens the call read: the first
    # step's four admissions (prompt lengths 2, 3, 4, 2) came back in the second call
    assert steps[0]["args"] == {"rows": 2 + 3 + 4 + 2, "slots": 4}
    assert sum(s["args"]["slots"] for s in steps) == batcher.total_new_tokens
    # the stage's seconds: every call's span less the admissions read inside
    # it (its own fetch starts where the last of them returned), and the two
    # phases of the one call that read nothing
    outside = [c for name in ("serve_args", "serve_dispatch") for c in phases[name]
               if not any(_inside(c, d) for d in steps)]
    assert len(outside) == 2
    walls = sum(s["t1"] - s["t0"] for s in steps + outside)
    reads = _stage(cap, "serve_fetch", "prefill")
    assert len(reads) == len(reqs)
    for d in steps:
        (own,) = [f for f in phases["serve_fetch"] if _inside(f, d)]
        dispatched = [c["t1"] for c in phases["serve_dispatch"] if _inside(c, d)] or [d["t0"]]
        fed = [r for r in reads if _inside(r, d)]
        assert all(dispatched[0] - _STAMP_EPS <= r["t0"] and r["t1"] <= own["t0"] + _STAMP_EPS
                   for r in fed)
        if fed:
            walls -= own["t0"] - dispatched[0]
    assert engine.stage_seconds["decode"] == pytest.approx(walls, abs=4 * len(steps) * _STAMP_EPS)
    assert sum(engine.phase_seconds["decode"].values()) <= engine.stage_seconds["decode"]
    # an admission's first token is stamped as its own read returns
    firsts = sorted(r.t_first for r in reqs)
    assert [r["t1"] for r in reads] == pytest.approx(firsts, abs=_STAMP_EPS)
    # a step's tokens wait for nothing that was enqueued after the step: the
    # admissions a call reads before the step's tokens were all enqueued before
    # that step was (they lie before it on the device), and those enqueued
    # behind it are read by the next call
    prefills = sorted(_named(cap, "serve_prefill"), key=lambda s: s["t0"])
    enqueues = phases["serve_args"]  # one a step, in the order the steps were enqueued
    behind = 0
    for admission, r in zip(prefills, reads):
        (k,) = [k for k, d in enumerate(steps) if _inside(r, d)]  # the call that read step k
        assert admission["t1"] <= enqueues[k]["t0"] + _STAMP_EPS
        behind += k > 0 and admission["t0"] >= enqueues[k - 1]["t1"] - _STAMP_EPS
    assert behind == 2  # the two that took the slots of the first to end
    # and a finished request is stamped when its last step's tokens are emitted,
    # before the loop goes on to the next iteration
    for r in reqs:
        (d,) = [d for d in steps if d["t1"] <= r.t_done and
                not any(d["t1"] < e["t1"] <= r.t_done for e in steps)]
        (iteration,) = [i for i in iterations if _inside(d, i)]
        assert r.t_done <= iteration["t1"]


def test_counters_of_deferred_admissions_grow_as_with_blocking_calls(tiny_cfg):
    from opendiloco_tpu.serve import ContinuousBatcher

    engine, blocking = _tiny_engine(tiny_cfg), _tiny_engine(tiny_cfg)
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5], [3, 4, 5, 6, 7, 8, 9], [2, 3]]
    batcher = ContinuousBatcher(engine)
    reqs = [batcher.submit(p, max_new_tokens=4) for p in prompts]
    batcher.start()
    try:
        for r in reqs:
            assert r.wait(300) and r.error is None
    finally:
        batcher.stop()
    # the same four requests by the blocking calls, batched as the loop batched them
    tokens, lens = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for slot, prompt in enumerate(prompts):
        tokens[slot], _ = blocking.admit(slot, prompt)
        lens[slot] = len(prompt)
    for _ in range(3):
        tokens, _ = blocking.decode_step(tokens, lens)
        lens = lens + 1
    assert engine.phase_calls == blocking.phase_calls == {"prefill": 4, "decode": 3}
    assert engine.admissions_deferred == 4 and blocking.admissions_deferred == 0
    for stage in ("prefill", "decode"):
        assert all(v > 0 for v in engine.phase_seconds[stage].values())
        # a stage's seconds hold its phases and never another stage's read
        assert sum(engine.phase_seconds[stage].values()) <= engine.stage_seconds[stage]
    assert sum(engine.stage_seconds.values()) <= batcher.loop_seconds
    stats = batcher.stats()
    assert stats["admissions_deferred"] == 4 == stats["phase_calls"]["prefill"]
    json.dumps(stats)


def test_the_last_capture_stays_readable():
    assert obs.capture.last() is None
    obs.capture.start()
    with obs.span("first"):
        pass
    assert obs.capture.last() is None  # an open capture is not yet one kept
    first = obs.capture.stop()
    assert obs.capture.last() is first  # the object itself, nothing copied
    obs.capture.start()
    assert obs.capture.last() is first
    second = obs.capture.stop()
    assert obs.capture.last() is second is not first
    obs.reset()
    assert obs.capture.last() is None


def _decode_past_the_gauges(batcher, steps):
    """One request of ``steps`` decode steps (its first token is the
    prefill's); the wait returns from inside the last of them."""
    r = batcher.submit([3, 4, 5], max_new_tokens=steps + 1)
    assert r.wait(120) and r.error is None


def test_no_tracer_means_no_gauge_is_computed(tiny_cfg, monkeypatch):
    from opendiloco_tpu.serve import ContinuousBatcher

    engine = _tiny_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine, gauge_every_steps=4)
    percentiles = []
    real = np.percentile
    monkeypatch.setattr(np, "percentile", lambda *a, **k: percentiles.append(1) or real(*a, **k))
    batcher.start()
    try:
        _decode_past_the_gauges(batcher, 9)  # the gauges came due twice
        assert not percentiles
        # a capture arms the tracer later
        obs.capture.start()
        _decode_past_the_gauges(batcher, 18)
        cap = obs.capture.stop()
    finally:
        batcher.stop()
    assert percentiles  # armed, the gauges are computed as they were
    assert any(k.startswith("serve_tokens_generated") for k in cap.counters)


def test_a_tracer_at_start_means_the_gauges_are_published(tiny_cfg, monkeypatch):
    from opendiloco_tpu.serve import ContinuousBatcher

    tr = _arm(monkeypatch)
    engine = _tiny_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine, gauge_every_steps=4).start()
    try:
        _decode_past_the_gauges(batcher, 9)
    finally:
        batcher.stop()
    # the gauges are published as they were at the parent, and none is a probe's
    names = {name for name, _labels in tr.gauges()}
    assert {"serve_batch_occupancy", "serve_tokens_per_s", "serve_queue_depth"} <= names
    assert "serve_decode_attn_us" not in names


def test_the_staleness_watchdog_is_called_with_no_tracer(tiny_cfg, monkeypatch):
    from opendiloco_tpu.serve import ContinuousBatcher

    seen = []

    class _Watchdog:
        def serve_staleness(self, staleness, bound, exemplars=()):
            seen.append((staleness, bound))

    monkeypatch.setattr(obs.anomaly, "watchdog", lambda: _Watchdog())
    engine = _tiny_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine, gauge_every_steps=4).start()
    try:
        _decode_past_the_gauges(batcher, 9)
    finally:
        batcher.stop()
    assert obs.tracer() is None and len(seen) >= 2
