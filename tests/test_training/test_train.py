"""Integration tests: drive the real CLI in subprocesses.

Mirror of the reference's tests/test_training/test_train.py:
- run the actual ``python -m opendiloco_tpu.train`` command a user types,
  on fake data with the dummy metric logger as a spy
- resume-determinism oracle: run N steps with checkpointing, rerun resuming
  mid-way, assert losses/LRs match at overlapping steps (:59-83)
- multi-worker DiLoCo over a real rendezvous + TCP backend in separate
  processes, then resume both workers from checkpoints (:115-206)
"""

import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_cli(args: list[str], env_extra=None, timeout=600) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "opendiloco_tpu.train", *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )


def base_args(tmp_path, logger_file, extra=None) -> list[str]:
    args = [
        "--path-model", "2m",
        "--fake-data",
        "--seq-length", "64",
        "--per-device-train-batch-size", "4",
        "--total-batch-size", "32",
        "--lr", "1e-3",
        "--warmup-steps", "4",
        "--total-steps", "20",
        "--precision", "fp32",
        "--metric-logger-type", "dummy",
        "--project", str(logger_file),
        "--ckpt.path", str(tmp_path / "ckpts"),
        "--ckpt.interval", "10",
    ]
    return args + (extra or [])



def spawn_rendezvous_daemon() -> tuple[subprocess.Popen, str]:
    """Launch one Python rendezvous daemon on an ephemeral port and harvest
    its announced host:port (chaos tests share this so daemon launch/parse
    changes happen in one place, like spawn_worker for workers)."""
    d = subprocess.Popen(
        [
            sys.executable, "-m", "opendiloco_tpu.diloco.rendezvous",
            "--host", "127.0.0.1", "--port", "0",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")},
        cwd=REPO,
    )
    # skip log lines; fail loudly on daemon death
    while True:
        line = d.stdout.readline()
        assert line, "rendezvous daemon died before announcing its port"
        if "initial_peers =" in line:
            return d, line.strip().split()[-1].replace("0.0.0.0", "127.0.0.1")


def spawn_worker(args) -> subprocess.Popen:
    """Launch one training worker process on the CPU mesh (multi-worker
    tests share this so env/launch changes happen in one place)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-m", "opendiloco_tpu.train", *args],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
        cwd=REPO,
    )

def read_metrics(logger_file) -> list[dict]:
    with open(logger_file, "rb") as f:
        return pickle.load(f)


def communicate_all(procs, timeout):
    """communicate() every proc, kill stragglers, assert all exited 0;
    returns the stdout texts. The multihost tests share this so
    wedged-process cleanup changes happen in one place (same convention as
    spawn_worker for launches)."""
    try:
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:  # never leak a wedged distributed process
            if p.poll() is None:
                p.kill()
    assert all(p.returncode == 0 for p in procs), "\n".join(
        o[-2000:] for o in outs
    )
    return outs


@pytest.mark.slow
def test_train_and_resume_deterministic(tmp_path):
    """Losses and LRs after resume match the uninterrupted run exactly
    (reference oracle: allclose atol=1e-3 loss, exact LR)."""
    full_log = tmp_path / "full.pkl"
    r = run_cli(base_args(tmp_path, full_log))
    assert r.returncode == 0, r.stderr[-3000:]
    full = read_metrics(full_log)
    assert len(full) == 20

    resume_log = tmp_path / "resume.pkl"
    resume_dir = str(tmp_path / "ckpts" / "model_step_10")
    r = run_cli(base_args(tmp_path, resume_log, ["--ckpt.resume", resume_dir]))
    assert r.returncode == 0, r.stderr[-3000:]
    resumed = read_metrics(resume_log)
    assert len(resumed) == 10 and resumed[0]["step"] == 11

    by_step_full = {m["step"]: m for m in full}
    for m in resumed:
        ref = by_step_full[m["step"]]
        np.testing.assert_allclose(m["Loss"], ref["Loss"], atol=1e-3)
        assert m["lr"] == ref["lr"]


@pytest.mark.slow
def test_multi_worker_diloco_tcp(tmp_path):
    """Two DiLoCo workers in separate processes over rendezvous+TCP."""
    from opendiloco_tpu.diloco.rendezvous import RendezvousServer

    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    try:
        procs, logs = [], []
        for rank in range(2):
            logf = tmp_path / f"worker{rank}.pkl"
            logs.append(logf)
            args = base_args(
                tmp_path,
                logf,
                [
                    "--total-steps", "12",
                    "--diloco.local-steps", "4",
                    "--diloco.initial-peers", server.address,
                    "--diloco.world-rank", str(rank),
                    "--diloco.galaxy-size", "2",
                    "--diloco.matchmaking-time", "2.0",
                    "--diloco.backend", "tcp",
                    "--diloco.skip-load-from-peers",
                    "--no-ckpt.interval",
                ],
            )
            procs.append(spawn_worker(args))
        outs = [p.communicate(timeout=600) for p in procs]
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, err[-3000:]

        metrics = [read_metrics(f) for f in logs]
        for rows in metrics:
            assert len(rows) == 12
            assert all(np.isfinite(r["Loss"]) for r in rows)
            # outer steps happened: epochs advanced and peers were seen
            assert rows[-1]["outer_epoch"] == 3
            assert rows[-1]["num_peers"] == 2
    finally:
        server.stop()


@pytest.mark.slow
def test_worker_sigkill_survivor_continues(tmp_path):
    """Chaos probe: SIGKILL one of two TCP workers mid-run; the survivor's
    rounds keep completing (elastic matchmaking) and it finishes all steps.
    The reference validated fault tolerance only by manual ablation
    (SURVEY.md §5.3); here it is an automated test."""
    import signal
    import time as _time

    from opendiloco_tpu.diloco.rendezvous import RendezvousServer

    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    try:
        procs, logs = [], []
        for rank in range(2):
            logf = tmp_path / f"chaos{rank}.pkl"
            logs.append(logf)
            args = base_args(
                tmp_path,
                logf,
                [
                    "--total-steps", "16",
                    "--diloco.local-steps", "4",
                    "--diloco.initial-peers", server.address,
                    "--diloco.world-rank", str(rank),
                    "--diloco.galaxy-size", "2",
                    "--diloco.matchmaking-time", "1.0",
                    "--diloco.averaging-timeout", "20",
                    "--diloco.all-reduce-strategy", "no_wait",
                    "--diloco.backend", "tcp",
                    "--diloco.skip-load-from-peers",
                    "--no-ckpt.interval",
                ],
            )
            procs.append(spawn_worker(args))
        # let both compile and sync at least one outer round, then kill 1
        _time.sleep(30)
        procs[1].send_signal(signal.SIGKILL)
        out0, err0 = procs[0].communicate(timeout=600)
        procs[1].communicate(timeout=30)
        assert procs[0].returncode == 0, err0[-3000:]
        rows = read_metrics(logs[0])
        assert len(rows) == 16  # survivor finished every step
        assert all(np.isfinite(r["Loss"]) for r in rows)
        assert rows[-1]["outer_epoch"] == 4
    finally:
        server.stop()


@pytest.mark.slow
def test_graft_dryrun_multichip(tmp_path):
    """The driver's multichip dry-run must work for 4 and 8 virtual devices."""
    for n in (4, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run(
            [
                sys.executable,
                "-c",
                f"import __graft_entry__ as g; g.dryrun_multichip({n})",
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=env,
            cwd=REPO,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "dryrun_multichip ok" in r.stdout


@pytest.mark.slow
def test_profile_dir_writes_trace(tmp_path):
    prof = tmp_path / "trace"
    r = run_cli(
        base_args(tmp_path, tmp_path / "prof.pkl", [
            "--total-steps", "8", "--no-ckpt.interval",
            "--profile-dir", str(prof), "--profile-start", "2", "--profile-steps", "3",
        ])
    )
    assert r.returncode == 0, r.stderr[-2000:]
    files = list(prof.rglob("*"))
    assert any(f.name.endswith(".xplane.pb") for f in files), "no trace written"
    # the window goes through obs.capture: the program's spans of those
    # steps lie beside the trace, with the anchor that places them on it
    import json

    capture = json.loads((prof / "odtp_capture.json").read_text())
    assert capture["anchor_pc"] > 0
    dispatches = [s for s in capture["spans"] if s["name"] == "inner/dispatch"]
    assert len(dispatches) == 3


@pytest.mark.slow
def test_run_training_sh_launcher(tmp_path):
    """The documented multi-worker launcher works end to end (auto
    rendezvous via the native daemon when built, else Python)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["WANDB_MODE"] = "disabled"
    r = subprocess.run(
        [
            os.path.join(REPO, "scripts", "run_training.sh"), "2", "auto",
            "--path-model", "2m", "--fake-data", "--seq-length", "64",
            "--per-device-train-batch-size", "4", "--total-batch-size", "16",
            "--total-steps", "8", "--precision", "fp32",
            "--metric-logger-type", "dummy",
            "--project", str(tmp_path / "w.pkl"),
            "--no-ckpt.interval",
            "--diloco.local-steps", "4",
            "--diloco.matchmaking-time", "1.5",
            "--diloco.backend", "tcp",
            "--diloco.skip-load-from-peers",
        ],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])


@pytest.mark.slow
def test_multi_worker_resume_deterministic(tmp_path):
    """Both DiLoCo workers restart from step-8 checkpoints (fresh rendezvous,
    like the reference's test_multi_gpu_hivemind restart phase) and reproduce
    the uninterrupted run's losses."""
    from opendiloco_tpu.diloco.rendezvous import RendezvousServer

    def launch(server, rank, logf, extra):
        args = base_args(
            tmp_path,
            logf,
            [
                "--total-steps", "12",
                "--ckpt.interval", "4",
                "--diloco.local-steps", "4",
                "--diloco.initial-peers", server.address,
                "--diloco.world-rank", str(rank),
                "--diloco.galaxy-size", "2",
                "--diloco.matchmaking-time", "2.0",
                "--diloco.backend", "tcp",
                "--diloco.skip-load-from-peers",
                *extra,
            ],
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        return subprocess.Popen(
            [sys.executable, "-m", "opendiloco_tpu.train", *args],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO,
        )

    # phase 1: full run with checkpoints
    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    try:
        procs = [
            launch(server, r, tmp_path / f"full{r}.pkl", []) for r in range(2)
        ]
        for p in procs:
            _, err = p.communicate(timeout=600)
            assert p.returncode == 0, err[-3000:]
    finally:
        server.stop()

    # phase 2: fresh rendezvous, both resume from step 8
    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    try:
        procs = [
            launch(
                server, r, tmp_path / f"res{r}.pkl",
                ["--ckpt.resume", str(tmp_path / "ckpts" / "model_step_8")],
            )
            for r in range(2)
        ]
        for p in procs:
            _, err = p.communicate(timeout=600)
            assert p.returncode == 0, err[-3000:]
    finally:
        server.stop()

    for r in range(2):
        full = {m["step"]: m for m in read_metrics(tmp_path / f"full{r}.pkl")}
        res = read_metrics(tmp_path / f"res{r}.pkl")
        assert [m["step"] for m in res] == [9, 10, 11, 12]
        for m in res:
            np.testing.assert_allclose(m["Loss"], full[m["step"]]["Loss"], atol=1e-2)
            assert m["lr"] == full[m["step"]]["lr"]


@pytest.mark.slow
def test_multihost_two_process_train_and_resume(tmp_path):
    """REAL multihost: two jax.distributed processes form one 4-device mesh
    (2 local CPU devices each), train FULL_SHARD, checkpoint, and resume
    deterministically -- per-process loader shards assemble into the global
    batch and sidecar files are scoped by process_index."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]

    def launch(pid, logf, extra):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        args = [
            "--path-model", "2m", "--fake-data",
            "--seq-length", "64",
            "--per-device-train-batch-size", "4",
            "--total-batch-size", "16",
            "--lr", "1e-3", "--warmup-steps", "2", "--total-steps", "8",
            "--precision", "fp32",
            "--sharding-strategy", "FULL_SHARD",
            "--metric-logger-type", "dummy", "--project", str(logf),
            "--ckpt.path", str(tmp_path / "ckpts"), "--ckpt.interval", "4",
            "--multihost",
            "--coordinator-address", f"127.0.0.1:{coord_port}",
            "--num-processes", "2", "--process-id", str(pid),
        ] + extra
        return subprocess.Popen(
            [sys.executable, "-m", "opendiloco_tpu.train", *args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO,
        )

    # generous timeout: two jax.distributed processes contend with the
    # rest of the suite for this box's single CPU
    run_pair = lambda procs: communicate_all(procs, 1200)

    run_pair([launch(p, tmp_path / f"full_{p}.pkl", []) for p in (0, 1)])
    full = read_metrics(tmp_path / "full_0.pkl")
    assert len(full) == 8

    # per-process loader sidecars exist for both hosts
    ckpt_dir = tmp_path / "ckpts" / "model_step_4"
    files = set(os.listdir(ckpt_dir))
    assert {"dataloader_0.json", "dataloader_1.json"} <= files

    # resume both processes from step 4; losses must match the full run
    import shutil

    shutil.rmtree(tmp_path / "ckpts" / "model_step_8")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]
    run_pair(
        [
            launch(p, tmp_path / f"res_{p}.pkl", ["--ckpt.resume", "True"])
            for p in (0, 1)
        ]
    )
    resumed = read_metrics(tmp_path / "res_0.pkl")
    assert resumed[0]["step"] == 5
    by_step = {m["step"]: m for m in full}
    for m in resumed:
        np.testing.assert_allclose(m["Loss"], by_step[m["step"]]["Loss"], atol=1e-4)
        assert m["lr"] == by_step[m["step"]]["lr"]


@pytest.mark.slow
def test_multihost_diloco_compose_hybrid(tmp_path):
    """The reference's flagship topology, composed (train_fsdp.py:183
    messenger election, :205-212 messenger-only DHT join, :410-413
    post-outer-step fan-out; SURVEY §1 "key structural fact"): each DiLoCo
    worker is a 2-process jax.distributed slice over a HYBRID dp=2 x fsdp=2
    mesh, and only process 0 of each slice joins the WAN fabric. Two such
    workers train over a real rendezvous + TCP butterfly. Oracles:
      - exactly one registered peer per worker (outer group size 2, not 4)
      - the loss trajectory matches the identical run with single-process
        workers (4 local devices each): the intra-worker topology is
        numerically invisible to the algorithm
      - bit-exact resume from the mid-run checkpoint on the hybrid
        multihost mesh (VERDICT r4 #8 folded in)
    """
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    daemon, addr = spawn_rendezvous_daemon()
    STEPS, LOCAL = 8, 4

    def worker_args(rank, logf, ckpt_dir):
        return [
            "--path-model", "2m", "--fake-data",
            "--seq-length", "64",
            "--per-device-train-batch-size", "4",
            "--total-batch-size", "16",
            "--lr", "1e-3", "--warmup-steps", "2",
            "--total-steps", str(STEPS),
            "--precision", "fp32",
            "--sharding-strategy", "HYBRID_SHARD",
            "--dp-size", "2", "--fsdp-size", "2",
            "--metric-logger-type", "dummy", "--project", str(logf),
            "--ckpt.path", str(ckpt_dir), "--ckpt.interval", str(LOCAL),
            "--diloco.local-steps", str(LOCAL),
            "--diloco.initial-peers", addr,
            "--diloco.world-rank", str(rank),
            "--diloco.galaxy-size", "2",
            "--diloco.backend", "tcp",
            "--diloco.skip-load-from-peers",
            "--diloco.matchmaking-time", "2.0",
            "--diloco.averaging-timeout", "120",
        ]

    def launch_slice_proc(rank, pid, coord_port, logf, ckpt_dir, extra):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        args = worker_args(rank, logf, ckpt_dir) + [
            "--multihost",
            "--coordinator-address", f"127.0.0.1:{coord_port}",
            "--num-processes", "2", "--process-id", str(pid),
        ] + extra
        return subprocess.Popen(
            [sys.executable, "-m", "opendiloco_tpu.train", *args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO,
        )

    run_all = lambda procs: communicate_all(procs, 1800)

    try:
        # --- composed arm: 2 workers x 2 processes ---------------------
        coords = [free_port(), free_port()]
        run_all(
            [
                launch_slice_proc(
                    r, p, coords[r],
                    tmp_path / f"mh_w{r}_p{p}.pkl", tmp_path / "ckpts", [],
                )
                for r in range(2)
                for p in range(2)
            ]
        )

        # --- reference arm: same run, single-process workers -----------
        ref = [
            spawn_worker(
                worker_args(r, tmp_path / f"ref_w{r}.pkl", tmp_path / "ckpts_ref")
            )
            for r in range(2)
        ]
        for p in ref:
            out, err = p.communicate(timeout=1800)
            assert p.returncode == 0, (out or "")[-2000:] + (err or "")[-2000:]
    finally:
        daemon.kill()

    for r in range(2):
        mh = read_metrics(tmp_path / f"mh_w{r}_p0.pkl")
        assert len(mh) == STEPS
        # one registered peer per WORKER: the outer group reaches 2 and
        # NEVER exceeds it (per-host duplicate registration would read 4);
        # early rows legitimately report 1 until the first round lands
        peers_seen = [m["num_peers"] for m in mh if "num_peers" in m]
        assert peers_seen and max(peers_seen) == 2, peers_seen
        assert mh[-1]["num_peers"] == 2, peers_seen
        # both slice processes observed the identical trajectory
        mh_p1 = read_metrics(tmp_path / f"mh_w{r}_p1.pkl")
        for a, b in zip(mh, mh_p1):
            assert a["Loss"] == b["Loss"], (a, b)
        # composition is numerically invisible vs single-process workers
        by_step_ref = {
            m["step"]: m for m in read_metrics(tmp_path / f"ref_w{r}.pkl")
        }
        for m in mh:
            np.testing.assert_allclose(
                m["Loss"], by_step_ref[m["step"]]["Loss"], atol=1e-4
            )
            assert m["lr"] == by_step_ref[m["step"]]["lr"]

    # --- resume arm: bit-exact restart of the whole composed topology --
    daemon2, addr2 = spawn_rendezvous_daemon()
    addr = addr2  # worker_args closes over `addr`
    resume_dir = str(tmp_path / "ckpts" / f"model_step_{LOCAL}")
    try:
        coords = [free_port(), free_port()]
        run_all(
            [
                launch_slice_proc(
                    r, p, coords[r],
                    tmp_path / f"res_w{r}_p{p}.pkl", tmp_path / "ckpts",
                    ["--ckpt.resume", resume_dir],
                )
                for r in range(2)
                for p in range(2)
            ]
        )
    finally:
        daemon2.kill()

    for r in range(2):
        full = {
            m["step"]: m
            for m in read_metrics(tmp_path / f"mh_w{r}_p0.pkl")
        }
        res = read_metrics(tmp_path / f"res_w{r}_p0.pkl")
        assert res and res[0]["step"] == LOCAL + 1
        for m in res:
            np.testing.assert_allclose(
                m["Loss"], full[m["step"]]["Loss"], atol=1e-4
            )
            assert m["lr"] == full[m["step"]]["lr"]

    # --- overlap arm: overlapped outer comm across the slice ------------
    # the landing step is timing-dependent by design, so no cross-topology
    # loss oracle; the invariants are lockstep within the slice (p0 == p1
    # at every step), one peer per worker, and a finite trained loss
    daemon3, addr3 = spawn_rendezvous_daemon()
    addr = addr3
    try:
        coords = [free_port(), free_port()]
        run_all(
            [
                launch_slice_proc(
                    r, p, coords[r],
                    tmp_path / f"ov_w{r}_p{p}.pkl", tmp_path / "ckpts_ov",
                    ["--diloco.overlap-comm", "delayed"],
                )
                for r in range(2)
                for p in range(2)
            ]
        )
    finally:
        daemon3.kill()
    for r in range(2):
        ov = read_metrics(tmp_path / f"ov_w{r}_p0.pkl")
        ov_p1 = read_metrics(tmp_path / f"ov_w{r}_p1.pkl")
        assert len(ov) == STEPS
        for a, b in zip(ov, ov_p1):
            assert a["Loss"] == b["Loss"], (a, b)
        peers_seen = [m["num_peers"] for m in ov if "num_peers" in m]
        assert peers_seen and max(peers_seen) == 2, peers_seen
        assert np.isfinite(ov[-1]["Loss"]) and ov[-1]["Loss"] < 7.0


@pytest.mark.slow
@pytest.mark.parametrize(
    "mode,extra",
    [
        ("streaming", ["--diloco.streaming-fragments", "2"]),
        ("gossip", ["--diloco.outer-mode", "gossip"]),
    ],
)
def test_multihost_diloco_slice_modes(tmp_path, mode, extra):
    """The beyond-ref outer modes compose with a multihost slice too: one
    worker as a 2-process jax.distributed slice (galaxy 1) runs streaming
    fragment sync / gossip through the world-messenger fan-out. Oracles:
    completes all steps, both slice processes record the identical
    trajectory, finite trained loss."""
    import socket

    daemon, addr = spawn_rendezvous_daemon()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = s.getsockname()[1]

    def launch(pid):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        args = [
            "--path-model", "2m", "--fake-data", "--seq-length", "64",
            "--per-device-train-batch-size", "4", "--total-batch-size", "16",
            "--lr", "1e-3", "--warmup-steps", "2", "--total-steps", "6",
            "--precision", "fp32",
            "--sharding-strategy", "FULL_SHARD",
            "--metric-logger-type", "dummy",
            "--project", str(tmp_path / f"{mode}_{pid}.pkl"),
            "--no-ckpt.interval",
            "--diloco.local-steps", "2",
            "--diloco.initial-peers", addr,
            "--diloco.world-rank", "0", "--diloco.galaxy-size", "1",
            "--diloco.backend", "tcp", "--diloco.skip-load-from-peers",
            "--diloco.matchmaking-time", "1.0",
            "--diloco.averaging-timeout", "60",
            "--multihost", "--coordinator-address", f"127.0.0.1:{coord}",
            "--num-processes", "2", "--process-id", str(pid),
        ] + extra
        return subprocess.Popen(
            [sys.executable, "-m", "opendiloco_tpu.train", *args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO,
        )

    procs = [launch(0), launch(1)]
    try:
        communicate_all(procs, 900)
    finally:
        daemon.kill()
        for p in procs:
            if p.poll() is None:
                p.kill()
    m0 = read_metrics(tmp_path / f"{mode}_0.pkl")
    m1 = read_metrics(tmp_path / f"{mode}_1.pkl")
    assert len(m0) == 6 and len(m1) == 6  # a short m1 would make zip vacuous
    for a, b in zip(m0, m1):
        assert a["Loss"] == b["Loss"], (a, b)
    assert np.isfinite(m0[-1]["Loss"]) and m0[-1]["Loss"] < 7.0


@pytest.mark.slow
def test_rendezvous_sigkill_failover_training_completes(tmp_path):
    """Chaos probe for the control plane: two rendezvous daemons, two TCP
    workers; the daemon the swarm is using is SIGKILLed mid-run. Both
    workers fail over to the second daemon in lockstep and finish every
    step (the reference's DHT survives bootstrap death; VERDICT round-1
    asked for exactly this test)."""
    import signal
    import time as _time

    daemons, addrs = zip(*(spawn_rendezvous_daemon() for _ in range(2)))
    peers = ",".join(addrs)

    procs, logs = [], []
    try:
        for rank in range(2):
            logf = tmp_path / f"rdvchaos{rank}.pkl"
            logs.append(logf)
            args = base_args(
                tmp_path,
                logf,
                [
                    "--total-steps", "16",
                    "--diloco.local-steps", "4",
                    "--diloco.initial-peers", peers,
                    "--diloco.world-rank", str(rank),
                    "--diloco.galaxy-size", "2",
                    "--diloco.matchmaking-time", "1.0",
                    "--diloco.averaging-timeout", "30",
                    "--diloco.backend", "tcp",
                    "--diloco.skip-load-from-peers",
                    "--no-ckpt.interval",
                ],
            )
            procs.append(spawn_worker(args))
        _time.sleep(25)  # let the swarm form and sync on daemon 0
        alive_at_kill = all(p.poll() is None for p in procs)
        daemons[0].send_signal(signal.SIGKILL)
        outs = [p.communicate(timeout=600) for p in procs]
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, err[-3000:]
        for logf in logs:
            rows = read_metrics(logf)
            assert len(rows) == 16
            assert all(np.isfinite(r["Loss"]) for r in rows)
            assert rows[-1]["outer_epoch"] == 4
            assert rows[-1]["num_peers"] == 2  # never split into solo groups
        if alive_at_kill:
            # workers outlived daemon 0 -> at least one must have failed over
            assert any("failing over" in (e or "") for _, e in outs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for d in daemons:
            if d.poll() is None:
                d.kill()


@pytest.mark.slow
def test_all_daemons_sigkill_training_reforms_on_worker(tmp_path):
    """The ONLY rendezvous daemon is SIGKILLed mid-training: the swarm must
    re-form on a worker-hosted embedded rendezvous (every worker is also a
    rendezvous node, like every hivemind peer is a DHT node) and finish
    every step with both peers -- never a solo split, never a crash."""
    import signal
    import time as _time

    daemon, peers = spawn_rendezvous_daemon()

    procs, logs = [], []
    try:
        for rank in range(2):
            logf = tmp_path / f"alldead{rank}.pkl"
            logs.append(logf)
            args = base_args(
                tmp_path,
                logf,
                [
                    "--total-steps", "60",
                    "--diloco.local-steps", "4",
                    "--diloco.initial-peers", peers,
                    "--diloco.world-rank", str(rank),
                    "--diloco.galaxy-size", "2",
                    "--diloco.matchmaking-time", "1.0",
                    "--diloco.averaging-timeout", "30",
                    "--diloco.backend", "tcp",
                    "--diloco.skip-load-from-peers",
                    "--no-ckpt.interval",
                ],
            )
            procs.append(spawn_worker(args))
        _time.sleep(30)  # compile + the first outer rounds on the daemon
        alive_at_kill = all(p.poll() is None for p in procs)
        daemon.send_signal(signal.SIGKILL)  # the ENTIRE daemon fabric dies
        outs = [p.communicate(timeout=600) for p in procs]
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, err[-3000:]
        for logf in logs:
            rows = read_metrics(logf)
            assert len(rows) == 60
            assert all(np.isfinite(r["Loss"]) for r in rows)
            assert rows[-1]["outer_epoch"] == 15
            assert rows[-1]["num_peers"] == 2  # never split into solo groups
        if alive_at_kill:
            assert any(
                "re-formed on worker-hosted rendezvous" in (e or "")
                for _, e in outs
            )
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        if daemon.poll() is None:
            daemon.kill()


@pytest.mark.slow
def test_bf16_pp_cpu_partitioner_bug_pinned():
    """Pins the upstream XLA CPU-partitioner CHECK-failure ("Invalid binary
    instruction opcode copy") on bf16 + the pp x sp x tp mesh -- the reason
    __graft_entry__.dryrun_multichip defaults to fp32 on the CPU dry-run.

    The crash is a process abort, so it must run in a subprocess (which
    dryrun_multichip's self-re-exec already provides). If THIS TEST FAILS,
    the upstream bug is fixed: drop the fp32 workaround (make bf16-mixed the
    dryrun default) and delete this pin.
    """
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
    finally:
        sys.path.pop(0)
    try:
        __graft_entry__.dryrun_multichip(8, precision="bf16-mixed")
    except RuntimeError:
        return  # still crashes: workaround still needed
    pytest.fail(
        "bf16 + pp x sp x tp now compiles on the CPU partitioner -- drop the "
        "fp32 workaround in __graft_entry__.dryrun_multichip and this pin"
    )
