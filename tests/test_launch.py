"""Gang supervisor tests (ISSUE 13, docs/robustness.md "Multi-host
fault model", docs/spmd.md "Launcher").

Fast tier: the supervisor's protocol machinery with raw-protocol
workers (plain `python -c` beaters — no jax import): heartbeat state /
step progress, kill -9 detection + gang restart + budget refund,
missed-heartbeat hang detection, restart-budget exhaustion going
sticky-terminal (typed GangFailed, /workerz + /readyz degraded, never
a hang), monotonic-only liveness math under a wall-clock jump, and the
bounded in-process rendezvous raising a typed RendezvousTimeout.

Slow tier (@slow @spmd, run by scripts/run_spmd_tests.sh): real
2-process jax gangs through tests/gang_runner.py — kill -9 mid-step
with BITWISE-identical resumed loss stream, and cross-process loss
parity against a single-process run of the same ShardingPlan.
"""
import os
import signal
import sys
import time

import numpy as np
import pytest

from paddle_tpu import failpoints, introspect, launch
from paddle_tpu.failpoints import InjectedFault
from paddle_tpu.launch import GangFailed, GangSupervisor
from paddle_tpu.monitor import stat_get

RUNNER = os.path.join(os.path.dirname(__file__), "gang_runner.py")

# a gang worker speaking the raw heartbeat protocol — no jax import, so
# the supervisor machinery tests stay in the fast tier. Modes:
#   clean    beat 3 steps, exit 0
#   sleep01  rank 0 wedges (still beating) on attempts 0 and 1 so the
#            parent can kill -9 it twice; attempt 2 runs clean
#   mute     attempt 0 stops beating but stays alive (the hang model);
#            restarted attempts run clean
RAW_WORKER = """
import json, os, socket, sys, time
host, _, port = os.environ["PADDLE_LAUNCH_HEARTBEAT"].rpartition(":")
rank = int(os.environ["PADDLE_TRAINER_ID"])
attempt = int(os.environ["PADDLE_LAUNCH_ATTEMPT"])
s = socket.create_connection((host, int(port)), timeout=5)
def beat(state, step=0):
    s.sendall((json.dumps({"rank": rank, "attempt": attempt,
                           "pid": os.getpid(), "state": state,
                           "step": step}) + "\\n").encode())
beat("rendezvous")
mode = sys.argv[1] if len(sys.argv) > 1 else "clean"
for n in (1, 2, 3):
    beat("running", n)
    time.sleep(0.05)
if mode == "sleep01" and rank == 0 and attempt < 2:
    for n in range(4, 1200):
        beat("running", n)
        time.sleep(0.05)
if mode == "mute" and attempt == 0:
    time.sleep(60)
"""


def _raw_gang(mode, name, **kw):
    kw.setdefault("heartbeat_interval_s", 0.05)
    kw.setdefault("heartbeat_timeout_s", 5.0)
    kw.setdefault("spawn_grace_s", 15.0)
    kw.setdefault("restart_backoff_ms", 10.0)
    kw.setdefault("max_restarts", 0)
    return GangSupervisor([sys.executable, "-c", RAW_WORKER, mode], 2,
                          name=name, **kw)


def _poll(pred, timeout=20.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError("condition not reached within %.1fs" % timeout)


# ---------------------------------------------------------------------------
# rendezvous: bounded, typed
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _disarm_all():
    failpoints.disarm()
    yield
    failpoints.disarm()


def test_rendezvous_timeout_typed(monkeypatch):
    """A gang missing a peer must raise RendezvousTimeout after the
    bounded retry budget — never hang until an operator notices."""
    import paddle_tpu.parallel as dist
    from paddle_tpu.parallel.env import RendezvousTimeout
    monkeypatch.setenv("PADDLE_RENDEZVOUS_TIMEOUT_S", "1")
    monkeypatch.setenv("PADDLE_RENDEZVOUS_RETRIES", "2")
    monkeypatch.setenv("PADDLE_RENDEZVOUS_BACKOFF_MS", "1")
    monkeypatch.delenv("PADDLE_LAUNCH_HEARTBEAT", raising=False)
    r0 = stat_get("STAT_worker_rendezvous_retries")
    with failpoints.armed("dist.rendezvous=raise"):
        with pytest.raises(RendezvousTimeout) as ei:
            dist.init_distributed_runtime(
                coordinator_address="127.0.0.1:1",
                num_processes=2, process_id=0)
    e = ei.value
    assert e.attempts == 3
    assert e.coordinator == "127.0.0.1:1"
    assert isinstance(e.cause, InjectedFault)
    assert e.elapsed_s >= 0.0
    assert stat_get("STAT_worker_rendezvous_retries") == r0 + 2


# ---------------------------------------------------------------------------
# liveness math: monotonic only
# ---------------------------------------------------------------------------

class _FakeProc:
    pid = 4242

    def poll(self):
        return None


def test_wallclock_jump_never_fakes_missed_heartbeats(monkeypatch):
    """An NTP step / VM-migration wall-clock jump must not trip (or
    mask) the missed-heartbeat window: liveness ages are differences of
    the supervisor's time.monotonic() receipts."""
    sup = GangSupervisor([sys.executable, "-c", "pass"], 1,
                         heartbeat_timeout_s=2.0, spawn_grace_s=2.0,
                         max_restarts=0, name="wallclock-unit")
    w = launch._Worker(0, _FakeProc(), None)
    w.state = "running"
    w.last_beat = time.monotonic()
    sup._workers[0] = w

    real_time = time.time
    monkeypatch.setattr(time, "time", lambda: real_time() + 3600.0)
    assert sup._check_gang() is None  # 1h wall jump: still healthy
    assert w.state == "running"

    real_mono = time.monotonic
    monkeypatch.setattr(time, "monotonic", lambda: real_mono() + 10.0)
    cause = sup._check_gang()  # monotonic age past the window: lost
    assert cause is not None and "missed heartbeats" in cause
    assert w.state == "lost"


# ---------------------------------------------------------------------------
# heartbeat protocol with raw workers
# ---------------------------------------------------------------------------

def test_heartbeat_state_and_step_progress():
    sup = _raw_gang("clean", "proto")
    sup.start()
    try:
        assert sup.wait(timeout=30) == 0
    finally:
        sup.stop()
    st = sup.status()
    assert st["state"] == "done"
    for w in st["workers"]:
        assert w["state"] == "exited" and w["exit_code"] == 0
        assert w["beats"] >= 4 and w["step"] == 3
    kinds = [e["kind"] for e in sup.events()]
    assert "worker_running" in kinds
    assert "step_progress" in kinds
    assert kinds[-1] == "done"


def test_kill9_detect_restart_and_budget_refund():
    """kill -9 a worker mid-run: the gang is torn down and restarted;
    because each incarnation made step progress the restart budget is
    REFUNDED — two consecutive kills survive max_restarts=1."""
    d0 = stat_get("STAT_launch_worker_deaths")
    sup = _raw_gang("sleep01", "kill9", max_restarts=1)
    sup.start()
    t_kills = []
    try:
        for k in (0, 1):
            def _armed():
                st = sup.status()
                w0 = [w for w in st["workers"] if w["rank"] == 0][0]
                return st["attempt"] == k and w0["step"] >= 1 and \
                    w0["state"] == "running" and st["state"] == "running" \
                    and w0
            w0 = _poll(_armed)
            t_kills.append(time.monotonic())
            os.kill(w0["pid"], signal.SIGKILL)
            _poll(lambda: sup.status()["attempt"] == k + 1)
        assert sup.wait(timeout=30) == 0
    finally:
        sup.stop()
    st = sup.status()
    # without the PR-9 refund the second kill would exhaust the budget
    assert st["state"] == "done" and st["restarts"] == 1
    deaths = [e for e in sup.events() if e["kind"] == "worker_death"]
    assert len(deaths) == 2 and all(e["rank"] == 0 for e in deaths)
    # kill -9 is caught by the process poll, well inside any heartbeat
    # window (50ms sweep; generous slack for a loaded CI host)
    assert deaths[0]["t_mono"] - t_kills[0] < 2.0
    assert stat_get("STAT_launch_worker_deaths") == d0 + 2


def test_missed_heartbeat_window_detects_hang():
    """A worker that stays alive but stops beating (wedged host) is
    LOST once its last beat ages past the window; the gang restarts."""
    l0 = stat_get("STAT_launch_worker_lost")
    sup = _raw_gang("mute", "hang", heartbeat_timeout_s=0.6,
                    max_restarts=1)
    sup.start()
    try:
        with pytest.raises(TimeoutError):  # typed, never a silent hang
            sup.wait(timeout=0.05)
        assert sup.wait(timeout=30) == 0
    finally:
        sup.stop()
    lost = [e for e in sup.events() if e["kind"] == "worker_lost"]
    assert lost and lost[0]["phase"] == "run"
    assert lost[0]["age_s"] >= 0.6
    assert stat_get("STAT_launch_worker_lost") > l0


# ---------------------------------------------------------------------------
# restart budget exhaustion: sticky-terminal
# ---------------------------------------------------------------------------

def test_restart_budget_exhaustion_sticky_terminal():
    x0 = stat_get("STAT_launch_restart_exhausted")
    sup = GangSupervisor(
        [sys.executable, "-c", "import sys; sys.exit(3)"], 2,
        heartbeat_interval_s=0.05, heartbeat_timeout_s=5.0,
        spawn_grace_s=15.0, max_restarts=1, restart_backoff_ms=10.0,
        name="exhaust")
    sup.start()
    try:
        with pytest.raises(GangFailed) as ei:
            sup.wait(timeout=30)
        e = ei.value
        assert e.name == "exhaust" and e.restarts == 1
        assert "died rc=3" in e.cause
        st = sup.status()
        assert st["state"] == "failed"
        assert st["failure_cause"] and st["restarts"] == 2
        # the first worker seen dead is the cause; its sibling may be
        # torn down before it exits on its own (a race under load)
        assert any(w["state"] == "died" and w["exit_code"] == 3
                   for w in st["workers"])
        assert all(w["exit_code"] is not None for w in st["workers"])
        # observable while terminal: /workerz lists it, /readyz degrades
        gz = [g for g in launch.workerz()["gangs"]
              if g["name"] == "exhaust"]
        assert gz and gz[0]["state"] == "failed"
        ready, checks = introspect.readiness()
        assert checks["gang_exhaust"] is False and ready is False
        with pytest.raises(GangFailed):  # sticky: every wait re-raises
            sup.wait(timeout=1)
        assert stat_get("STAT_launch_restart_exhausted") == x0 + 1
    finally:
        sup.stop()
    _ready, checks = introspect.readiness()
    assert "gang_exhaust" not in checks  # probe unregistered by stop()


def test_cli_clean_run(tmp_path):
    rc = launch.main(["--nproc", "1", "--max-restarts", "0",
                      "--log-dir", str(tmp_path), "--",
                      sys.executable, "-c", "print('cli-ok')"])
    assert rc == 0
    logs = list(tmp_path.iterdir())
    assert logs and "cli-ok" in logs[0].read_text()


# ---------------------------------------------------------------------------
# real jax gangs (slow tier; scripts/run_spmd_tests.sh runs these)
# ---------------------------------------------------------------------------

def _jax_gang(name, tmp, nproc, dev_per_proc, ckdir="", **kw):
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["GANG_STEPS"] = "8"
    env["GANG_CK_EVERY"] = "2"
    env["GANG_CKDIR"] = ckdir
    logd = os.path.join(str(tmp), name)
    kw.setdefault("max_restarts", 2)
    return GangSupervisor(
        [RUNNER], nproc, cpu_devices_per_proc=dev_per_proc,
        log_dir=logd, env=env, heartbeat_interval_s=0.2,
        heartbeat_timeout_s=30.0, spawn_grace_s=300.0,
        restart_backoff_ms=50.0, name=name, **kw), logd


def _losses(logd):
    """step -> float32-hex, spliced across attempts (later attempts
    re-print from the resume point; bitwise resume makes the overlap
    identical, which the caller asserts)."""
    out = {}
    for fn in sorted(os.listdir(logd)):
        with open(os.path.join(logd, fn)) as f:
            for line in f:
                parts = line.split()
                if len(parts) == 3 and parts[0] == "STEP":
                    out[int(parts[1])] = parts[2]
    return out


@pytest.mark.slow
@pytest.mark.spmd
def test_gang_kill9_midstep_bitwise_resume(tmp_path):
    """THE acceptance pin: kill -9 one rank of a live 2-process jax
    gang mid-step; the supervisor detects it within the heartbeat
    window, restarts the gang, and the resumed loss stream is
    BITWISE-identical to an uninterrupted run."""
    ref_sup, ref_logd = _jax_gang("ref", tmp_path, 2, 1,
                                  ckdir=str(tmp_path / "ck_ref"))
    assert ref_sup.run(timeout=600) == 0
    ref = _losses(ref_logd)
    assert sorted(ref) == list(range(1, 9))

    sup, logd = _jax_gang("chaos", tmp_path, 2, 1,
                          ckdir=str(tmp_path / "ck_chaos"))
    sup.start()
    try:
        def _mid_step():
            st = sup.status()
            if st["attempt"] != 0:
                return None
            if max(w["step"] for w in st["workers"]) < 3:
                return None
            return [w for w in st["workers"] if w["rank"] == 1][0]
        w1 = _poll(_mid_step, timeout=480, interval=0.02)
        t_kill = time.monotonic()
        os.kill(w1["pid"], signal.SIGKILL)
        assert sup.wait(timeout=600) == 0
    finally:
        sup.stop()

    det = [e for e in sup.events() if e["t_mono"] >= t_kill
           and e["kind"] in ("worker_death", "worker_lost")]
    assert det, sup.events()
    # detected within the heartbeat window (kill -9 lands much faster,
    # via the 50ms process poll)
    assert det[0]["t_mono"] - t_kill < sup.heartbeat_timeout_s + 5.0
    assert any(e["kind"] == "restart" for e in sup.events())

    got = _losses(logd)
    assert sorted(got) == list(range(1, 9))
    assert got == ref  # bitwise: float32 hex, every step


@pytest.mark.slow
@pytest.mark.spmd
def test_cross_process_loss_parity(tmp_path):
    """2 processes x 1 device vs 1 process x 2 devices under the same
    ShardingPlan({"dp": 2}): per-step loss parity (the
    test_dist_multiproc.py bar) through the launcher path."""
    multi, multi_logd = _jax_gang("multi", tmp_path, 2, 1)
    assert multi.run(timeout=600) == 0
    single, single_logd = _jax_gang("single", tmp_path, 1, 2)
    assert single.run(timeout=600) == 0

    def _vals(logd):
        hx = _losses(logd)
        assert sorted(hx) == list(range(1, 9)), hx
        return [np.frombuffer(bytes.fromhex(hx[n]), np.float32)[0]
                for n in sorted(hx)]
    got, ref = _vals(multi_logd), _vals(single_logd)
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
    assert got[-1] < got[0]  # training actually progressed
