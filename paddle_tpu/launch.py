"""Gang launcher + supervisor for multi-process SPMD (docs/robustness.md
"Multi-host fault model", docs/spmd.md "Launcher").

The reference's distributed families both assume workers die: the
parameter-server path heartbeats trainers from the pserver
(/root/reference/paddle/fluid/operators/distributed/ listen-and-serve
keeps per-trainer liveness), and the collective path restarts the whole
gang from checkpoints. This module is that story for the mesh runtime:
one supervisor process spawns N workers under the cluster env contract
(fleet/launch.py's PADDLE_TRAINER_* variables), watches them through
**monotonic-clock heartbeats**, and on any worker death (kill -9), hang
(missed heartbeats), or raise tears the WHOLE gang down and restarts it
— SPMD collectives make partial membership meaningless, so recovery is
always gang-granular, exactly like the reference's collective mode.

Recovery composes three existing pieces instead of inventing new ones:

- restart budget: the PR-9 pool pattern (serving.py `_supervisor`) at
  gang granularity — capped exponential backoff doubling from
  FLAGS_launch_restart_backoff_ms (capped at 32x), budget refunded once
  an incarnation makes step progress, sticky-terminal
  :class:`GangFailed` on exhaustion (never a silent retry loop).
- bounded rendezvous: workers call parallel/env.py's
  init_distributed_runtime, which retries jax.distributed.initialize
  under a budget and raises a typed RendezvousTimeout instead of
  hanging; the supervisor sees the nonzero exit and restarts.
- deterministic resume: workers run TrainStep.run_loop with
  FLAGS_auto_checkpoint_steps; on restart the gang resumes from the
  newest AtomicCheckpointer commit and fast-forwards the deterministic
  batch stream, so the resumed loss stream is BITWISE-identical to an
  uninterrupted run (pinned in tests/test_launch.py and measured by
  bench.py's chaos_multihost block).

Heartbeats ride a localhost TCP socket: each worker connects to the
supervisor (PADDLE_LAUNCH_HEARTBEAT=host:port) and sends one JSON line
every FLAGS_launch_heartbeat_interval_s. The supervisor stamps receipt
with ``time.monotonic()`` — wall-clock jumps (NTP step, VM migration)
can never fake or mask a missed-heartbeat window (the PR-8
`_Future.t_submit` lesson, pinned by a wall-clock-jump test). A worker
whose last beat is older than FLAGS_launch_heartbeat_timeout_s is LOST;
a worker that never beats gets FLAGS_launch_spawn_grace_s (jax import +
rendezvous ride inside it).

Failpoint sites `dist.rendezvous`, `worker.heartbeat`, `worker.step`
drive the chaos tests; workers inherit arming through the
PADDLE_TPU_FAILPOINTS environment variable (read once at import; the
PADDLE_TPU_FAILPOINTS_RANK<k> variant arms a single rank — the
straggler drill's injection path).
Observability: ``/workerz`` on the introspection server (per-worker
state, last-heartbeat age, restart counts), STAT_launch_restarts /
STAT_launch_worker_deaths / STAT_launch_worker_lost counters and the
GAUGE_launch_worker_state{rank=...} series.

Gang-wide observability plane (docs/observability.md "Gang-wide
observability"): when FLAGS_launch_digest is on (default), every
heartbeat line piggybacks a bounded, versioned ``digest`` —
:func:`build_digest`: step counter, TIMER_step_phase_us window stats,
collective-byte census deltas, KV-pool occupancy. The supervisor
re-emits digests as rank-labeled instruments (GAUGE_gang_step,
TIMER_gang_step_phase_us, GAUGE_gang_collective_wait_frac), scores
per-rank skew into GAUGE_gang_straggler_score (self step-time — wall
time minus the host's device/gang waits — vs the gang's lower
median), and feeds the skew SLO objective (slo.py) so the burn-rate
engine pages on a persistent straggler. ``/gangz`` serves the
per-rank table (text + ?format=json). Digest-off keeps the wire
byte-identical to the PR-13 format and costs one flag lookup. Workers
additionally export per-rank chrome traces at exit when
PADDLE_TPU_TRACE_DIR is set (merge with tools/trace_merge.py).

CLI::

    python -m paddle_tpu.launch --nproc 2 --cpu-devices-per-proc 1 \\
        train.py --epochs 10
"""
from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

from .failpoints import failpoint
from .monitor import gauge_set, labeled, observe_many, stat_add

__all__ = [
    "GangFailed",
    "GangSupervisor",
    "build_digest",
    "gangz",
    "gangz_text",
    "heartbeat_step",
    "main",
    "maybe_start_worker_heartbeat",
    "set_worker_state",
    "workerz",
]

# GAUGE_launch_worker_state{rank=...} value encoding
WORKER_STATE_CODES = {
    "spawned": 0,     # process started, no heartbeat yet
    "rendezvous": 1,  # beating, jax.distributed rendezvous in flight
    "running": 2,     # rendezvous formed, training
    "exited": 3,      # clean exit (rc 0)
    "lost": 4,        # heartbeat window missed (host hang / kill -9)
    "died": 5,        # nonzero exit / killed by signal
}


class GangFailed(RuntimeError):
    """The gang exhausted its restart budget and is sticky-terminal.
    Raised by :meth:`GangSupervisor.wait` / :meth:`run` — an in-flight
    caller gets a typed error, never a hang. Carries the restart count
    and the last failure cause for postmortems."""

    def __init__(self, name: str, restarts: int, cause: str):
        super().__init__(
            "gang %r terminally failed after %d restart(s): %s"
            % (name, restarts, cause))
        self.name = name
        self.restarts = restarts
        self.cause = cause


# ---------------------------------------------------------------------------
# worker side: heartbeat client + metrics digest
# ---------------------------------------------------------------------------

# digest wire-format version: the supervisor accepts 1..DIGEST_VERSION
# and counts anything else into STAT_launch_digest_rejected without
# touching the beat's liveness fields, so mixed-version gangs degrade
# to metrics loss, never to restarts
DIGEST_VERSION = 1

# supervisor-side hard cap on ONE heartbeat line: a line that blows it
# is skimmed to the next newline and counted, never buffered or parsed
# (satellite bugfix: the old reader buffered unbounded lines)
MAX_BEAT_LINE = 64 * 1024

# phase keys mirrored from jit.STEP_PHASES — spelled out here because
# launch.py must stay importable without jax (workers heartbeat before
# and during the jax import)
_DIGEST_PHASES = ("stage", "dispatch", "compute", "exchange", "sync",
                  "total")

_DTYPE_RE = re.compile(r'dtype="([^"]*)"')


def build_digest(step: int, prev: Optional[Dict[str, Any]] = None,
                 max_bytes: Optional[int] = None
                 ) -> Optional[Dict[str, Any]]:
    """The bounded worker metrics digest one heartbeat line carries.

    Fields (all optional beyond v/step, dropped oldest-luxury-first
    when the serialized JSON would exceed the cap):

    - ``v``/``step`` — format version + the worker's step counter.
    - ``phases`` — per-phase {n,p50,p95} from the TIMER_step_phase_us
      windowed monitor (all-time stats when windows are off).
    - ``dev_us``/``wait_us`` — cumulative microseconds spent INSIDE
      the step call (the "total" phase: staging through the loss sync)
      and in the exchange+sync gang tail alone. The supervisor
      subtracts dev_us deltas from beat-to-beat wall time to get the
      rank's own "self time" — the straggler score numerator. The
      whole call counts, not just compute+waits, because on a
      synchronous gang the healthy ranks absorb a straggler's lag as
      device-queue backpressure anywhere inside their call (staging
      blocks behind the stuck collective), while the dragging host's
      own stall is by definition OUTSIDE its step call.
    - ``coll`` — dtype -> collective wire-byte deltas since the last
      digest (census counters; *prev* carries the totals between
      calls).
    - ``kv`` — KV block-pool occupancy when serving.

    Returns None when even the minimal digest would not fit.
    """
    from . import monitor
    if max_bytes is None:
        from .flags import get_flag
        max_bytes = int(get_flag("FLAGS_launch_digest_max_bytes"))
    d: Dict[str, Any] = {"v": DIGEST_VERSION, "step": int(step)}
    use_win = monitor.windows_enabled()
    phases: Dict[str, Any] = {}
    dev_us = wait_us = 0.0
    for ph in _DIGEST_PHASES:
        key = labeled("TIMER_step_phase_us", {"phase": ph})
        tot = monitor.timer_get(key)
        if not tot["count"]:
            continue
        st = monitor.timer_window(key, 60.0) if use_win else tot
        if st["count"]:
            phases[ph] = {"n": int(st["count"]),
                          "p50": round(float(st["p50"]), 1),
                          "p95": round(float(st["p95"]), 1)}
        if ph == "total":
            dev_us += tot["sum"]
        if ph in ("exchange", "sync"):
            wait_us += tot["sum"]
    if phases:
        d["phases"] = phases
        d["dev_us"] = round(dev_us, 1)
        d["wait_us"] = round(wait_us, 1)
    counters = monitor.get_float_stats()
    totals = {k: v for k, v in counters.items()
              if k.startswith("STAT_mesh_collective_bytes{")}
    if totals:
        prev_c = prev.get("coll", {}) if prev is not None else {}
        deltas: Dict[str, int] = {}
        for k, v in totals.items():
            dv = v - prev_c.get(k, 0.0)
            if dv > 0:
                m = _DTYPE_RE.search(k)
                dt = m.group(1) if m else "?"
                deltas[dt] = deltas.get(dt, 0) + int(dv)
        if deltas:
            d["coll"] = deltas
        if prev is not None:
            prev["coll"] = totals
    free = monitor.gauge_get("GAUGE_generation_blocks_free", -1.0)
    used = monitor.gauge_get("GAUGE_generation_blocks_used", -1.0)
    if free >= 0 and used >= 0 and free + used > 0:
        d["kv"] = {"free": int(free), "used": int(used)}
    compact = (",", ":")
    if len(json.dumps(d, separators=compact)) <= max_bytes:
        return d
    stat_add("STAT_launch_digest_truncated")
    for key in ("coll", "kv", "phases", "wait_us", "dev_us"):
        d.pop(key, None)
        if len(json.dumps(d, separators=compact)) <= max_bytes:
            return d
    return None


class _Beater:
    """Worker-side heartbeat thread. One JSON line per interval over the
    supervisor's TCP socket; an immediate extra beat on every
    state/step change so transitions reach the supervisor promptly."""

    def __init__(self, addr: str, rank: int, attempt: int,
                 interval_s: float, state: str):
        host, _, port = addr.rpartition(":")
        self.rank = rank
        self.attempt = attempt
        self.interval_s = interval_s
        self.state = state
        self.step = 0
        # PADDLE_LAUNCH_DIGEST (set by the supervisor from its own
        # FLAGS_launch_digest) wins over this worker's flag so a
        # digest-off supervisor gets a PR-13 wire from every worker;
        # unset (plain maybe_start_worker_heartbeat) defers to the flag
        denv = os.environ.get("PADDLE_LAUNCH_DIGEST")
        self._digest_env = None if denv is None \
            else denv not in ("0", "", "false")
        self._digest_prev: Dict[str, Any] = {}
        from .flags import get_flag
        self._get_flag = get_flag
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._sock = socket.create_connection((host, int(port)), timeout=5)
        self._thread = threading.Thread(target=self._loop,
                                        name="pt-heartbeat", daemon=True)
        self._thread.start()

    def _maybe_digest(self) -> Optional[Dict[str, Any]]:
        on = self._digest_env
        if on is None:
            # disabled path = this one flag lookup (pinned like
            # tracing/failpoints/slo): build_digest is never called
            on = bool(self._get_flag("FLAGS_launch_digest"))
        if not on:
            return None
        try:
            return build_digest(self.step, prev=self._digest_prev)
        except Exception:
            return None  # metrics must never break liveness

    def _send(self) -> None:
        dig = self._maybe_digest()
        with self._lock:
            msg = {"rank": self.rank, "attempt": self.attempt,
                   "pid": os.getpid(), "state": self.state,
                   "step": self.step}
            if dig is not None:
                # appended AFTER the PR-13 fields: digest-off stays
                # byte-identical, digest-on parses on old supervisors
                # (unknown key ignored)
                msg["digest"] = dig
            self._sock.sendall((json.dumps(msg) + "\n").encode("utf-8"))
        stat_add("STAT_worker_heartbeats_sent")

    def beat(self) -> None:
        try:
            self._send()
        except OSError:
            pass  # supervisor gone; the beat loop will exit too

    def _loop(self) -> None:
        while not self._stop.is_set():
            # OUTSIDE any try: an armed worker.heartbeat=raise kills
            # this thread and the beats simply stop — the host-hang
            # model the supervisor's missed-beat window detects.
            # delay(ms) models a wedged-but-crawling host.
            failpoint("worker.heartbeat")
            try:
                self._send()
            except OSError:
                return
            self._stop.wait(self.interval_s)


_BEATER: Optional[_Beater] = None
_BEATER_LOCK = threading.Lock()


def maybe_start_worker_heartbeat(state: str = "spawned") -> bool:
    """Start the worker-side heartbeat thread iff this process was
    spawned by a :class:`GangSupervisor` (PADDLE_LAUNCH_HEARTBEAT set).
    Idempotent; returns True when a beater is running. Called from
    parallel/env.py before rendezvous so a worker wedged in rendezvous
    still reads as alive-but-stuck rather than silent."""
    global _BEATER
    addr = os.environ.get("PADDLE_LAUNCH_HEARTBEAT")
    if not addr:
        return False
    with _BEATER_LOCK:
        if _BEATER is not None:
            return True
        try:
            _BEATER = _Beater(
                addr,
                rank=int(os.environ.get("PADDLE_TRAINER_ID", "0")),
                attempt=int(os.environ.get("PADDLE_LAUNCH_ATTEMPT", "0")),
                interval_s=float(os.environ.get(
                    "PADDLE_LAUNCH_HEARTBEAT_INTERVAL_S", "1.0")),
                state=state)
        except OSError:
            return False  # supervisor already gone; run unsupervised
        if os.environ.get("PADDLE_TPU_TRACE_DIR"):
            # per-rank chrome trace for tools/trace_merge.py, written
            # at exit so one file covers the worker's whole life
            import atexit
            from . import profiler
            atexit.register(profiler.maybe_export_rank_trace)
    return True


def set_worker_state(state: str) -> None:
    """Update this worker's reported state ('rendezvous' -> 'running');
    no-op outside a supervised gang."""
    b = _BEATER
    if b is None:
        return
    b.state = state
    b.beat()


def heartbeat_step(step: int) -> None:
    """Stamp training progress into the heartbeat stream — call once
    per training step. Fires the `worker.step` failpoint (the
    mid-step host-loss model for chaos tests) and, under a supervisor,
    beats immediately so step progress refunds the restart budget
    without waiting out the interval. No-op-cheap standalone."""
    failpoint("worker.step")
    b = _BEATER
    if b is None:
        return
    b.step = int(step)
    stat_add("STAT_worker_steps")
    b.beat()


# ---------------------------------------------------------------------------
# supervisor side
# ---------------------------------------------------------------------------

class _Worker:
    """Supervisor-side view of one gang member."""

    __slots__ = ("rank", "proc", "state", "spawned_at", "last_beat",
                 "beats", "step", "exit_code", "log_path",
                 "digest", "digest_at", "hist", "score", "wait_frac")

    def __init__(self, rank: int, proc: subprocess.Popen,
                 log_path: Optional[str]):
        from collections import deque
        self.rank = rank
        self.proc = proc
        self.state = "spawned"
        self.spawned_at = time.monotonic()
        self.last_beat: Optional[float] = None
        self.beats = 0
        self.step = 0
        self.exit_code: Optional[int] = None
        self.log_path = log_path
        # gang-observability state, all digest-fed: the latest digest
        # (for /gangz), a (t_mono, step, dev_us, wait_us) history the
        # straggler window slides over, and the derived scores
        self.digest: Optional[Dict[str, Any]] = None
        self.digest_at: Optional[float] = None
        self.hist: "deque" = deque(maxlen=512)
        self.score: Optional[float] = None
        self.wait_frac: Optional[float] = None


_SUPERVISORS: "weakref.WeakSet[GangSupervisor]" = weakref.WeakSet()


def workerz() -> Dict[str, Any]:
    """The /workerz payload: every live supervisor's status."""
    return {"gangs": [s.status() for s in list(_SUPERVISORS)]}


def _free_ports(n: int) -> List[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class GangSupervisor:
    """Spawn and supervise an N-process SPMD gang.

    ``argv`` is the worker command (a leading ``*.py`` gets
    ``sys.executable`` prepended); every worker runs the same command
    and learns its rank from the cluster env contract. With
    ``cpu_devices_per_proc`` set, workers are pinned to the CPU backend
    with that many fake devices (this container / CI); leave it None on
    real TPU pods where each process owns its local chips.

    Lifecycle: :meth:`start` spawns the gang and the supervision
    thread; :meth:`wait` blocks until the gang completes (returns 0) or
    goes sticky-terminal (raises :class:`GangFailed` — never hangs);
    :meth:`run` is start+wait+stop. All deadline arithmetic uses
    ``time.monotonic()``.
    """

    def __init__(self, argv: List[str], nprocs: int, *,
                 cpu_devices_per_proc: Optional[int] = None,
                 log_dir: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None,
                 heartbeat_interval_s: Optional[float] = None,
                 heartbeat_timeout_s: Optional[float] = None,
                 spawn_grace_s: Optional[float] = None,
                 max_restarts: Optional[int] = None,
                 restart_backoff_ms: Optional[float] = None,
                 rendezvous_timeout_s: Optional[float] = None,
                 term_grace_s: float = 5.0,
                 straggler_threshold: Optional[float] = None,
                 straggler_window_s: Optional[float] = None,
                 name: Optional[str] = None):
        from .flags import get_flag

        def _flag(v, fname, cast):
            return cast(get_flag(fname)) if v is None else cast(v)

        if argv and argv[0].endswith(".py"):
            argv = [sys.executable] + list(argv)
        self.argv = list(argv)
        self.nprocs = int(nprocs)
        self.cpu_devices_per_proc = cpu_devices_per_proc
        self.log_dir = log_dir
        self._base_env = dict(env) if env is not None else dict(os.environ)
        self.heartbeat_interval_s = _flag(
            heartbeat_interval_s, "FLAGS_launch_heartbeat_interval_s", float)
        self.heartbeat_timeout_s = _flag(
            heartbeat_timeout_s, "FLAGS_launch_heartbeat_timeout_s", float)
        self.spawn_grace_s = _flag(
            spawn_grace_s, "FLAGS_launch_spawn_grace_s", float)
        self.max_restarts = _flag(
            max_restarts, "FLAGS_launch_max_restarts", int)
        self.restart_backoff_s = _flag(
            restart_backoff_ms, "FLAGS_launch_restart_backoff_ms",
            float) / 1e3
        self.rendezvous_timeout_s = None if rendezvous_timeout_s is None \
            else float(rendezvous_timeout_s)
        self.term_grace_s = float(term_grace_s)
        self.straggler_threshold = _flag(
            straggler_threshold, "FLAGS_launch_straggler_threshold", float)
        sw = _flag(straggler_window_s,
                   "FLAGS_launch_straggler_window_s", float)
        # auto window scales with the beat cadence so a fast-beating
        # test gang converges (and clears) in seconds
        self.straggler_window_s = sw if sw > 0 else \
            max(20.0 * self.heartbeat_interval_s, 2.0)
        # read once here: workers inherit the supervisor's digest
        # setting through PADDLE_LAUNCH_DIGEST (fresh processes would
        # otherwise reset to the flag default on every restart)
        self._digest_on = bool(get_flag("FLAGS_launch_digest"))
        self.name = name or "gang%d" % os.getpid()

        self._lock = threading.Lock()
        self._state = "idle"  # idle -> running -> (restarting ->)
        #                       done | failed (sticky)
        self._attempt = 0
        self._restarts = 0
        self._progress_since_restart = False
        self._failure_cause = ""
        self._workers: Dict[int, _Worker] = {}
        self._events: List[Dict[str, Any]] = []
        self._stop_ev = threading.Event()
        self._done_ev = threading.Event()
        self._hb_sock: Optional[socket.socket] = None
        self._threads: List[threading.Thread] = []

    # -- events / status ---------------------------------------------------

    def _event(self, kind: str, **detail) -> None:
        e = {"t_mono": time.monotonic(), "kind": kind}
        e.update(detail)
        with self._lock:
            self._events.append(e)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._events]

    def status(self) -> Dict[str, Any]:
        now = time.monotonic()
        with self._lock:
            workers = []
            for w in self._workers.values():
                workers.append({
                    "rank": w.rank,
                    "pid": w.proc.pid,
                    "state": w.state,
                    "beats": w.beats,
                    "step": w.step,
                    "exit_code": w.exit_code,
                    "last_beat_age_s": (
                        round(now - w.last_beat, 3)
                        if w.last_beat is not None else None),
                    "straggler_score": (
                        round(w.score, 3) if w.score is not None
                        else None),
                    "wait_frac": (
                        round(w.wait_frac, 4) if w.wait_frac is not None
                        else None),
                })
            return {
                "name": self.name,
                "state": self._state,
                "attempt": self._attempt,
                "restarts": self._restarts,
                "max_restarts": self.max_restarts,
                "nprocs": self.nprocs,
                "failure_cause": self._failure_cause or None,
                "heartbeat": {
                    "interval_s": self.heartbeat_interval_s,
                    "timeout_s": self.heartbeat_timeout_s,
                    "spawn_grace_s": self.spawn_grace_s,
                },
                "straggler": {
                    "threshold": self.straggler_threshold,
                    "window_s": self.straggler_window_s,
                },
                "workers": sorted(workers, key=lambda w: w["rank"]),
            }

    def _set_worker_state(self, w: _Worker, state: str) -> None:
        w.state = state
        gauge_set(labeled("GAUGE_launch_worker_state",
                          {"gang": self.name, "rank": str(w.rank)}),
                  WORKER_STATE_CODES.get(state, -1))

    # -- heartbeat server --------------------------------------------------

    def _hb_serve(self) -> None:
        assert self._hb_sock is not None
        while not self._stop_ev.is_set():
            try:
                conn, _ = self._hb_sock.accept()
            except OSError:
                return  # socket closed by stop()
            t = threading.Thread(target=self._hb_conn, args=(conn,),
                                 name="pt-gang-hb", daemon=True)
            t.start()

    def _hb_conn(self, conn: socket.socket) -> None:
        try:
            with conn, conn.makefile("r", encoding="utf-8") as f:
                while True:
                    # bounded readline: the old `for line in f` buffered
                    # arbitrarily long lines, so one runaway digest
                    # could balloon supervisor memory. A line that hits
                    # the cap is counted, skimmed to its newline, and
                    # the connection keeps serving — a bad metrics line
                    # must never tear the gang down
                    line = f.readline(MAX_BEAT_LINE)
                    if not line:
                        return
                    if not line.endswith("\n") and \
                            len(line) >= MAX_BEAT_LINE:
                        stat_add("STAT_launch_digest_rejected")
                        while True:
                            rest = f.readline(MAX_BEAT_LINE)
                            if not rest or rest.endswith("\n"):
                                break
                        continue
                    try:
                        msg = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(msg, dict):
                        self._on_beat(msg)
        except OSError:
            pass

    def _on_beat(self, msg: Dict[str, Any]) -> None:
        now = time.monotonic()  # receipt-stamped on the SUPERVISOR's
        # monotonic clock: worker clocks and wall time never enter the
        # liveness math
        with self._lock:
            if int(msg.get("attempt", -1)) != self._attempt:
                return  # stale beat from a torn-down incarnation
            w = self._workers.get(int(msg.get("rank", -1)))
            if w is None or w.state in ("lost", "died", "exited"):
                return
            w.last_beat = now
            w.beats += 1
            step = int(msg.get("step", 0) or 0)
            if step > w.step:
                w.step = step
            state = msg.get("state")
            if state in ("rendezvous", "running") and w.state != state:
                self._set_worker_state(w, state)
                first_running = state == "running"
            else:
                first_running = False
            progressed = step > 0 and not self._progress_since_restart
            if progressed:
                self._progress_since_restart = True
        if first_running:
            self._event("worker_running", rank=w.rank)
        if progressed:
            self._event("step_progress", rank=w.rank, step=step)
        dig = msg.get("digest")
        if dig is not None:
            try:
                self._ingest_digest(w, dig, now)
            except Exception:
                # malformed/unsupported digest: drop the metrics, keep
                # the beat — liveness already updated above
                stat_add("STAT_launch_digest_rejected")

    # -- digest aggregation / straggler scoring ---------------------------

    def _ingest_digest(self, w: _Worker, dig: Dict[str, Any],
                       now: float) -> None:
        """Re-emit one worker digest as rank-labeled instruments and
        refresh the gang's straggler scores. Any malformed field raises
        and the caller counts one STAT_launch_digest_rejected."""
        if not isinstance(dig, dict):
            raise ValueError("digest is not an object")
        v = int(dig.get("v", -1))
        if not 1 <= v <= DIGEST_VERSION:
            raise ValueError("unsupported digest version %d" % v)
        step = int(dig.get("step", w.step) or 0)
        lbl = {"gang": self.name, "rank": str(w.rank)}
        timers = []
        phases = dig.get("phases")
        if phases is not None:
            # one window-p50 sample per beat: TIMER_gang_step_phase_us
            # is a summary-of-summaries (documented), good for skew and
            # trend — not a raw latency histogram
            for ph, st in sorted(phases.items()):
                timers.append((
                    labeled("TIMER_gang_step_phase_us",
                            {**lbl, "phase": str(ph)[:16]}),
                    float(st["p50"])))
        dev = dig.get("dev_us")
        wait = dig.get("wait_us")
        with self._lock:
            w.digest = dig
            w.digest_at = now
            w.hist.append((now, step,
                           None if dev is None else float(dev),
                           None if wait is None else float(wait)))
            scores, fracs = self._straggler_scores(now)
        worst = 0.0
        for rank, sc in scores.items():
            gauge_set(labeled("GAUGE_gang_straggler_score",
                              {"gang": self.name, "rank": str(rank)}), sc)
            wr = self._workers.get(rank)
            if wr is not None:
                wr.score = sc
            worst = max(worst, sc)
        for rank, fr in fracs.items():
            gauge_set(labeled("GAUGE_gang_collective_wait_frac",
                              {"gang": self.name, "rank": str(rank)}), fr)
            wr = self._workers.get(rank)
            if wr is not None:
                wr.wait_frac = fr
        gauge_set(labeled("GAUGE_gang_step", lbl), float(step))
        # the skew SLO's ratio: beats observed while the gang had a
        # straggler / all digest beats (slo.install_gang_objectives)
        stats = [("STAT_gang_digest_beats", 1.0)]
        if worst > self.straggler_threshold:
            stats.append(("STAT_gang_straggler_beats", 1.0))
        observe_many(timers=timers, stats=stats)
        if self.log_dir:
            # append the raw digest to the rank's JSONL log so offline
            # tools (tools/trace_merge.py --digests) can join wire-byte
            # deltas onto the rank's exchange-phase trace slices.
            # Receipt-stamped with the supervisor's monotonic clock —
            # same basis as the liveness math; best-effort, a full
            # disk must never tear the gang down
            try:
                os.makedirs(self.log_dir, exist_ok=True)
                path = os.path.join(self.log_dir,
                                    "digests_rank%d.jsonl" % w.rank)
                line = json.dumps({"t_mono": round(now, 6),
                                   "rank": w.rank, **dig},
                                  separators=(",", ":"))
                with open(path, "a", encoding="utf-8") as f:
                    f.write(line + "\n")
            except OSError:
                pass

    def _straggler_scores(self, now: float):
        """(scores, wait_fracs) by rank, from each worker's digest
        history over the trailing straggler window. Self step-time =
        (wall delta - dev_us delta) / steps: the time the rank's HOST
        spent outside its step call — in a synchronous gang every
        rank's raw step RATE equals the slowest rank's, so raw rate
        cannot finger the straggler, but the dragging host accrues its
        stall outside its call while everyone else absorbs that lag as
        backpressure INSIDE their calls (dev_us). Scores are self-time
        over the gang lower median (biases healthy when half the gang
        drags — we assume a minority of stragglers), floored at a
        quarter of the gang's median step time so near-zero self-times
        score ~0 instead of amplifying noise. Callers hold
        self._lock."""
        win = self.straggler_window_s
        selfs: Dict[int, float] = {}
        rates: Dict[int, float] = {}
        fracs: Dict[int, float] = {}
        for w in self._workers.values():
            ent = [e for e in w.hist if e[0] >= now - win]
            if len(ent) < 2:
                continue
            t0, s0, d0, w0 = ent[0]
            t1, s1, d1, w1 = ent[-1]
            dsteps = s1 - s0
            dt_us = (t1 - t0) * 1e6
            if dsteps <= 0 or dt_us <= 0:
                continue
            if w0 is not None and w1 is not None:
                fracs[w.rank] = min(max((w1 - w0) / dt_us, 0.0), 1.0)
            rates[w.rank] = dt_us / dsteps
            if d0 is not None and d1 is not None:
                self_us = max(dt_us - max(d1 - d0, 0.0), 0.0)
            else:
                # no phase timers in this worker: fall back to the raw
                # step time (still catches asynchronous stragglers)
                self_us = dt_us
            selfs[w.rank] = self_us / dsteps
        if not selfs:
            return {}, fracs
        vals = sorted(selfs.values())
        rvals = sorted(rates[r] for r in selfs)
        # the denominator floors at a quarter of the gang's median step
        # time: a healthy gang's self-times are near zero, and a ratio
        # of two near-zeros is noise — self-time only MEANS straggling
        # once it's a real fraction of a step, and the floor also keeps
        # the score finite when the median self-time is ~0
        med = max(vals[(len(vals) - 1) // 2],
                  0.25 * rvals[(len(rvals) - 1) // 2], 1.0)
        return {r: v / med for r, v in selfs.items()}, fracs

    # -- spawning / teardown -----------------------------------------------

    def _worker_env(self, rank: int, endpoints: List[str],
                    hb_port: int) -> Dict[str, str]:
        env = dict(self._base_env)
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = str(self.nprocs)
        env["PADDLE_TRAINER_ENDPOINTS"] = ",".join(endpoints)
        env["PADDLE_COORDINATOR_ENDPOINT"] = endpoints[0]
        env["PADDLE_CURRENT_ENDPOINT"] = endpoints[rank]
        env["TRAINING_ROLE"] = "TRAINER"
        env["PADDLE_LAUNCH_HEARTBEAT"] = "127.0.0.1:%d" % hb_port
        env["PADDLE_LAUNCH_HEARTBEAT_INTERVAL_S"] = \
            str(self.heartbeat_interval_s)
        env["PADDLE_LAUNCH_ATTEMPT"] = str(self._attempt)
        env["PADDLE_LAUNCH_DIGEST"] = "1" if self._digest_on else "0"
        # Workers run `python <script>`, so sys.path[0] is the script's
        # directory, not the supervisor's cwd. Propagate the cwd on
        # PYTHONPATH (append, never overwrite: the user's own entries
        # also ride this variable) so `import paddle_tpu` resolves the
        # same way for workers as it did for the launcher.
        cwd = os.getcwd()
        paths = env.get("PYTHONPATH", "")
        if cwd not in paths.split(os.pathsep):
            env["PYTHONPATH"] = \
                cwd + os.pathsep + paths if paths else cwd
        if self.rendezvous_timeout_s is not None:
            env["PADDLE_RENDEZVOUS_TIMEOUT_S"] = \
                str(self.rendezvous_timeout_s)
        if self.cpu_devices_per_proc is not None:
            env["JAX_PLATFORMS"] = "cpu"
            xla = [t for t in env.get("XLA_FLAGS", "").split()
                   if not t.startswith(
                       "--xla_force_host_platform_device_count")]
            xla.append("--xla_force_host_platform_device_count=%d"
                       % self.cpu_devices_per_proc)
            env["XLA_FLAGS"] = " ".join(xla)
        return env

    def _spawn_gang(self) -> None:
        endpoints = ["127.0.0.1:%d" % p for p in _free_ports(self.nprocs)]
        hb_port = self._hb_sock.getsockname()[1]
        with self._lock:
            attempt = self._attempt
        for rank in range(self.nprocs):
            env = self._worker_env(rank, endpoints, hb_port)
            log_path = None
            out = None
            if self.log_dir:
                os.makedirs(self.log_dir, exist_ok=True)
                log_path = os.path.join(
                    self.log_dir,
                    "worker%d.attempt%d.log" % (rank, attempt))
                out = open(log_path, "wb")
            try:
                proc = subprocess.Popen(
                    self.argv, env=env, stdout=out, stderr=out,
                    start_new_session=True)
            finally:
                if out is not None:
                    out.close()  # child holds its own fd
            w = _Worker(rank, proc, log_path)
            with self._lock:
                self._workers[rank] = w
            self._set_worker_state(w, "spawned")
            self._event("spawn", rank=rank, pid=proc.pid, attempt=attempt)

    def _kill_gang(self) -> None:
        with self._lock:
            workers = list(self._workers.values())
        for sig in (signal.SIGTERM, signal.SIGKILL):
            alive = [w for w in workers if w.proc.poll() is None]
            if not alive:
                break
            for w in alive:
                try:
                    os.killpg(w.proc.pid, sig)
                except (ProcessLookupError, PermissionError, OSError):
                    try:
                        w.proc.send_signal(sig)
                    except Exception:
                        pass
            deadline = time.monotonic() + \
                (self.term_grace_s if sig == signal.SIGTERM else 10.0)
            for w in alive:
                try:
                    w.proc.wait(timeout=max(
                        0.05, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    pass
        for w in workers:
            if w.proc.poll() is not None and w.exit_code is None:
                w.exit_code = w.proc.returncode

    # -- supervision loop --------------------------------------------------

    def _check_gang(self) -> Optional[str]:
        """One liveness sweep. Returns a failure cause string when the
        gang must restart, None while healthy / still finishing."""
        now = time.monotonic()
        cause = None
        done = True
        with self._lock:
            workers = list(self._workers.values())
        for w in workers:
            if w.state == "exited":
                continue
            rc = w.proc.poll()
            if rc is not None:
                w.exit_code = rc
                if rc == 0:
                    self._set_worker_state(w, "exited")
                    self._event("worker_exit", rank=w.rank, rc=0)
                    continue
                self._set_worker_state(w, "died")
                stat_add("STAT_launch_worker_deaths")
                self._event("worker_death", rank=w.rank, rc=rc)
                cause = cause or ("worker %d died rc=%d" % (w.rank, rc))
                done = False
                continue
            done = False
            if w.last_beat is None:
                if now - w.spawned_at > self.spawn_grace_s:
                    self._set_worker_state(w, "lost")
                    stat_add("STAT_launch_worker_lost")
                    self._event("worker_lost", rank=w.rank,
                                age_s=round(now - w.spawned_at, 3),
                                phase="spawn")
                    cause = cause or (
                        "worker %d never heartbeat within spawn grace "
                        "%.1fs" % (w.rank, self.spawn_grace_s))
            elif now - w.last_beat > self.heartbeat_timeout_s:
                self._set_worker_state(w, "lost")
                stat_add("STAT_launch_worker_lost")
                self._event("worker_lost", rank=w.rank,
                            age_s=round(now - w.last_beat, 3),
                            phase="run")
                cause = cause or (
                    "worker %d missed heartbeats for %.1fs (window "
                    "%.1fs)" % (w.rank, now - w.last_beat,
                                self.heartbeat_timeout_s))
        if cause:
            return cause
        if done and workers:
            with self._lock:
                self._state = "done"
            self._event("done")
            self._done_ev.set()
        return None

    def _supervise(self) -> None:
        while not self._stop_ev.is_set() and not self._done_ev.is_set():
            cause = self._check_gang()
            if cause is None:
                self._stop_ev.wait(0.05)
                continue
            self._event("teardown", cause=cause)
            self._kill_gang()
            with self._lock:
                # PR-9 refund: an incarnation that made step progress
                # pays its own restart; only consecutive no-progress
                # failures burn down the budget
                if self._progress_since_restart:
                    self._restarts = 0
                self._restarts += 1
                restarts = self._restarts
                self._progress_since_restart = False
                exhausted = restarts > self.max_restarts
                if exhausted:
                    self._state = "failed"
                    self._failure_cause = cause
                else:
                    self._state = "restarting"
                    self._attempt += 1
            if exhausted:
                stat_add("STAT_launch_restart_exhausted")
                self._event("failed", restarts=restarts - 1, cause=cause)
                self._done_ev.set()
                return
            stat_add("STAT_launch_restarts")
            backoff = min(self.restart_backoff_s * 2 ** (restarts - 1),
                          self.restart_backoff_s * 32)
            self._event("restart", attempt=self._attempt,
                        restarts=restarts, backoff_s=round(backoff, 3),
                        cause=cause)
            if self._stop_ev.wait(backoff):
                return
            self._spawn_gang()
            with self._lock:
                if self._state == "restarting":
                    self._state = "running"

    # -- public lifecycle --------------------------------------------------

    def start(self) -> "GangSupervisor":
        with self._lock:
            if self._state != "idle":
                return self
            self._state = "running"
        self._hb_sock = socket.socket()
        self._hb_sock.bind(("127.0.0.1", 0))
        self._hb_sock.listen(self.nprocs * 2 + 4)
        _SUPERVISORS.add(self)
        from . import introspect
        introspect.register_readiness(
            "gang_" + self.name,
            lambda: self._state in ("running", "done"))
        # default skew objective: registration is idempotent and free
        # when FLAGS_slo is off (evaluation is the gated part)
        from . import slo as _slo
        _slo.install_gang_objectives()
        self._spawn_gang()
        for target, nm in ((self._hb_serve, "pt-gang-accept"),
                           (self._supervise, "pt-gang-supervise")):
            t = threading.Thread(target=target, name=nm, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def wait(self, timeout: Optional[float] = None) -> int:
        """Block until the gang completes. Returns 0 on success; raises
        :class:`GangFailed` when the restart budget is exhausted and
        TimeoutError when `timeout` elapses first — never hangs."""
        if not self._done_ev.wait(timeout):
            raise TimeoutError(
                "gang %r still %s after %.1fs"
                % (self.name, self._state, timeout or 0.0))
        with self._lock:
            if self._state == "failed":
                raise GangFailed(self.name, self._restarts - 1,
                                 self._failure_cause)
        return 0

    def run(self, timeout: Optional[float] = None) -> int:
        self.start()
        try:
            return self.wait(timeout)
        finally:
            self.stop()

    def stop(self) -> None:
        """Tear everything down (idempotent). Keeps the terminal state
        readable through status(); unregisters the readiness probe."""
        self._stop_ev.set()
        self._done_ev.set()
        self._kill_gang()
        if self._hb_sock is not None:
            try:
                self._hb_sock.close()
            except OSError:
                pass
        from . import introspect
        introspect.unregister_readiness("gang_" + self.name)
        self._retract_gauges()

    # every rank-labeled gauge family this supervisor emits; timers and
    # counters keep their history like every other family
    GANG_GAUGE_FAMILIES = ("GAUGE_gang_step",
                           "GAUGE_gang_straggler_score",
                           "GAUGE_gang_collective_wait_frac")

    def _retract_gauges(self) -> None:
        """Remove this gang's rank-labeled gauges entirely (not zero
        them) on stop — a dead gang must not keep advertising stale
        per-rank scores. Same discipline as mesh/collectives.py
        retract_gauges."""
        from . import monitor
        prefixes = tuple(labeled(f, {"gang": self.name})[:-1]
                         for f in self.GANG_GAUGE_FAMILIES)
        with monitor._LOCK:
            for k in list(monitor._GAUGES):
                if k.startswith(prefixes):
                    monitor._GAUGES.pop(k)


# ---------------------------------------------------------------------------
# /gangz payload (introspect.py serves it; built here with the data)
# ---------------------------------------------------------------------------

def gangz() -> Dict[str, Any]:
    """The /gangz JSON payload: every live gang's status() enriched
    with each rank's latest digest-derived phase breakdown."""
    gangs = []
    for s in list(_SUPERVISORS):
        st = s.status()
        for row in st["workers"]:
            w = s._workers.get(row["rank"])
            dig = w.digest if w is not None else None
            if dig:
                row["digest_v"] = dig.get("v")
                row["phases"] = dig.get("phases")
                row["kv"] = dig.get("kv")
        gangs.append(st)
    return {"gangs": gangs}


def gangz_text() -> str:
    """Plain-text /gangz: one table per gang, one row per rank."""
    z = gangz()
    if not z["gangs"]:
        return "no live gangs\n"
    out = []
    for g in z["gangs"]:
        out.append(
            "gang %s  state=%s attempt=%d restarts=%d/%d  "
            "straggler thr=%.2f window=%.1fs" % (
                g["name"], g["state"], g["attempt"], g["restarts"],
                g["max_restarts"], g["straggler"]["threshold"],
                g["straggler"]["window_s"]))
        out.append("%-5s %-11s %9s %8s %10s %6s  %s" % (
            "rank", "state", "beat_age", "step", "straggler",
            "wait%", "phases p50 us"))
        for w in g["workers"]:
            phases = w.get("phases") or {}
            ptxt = " ".join(
                "%s=%.0f" % (ph, st.get("p50", 0.0))
                for ph, st in sorted(phases.items())
                if ph != "total") or "-"
            out.append("%-5d %-11s %9s %8d %10s %6s  %s" % (
                w["rank"], w["state"],
                ("%.2fs" % w["last_beat_age_s"]
                 if w["last_beat_age_s"] is not None else "-"),
                w["step"],
                ("%.2f" % w["straggler_score"]
                 if w["straggler_score"] is not None else "-"),
                ("%.0f%%" % (100.0 * w["wait_frac"])
                 if w["wait_frac"] is not None else "-"),
                ptxt))
        out.append("")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.launch",
        description="supervised gang launcher for multi-process SPMD")
    p.add_argument("--nproc", type=int, default=2)
    p.add_argument("--cpu-devices-per-proc", type=int, default=None,
                   help="pin workers to the CPU backend with N fake "
                        "devices each (omit on TPU pods)")
    p.add_argument("--log-dir", default=None)
    p.add_argument("--max-restarts", type=int, default=None)
    p.add_argument("--heartbeat-interval-s", type=float, default=None)
    p.add_argument("--heartbeat-timeout-s", type=float, default=None)
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="worker command (script.py args...)")
    ns = p.parse_args(argv)
    cmd = ns.cmd[1:] if ns.cmd[:1] == ["--"] else ns.cmd
    if not cmd:
        p.error("missing worker command")
    sup = GangSupervisor(
        cmd, ns.nproc,
        cpu_devices_per_proc=ns.cpu_devices_per_proc,
        log_dir=ns.log_dir,
        max_restarts=ns.max_restarts,
        heartbeat_interval_s=ns.heartbeat_interval_s,
        heartbeat_timeout_s=ns.heartbeat_timeout_s)
    try:
        return sup.run()
    except GangFailed as e:
        print("launch: %s" % e, file=sys.stderr)
        return 1
    except KeyboardInterrupt:
        sup.stop()
        return 130


if __name__ == "__main__":
    sys.exit(main())
