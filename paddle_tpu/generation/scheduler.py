"""GenerationPool: the concurrent continuous-batching front-end.

serving.PredictorPool's batcher coalesces whole REQUESTS into one
execution; generation needs a step-level scheduler instead — requests
join the in-flight batch at admission, stream their prompt through the
mixed step a chunk at a time (chunked prefill; engine.py), ride the
batch one token per step, and leave at EOS/max-len while their
batch-mates keep going.
This class is that extension: the same bounded-queue + condition-
variable front door and the same `_Future` completion handles as the
serving pool (literally reused), but the worker loop drives
GenerationEngine.step() continuously instead of executing one batch
per wakeup.

Contracts, matching PredictorPool:
- backpressure: the request queue is bounded
  (FLAGS_generation_queue_depth); submit() blocks, then raises
  serving.ServingQueueFull.
- per-request error isolation: a request the engine rejects
  (too-long prompt, bad sampling params) fails ONLY its own future.
  A decode-step failure is a batch-level fault: every in-flight
  future fails with a typed PoolRestarted carrying its trace id, the
  engine is rebuilt, and the SUPERVISOR restarts the worker with
  capped exponential backoff (FLAGS_pool_max_restarts /
  FLAGS_pool_restart_backoff_ms; /readyz reads unready during the
  restart; budget exhaustion is terminal — docs/robustness.md).
- deadline-aware shedding: a request whose deadline budget is burned
  before admit is rejected with DeadlineBurned
  (STAT_generation_shed_at_admit) instead of occupying a lane.
- close() drains: already-queued and in-flight requests finish
  before the worker exits (like PredictorPool.close).
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from .. import telemetry as _tm
from .. import tracing as _tr
from ..flags import get_flag
from ..monitor import gauge_set, stat_add
from ..serving import (DeadlineBurned, PoolRestarted, ServingQueueFull,
                       _Future, _WorkerCrash)
from .engine import GenerationEngine, GenerationRequest

__all__ = ["GenerationPool"]


class GenerationPool:
    """Thread-safe continuous-batching wrapper around one
    GenerationEngine. Only the worker thread ever touches the engine,
    so its lane/pool state needs no locking.

    Usage::

        pool = GenerationPool(engine)
        fut = pool.submit(GenerationRequest(prompt=[1, 2, 3]))
        result = fut.result(timeout=30)     # GenerationResult
        pool.close()                        # or `with` block
    """

    def __init__(self, engine: GenerationEngine, *,
                 queue_depth: Optional[int] = None,
                 _start: bool = True):
        self.engine = engine
        self.queue_depth = int(
            queue_depth if queue_depth is not None
            else get_flag("FLAGS_generation_queue_depth"))
        if self.queue_depth < 1:
            raise ValueError("queue_depth must be >= 1")
        self._queue: deque = deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._closed = False
        self._worker: Optional[threading.Thread] = None
        # engine-side request_id -> future, owned by the worker thread
        self._inflight: Dict[int, _Future] = {}
        self._next_id = 0
        # supervision state (docs/robustness.md)
        self._healthy = True
        self._failed = False
        self._fail_cause: Optional[BaseException] = None
        self._ok_since_restart = False
        self._last_step_s = 0.0
        # scheduler-side eviction replay happens inside the engine;
        # the future survives it untouched
        engine.on_request_error = self._on_request_error
        if _start:
            self.start()

    def _on_request_error(self, req: GenerationRequest,
                          exc: Exception) -> None:
        """Engine-reported per-request failure (prefill raised): fail
        only that request's future; batch-mates are untouched."""
        fut = self._inflight.pop(req.request_id, None)
        if fut is not None:
            fut._set_error(exc)

    # --- lifecycle -----------------------------------------------------

    def start(self) -> "GenerationPool":
        with self._lock:
            if self._closed:
                raise RuntimeError("pool is closed")
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._supervisor, name="pt-generation-sched",
                    daemon=True)
                self._worker.start()
        # a started-but-unwarmed pool reads as unready on /readyz until
        # engine.warmup() flips _warmed (introspect.py readiness); a
        # restarting pool reads unready for the backoff window
        from .. import introspect
        introspect.register_readiness(
            "generation_pool_%d" % id(self),
            lambda: getattr(self.engine, "_warmed", False)
            and self._healthy)
        introspect.maybe_start()
        return self

    def close(self) -> None:
        """Drain: queued and in-flight sequences run to completion,
        then the worker exits."""
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()
            worker = self._worker
        if worker is not None:
            worker.join(timeout=300.0)
        with self._lock:
            while self._queue:
                _, fut = self._queue.popleft()
                exc = RuntimeError("GenerationPool closed")
                fut.trace.finish(error=exc)
                fut._set_error(exc)
            gauge_set("GAUGE_generation_queue_depth", 0)
        from .. import introspect
        introspect.unregister_readiness("generation_pool_%d" % id(self))
        # the engine pointed back at this pool: left so, engine and pool
        # keep each other (and the engine's device pools and weights)
        # alive until a garbage collection finds the cycle
        if self.engine.on_request_error == self._on_request_error:
            self.engine.on_request_error = None

    def __enter__(self) -> "GenerationPool":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # --- client API ----------------------------------------------------

    def submit(self, req: GenerationRequest,
               timeout: Optional[float] = None,
               deadline: Optional[float] = None,
               tenant: Optional[str] = None,
               model: Optional[str] = None,
               version: Optional[str] = None) -> _Future:
        """Enqueue one request; returns a future whose .result() is a
        GenerationResult. Blocks while the queue is full, then raises
        ServingQueueFull — the same backpressure contract as
        serving.PredictorPool.submit. `deadline` arms a latency budget
        (seconds) on the request's trace: STAT_generation_deadline_missed
        + per-stage budget burn when blown (never cancels). `tenant`
        attributes the request to a workload (labeled per-tenant
        series at finish; /tracez?tenant= filter). `model`/`version`
        stamp front-door routing identity ({model,version}-labeled
        series at finish — frontdoor.py sets them)."""
        fut = _Future()
        fut.trace = _tr.begin("generation", deadline=deadline,
                              tenant=tenant, model=model,
                              version=version)
        # ONE shared budget: the enqueue wait is bounded by timeout AND
        # by the request's own deadline (serving.PredictorPool.submit
        # has the same contract)
        timeout_end = (None if timeout is None
                       else fut.t_submit + timeout)
        deadline_end = (None if deadline is None
                        else fut.t_submit + deadline)
        ends = [e for e in (timeout_end, deadline_end) if e is not None]
        wait_deadline = min(ends) if ends else None
        with self._not_full:
            while not self._closed and not self._failed and \
                    len(self._queue) >= self.queue_depth:
                now = time.monotonic()
                if deadline_end is not None and now >= deadline_end:
                    stat_add("STAT_generation_shed_at_admit")
                    exc: BaseException = DeadlineBurned(
                        "deadline (%.3fs) burned waiting for a queue "
                        "slot" % deadline, trace_id=fut.trace.trace_id)
                    fut.trace.finish(error=exc)
                    raise exc
                remaining = (None if wait_deadline is None
                             else wait_deadline - now)
                if remaining is not None and remaining <= 0:
                    stat_add("STAT_generation_rejected")
                    exc = ServingQueueFull(
                        "generation queue full (depth %d) for %.3fs"
                        % (self.queue_depth, now - fut.t_submit),
                        queue_depth=len(self._queue),
                        retry_after_s=max(
                            0.01, self._last_step_s) * len(self._queue))
                    fut.trace.finish(error=exc)
                    raise exc
                self._not_full.wait(remaining)
            if self._closed or self._failed:
                exc = PoolRestarted(
                    "GenerationPool failed (restart budget exhausted)",
                    trace_id=fut.trace.trace_id,
                    cause=self._fail_cause) if self._failed \
                    else RuntimeError("GenerationPool closed")
                fut.trace.finish(error=exc)
                raise exc
            if deadline is not None and \
                    time.monotonic() - fut.t_submit >= deadline:
                stat_add("STAT_generation_shed_at_admit")
                exc = DeadlineBurned(
                    "deadline (%.3fs) burned before admit" % deadline,
                    trace_id=fut.trace.trace_id)
                fut.trace.finish(error=exc)
                raise exc
            self._queue.append((req, fut))
            gauge_set("GAUGE_generation_queue_depth", len(self._queue))
            self._not_empty.notify()
        return fut

    def run(self, req: GenerationRequest,
            timeout: Optional[float] = None,
            deadline: Optional[float] = None,
            tenant: Optional[str] = None,
            model: Optional[str] = None,
            version: Optional[str] = None):
        """Blocking submit+wait. `timeout` is ONE budget shared by the
        enqueue wait and the result wait (it used to be handed to both,
        so a 1 s budget could block ~2 s)."""
        if timeout is None:
            return self.submit(req, deadline=deadline, tenant=tenant,
                               model=model, version=version).result()
        t_end = time.monotonic() + timeout
        fut = self.submit(req, timeout=timeout, deadline=deadline,
                          tenant=tenant, model=model, version=version)
        return fut.result(max(0.0, t_end - time.monotonic()))

    # --- worker --------------------------------------------------------

    def _admit_locked(self) -> None:
        """Move queued requests into the engine while it has headroom
        (pending + active < 2x decode_width keeps prefill fed without
        hoarding the whole queue in engine-pending state). Engine
        rejections (ValueError) fail only that request's future."""
        eng = self.engine
        headroom = 2 * eng.decode_width
        while self._queue and \
                eng.pending_count + eng.active_count < headroom:
            req, fut = self._queue.popleft()
            rid = self._next_id
            self._next_id += 1
            try:
                from dataclasses import replace
                eng.submit(replace(req, request_id=rid,
                                   trace=fut.trace))
            except Exception as e:
                stat_add("STAT_generation_errors")
                fut.trace.finish(error=e)
                fut._set_error(e)
                continue
            self._inflight[rid] = fut
        gauge_set("GAUGE_generation_queue_depth", len(self._queue))
        self._not_full.notify_all()

    def _supervisor(self) -> None:
        """Worker thread top-level: run the serve loop; on a batch-level
        fault fail every in-flight future with a typed PoolRestarted,
        rebuild the engine, and restart with capped exponential backoff.
        FLAGS_pool_max_restarts bounds consecutive faulty restarts (a
        healthy step since the last restart refunds the budget);
        exhaustion is terminal."""
        base = max(1e-3, float(
            get_flag("FLAGS_pool_restart_backoff_ms", 50.0))) / 1e3
        max_restarts = int(get_flag("FLAGS_pool_max_restarts", 3))
        restarts = 0
        while True:
            try:
                self._serve_loop()
                return  # clean close()
            except BaseException as e:  # noqa: BLE001 - supervisor
                cause = getattr(e, "cause", None) or e
                self._healthy = False
                stat_add("STAT_generation_errors")
                self._fail_inflight(cause)
                self._reset_engine()
                if self._closed:
                    return
                if self._ok_since_restart:
                    restarts = 0  # healthy period earns the budget back
                self._ok_since_restart = False
                if restarts >= max_restarts:
                    stat_add("STAT_generation_restart_exhausted")
                    self._enter_failed(cause)
                    return
                restarts += 1
                stat_add("STAT_generation_restarts")
                time.sleep(min(base * (2 ** (restarts - 1)), base * 32))
                self._healthy = True

    def _fail_inflight(self, cause: BaseException) -> None:
        for fut in self._inflight.values():
            exc = PoolRestarted(
                "generation worker restarted mid-stream",
                trace_id=fut.trace.trace_id, cause=cause)
            fut.trace.finish(error=exc)
            fut._set_error(exc)
        self._inflight.clear()

    def _enter_failed(self, cause: BaseException) -> None:
        with self._lock:
            self._failed = True
            self._fail_cause = cause
            while self._queue:
                _, fut = self._queue.popleft()
                exc = PoolRestarted(
                    "GenerationPool failed (restart budget exhausted)",
                    trace_id=fut.trace.trace_id, cause=cause)
                fut.trace.finish(error=exc)
                fut._set_error(exc)
            gauge_set("GAUGE_generation_queue_depth", 0)
            self._not_full.notify_all()
            self._not_empty.notify_all()

    def _serve_loop(self) -> None:
        eng = self.engine
        while True:
            with contextlib.ExitStack() as held:
                with _tm.span("pt/pool/wait", track="generation"):
                    # taking the lock is part of the wait: every
                    # submitter contends for it
                    held.enter_context(self._not_empty)
                    while not self._queue and eng.idle \
                            and not self._closed:
                        self._not_empty.wait()
                if self._closed and not self._queue and eng.idle:
                    return
                with _tm.span("pt/pool/admit", track="generation"):
                    self._admit_locked()
            # step OUTSIDE the lock: the decode executable can run
            # while submitters enqueue
            t0 = time.monotonic()
            try:
                finished = eng.step()
            except Exception as e:
                # batch-level fault: escalate to the supervisor, which
                # fails the in-flight futures (PoolRestarted), rebuilds
                # the engine and restarts this loop with backoff
                raise _WorkerCrash(e)
            self._last_step_s = time.monotonic() - t0
            self._ok_since_restart = True
            with _tm.span("pt/pool/deliver", track="generation"):
                for res in finished:
                    fut = self._inflight.pop(res.request_id, None)
                    if fut is not None:
                        fut._set(res)

    def _reset_engine(self) -> None:
        """After a batch-level fault: rebuild the engine's sequence
        state (fresh KV ledger + lanes) reusing its compiled steps and
        the device pools that are still alive — in-flight sequences are
        gone, their futures already hold the error. The pools are
        donated to every program that writes them, so a fault raised
        while such a call ran may have left the engine holding deleted
        arrays: those are made anew, zeros, as a new engine holds them
        (engine._restore_pools). EVERY generation occupancy gauge is
        retracted here, not lazily at the next allocation: a monitoring
        scrape between the fault and the next request must see the
        true (empty) state, not the pre-fault occupancy (pinned by
        tests/test_failpoints.py)."""
        eng = self.engine
        eng.kv = type(eng.kv)(eng.kv.num_blocks, eng.kv.block_size)
        if eng.prefix_cache is not None:
            # the cache is deliberately DROPPED, not carried over: a
            # batch-level fault may have poisoned pool contents, and
            # the fresh ledger has no refcounts for the old entries —
            # survivors would be dangling. Rebuilding re-publishes the
            # prefix gauges at zero.
            eng.prefix_cache = type(eng.prefix_cache)(eng.kv)
        eng._restore_pools()
        eng._lane_seq = [None] * eng.decode_width
        eng._tables[:] = 0
        eng._pending = []
        eng._inflight = None    # lookahead: the step the fault took
        # kv.__init__ republished the block gauges; retract the rest
        # explicitly so the reset is retraction-COMPLETE even if the
        # ledger's publish set ever narrows
        gauge_set("GAUGE_generation_blocks_free", eng.kv.num_blocks - 1)
        gauge_set("GAUGE_generation_blocks_used", 0)
        gauge_set("GAUGE_generation_active_seqs", 0)
        gauge_set("GAUGE_kv_shared_blocks", 0)
        gauge_set("GAUGE_kv_blocks_saved", 0)
        gauge_set("GAUGE_generation_prefix_entries", 0)
        gauge_set("GAUGE_generation_prefix_blocks", 0)
        # the quant gauges are derived from surviving engine state
        # (pool dtype, quantized params), so re-deriving them IS the
        # retraction — a rebuilt fp32 engine publishes zeros
        eng._publish_quant_gauges()
        # likewise the autotune gauges: a rebuilt engine without a
        # resolved policy entry retracts GAUGE_autotune_* to zero
        eng._publish_autotune_gauges()
