"""Runtime stats registry — the platform monitor analog, grown into a
typed-instrument registry.

Analog of /root/reference/paddle/fluid/platform/monitor.{h,cc} (the
STAT_ADD/STAT_RESET int64 registry) exposed to python as
get_float_stats/get_int_stats (pybind.cc:1664 get_float_stats), extended
with the instrument kinds a runtime that wants to explain its own time
needs (docs/observability.md):

- **counters** — monotonically accumulated floats (`stat_add`). The
  original STAT registry; every legacy call keeps working unchanged.
- **gauges** — last-written values (`gauge_set`): queue depths,
  in-flight windows, cache sizes.
- **timers** — latency histograms (`timer_observe`, microseconds by
  convention, TIMER_* names): count/sum/min/max plus p50/p95 computed
  over a bounded ring of the most recent samples.

`snapshot()` returns all three as one plain dict; `dump()` serializes it
to JSON and `to_prometheus()` to Prometheus text exposition format, so a
bench artifact and a scrape endpoint read the same registry.
Everything is thread-safe and process-global.

    from paddle_tpu.monitor import stat_add, timer_observe, snapshot
    stat_add("STAT_executor_compile", 1)
    timer_observe("TIMER_executor_dispatch_us", 412.0)
    snapshot()  # {"counters": {...}, "gauges": {...}, "timers": {...}}

Well-known counters include STAT_executor_compile (in-memory cache
miss -> trace), STAT_executor_cache_evict (LRU bound hit), and the
persistent AOT program cache set (core/program_cache.py):
STAT_program_cache_trace_hit / _trace_miss / _corrupt / _unexportable
and _bytes_read / _bytes_written.

The async dispatch pipeline (docs/async_pipeline.md) exposes:
- STAT_executor_dispatch: jitted steps dispatched by Executor.run
  (bumped at dispatch, before any fetch is read), and
- STAT_executor_sync: blocking device->host materialization events
  (Executor.run's return_numpy=True conversion, a FetchHandle's first
  read, the fast_check_nan_inf scalar check).
The dispatch/sync ratio is the pipeline's health signal: a loop that
should be dispatch-ahead but shows sync == dispatch has a forced sync
on its hot path, and tests pin the ratio so regressions are visible.

Timer latencies land here when FLAGS_telemetry is on (telemetry.py):
TIMER_executor_compile_us / _dispatch_us / _sync_us,
TIMER_program_cache_load_us / _store_us, TIMER_fetch_sync_us,
TIMER_pipeline_drain_us / _feed_stage_us, TIMER_trainstep_dispatch_us,
TIMER_hapi_epoch_drain_us / _callback_us.

The serving path (docs/serving.md) exposes:
- bucketing: STAT_predictor_bucket_hit / _cold (warm-signature vs
  newly-compiled bucketed calls), _skip / _overflow (calls that
  bypassed bucketing), STAT_predictor_pad_rows / _pad_elements
  (padding waste), STAT_program_cache_warm (warmup_buckets compiles);
- the PredictorPool batcher: STAT_serving_requests / _batches /
  _batched_rows (rows/batches = the amortization factor), _rejected
  (ServingQueueFull backpressure), _batch_errors,
  GAUGE_serving_queue_depth / _last_batch_rows, and the always-on
  TIMER_serving_queue_wait_us / _batch_us histograms (queue wait and
  batch execution are the serving SLO — recorded without
  FLAGS_telemetry, like the program-cache timers).

The generation engine (docs/generation.md) exposes:
- STAT_generation_requests / _prefills / _tokens (throughput),
  _compile (engine-level compilations — the zero-steady-state-
  recompile pin counts THIS standing still), _evictions (pool-pressure
  preemptions), _errors, _rejected (ServingQueueFull backpressure),
  STAT_generation_blocks_allocated / _blocks_freed (KV ledger churn);
- GAUGE_generation_blocks_free / _blocks_used (pool occupancy),
  _active_seqs, _queue_depth;
- the always-on TIMER_generation_mixed_step_us histogram (bench.py's
  generation block gates on its p95 via tools/stat_diff.py); per-token
  latency is the request trace's TIMER_generation_tpot_us (tracing.py).

The mesh-native SPMD runtime (paddle_tpu/mesh/, docs/spmd.md)
exposes (always-on, like the serving timers):
- STAT_mesh_placements / STAT_mesh_reshard_bytes: device_put work a
  ShardingPlan actually performed (values already resident with the
  right sharding are skipped) — a steady-state training loop must show
  these standing still, or state is ping-ponging between layouts;
- STAT_mesh_collective_<axis>: collective launches per mesh axis —
  host-level calls (parallel/collective.py: all_reduce/all_gather/
  broadcast/all_to_all outside shard_map) plus TrainStep's explicit
  gradient exchange (counted from its build-time wire manifest), the
  per-axis traffic census the MULTICHIP round artifact records;
- STAT_mesh_collective_bytes{axis,dtype}: payload bytes those
  launches put on the wire, by dtype, under a ring model: each of the
  p ranks forwards (p-1)/p of the payload per ring pass, AllReduce-
  family ops (psum/pmean/pmax) cost two passes, all_gather /
  psum_scatter / all_to_all one. This is the census that proves the
  int8 collective path (mesh/collectives.py) shrank gradient-sync
  bytes ≥3x vs fp32;
- STAT_collective_quant_buckets / _fallbacks and
  GAUGE_collective_quant_buckets / _small / _wire_bytes: quantized-
  collective health — bucket exchanges dispatched, buckets demoted to
  fp32 by the dist.collective_quant failpoint, and the live step's
  bucket geometry (gauges retracted when the step rebuilds with the
  flag off, like every PR-14+ gauge family);
- GAUGE_mesh_devices: device count of the most recently built plan;
- TIMER_mesh_compile_us: walltime of plan.compile()'s first
  (trace+compile) call with explicit in/out shardings.

XLA program accounting (core/program_accounting.py, scraped live via
introspect.py /programz):
- GAUGE_program_flops_<tag> / _bytes_accessed_<tag> / _temp_bytes_<tag>
  / _hbm_bytes_<tag>: per compiled program, captured at compile time
  from compiled.cost_analysis() / memory_analysis();
- GAUGE_programs_count / _hbm_bytes (process-wide compiled HBM
  footprint) / _flops_compiled / _achieved_flops_per_s (FLOPs
  dispatched per wall-second over the process lifetime);
- STAT_program_account_fallback: accounted executions that fell back
  to the plain jitted path (input mismatch — costs one recompile).

Request-lifecycle tracing (tracing.py, /tracez — always-on like the
serving timers, gated by FLAGS_request_tracing):
- stage-decomposition timers observed at trace finish:
  TIMER_serving_admit_us / _batch_join_us / _dispatch_us / _execute_us
  / _fetch_us / _total_us and TIMER_generation_queue_wait_us /
  _decode_us / _total_us — plus TIMER_generation_ttft_us (first token,
  observed once per request) and TIMER_generation_tpot_us (per-decode-
  token deltas), observed inline as tokens arrive;
- STAT_trace_completed / _errored / _nonmonotonic (ordering audit),
  STAT_<kind>_deadline_missed and STAT_<kind>_budget_<stage>_us for
  deadline-armed submits (where deadlined traffic burns its budget);
- GAUGE_tracing_exemplars + GAUGE_trace_exemplar_us_<id> per kept
  slow/errored exemplar (retracted on ring eviction,
  STAT_tracing_exemplar_evict).

The robustness layer (failpoints.py, docs/robustness.md):
- self-healing pools: STAT_serving_restarts / _restart_exhausted and
  STAT_generation_restarts / _restart_exhausted (supervised worker
  restarts and terminal budget exhaustion — tools/stat_diff.py treats
  the whole _shed_/_restart families as cost counters);
- deadline shedding: STAT_serving_shed_at_admit /
  STAT_generation_shed_at_admit (requests whose deadline burned while
  queuing — rejected before any device work);
- crash-safe checkpoints (incubate/checkpoint/atomic.py):
  STAT_checkpoint_saves / _loads / _resumes / _corrupt_fallback and
  TIMER_checkpoint_save_us.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

_LOCK = threading.Lock()
_STATS: Dict[str, float] = {}
_GAUGES: Dict[str, float] = {}
_TIMERS: Dict[str, "_Timer"] = {}

# quantiles are computed over a bounded ring of recent samples: exact
# for short runs, a sliding-window estimate for long ones — never
# unbounded memory
_TIMER_RING = 1024


class _Timer:
    """One latency histogram. All mutation happens under _LOCK."""

    __slots__ = ("count", "sum", "min", "max", "ring", "idx")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.ring: List[float] = []
        self.idx = 0

    def observe(self, v: float) -> None:
        self.count += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        if len(self.ring) < _TIMER_RING:
            self.ring.append(v)
        else:
            self.ring[self.idx] = v
            self.idx = (self.idx + 1) % _TIMER_RING

    def stats(self) -> Dict[str, float]:
        if not self.count:
            return {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                    "ring_min": 0.0, "ring_max": 0.0,
                    "p50": 0.0, "p95": 0.0}
        s = sorted(self.ring)
        n = len(s)

        def q(p: float) -> float:
            return s[min(n - 1, int(p * (n - 1) + 0.5))]

        # min/max are ALL-TIME extremes; p50/p95 come from the ring of
        # the last _TIMER_RING samples. ring_min/ring_max share the
        # ring's time base so one scrape line can be read consistently
        # against the quantiles (pinned by test_telemetry).
        return {"count": self.count, "sum": self.sum,
                "min": self.min, "max": self.max,
                "ring_min": s[0], "ring_max": s[-1],
                "p50": q(0.50), "p95": q(0.95)}


# ---------------------------------------------------------------------------
# time-windowed aggregation (docs/observability.md, slo.py)
# ---------------------------------------------------------------------------
#
# All-time counters can't rate and the _Timer ring can't answer "p95
# over the last 5 minutes", so SLO evaluation needs a second, windowed
# view. Multi-resolution on the cheap: every write lands in a
# fixed-duration sub-bucket (default 10s); any window (1m/5m/1h) is
# composed from sub-buckets at READ time, so one write feeds every
# window. Buckets live in sparse bounded deques — an idle instrument
# costs nothing, a busy one is capped at n_buckets entries.
#
# Disabled by default: when _WINDOWS is None the only cost on the hot
# write paths is one attribute load + `is not None` test under the
# already-held _LOCK. slo.py enables this from FLAGS_slo; monitor stays
# flag-free.

# per-bucket sample reservoir for windowed quantiles: deterministic
# overwrite (newest wins) keeps memory bounded without randomness
_WINDOW_RESERVOIR = 64


class _Windows:
    """Sub-bucketed rolling state for every instrument kind.

    Bucket entries (mutated in place while current):
      counters: [bucket_id, sum]
      timers:   [bucket_id, count, sum, min, max, samples]
      gauges:   [bucket_id, last_value]
    All access happens under the registry _LOCK.
    """

    __slots__ = ("bucket_s", "n_buckets", "clock",
                 "counters", "timers", "gauges")

    def __init__(self, bucket_s: float = 10.0, n_buckets: int = 360,
                 clock=None):
        self.bucket_s = float(bucket_s)
        self.n_buckets = int(n_buckets)
        self.clock = clock if clock is not None else time.monotonic
        self.counters: Dict[str, deque] = {}
        self.timers: Dict[str, deque] = {}
        self.gauges: Dict[str, deque] = {}

    def _bid(self) -> int:
        return int(self.clock() / self.bucket_s)

    def record_counter(self, name: str, v: float) -> None:
        bid = self._bid()
        dq = self.counters.get(name)
        if dq is None:
            dq = self.counters[name] = deque(maxlen=self.n_buckets)
        if dq and dq[-1][0] == bid:
            dq[-1][1] += v
        else:
            dq.append([bid, v])

    def record_timer(self, name: str, v: float) -> None:
        bid = self._bid()
        dq = self.timers.get(name)
        if dq is None:
            dq = self.timers[name] = deque(maxlen=self.n_buckets)
        if dq and dq[-1][0] == bid:
            e = dq[-1]
            e[1] += 1
            e[2] += v
            if v < e[3]:
                e[3] = v
            if v > e[4]:
                e[4] = v
            if len(e[5]) < _WINDOW_RESERVOIR:
                e[5].append(v)
            else:
                e[5][e[1] % _WINDOW_RESERVOIR] = v
        else:
            dq.append([bid, 1, v, v, v, [v]])

    def record_gauge(self, name: str, v: float) -> None:
        bid = self._bid()
        dq = self.gauges.get(name)
        if dq is None:
            dq = self.gauges[name] = deque(maxlen=self.n_buckets)
        if dq and dq[-1][0] == bid:
            dq[-1][1] = v
        else:
            dq.append([bid, v])

    def _min_bid(self, window_s: float, now: float) -> int:
        # include buckets whose start lies within (now - window_s, now]
        return int((now - window_s) / self.bucket_s) + 1


_WINDOWS: Optional[_Windows] = None


def enable_windows(bucket_s: float = 10.0, n_buckets: int = 360,
                   clock=None) -> None:
    """Turn on windowed aggregation (idempotent for same config;
    reconfiguring discards accumulated window state)."""
    global _WINDOWS
    with _LOCK:
        w = _WINDOWS
        if w is not None and w.bucket_s == float(bucket_s) \
                and w.n_buckets == int(n_buckets) and clock is None:
            return
        _WINDOWS = _Windows(bucket_s, n_buckets, clock)


def disable_windows() -> None:
    global _WINDOWS
    with _LOCK:
        _WINDOWS = None


def windows_enabled() -> bool:
    return _WINDOWS is not None


def window_config() -> Optional[Dict[str, float]]:
    with _LOCK:
        w = _WINDOWS
        if w is None:
            return None
        return {"bucket_s": w.bucket_s, "n_buckets": w.n_buckets,
                "span_s": w.bucket_s * w.n_buckets}


def counter_window_sum(name: str, window_s: float,
                       now: Optional[float] = None) -> float:
    """Sum of a counter's increments over the trailing window (0.0 when
    windows are disabled or the counter never fired in-window)."""
    with _LOCK:
        w = _WINDOWS
        if w is None:
            return 0.0
        dq = w.counters.get(name)
        if not dq:
            return 0.0
        t = w.clock() if now is None else now
        lo = w._min_bid(window_s, t)
        return float(sum(e[1] for e in dq if e[0] >= lo))


def counter_rate(name: str, window_s: float,
                 now: Optional[float] = None) -> float:
    """Per-second rate of a counter over the trailing window — QPS,
    error rate, shed rate. 0.0 when windows are disabled."""
    with _LOCK:
        w = _WINDOWS
        if w is None:
            return 0.0
        dq = w.counters.get(name)
        if not dq:
            return 0.0
        t = w.clock() if now is None else now
        lo = w._min_bid(window_s, t)
        total = sum(e[1] for e in dq if e[0] >= lo)
        elapsed = max(t - lo * w.bucket_s, w.bucket_s)
        return float(total) / elapsed


def timer_window(name: str, window_s: float,
                 now: Optional[float] = None) -> Dict[str, float]:
    """count/sum/min/max/p50/p95 merged over the trailing window's
    sub-buckets (quantiles estimated from the per-bucket reservoirs).
    All-zero when windows are disabled or no samples landed."""
    zero = {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
            "p50": 0.0, "p95": 0.0}
    with _LOCK:
        w = _WINDOWS
        if w is None:
            return zero
        dq = w.timers.get(name)
        if not dq:
            return zero
        t = w.clock() if now is None else now
        lo = w._min_bid(window_s, t)
        count, total = 0, 0.0
        mn, mx = float("inf"), float("-inf")
        samples: List[float] = []
        for e in dq:
            if e[0] < lo:
                continue
            count += e[1]
            total += e[2]
            if e[3] < mn:
                mn = e[3]
            if e[4] > mx:
                mx = e[4]
            samples.extend(e[5])
        if not count:
            return zero
        samples.sort()
        n = len(samples)

        def q(p: float) -> float:
            return samples[min(n - 1, int(p * (n - 1) + 0.5))]

        return {"count": count, "sum": total, "min": mn, "max": mx,
                "p50": q(0.50), "p95": q(0.95)}


def timer_window_frac_le(name: str, threshold: float, window_s: float,
                         now: Optional[float] = None) -> Optional[float]:
    """Estimated fraction of in-window samples <= threshold — the
    good-ratio a latency SLO reads. Per-bucket reservoir fractions are
    weighted by true bucket counts. None when windows are disabled or
    no samples landed in-window."""
    with _LOCK:
        w = _WINDOWS
        if w is None:
            return None
        dq = w.timers.get(name)
        if not dq:
            return None
        t = w.clock() if now is None else now
        lo = w._min_bid(window_s, t)
        total, good = 0, 0.0
        for e in dq:
            if e[0] < lo or not e[5]:
                continue
            total += e[1]
            frac = sum(1 for s in e[5] if s <= threshold) / len(e[5])
            good += frac * e[1]
        if not total:
            return None
        return good / total


def gauge_trend(name: str, window_s: float,
                now: Optional[float] = None) -> float:
    """Per-second slope of a gauge over the trailing window — (last −
    first)/dt across in-window buckets. 0.0 when windows are disabled
    or fewer than two in-window buckets exist (no trend computable)."""
    with _LOCK:
        w = _WINDOWS
        if w is None:
            return 0.0
        dq = w.gauges.get(name)
        if not dq:
            return 0.0
        t = w.clock() if now is None else now
        lo = w._min_bid(window_s, t)
        ent = [e for e in dq if e[0] >= lo]
        if len(ent) < 2:
            return 0.0
        dt = (ent[-1][0] - ent[0][0]) * w.bucket_s
        return (ent[-1][1] - ent[0][1]) / dt if dt else 0.0


# ---------------------------------------------------------------------------
# labels — per-tenant / per-model series in one family
# ---------------------------------------------------------------------------

def labeled(name: str, labels: Dict[str, str]) -> str:
    """Compose a labeled series name, Prometheus-style:
    labeled("STAT_serving_requests", {"tenant": "acme"}) ->
    'STAT_serving_requests{tenant="acme"}'. The composed string is an
    ordinary registry key — stat_add/timer_observe/observe_many take it
    unchanged — and to_prometheus() groups all series of one family
    under a single # TYPE line. Label keys sort so the same label set
    always composes the same key; values are escaped per the
    exposition format."""
    if not labels:
        return name
    parts = []
    for k in sorted(labels):
        v = str(labels[k]).replace("\\", "\\\\") \
            .replace('"', '\\"').replace("\n", "\\n")
        parts.append('%s="%s"' % (k, v))
    return "%s{%s}" % (name, ",".join(parts))


def _split_series(name: str) -> Tuple[str, str]:
    """Split a (possibly labeled) registry key into (family,
    label_block) — label_block keeps its braces, '' when unlabeled."""
    i = name.find("{")
    if i < 0:
        return name, ""
    return name[:i], name[i:]


# ---------------------------------------------------------------------------
# counters — the original STAT registry (API unchanged)
# ---------------------------------------------------------------------------

def stat_add(name: str, value: float = 1.0) -> None:
    with _LOCK:
        _STATS[name] = _STATS.get(name, 0.0) + float(value)
        w = _WINDOWS
        if w is not None:
            w.record_counter(name, float(value))


def stat_reset(name: str, value: float = 0.0) -> None:
    with _LOCK:
        _STATS[name] = float(value)


def stat_get(name: str) -> float:
    with _LOCK:
        return _STATS.get(name, 0.0)


def get_float_stats() -> Dict[str, float]:
    """pybind.cc:1664 get_float_stats: snapshot of every registered
    stat."""
    with _LOCK:
        return dict(_STATS)


def get_int_stats() -> Dict[str, int]:
    with _LOCK:
        return {k: int(v) for k, v in _STATS.items()}


# ---------------------------------------------------------------------------
# gauges
# ---------------------------------------------------------------------------

def gauge_set(name: str, value: float) -> None:
    with _LOCK:
        _GAUGES[name] = float(value)
        w = _WINDOWS
        if w is not None:
            w.record_gauge(name, float(value))


def gauge_get(name: str, default: float = 0.0) -> float:
    with _LOCK:
        return _GAUGES.get(name, default)


def gauge_retract(*names: str) -> int:
    """Remove gauges from the registry (and /metrics) by exact name.

    Gauges normally only accrete; retraction is for lifecycle events
    where a series must STOP being exported rather than freeze at its
    last value — e.g. slo.py retiring a front-door endpoint's objective
    gauges, or pools resetting per-request KV gauges. Returns how many
    of the given names were present and removed.
    """
    removed = 0
    with _LOCK:
        for n in names:
            if _GAUGES.pop(n, None) is not None:
                removed += 1
    return removed


# ---------------------------------------------------------------------------
# timers (latency histograms)
# ---------------------------------------------------------------------------

def timer_observe(name: str, value: float) -> None:
    """Record one latency sample (microseconds by convention)."""
    with _LOCK:
        t = _TIMERS.get(name)
        if t is None:
            t = _TIMERS[name] = _Timer()
        t.observe(float(value))
        w = _WINDOWS
        if w is not None:
            w.record_timer(name, float(value))


def timer_get(name: str) -> Dict[str, float]:
    """count/sum/min/max/p50/p95 for one timer (zeros when absent)."""
    with _LOCK:
        t = _TIMERS.get(name)
        return t.stats() if t is not None else _Timer().stats()


def observe_many(timers=(), stats=()) -> None:
    """Record several timer samples and counter increments under ONE
    lock acquisition — for hot paths that emit a burst of instruments
    per event (tracing.RequestTrace.finish observes a whole latency
    decomposition at once)."""
    with _LOCK:
        w = _WINDOWS
        for name, v in timers:
            t = _TIMERS.get(name)
            if t is None:
                t = _TIMERS[name] = _Timer()
            t.observe(float(v))
            if w is not None:
                w.record_timer(name, float(v))
        for name, v in stats:
            _STATS[name] = _STATS.get(name, 0.0) + float(v)
            if w is not None:
                w.record_counter(name, float(v))


# ---------------------------------------------------------------------------
# whole-registry export
# ---------------------------------------------------------------------------

def snapshot() -> Dict[str, Dict]:
    """One consistent view of every instrument: a single lock
    acquisition covers all three registries, so a snapshot taken under
    concurrent writers never shows a counter ahead of the timer that
    timed it being updated mid-read."""
    with _LOCK:
        return {
            "counters": dict(_STATS),
            "gauges": dict(_GAUGES),
            "timers": {k: t.stats() for k, t in _TIMERS.items()},
        }


def dump(path: Optional[str] = None) -> str:
    """Serialize snapshot() to JSON; optionally also write it to
    `path` (the format tools/stat_diff.py consumes)."""
    text = json.dumps(snapshot(), sort_keys=True, indent=1)
    if path:
        with open(path, "w") as f:
            f.write(text)
    return text


def _prom_name(name: str) -> str:
    out = "".join(c if c.isalnum() or c == "_" else "_" for c in name)
    if not out or not (out[0].isalpha() or out[0] == "_"):
        out = "_" + out
    return out


def _group_families(series: Dict) -> List[Tuple[str, List[Tuple[str, object]]]]:
    """Group (possibly labeled) registry keys by family: returns
    [(family, [(label_block, value), ...])] with families sorted and
    each family's label blocks sorted — labeled series don't sort
    adjacent to their base name, so explicit grouping keeps every
    family's samples contiguous under one # TYPE line."""
    fams: Dict[str, List[Tuple[str, object]]] = {}
    for name, v in series.items():
        fam, lbl = _split_series(name)
        fams.setdefault(fam, []).append((lbl, v))
    return [(f, sorted(fams[f])) for f in sorted(fams)]


def _merge_label(lbl: str, extra: str) -> str:
    """Merge one extra label into an existing label block:
    '{tenant="a"}' + 'quantile="0.5"' -> '{tenant="a",quantile="0.5"}'."""
    if not lbl:
        return "{%s}" % extra
    return lbl[:-1] + "," + extra + "}"


def to_prometheus(prefix: str = "paddle_tpu") -> str:
    """Prometheus text exposition format: counters as `<name>_total`,
    gauges as-is, timers as summaries (`_count`/`_sum` + quantile
    samples). Labeled series (see labeled()) render as label blocks on
    their family's samples, one # TYPE per family. One scrape-able
    string, same registry as dump()."""
    snap = snapshot()
    lines: List[str] = []
    for fam, entries in _group_families(snap["counters"]):
        m = "%s_%s_total" % (prefix, _prom_name(fam))
        lines.append("# TYPE %s counter" % m)
        for lbl, v in entries:
            lines.append("%s%s %.17g" % (m, lbl, v))
    for fam, entries in _group_families(snap["gauges"]):
        m = "%s_%s" % (prefix, _prom_name(fam))
        lines.append("# TYPE %s gauge" % m)
        for lbl, v in entries:
            lines.append("%s%s %.17g" % (m, lbl, v))
    timer_fams = _group_families(snap["timers"])
    for fam, entries in timer_fams:
        m = "%s_%s" % (prefix, _prom_name(fam))
        lines.append("# TYPE %s summary" % m)
        for lbl, st in entries:
            lines.append("%s%s %.17g"
                         % (m, _merge_label(lbl, 'quantile="0.5"'),
                            st["p50"]))
            lines.append("%s%s %.17g"
                         % (m, _merge_label(lbl, 'quantile="0.95"'),
                            st["p95"]))
            lines.append("%s_sum%s %.17g" % (m, lbl, st["sum"]))
            lines.append("%s_count%s %d" % (m, lbl, st["count"]))
    # a summary family may only contain {quantile}/_sum/_count
    # samples — strict scrapers reject anything else inside it, so
    # min/max (all-time) and ring_min/ring_max (quantile window) go
    # out as their own gauge families
    for suffix, key in (("min", "min"), ("max", "max"),
                        ("ring_min", "ring_min"), ("ring_max", "ring_max")):
        for fam, entries in timer_fams:
            m = "%s_%s_%s" % (prefix, _prom_name(fam), suffix)
            lines.append("# TYPE %s gauge" % m)
            for lbl, st in entries:
                lines.append("%s%s %.17g"
                             % (m, lbl, st[key] if st["count"] else 0))
    return "\n".join(lines) + "\n"


def reset_all() -> None:
    """Clear every instrument (bench/test isolation). Window state is
    cleared too but the window configuration survives."""
    with _LOCK:
        _STATS.clear()
        _GAUGES.clear()
        _TIMERS.clear()
        w = _WINDOWS
        if w is not None:
            w.counters.clear()
            w.timers.clear()
            w.gauges.clear()
