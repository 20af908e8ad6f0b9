"""Unified runtime telemetry tests (docs/observability.md).

Covers the typed monitor instruments (counter exactness under threads,
snapshot consistency, timer histogram quantiles, Prometheus export),
the telemetry gate and step-correlated spans, the step-correlated
chrome trace of a pipelined train_from_dataset run, the flight
recorder (bound + exception notes), tools/stat_diff.py, and the
profiler satellites (RecordEvent functools.wraps, start_profiler
honoring state='All'/'GPU').
"""
import json
import re
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor, profiler, telemetry
from tools import stat_diff


@pytest.fixture
def telemetry_flags():
    """Restore telemetry flags + profiler/flight state after each test."""
    from paddle_tpu.flags import get_flags
    keys = ["FLAGS_telemetry", "FLAGS_telemetry_flight_steps",
            "FLAGS_fast_check_nan_inf", "FLAGS_executor_inflight_steps"]
    saved = get_flags(keys)
    yield
    pt.set_flags(saved)
    profiler.reset_profiler()
    telemetry.flight_reset()


# ---------------------------------------------------------------------------
# monitor: typed instruments
# ---------------------------------------------------------------------------

def test_concurrent_stat_add_sums_exactly():
    """Parallel stat_add from many threads loses no increment."""
    name = "STAT_tm_concurrent"
    monitor.stat_reset(name)
    n_threads, n_adds = 8, 2000
    barrier = threading.Barrier(n_threads)

    def worker():
        barrier.wait()
        for _ in range(n_adds):
            monitor.stat_add(name, 1)

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert monitor.stat_get(name) == float(n_threads * n_adds)


def test_snapshot_consistent_under_writers():
    """snapshot() taken while writers run never tears: counters are
    monotonic across successive snapshots and the final view is exact."""
    cname, tname = "STAT_tm_snap", "TIMER_tm_snap_us"
    monitor.stat_reset(cname)
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            monitor.stat_add(cname, 1)
            monitor.timer_observe(tname, 1.0)

    ts = [threading.Thread(target=writer) for _ in range(4)]
    for t in ts:
        t.start()
    last = -1.0
    try:
        for _ in range(200):
            snap = monitor.snapshot()
            v = snap["counters"].get(cname, 0.0)
            assert v >= last  # never goes backwards
            last = v
            t = snap["timers"].get(tname)
            if t is not None:
                assert t["count"] >= 0 and t["sum"] >= 0
    finally:
        stop.set()
        for t in ts:
            t.join()
    final = monitor.snapshot()
    # after joining, counter and timer agree: one observe per add
    assert final["counters"][cname] == final["timers"][tname]["count"]


def test_timer_histogram_quantiles():
    name = "TIMER_tm_quant_us"
    rng = np.random.RandomState(0)
    vals = np.arange(1, 101, dtype=np.float64)
    rng.shuffle(vals)
    for v in vals:
        monitor.timer_observe(name, float(v))
    st = monitor.timer_get(name)
    assert st["count"] == 100
    assert st["sum"] == pytest.approx(5050.0)
    assert st["min"] == 1.0 and st["max"] == 100.0
    assert st["p50"] == 51.0  # nearest-rank over 1..100
    assert st["p95"] == 95.0
    # absent timers read as zeros, not KeyError
    empty = monitor.timer_get("TIMER_tm_never_observed")
    assert empty["count"] == 0 and empty["p95"] == 0.0


def test_timer_ring_is_sliding_window():
    """Quantiles follow the RECENT distribution; count/sum/min/max stay
    lifetime-exact."""
    name = "TIMER_tm_ring_us"
    for v in range(2000):
        monitor.timer_observe(name, float(v))
    st = monitor.timer_get(name)
    assert st["count"] == 2000
    assert st["sum"] == pytest.approx(sum(range(2000)))
    assert st["min"] == 0.0 and st["max"] == 1999.0
    # ring holds the last 1024 samples (976..1999): early samples no
    # longer drag the quantiles down
    assert st["p50"] >= 976.0
    assert st["p95"] > st["p50"]


def test_timer_ring_min_max_tracks_recent_extremes():
    """ring_min/ring_max follow the RECENT window while min/max stay
    lifetime-exact: a startup latency spike that has rotated out of
    the ring stops inflating ring_max, so "worst recently" and "worst
    ever" are separately readable."""
    name = "TIMER_tm_ring_extremes_us"
    monitor.timer_observe(name, 1e6)  # startup spike, rotates out
    for v in range(2000):
        monitor.timer_observe(name, 100.0 + float(v % 50))
    st = monitor.timer_get(name)
    assert st["min"] == 100.0 and st["max"] == 1e6
    assert st["ring_min"] == 100.0 and st["ring_max"] == 149.0
    # never-observed timers read ring extremes as zeros, like the rest
    empty = monitor.timer_get("TIMER_tm_ring_never_observed")
    assert empty["ring_min"] == 0.0 and empty["ring_max"] == 0.0
    # the extremes export as their own gauge families (a summary family
    # may only carry {quantile}/_sum/_count samples)
    text = monitor.to_prometheus()
    assert "# TYPE paddle_tpu_%s_ring_max gauge" % name in text
    assert "paddle_tpu_%s_ring_max 149" % name in text
    assert "paddle_tpu_%s_max 1000000" % name in text


def test_gauges_last_write_wins():
    monitor.gauge_set("GAUGE_tm_depth", 3)
    monitor.gauge_set("GAUGE_tm_depth", 7)
    assert monitor.gauge_get("GAUGE_tm_depth") == 7.0
    assert monitor.gauge_get("GAUGE_tm_absent", default=-1.0) == -1.0
    assert monitor.snapshot()["gauges"]["GAUGE_tm_depth"] == 7.0


PROM_LINE = re.compile(
    r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eEinfa]+)$")


def test_prometheus_export_format():
    monitor.stat_reset("STAT_tm_prom")
    monitor.stat_add("STAT_tm_prom", 5)
    monitor.gauge_set("GAUGE_tm_prom", 2.5)
    for v in (10.0, 20.0, 30.0):
        monitor.timer_observe("TIMER_tm_prom_us", v)
    text = monitor.to_prometheus()
    for line in text.splitlines():
        if line:
            assert PROM_LINE.match(line), line
    assert "paddle_tpu_STAT_tm_prom_total 5" in text
    assert "# TYPE paddle_tpu_STAT_tm_prom_total counter" in text
    assert "paddle_tpu_GAUGE_tm_prom 2.5" in text
    assert 'paddle_tpu_TIMER_tm_prom_us{quantile="0.5"} 20' in text
    assert "paddle_tpu_TIMER_tm_prom_us_count 3" in text


# ---------------------------------------------------------------------------
# tools/stat_diff.py
# ---------------------------------------------------------------------------

def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def test_stat_diff_flags_cost_counters_only(tmp_path):
    old = {"counters": {"STAT_a_sync": 100, "STAT_a_hit": 100},
           "gauges": {}, "timers": {}}
    new = {"counters": {"STAT_a_sync": 160, "STAT_a_hit": 900},
           "gauges": {}, "timers": {}}
    d = stat_diff.diff_snapshots(old, new)
    assert d["counters"]["STAT_a_sync"]["delta"] == 60
    regs = stat_diff.find_regressions(d, threshold_pct=10.0)
    # the sync (cost) counter regresses; the hit (throughput) one never
    assert any("STAT_a_sync" in r for r in regs)
    assert not any("STAT_a_hit" in r for r in regs)
    # CLI: exit 1 only under --strict
    po, pn = _write(tmp_path, "old.json", old), _write(tmp_path, "new.json",
                                                      new)
    assert stat_diff.main([po, pn]) == 0
    assert stat_diff.main([po, pn, "--strict"]) == 1
    assert stat_diff.main([po, pn, "--strict", "--threshold", "100"]) == 0


def test_stat_diff_timer_p95_regression_and_flat_shape(tmp_path):
    old = {"TIMER_x_us": 1.0}  # legacy flat dict normalizes to counters
    new = {"TIMER_x_us": 2.0}
    d = stat_diff.diff_snapshots(old, new)
    assert d["counters"]["TIMER_x_us"]["delta"] == 1.0
    t_old = {"timers": {"TIMER_d_us": {"count": 50, "sum": 500,
                                       "p95": 10.0}}}
    t_new = {"timers": {"TIMER_d_us": {"count": 50, "sum": 900,
                                       "p95": 18.0}}}
    regs = stat_diff.find_regressions(stat_diff.diff_snapshots(t_old,
                                                               t_new))
    assert any("TIMER_d_us" in r and "p95" in r for r in regs)
    # low sample counts don't flag
    t_new["timers"]["TIMER_d_us"]["count"] = 2
    regs = stat_diff.find_regressions(stat_diff.diff_snapshots(t_old,
                                                               t_new))
    assert not regs


# ---------------------------------------------------------------------------
# telemetry gate + spans
# ---------------------------------------------------------------------------

def test_disabled_span_is_the_annotation_and_nothing_else(telemetry_flags):
    import jax
    pt.set_flags({"FLAGS_telemetry": False})
    s1 = telemetry.span("x", track="dispatch", timer="TIMER_tm_off_us")
    # the profiler's own annotation (it lands in any open jax profiler
    # session); no _Span, no chrome event, no timer
    assert type(s1) is jax.profiler.TraceAnnotation
    profiler.reset_profiler()
    with s1:
        pass
    assert profiler.summary() == []
    assert monitor.timer_get("TIMER_tm_off_us")["count"] == 0


def test_enabled_span_records_trace_and_timer(telemetry_flags):
    pt.set_flags({"FLAGS_telemetry": True})
    profiler.reset_profiler()
    with telemetry.step_scope(42):
        assert telemetry.current_step() == 42
        with telemetry.span("tm/work", track="dispatch",
                            timer="TIMER_tm_span_us"):
            pass
        # trace=False keeps aggregate-only timers out of the timeline
        with telemetry.span("tm/quiet", timer="TIMER_tm_quiet_us",
                            trace=False):
            pass
    assert telemetry.current_step() is None  # scope restored
    assert monitor.timer_get("TIMER_tm_span_us")["count"] == 1
    assert monitor.timer_get("TIMER_tm_quiet_us")["count"] == 1
    rows = {r["name"] for r in profiler.summary()}
    assert "tm/work" in rows and "tm/quiet" not in rows


def test_step_scope_nesting_restores_outer(telemetry_flags):
    with telemetry.step_scope(1):
        with telemetry.step_scope(2):
            assert telemetry.current_step() == 2
        assert telemetry.current_step() == 1
    assert telemetry.current_step() is None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_bounded_and_notes(telemetry_flags):
    pt.set_flags({"FLAGS_telemetry": True,
                  "FLAGS_telemetry_flight_steps": 4})
    telemetry.flight_reset()
    for s in range(1, 11):
        telemetry.flight_begin(s, program="p%d" % s)
        telemetry.flight_note(s, "sync_count", add=1)
        telemetry.flight_note(s, "sync_count", add=1)
    recs = telemetry.flight_records()
    assert [r["step"] for r in recs] == [7, 8, 9, 10]  # bounded, newest
    assert all(r["sync_count"] == 2 for r in recs)
    # same-step begin merges instead of duplicating
    telemetry.flight_begin(10, extra="x")
    recs = telemetry.flight_records()
    assert [r["step"] for r in recs] == [7, 8, 9, 10]
    assert recs[-1]["extra"] == "x"
    dump = telemetry.flight_dump()
    assert "flight recorder" in dump and "step=10" in dump


def test_flight_attached_to_executor_exception(telemetry_flags):
    pt.set_flags({"FLAGS_telemetry": True,
                  "FLAGS_fast_check_nan_inf": True})
    telemetry.flight_reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [2])
        bad = pt.layers.log(pt.layers.elementwise_sub(x, x))  # log(0)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(pt.EnforceNotMet) as ei:
            exe.run(main, feed={"x": np.ones((3, 2), np.float32)},
                    fetch_list=[bad])
    notes = getattr(ei.value, "__notes__", None) or []
    flight_notes = [n for n in notes if "flight recorder" in n]
    assert len(flight_notes) == 1  # attached exactly once
    assert "error=" in flight_notes[0]
    # disabled telemetry attaches nothing
    pt.set_flags({"FLAGS_telemetry": False})
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        with pytest.raises(pt.EnforceNotMet) as ei2:
            exe.run(main, feed={"x": np.ones((3, 2), np.float32)},
                    fetch_list=[bad])
    assert not (getattr(ei2.value, "__notes__", None) or [])


# ---------------------------------------------------------------------------
# step-correlated trace of a pipelined run
# ---------------------------------------------------------------------------

def test_pipelined_trace_correlates_steps(telemetry_flags, tmp_path):
    pt.set_flags({"FLAGS_telemetry": True,
                  "FLAGS_executor_inflight_steps": 2})
    profiler.reset_profiler()
    telemetry.flight_reset()

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [4])
        y = pt.layers.data("y", [1])
        pred = pt.layers.fc(x, 1)
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.SGD(0.1).minimize(loss, startup_program=startup,
                                       program=main)

    def batches(n):
        rng = np.random.RandomState(1)
        for _ in range(n):
            yield {"x": rng.rand(8, 4).astype(np.float32),
                   "y": rng.rand(8, 1).astype(np.float32)}

    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(program=main, dataset=batches(5),
                               fetch_list=[loss])

    path = str(tmp_path / "trace.json")
    profiler.export_chrome_tracing(path)
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    # named track rows exist (thread_name metadata)
    tracks = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"dispatch", "drain"} <= tracks
    # spans of one batch share a step id across tracks — the
    # correlation the whole exercise exists for
    by_step = {}
    for e in events:
        if e["ph"] == "X" and "step" in e.get("args", {}):
            by_step.setdefault(e["args"]["step"], set()).add(e["name"])
            assert e["id"] == str(e["args"]["step"])  # highlightable
    assert any({"pipeline/dispatch", "pipeline/drain"} <= names
               for names in by_step.values())
    # the flight recorder saw the same steps
    steps = {r["step"] for r in telemetry.flight_records()}
    assert steps & set(by_step)


# ---------------------------------------------------------------------------
# profiler satellites
# ---------------------------------------------------------------------------

def test_record_event_decorator_preserves_metadata():
    @profiler.RecordEvent("tm_decorated")
    def my_documented_fn(a, b=1):
        """docstring survives."""
        return a + b

    assert my_documented_fn.__name__ == "my_documented_fn"
    assert my_documented_fn.__doc__ == "docstring survives."
    assert my_documented_fn(2, b=3) == 5


def test_start_profiler_honors_state(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(profiler, "start_device_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(profiler, "stop_device_trace",
                        lambda: calls.append(("stop", None)))
    try:
        # CPU state: host spans only, device tier untouched
        profiler.set_device_trace_dir(str(tmp_path))
        profiler.start_profiler("CPU")
        profiler.stop_profiler()
        assert calls == []
        # All state + configured dir: device trace started AND stopped
        profiler.start_profiler("All")
        assert calls == [("start", str(tmp_path))]
        profiler.stop_profiler()
        assert calls == [("start", str(tmp_path)), ("stop", None)]
        # no dir configured: All degrades to host-only, no error
        calls.clear()
        profiler.set_device_trace_dir(None)
        monkeypatch.delenv("PADDLE_TPU_DEVICE_TRACE_DIR", raising=False)
        profiler.start_profiler("All")
        profiler.stop_profiler()
        assert calls == []
    finally:
        profiler.set_device_trace_dir(None)
        profiler.reset_profiler()
